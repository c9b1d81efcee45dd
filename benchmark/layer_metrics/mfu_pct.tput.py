"""Decode step: the share of the chip's bf16 peak behind the tokens the window
delivered. Output tokens a second (the `quantum` events' `delivered`, summed
over the traced window's quanta, over the window) x the forward FLOPs of one
output token (benchmark/flops.py at sequence length 0, a third of the training
figure: 2 per matmul parameter) over the bf16 peak of the exact device_kind
(benchmark/peaks.py). NOT credited: the attention's score and value matmuls
over the cached prefix, and every prompt token the prefill chunks forward. So
it is a floor on the work per delivered token, and a decode bound by weight
and cache reads sits far under 1%."""

from benchmark import flops


def read(rec):
    quanta = [q for q in rec.get("quanta") or () if "delivered" in q]
    if not quanta or not rec.get("peaks") or not rec.get("window_s") or rec.get("cfg") is None:
        return None
    tokens_per_s = sum(q["delivered"] for q in quanta) / rec["window_s"]
    forward_flops_per_token = flops.cfg_train_flops_per_token(rec["cfg"], 0) / 3.0
    return 100.0 * tokens_per_s * forward_flops_per_token / rec["peaks"]["flops_bf16"]
