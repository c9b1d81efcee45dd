"""Model step: model FLOPs utilisation. Tokens/s/chip of the window x the
benchmark's own FLOPs per token (benchmark/flops.py; recompute not credited)
over the bf16 peak of the exact device_kind (benchmark/peaks.py)."""


def read(rec):
    if rec.get("peaks") is None or rec.get("tokens_per_s_chip") is None:
        return None
    return 100.0 * rec["tokens_per_s_chip"] * rec["flops_per_token"] / rec["peaks"]["flops_bf16"]
