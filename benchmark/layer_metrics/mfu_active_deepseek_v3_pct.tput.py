"""Model: the share of the chip's bf16 peak behind the tokens the window
forwarded, for the deepseek-v3 configuration as it is cut, with the ACTIVE
parameters counted (benchmark/flops_deepseek_v3.py: 2 per matmul parameter a
token crosses on this chip, the routed experts at the share that lands here,
plus the 128 heads' absorbed attention over every key of the token's context
in every layer). Both kinds of work are credited: each `prefill` event's
`tokens` at the context its chunk starts from (`chunk` x the record's
`prefill_chunk`, plus half the chunk), without the head and the last layer's
FFN, which a prefill does not need; each quantum's `delivered` output tokens
at the quanta's mean context (`ctx_tokens` / `decoding`). A prompt's tokens
the page registry served are never forwarded and never credited. Over the
traced window's seconds and the bf16 peak of the exact device_kind: the
cell's share of the whole step. A configuration of another `model_type`
reads nothing here."""

from benchmark import flops_deepseek_v3


def read(rec):
    quanta = [q for q in rec.get("quanta") or () if "delivered" in q and "ctx_tokens" in q]
    config = rec.get("config") or {}
    if not quanta or not rec.get("peaks") or not rec.get("window_s") or config.get("model_type") != "deepseek_v3":
        return None
    lanes = sum(q["decoding"] for q in quanta)
    if not lanes:
        return None
    ctx = sum(q["ctx_tokens"] for q in quanta) / lanes
    flops = sum(q["delivered"] for q in quanta) * flops_deepseek_v3.forward_flops_per_output_token(config, ctx)
    chunk = rec.get("prefill_chunk", 0)
    for p in rec.get("prefills") or ():
        if p.get("tokens"):
            at = p["chunk"] * chunk + p["tokens"] / 2.0
            flops += p["tokens"] * flops_deepseek_v3.forward_flops_per_prompt_token(config, at)
    return 100.0 * flops / rec["window_s"] / rec["peaks"]["flops_bf16"]
