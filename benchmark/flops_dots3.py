"""Forward FLOPs of one token of the dots3-note-prev configuration as it is cut
for one chip, from the configuration file's published keys.

Counted: 2 per matmul parameter the token crosses HERE (every attention
projection of the five layers, the indexer's three, layer 0's dense FFN, and
in each expert layer the router, the shared expert and the routed experts
that land on this chip: `num_experts_per_tok` x held / published = one of the
eight on average, uniform routing assumed), the head's slice, and what grows
with the context L: the indexer's score of every earlier key, absorbed latent
attention over the min(L, index_topk) selected keys of a full layer and over
the min(L, window) keys of a window layer (scores over latent + rotary key,
values over the latent). Embedding gathers, norms, softmax and the top-k are
not counted. An OUTPUT token crosses all of it. A PROMPT token forwarded by a
prefill chunk needs neither the head nor the last layer's FFN (they feed
logits a prefill drops: the tick that follows forwards the last prompt token
again), so it is credited without them.
tests/benchmark_tests/test_bench_flops_dots3.py holds the parameter table to
ISSUE 27's and to what `init_params` builds.
"""

from __future__ import annotations

FULL = "full_attention"


def attention_params(config: dict, kind: str) -> dict:
    """Matmul parameters of one layer's attention, by projection."""
    pre = "" if kind == FULL else "swa_"
    d = config["hidden_size"]
    h, nope, rope, v = (config[pre + k] for k in
                        ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    q_rank, kv_rank = config[pre + "q_lora_rank"], config[pre + "kv_lora_rank"]
    out = {"q_a": d * q_rank, "q_b": q_rank * h * (nope + rope), "kv_a": d * (kv_rank + rope),
           "kv_b": kv_rank * h * (nope + v), "o": h * v * d, "gate": d * h}
    if kind == FULL:
        ih, idim = config["index_n_heads"], config["index_head_dim"]
        out.update(idx_q=q_rank * ih * idim, idx_k=d * idim, idx_w=d * ih)
    return out


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def held_matmul_params(config: dict) -> dict:
    """Matmul parameters this chip HOLDS, by part (norm weights and the
    router's selection bias are not matmul parameters)."""
    d, layers = config["hidden_size"], config["layer_types"][: config["num_hidden_layers"]]
    dense = config["first_k_dense_replace"]
    moe_layers = len(layers) - dense
    return {
        "attention": sum(sum(attention_params(config, k).values()) for k in layers),
        "dense_ffn": dense * 3 * d * config["intermediate_size"],
        "router": moe_layers * d * config["published"]["n_routed_experts"],
        "shared_experts": moe_layers * config["n_shared_experts"] * expert_params(config),
        "routed_experts": moe_layers * config["n_routed_experts"] * expert_params(config),
        "embedding": config["vocab_size"] * d,
        "head": d * config["vocab_size"],
    }


def crossed_matmul_params(config: dict) -> dict:
    """Matmul parameters ONE token crosses on this chip, by part: the held
    ones, with the routed experts at the share that lands here and the
    embedding (a gather) left out."""
    held = held_matmul_params(config)
    moe_layers = len(config["layer_types"][: config["num_hidden_layers"]]) - config["first_k_dense_replace"]
    landing = config["num_experts_per_tok"] * config["n_routed_experts"] / config["published"]["n_routed_experts"]
    held["routed_experts"] = moe_layers * landing * expert_params(config)
    del held["embedding"]
    return held


def context_flops(config: dict, ctx: float) -> float:
    """The part of an output token's forward that grows with its context of
    `ctx` tokens (a mean over slots is fine: every term is linear in the
    context up to its cap)."""
    total = 0.0
    for kind in config["layer_types"][: config["num_hidden_layers"]]:
        pre = "" if kind == FULL else "swa_"
        h, rope, kv_rank = (config[pre + k] for k in ("num_attention_heads", "qk_rope_head_dim", "kv_lora_rank"))
        if kind == FULL:
            total += 2.0 * config["index_n_heads"] * config["index_head_dim"] * ctx
            keys = min(ctx, config["index_topk"])
        else:
            keys = min(ctx, config["sliding_window_size"])
        total += 2.0 * h * ((kv_rank + rope) + kv_rank) * keys
    return total


def forward_flops_per_output_token(config: dict, ctx: float) -> float:
    return 2.0 * sum(crossed_matmul_params(config).values()) + context_flops(config, ctx)


def forward_flops_per_prompt_token(config: dict, ctx: float) -> float:
    """A token a prefill chunk forwards at context `ctx`: everything an output
    token crosses but the head and the last layer's FFN."""
    crossed = crossed_matmul_params(config)
    layers = config["layer_types"][: config["num_hidden_layers"]]
    moe_layers = len(layers) - config["first_k_dense_replace"]
    if moe_layers:  # the last layer is an expert layer: one layer's share of each expert part
        last_ffn = sum(crossed[k] for k in ("router", "shared_experts", "routed_experts")) / moe_layers
    else:
        last_ffn = crossed["dense_ffn"] / len(layers)
    return 2.0 * (sum(crossed.values()) - crossed["head"] - last_ffn) + context_flops(config, ctx)
