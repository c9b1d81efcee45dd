"""Forward FLOPs of one token of the motif-3-beta configuration as it is cut
for one chip, from the configuration file's published keys.

Counted: 2 per matmul parameter the token crosses HERE (every attention
projection of the five layers, lambda's and the elementwise gate's among
them; the two hyper-connection projections a layer; the dense layer's FFN;
in each expert layer the router, the shared expert and the routed experts
that land on this chip: `experts_top_k` x held / published = one of the eight
on average, uniform routing assumed), the head's slice, and what grows with
the context L: absorbed latent attention of all 80 heads (scores over latent +
rotary key, values over the latent) over the L keys of a full layer and the
min(L, window) keys of a window layer. Embedding gathers, norms, softmax,
Sinkhorn, the streams' mixing and PolyNorm are not counted. An OUTPUT token
crosses all of it. A PROMPT token forwarded by a prefill chunk needs neither
the head nor the last layer's FFN (they feed logits a prefill drops: the tick
that follows forwards the last prompt token again), so it is credited without
them. tests/benchmark_tests/test_bench_flops_motif3.py holds the parameter
table to ISSUE 33's and to what `init_params` builds.
"""

from __future__ import annotations


def held_layers(config: dict) -> list[int]:
    return list(config.get("held_layers", range(config["num_hidden_layers"])))


def layer_is_full(config: dict, published_index: int) -> bool:
    return (published_index + 1) % config["sliding_window_period"] == 0


def attention_params(config: dict) -> dict:
    """Matmul parameters of one layer's attention, by projection (both kinds
    of layer have the same)."""
    d, h, g = config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"]
    rope, v = config["qk_rope_head_dim"], config["v_head_dim"]
    nope = config["head_dim"] - rope
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    signal = h - config["num_noise_heads"]
    return {"q_a": d * q_rank, "q_b": q_rank * h * config["head_dim"], "kv_a": d * (kv_rank + rope),
            "kv_b": kv_rank * g * (nope + v), "lam": d * signal, "gate": d * signal * v, "o": signal * v * d}


def stream_params(config: dict) -> int:
    """One layer's two hyper-connection projections."""
    n = config["mhc_expansion_rate"]
    return 2 * (n * config["hidden_size"]) * (2 * n + n * n)


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def _layer_counts(config: dict) -> tuple[int, int]:
    """(dense layers, expert layers) among the held ones."""
    dense = sum(i < config["n_dense_first_layers"] for i in held_layers(config))
    return dense, len(held_layers(config)) - dense


def held_matmul_params(config: dict) -> dict:
    """Matmul parameters this chip HOLDS, by part (norm weights, PolyNorm's
    weights and the hyper-connections' alpha and b are not matmul parameters)."""
    d = config["hidden_size"]
    dense, moe_layers = _layer_counts(config)
    layers = dense + moe_layers
    return {
        "attention": layers * sum(attention_params(config).values()),
        "streams": layers * stream_params(config),
        "dense_ffn": dense * 3 * d * config["intermediate_size"],
        "router": moe_layers * d * config["published"]["num_experts"],
        "shared_experts": moe_layers * config["num_shared_experts"] * expert_params(config),
        "routed_experts": moe_layers * config["num_experts"] * expert_params(config),
        "embedding": config["vocab_size"] * d,
        "head": d * config["vocab_size"],
    }


def crossed_matmul_params(config: dict) -> dict:
    """Matmul parameters ONE token crosses on this chip, by part: the held
    ones, with the routed experts at the share that lands here and the
    embedding (a gather) left out."""
    held = held_matmul_params(config)
    _, moe_layers = _layer_counts(config)
    landing = config["experts_top_k"] * config["num_experts"] / config["published"]["num_experts"]
    held["routed_experts"] = moe_layers * landing * expert_params(config)
    del held["embedding"]
    return held


def context_flops(config: dict, ctx: float) -> float:
    """The part of an output token's forward that grows with its context of
    `ctx` tokens (a mean over slots is fine: every term is linear in the
    context up to its cap)."""
    h, rope, kv_rank = config["num_attention_heads"], config["qk_rope_head_dim"], config["kv_lora_rank"]
    a_key = 2.0 * h * ((kv_rank + rope) + kv_rank)
    return sum(a_key * (ctx if layer_is_full(config, i) else min(ctx, config["sliding_window"]))
               for i in held_layers(config))


def forward_flops_per_output_token(config: dict, ctx: float) -> float:
    return 2.0 * sum(crossed_matmul_params(config).values()) + context_flops(config, ctx)


def forward_flops_per_prompt_token(config: dict, ctx: float) -> float:
    """A token a prefill chunk forwards at context `ctx`: everything an output
    token crosses but the head and the last layer's FFN."""
    crossed = crossed_matmul_params(config)
    dense, moe_layers = _layer_counts(config)
    if moe_layers:  # the last layer is an expert layer: one layer's share of each expert part
        last_ffn = sum(crossed[k] for k in ("router", "shared_experts", "routed_experts")) / moe_layers
    else:
        last_ffn = crossed["dense_ffn"] / dense
    return 2.0 * (sum(crossed.values()) - crossed["head"] - last_ffn) + context_flops(config, ctx)
