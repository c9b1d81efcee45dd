"""Forward FLOPs of one token of the deepseek-v3 configuration as it is cut
for one chip, from the configuration file's published keys.

Counted: 2 per matmul parameter the token crosses HERE (the five projections
of every layer's latent attention, layer 0's dense FFN, and in each expert
layer the router, the shared expert and the routed experts that land on this
chip: `num_experts_per_tok` x held / published = half an expert on average,
uniform routing assumed), the head's slice, and what grows with the context
L: absorbed latent attention of all 128 heads over every one of the L keys of
every layer (scores over latent + rotary key, values over the latent).
Embedding gathers, norms, softmax and the routing's top-k are not counted. An
OUTPUT token crosses all of it. A PROMPT token forwarded by a prefill chunk
needs neither the head nor the last layer's FFN (they feed logits a prefill
drops: the tick that follows forwards the last prompt token again), so it is
credited without them. A decode tick sends its rows through every held expert
(`held_experts_ffn(every_row=True)`): the rows an expert computes and the
gates drop are not credited. tests/benchmark_tests/test_bench_flops_deepseek_v3.py
holds the parameter table to the configuration file's `held.table` and to
what `init_params` builds.
"""

from __future__ import annotations


def attention_params(config: dict) -> dict:
    """Matmul parameters of one layer's attention, by projection (every
    layer's are the same)."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope, v = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    return {"q_a": d * q_rank, "q_b": q_rank * h * (nope + rope), "kv_a": d * (kv_rank + rope),
            "kv_b": kv_rank * h * (nope + v), "o": h * v * d}


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def _layer_counts(config: dict) -> tuple[int, int]:
    """(dense layers, expert layers) among the held ones."""
    dense = config["first_k_dense_replace"]
    return dense, config["num_hidden_layers"] - dense


def held_matmul_params(config: dict) -> dict:
    """Matmul parameters this chip HOLDS, by part (norm weights and the
    router's selection bias are not matmul parameters)."""
    d = config["hidden_size"]
    dense, moe_layers = _layer_counts(config)
    return {
        "attention": (dense + moe_layers) * sum(attention_params(config).values()),
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
    _, moe_layers = _layer_counts(config)
    landing = config["num_experts_per_tok"] * config["n_routed_experts"] / config["published"]["n_routed_experts"]
    held["routed_experts"] = moe_layers * landing * expert_params(config)
    del held["embedding"]
    return held


def context_flops(config: dict, ctx: float) -> float:
    """The part of a token's forward that grows with its context of `ctx`
    tokens (linear in it: a mean over slots is fine)."""
    h, rope, kv_rank = config["num_attention_heads"], config["qk_rope_head_dim"], config["kv_lora_rank"]
    return config["num_hidden_layers"] * 2.0 * h * ((kv_rank + rope) + kv_rank) * ctx


def forward_flops_per_output_token(config: dict, ctx: float) -> float:
    return 2.0 * sum(crossed_matmul_params(config).values()) + context_flops(config, ctx)


def forward_flops_per_prompt_token(config: dict, ctx: float) -> float:
    """A token a prefill chunk forwards at context `ctx`: everything an output
    token crosses but the head and the last layer's FFN (an expert layer)."""
    crossed = crossed_matmul_params(config)
    _, moe_layers = _layer_counts(config)
    last_ffn = sum(crossed[k] for k in ("router", "shared_experts", "routed_experts")) / moe_layers
    return 2.0 * (sum(crossed.values()) - crossed["head"] - last_ffn) + context_flops(config, ctx)
