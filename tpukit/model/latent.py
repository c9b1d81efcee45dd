"""The latent-attention decoder family: the second block family beside
`gpt.py`, SERVED ONLY (training it raises `ServedOnlyError`).

What a layer is (x `[T, dim]`; RMSNorm with a weight; every linear bias-free):

    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h));  final RMSNorm, head.

`layer_types` gives each layer's attention kind; the first `first_dense`
layers have a dense gated-SiLU FFN, every later one the expert layer.

- **Latent attention** (both kinds, each with its own sizes and weights,
  `AttnSpec`): queries through a low-rank bottleneck, `c_q = s_q RMSNorm(x
  W_qa)`, `[q_n; q_r] = c_q W_qb` per head, rotary on `q_r`; keys and values
  through ONE latent a token, `[c_kv; k_r] = x W_kva`, `c_kv <- s_kv
  RMSNorm(c_kv)`, rotary on `k_r` (one for all heads), `[k_n; v] = c_kv W_kvb`
  per head. The cache keeps `(c_kv, k_r)` only, and attention runs in the
  ABSORBED form: `q_n` is carried into the latent space through `W_kvb`'s key
  half, scores are `(q_abs . c_kv + q_r . k_r) / sqrt(nope + rope)`, the
  weighted latents come back through `W_kvb`'s value half. A headwise
  sigmoid gate read off the layer's input scales each head's output.
- **Full layers** choose their keys: an indexer (few small heads, its own
  cached key a token) scores every earlier token, `I[t, s] = sum_j w[t, j]
  ReLU(q_j[t] . k[s])`, and the token attends the `index_topk` keys of
  largest `I` (all of them while there are fewer). The selected latents are
  gathered from the page pool by token.
- **Window layers** attend the last `window` tokens (the token itself
  counted) and keep no more than that: their pages are a ring.
- **Expert layer**: `tpukit/ops/moe_dispatch.py` (`sigmoid_topk_route`,
  `held_experts_ffn`) — scores over all `n_experts`, this chip computes the
  experts `[expert_lo, expert_lo + experts_held)` it holds — plus one shared
  expert every token crosses.

A second published form of the family shares the latent cache, the window
ring and the held experts, and differs in four ways, each switched by the
config alone:

- **Grouped differential heads** (`AttnSpec.kv_heads`, `noise_heads`):
  `W_kvb` expands the latent to `kv_heads` KV heads and the query heads read
  them in groups; the last head of each group is its NOISE head, and a signal
  head's output is `o_i - lambda_i o_noise`, `lambda = sigmoid(a W_lambda)` a
  token a signal head. Both share the group's value projection, so the
  subtraction is taken on the weighted latents. The output gate is
  elementwise (`gate == "elementwise"`: a value a signal head's channel).
- **No key selection** (`index_topk == 0`): a full layer attends every key of
  the context, in blocks of `KEY_BLOCK` keys read off the block table with a
  running max and sum (`_attend_paged`): no `[.., max_len]` score array and no
  gathered view of a slot's whole context ever exists.
- **A multi-stream residual** (`streams` n > 1, manifold-constrained
  hyper-connections): a token's state is `X [n, dim]`; each sublayer F reads
  `u = sum_i H_pre[i] X[i]`, computes `y = clamp(F(u))`, and writes `X'[i] =
  sum_j H_res[i, j] X[j] + H_post[i] y`, with `H_pre = sigmoid(.)`, `H_post =
  2 sigmoid(.)` and `H_res` the Sinkhorn-normalised `exp(.)` of learned
  projections of the RMS-normalised `vec(X)`. Embedding repeated in, streams
  summed out.
- **PolyNorm** (`activation == "poly_norm"`) in place of SiLU in every gated
  FFN: `scale (w1 N(z) + w2 N(z^2) + w3 N(z^3) + clamp(b))`, `N` the RMS
  normalisation over the FFN's width, per token.

It also sends a decode tick's rows through EVERY held expert
(`tick_experts_every_row`; `held_experts_ffn(every_row=True)`): the grouped
matmul skips an expert that got no row, so a tick's time followed how the
lanes' tokens happened to route (+10% to +24% of tokens/s in the runs where
greedy decoding let the lanes' contents converge); with a row or two an
expert the weights' read is the cost either way. The first form keeps the
grouped tick: its programs are held byte-equal.

The model reaches the serving stack through what every family offers
(`tpukit.model.family(cfg)`): `init_params`, `forward`, `forward_cached`,
`init_kv_cache`, `init_paged_cache`, `page_kinds`, `select_lanes` /
`merge_lanes`, `max_context`, `cached_decode_exact`. The cache is paged only.

Numerics: parameters in `param_dtype` (bf16 at the published widths: the
float32 weights of one chip's share would not fit it), matmuls in
`compute_dtype` with float32 accumulation, the residual stream, norms,
softmax, router scores and the indexer's accumulation in float32; so are the
streams, the hyper-connection maps, Sinkhorn, PolyNorm and lambda.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from tpukit.ops.moe_dispatch import held_experts_ffn, sigmoid_topk_route
from tpukit.ops.pallas_attention import online_softmax_update

Params = Any
FULL, WINDOW = "full_attention", "sliding_attention"
ATTEND_BLOCK = 256  # queries attended at a time: bounds the gathered latents and the scores
INDEX_KEY_BLOCK = 2048  # keys scored at a time by the indexer: bounds [queries, heads, keys]
KEY_BLOCK = 512  # keys a full layer without selection attends at a time, whole pages of one block-table stretch


class ServedOnlyError(NotImplementedError):
    """The latent family has a serving path only."""


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """One latent-attention variant's sizes."""

    heads: int
    nope: int  # per-head query/key width without position
    rope: int  # per-head rotary width (the key's is shared by all heads)
    v: int
    q_rank: int
    kv_rank: int
    theta: float
    kv_heads: int = 0  # KV heads `W_kvb` expands the latent to, read by query heads in groups; 0: one a query head
    noise_heads: int = 0  # of `heads`, how many are noise heads (the last of each group): differential attention
    gate: str = "headwise"  # the sigmoid output gate: one value a head, or "elementwise" (a value a channel)

    def __post_init__(self):
        if self.kv_heads and (self.heads % self.kv_heads or self.noise_heads not in (0, self.kv_heads)):
            raise ValueError(f"{self.heads} heads in {self.kv_heads} groups with {self.noise_heads} noise heads: "
                             "groups are equal and hold one noise head each, or none")
        if self.noise_heads and not self.kv_heads:
            raise ValueError("noise heads are the last head of each KV group: kv_heads must be given")

    @property
    def row(self) -> int:
        """Values the cache keeps a token: the latent and the rotary key."""
        return self.kv_rank + self.rope

    @property
    def groups(self) -> int:
        return self.kv_heads or self.heads

    @property
    def out_heads(self) -> int:
        """Heads whose output reaches `W_o`: the signal heads."""
        return self.heads - self.noise_heads


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    dim: int
    vocab_size: int
    layer_types: tuple[str, ...]
    full: AttnSpec
    window: AttnSpec
    window_size: int  # keys a window layer attends, the token itself counted
    index_heads: int
    index_dim: int
    index_topk: int
    dense_width: int
    expert_width: int
    n_experts: int  # the router's width: every expert of the model
    experts_per_token: int
    experts_held: int  # how many of them this chip holds ...
    expert_lo: int = 0  # ... starting at this one
    first_dense: int = 1
    rescale_latents: bool = True  # s_q = sqrt(dim / q_rank), s_kv = sqrt(dim / kv_rank)
    select_bias: bool = True  # the router's choice adds a learned per-expert bias
    route_scale: float = 1.0  # the gates, normalised over the chosen, times this
    activation: str = "silu"  # of every gated FFN: "silu" or "poly_norm"
    poly_scale: float = 1.0
    poly_bias_clamp: float = 0.5
    streams: int = 1  # residual streams a token; > 1: every sublayer under hyper-connections
    sinkhorn_iters: int = 20
    hidden_clamp: float = 0.0  # a sublayer's output clamped to +- this under hyper-connections; 0: not
    tick_experts_every_row: bool = False  # a decode tick's rows through EVERY held expert (`held_experts_ffn`); a stopgap: ROADMAP R1
    norm_eps: float = 1e-5
    max_position_embeddings: int = 524288
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        bad = [t for t in self.layer_types if t not in (FULL, WINDOW)]
        if bad:
            raise ValueError(f"layer_types must be {FULL!r} or {WINDOW!r}, got {bad}")
        if not 0 <= self.expert_lo <= self.n_experts - self.experts_held:
            raise ValueError(
                f"held experts [{self.expert_lo}, {self.expert_lo + self.experts_held}) "
                f"are not among the model's {self.n_experts}"
            )
        if self.activation not in ("silu", "poly_norm"):
            raise ValueError(f"activation must be 'silu' or 'poly_norm', got {self.activation!r}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def padded_vocab_size(self) -> int:
        return self.vocab_size

    def replace(self, **kw) -> "LatentConfig":
        return dataclasses.replace(self, **kw)

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)


def config_from_hf(hf: dict, *, experts_held: int | None = None, expert_lo: int = 0,
                   compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16) -> LatentConfig:
    """A `LatentConfig` from the keys of the published `config.json` (as a
    benchmark configuration file carries them). `n_routed_experts` there is
    the experts HELD when the file also states `published.n_routed_experts`
    (one chip's share of a deployment); `experts_held` overrides. The family
    is published under two sets of key names: a config that states a
    `sliding_window_pattern` is read by `_config_from_pattern_keys`."""
    if "sliding_window_pattern" in hf:
        return _config_from_pattern_keys(hf, experts_held, expert_lo, compute_dtype, param_dtype)
    routed = hf.get("published", {}).get("n_routed_experts", hf["n_routed_experts"])
    held = experts_held if experts_held is not None else hf["n_routed_experts"]
    layers = hf["num_hidden_layers"]
    return LatentConfig(
        dim=hf["hidden_size"], vocab_size=hf["vocab_size"],
        layer_types=tuple(hf["layer_types"][:layers]),
        full=AttnSpec(hf["num_attention_heads"], hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                      hf["v_head_dim"], hf["q_lora_rank"], hf["kv_lora_rank"], float(hf["rope_theta"])),
        window=AttnSpec(hf["swa_num_attention_heads"], hf["swa_qk_nope_head_dim"],
                        hf["swa_qk_rope_head_dim"], hf["swa_v_head_dim"], hf["swa_q_lora_rank"],
                        hf["swa_kv_lora_rank"], float(hf["swa_rope_theta"])),
        window_size=hf["sliding_window_size"],
        index_heads=hf["index_n_heads"], index_dim=hf["index_head_dim"], index_topk=hf["index_topk"],
        dense_width=hf["intermediate_size"], expert_width=hf["moe_intermediate_size"],
        n_experts=routed, experts_per_token=hf["num_experts_per_tok"], experts_held=held,
        expert_lo=expert_lo, first_dense=hf["first_k_dense_replace"],
        rescale_latents=bool(hf.get("apply_mla_qkv_lora_rescale", False)),
        norm_eps=hf["rms_norm_eps"], max_position_embeddings=hf["max_position_embeddings"],
        compute_dtype=jnp.dtype(compute_dtype), param_dtype=jnp.dtype(param_dtype),
    )


def _config_from_pattern_keys(hf: dict, experts_held, expert_lo, compute_dtype, param_dtype) -> LatentConfig:
    """The key names of the grouped-differential form: one set of attention
    sizes for both kinds of layer, the kinds from `sliding_window_pattern`
    ("interleave": layer i is full where `(i + 1) % sliding_window_period ==
    0`), `num_experts` the experts HELD beside `published.num_experts`, and
    `held_layers`, the published indices of the layers this chip holds (the
    first `num_hidden_layers` without it): a layer's kind and whether its FFN
    is dense go by its published index."""
    if hf["sliding_window_pattern"] != "interleave" or not hf.get("use_sliding_window", True):
        raise ValueError(f"sliding_window_pattern {hf['sliding_window_pattern']!r}: only 'interleave' is read")
    if hf.get("rope_scaling", {}).get("apply_yarn_scaling") or hf.get("k_ratio", 1) != 1:
        raise ValueError("scaled rotary and a key ratio other than 1 are not implemented")
    held_layers = tuple(hf.get("held_layers", range(hf["num_hidden_layers"])))
    if len(held_layers) != hf["num_hidden_layers"] or list(held_layers) != sorted(held_layers):
        raise ValueError(f"held_layers {held_layers} are not num_hidden_layers {hf['num_hidden_layers']} indices in order")
    dense = sum(i < hf["n_dense_first_layers"] for i in held_layers)
    rope = hf["qk_rope_head_dim"]
    spec = lambda theta: AttnSpec(  # noqa: E731
        hf["num_attention_heads"], hf["head_dim"] - rope, rope, hf["v_head_dim"], hf["q_lora_rank"],
        hf["kv_lora_rank"], float(theta), kv_heads=hf["num_key_value_heads"], noise_heads=hf["num_noise_heads"],
        gate="elementwise" if hf["elementwise_attn_output_gate"] else "headwise")
    mhc = bool(hf.get("mhc_enabled"))
    return LatentConfig(
        dim=hf["hidden_size"], vocab_size=hf["vocab_size"],
        layer_types=tuple(FULL if (i + 1) % hf["sliding_window_period"] == 0 else WINDOW for i in held_layers),
        full=spec(hf["rope_theta"]), window=spec(hf["swa_rope_theta"]), window_size=hf["sliding_window"],
        index_heads=0, index_dim=0, index_topk=0,
        dense_width=hf["intermediate_size"], expert_width=hf["moe_intermediate_size"],
        n_experts=hf.get("published", {}).get("num_experts", hf["num_experts"]),
        experts_per_token=hf["experts_top_k"],
        experts_held=experts_held if experts_held is not None else hf["num_experts"],
        expert_lo=expert_lo, first_dense=dense, rescale_latents=False, select_bias=False,
        route_scale=float(hf["route_scale"]) if hf.get("route_norm", True) else 1.0,
        activation=hf["hidden_act"], poly_scale=float(hf.get("polynorm_output_scale", 1.0)),
        poly_bias_clamp=float(hf.get("polynorm_bias_clamp", 0.5)),
        streams=hf["mhc_expansion_rate"] if mhc else 1, sinkhorn_iters=hf.get("mhc_sinkhorn_iters", 20),
        hidden_clamp=float(hf.get("hidden_clamp", 0.0)), tick_experts_every_row=True,
        norm_eps=hf["rms_norm_eps"], max_position_embeddings=hf["max_position_embeddings"],
        compute_dtype=jnp.dtype(compute_dtype), param_dtype=jnp.dtype(param_dtype),
    )


def tiny_config(**kw) -> LatentConfig:
    """The CPU tests' preset: five layers of the same kinds, a top-k and a
    window smaller than a test's sequence, more experts than are held."""
    base = dict(
        dim=64, vocab_size=97, layer_types=(FULL, FULL, WINDOW, WINDOW, WINDOW),
        full=AttnSpec(heads=4, nope=16, rope=8, v=16, q_rank=32, kv_rank=24, theta=8e7),
        window=AttnSpec(heads=2, nope=24, rope=8, v=16, q_rank=32, kv_rank=32, theta=5e4),
        window_size=9, index_heads=4, index_dim=16, index_topk=16,
        dense_width=128, expert_width=32, n_experts=16, experts_per_token=4, experts_held=2,
        max_position_embeddings=4096, compute_dtype=jnp.float32, param_dtype=jnp.float32,
    )
    base.update(kw)
    return LatentConfig(**base)


def tiny_diff_config(**kw) -> LatentConfig:
    """The CPU tests' preset of the grouped-differential form: 2 KV groups of
    4 signal heads and a noise head, 4 streams, window 9, no key selection,
    PolyNorm, 16 experts of which 2 are held."""
    spec = AttnSpec(heads=10, nope=16, rope=8, v=16, q_rank=32, kv_rank=24, theta=1e4, kv_heads=2, noise_heads=2,
                    gate="elementwise")
    base = dict(
        dim=64, vocab_size=97, layer_types=(WINDOW, WINDOW, WINDOW, WINDOW, FULL), full=spec, window=spec,
        window_size=9, index_heads=0, index_dim=0, index_topk=0,
        dense_width=128, expert_width=32, n_experts=16, experts_per_token=4, experts_held=2,
        rescale_latents=False, select_bias=False, route_scale=2.0, activation="poly_norm", poly_scale=0.5,
        streams=4, hidden_clamp=1e6, tick_experts_every_row=True,
        max_position_embeddings=4096, compute_dtype=jnp.float32, param_dtype=jnp.float32,
    )
    base.update(kw)
    return LatentConfig(**base)


# -- parameters ---------------------------------------------------------------


def _uniform(rng, shape, fan_in: int, dtype):
    bound = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(rng, shape, jnp.float32, -bound, bound).astype(dtype)


def _init_attention(rng, cfg: LatentConfig, spec: AttnSpec, indexer: bool) -> dict:
    k = iter(jax.random.split(rng, 10))
    d, dt = cfg.dim, cfg.param_dtype
    out_heads = spec.out_heads
    gate_width = out_heads * spec.v if spec.gate == "elementwise" else out_heads
    out = {
        "q_a": _uniform(next(k), (d, spec.q_rank), d, dt),
        "q_norm": jnp.ones((spec.q_rank,), jnp.float32),
        "q_b": _uniform(next(k), (spec.q_rank, spec.heads, spec.nope + spec.rope), spec.q_rank, dt),
        "kv_a": _uniform(next(k), (d, spec.row), d, dt),
        "kv_norm": jnp.ones((spec.kv_rank,), jnp.float32),
        "kv_b": _uniform(next(k), (spec.kv_rank, spec.groups, spec.nope + spec.v), spec.kv_rank, dt),
        "o": _uniform(next(k), (out_heads, spec.v, d), out_heads * spec.v, dt),
        "gate": _uniform(next(k), (d, gate_width), d, dt),
    }
    if indexer:
        out.update(
            idx_q=_uniform(next(k), (spec.q_rank, cfg.index_heads, cfg.index_dim), spec.q_rank, dt),
            idx_k=_uniform(next(k), (d, cfg.index_dim), d, dt),
            idx_k_norm={"scale": jnp.ones((cfg.index_dim,), jnp.float32),
                        "bias": jnp.zeros((cfg.index_dim,), jnp.float32)},
            idx_w=_uniform(next(k), (d, cfg.index_heads), d, dt),
        )
    if spec.noise_heads:
        out["lam"] = _uniform(next(k), (d, out_heads), d, dt)
    return out


def _init_gated_ffn(rng, d: int, width: int, dtype, experts: int = 0, poly_norm: bool = False) -> dict:
    kg, ku, kd = jax.random.split(rng, 3)
    lead = (experts,) if experts else ()
    out = {"gate": _uniform(kg, lead + (d, width), d, dtype),
           "up": _uniform(ku, lead + (d, width), d, dtype),
           "down": _uniform(kd, lead + (width, d), width, dtype)}
    if poly_norm:  # each FFN its own three weights and bias; drawn so that every power and the bias's clamp are live
        kw, kb = jax.random.split(jax.random.fold_in(rng, 1))
        out["poly_w"] = 1.0 / 3.0 + jax.random.uniform(kw, lead + (3,), jnp.float32, -0.1, 0.1)
        out["poly_b"] = 0.5 * jax.random.normal(kb, lead + (1,), jnp.float32)
    return out


def _init_streams(rng, cfg: LatentConfig) -> dict:
    """One sublayer's hyper-connection: `phi [n dim, n + n + n n]` projects the
    normed `vec(X)` to the pre, post and stream-mixing maps' dynamic parts,
    `alpha` (3) scales them, `b` is their static part. All float32; alpha and
    b are drawn large enough that the dynamic path and the Sinkhorn iterations
    are live (an identity start would leave both untested)."""
    n = cfg.streams
    kp, ka, kb = jax.random.split(rng, 3)
    return {"phi": _uniform(kp, (n * cfg.dim, 2 * n + n * n), n * cfg.dim, jnp.float32),
            "alpha": jax.random.uniform(ka, (3,), jnp.float32, 0.05, 0.2),
            "b": 0.5 * jax.random.normal(kb, (2 * n + n * n,), jnp.float32)}


def init_params(rng: jax.Array, cfg: LatentConfig) -> Params:
    """Seeded weights of the layers this chip holds: a tuple of per-layer
    dicts (the kinds differ, so nothing is stacked). The router is whole and
    float32; its selection bias (where the config has one) is drawn at a small
    scale so that the path that uses it is live."""
    k_embed, k_head, *k_layers = jax.random.split(rng, 2 + cfg.num_layers)
    poly = cfg.activation == "poly_norm"
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        ka, kf, kr, kb, ks = jax.random.split(k_layers[i], 5)
        spec = cfg.full if kind == FULL else cfg.window
        layer = {"norm1": jnp.ones((cfg.dim,), jnp.float32), "norm2": jnp.ones((cfg.dim,), jnp.float32),
                 "attn": _init_attention(ka, cfg, spec, indexer=kind == FULL and cfg.index_topk > 0)}
        if i < cfg.first_dense:
            layer["ffn"] = _init_gated_ffn(kf, cfg.dim, cfg.dense_width, cfg.param_dtype, poly_norm=poly)
        else:
            layer["moe"] = {
                "router": _uniform(kr, (cfg.dim, cfg.n_experts), cfg.dim, jnp.float32),
                "experts": _init_gated_ffn(kf, cfg.dim, cfg.expert_width, cfg.param_dtype, cfg.experts_held, poly),
                "shared": _init_gated_ffn(ks, cfg.dim, cfg.expert_width, cfg.param_dtype, poly_norm=poly),
            }
            if cfg.select_bias:
                layer["moe"]["select_bias"] = 0.02 * jax.random.normal(kb, (cfg.n_experts,), jnp.float32)
        if cfg.streams > 1:
            k1, k2 = jax.random.split(jax.random.fold_in(k_layers[i], 7))
            layer["mhc1"], layer["mhc2"] = _init_streams(k1, cfg), _init_streams(k2, cfg)
        layers.append(layer)
    return {
        "embed": jax.random.normal(k_embed, (cfg.vocab_size, cfg.dim), jnp.float32).astype(cfg.param_dtype),
        "layers": tuple(layers),
        "norm_out": jnp.ones((cfg.dim,), jnp.float32),
        "lm_head": _uniform(k_head, (cfg.dim, cfg.vocab_size), cfg.dim, cfg.param_dtype),
    }


# -- the cache ----------------------------------------------------------------


def max_context(cfg: LatentConfig) -> int:
    """Rotary positions: no table to run off, the published longest context."""
    return cfg.max_position_embeddings


def kv_heads(cfg: LatentConfig) -> int:
    """The cache keeps one latent row a token, for all heads: nothing for a
    mesh's `model` axis to divide."""
    return 1


def cached_decode_exact(cfg: LatentConfig) -> bool:
    """The expert layer is dropless and routes token by token, so a chunk's
    composition changes no token's experts."""
    return True


def _window_back_pages(cfg: LatentConfig, page_size: int) -> int:
    """Whole pages that can hold the `window_size - 1` tokens before a
    page-aligned position."""
    return -(-(cfg.window_size - 1) // page_size)


def page_kinds(cfg: LatentConfig, page_size: int, kv_dtype: str):
    """Two kinds of page: a full layer's latent row (and indexer key, where
    keys are selected) a token, for the whole context; a window layer's
    latent row, as a ring of the pages a window can touch (`ceil(window / P) +
    1`: the window's span at any alignment, and the page being written)."""
    from tpukit.serve import paged as paged_lib  # lazy: tpukit.serve imports tpukit.model

    item = jnp.dtype(paged_lib.storage_dtype(kv_dtype)).itemsize
    ring = -(-cfg.window_size // page_size) + 1
    return (
        paged_lib.PageKind("bt", cfg.layers_of(FULL), page_size * (cfg.full.row + cfg.index_dim) * item),
        paged_lib.PageKind("bt_w", cfg.layers_of(WINDOW), page_size * cfg.window.row * item, ring_pages=ring),
    )


def init_kv_cache(cfg: LatentConfig, batch: int, max_len: int) -> dict:
    raise ServedOnlyError(
        "the latent family keeps a paged cache only (ServeConfig.page_size > 0): "
        "its window layers' store is a ring of pages, which a contiguous ring per slot has no room for"
    )


def init_paged_cache(cfg: LatentConfig, num_pages: dict, page_size: int, pages_per_slot: int,
                     slots: int, kv_dtype: str = "bf16") -> dict:
    """The pytree the serve programs thread: `lat [Lf, NP, P, kv_rank + rope]`
    and `idx [Lf, NP, P, index_dim]` behind block table `bt [N, MP]`, `win
    [Lw, NPw, P, kv_rank_w + rope_w]` behind the ring table `bt_w [N, R]`,
    and `moe_rows`, two counters the decode ticks add to (rows the held
    experts computed; the fullest expert's, summed over layers and ticks).
    `num_pages`: pages of each pool, by block-table key. Without key selection
    there is no `idx`; with hyper-connections there is `health`, two float32
    gauges of the decode ticks (`counters`)."""
    from tpukit.serve import paged as paged_lib

    if kv_dtype == "int8":
        raise ValueError("the latent family's pages are f32 or bf16 rows: no int8 row quantizer exists")
    full, win = page_kinds(cfg, page_size, kv_dtype)
    cache = {
        "lat": paged_lib.init_row_pool(full.layers, num_pages["bt"], page_size, cfg.full.row, kv_dtype),
        "win": paged_lib.init_row_pool(win.layers, num_pages["bt_w"], page_size, cfg.window.row, kv_dtype),
        "bt": jnp.zeros((slots, pages_per_slot), jnp.int32),
        "bt_w": jnp.zeros((slots, win.ring_pages), jnp.int32),
        "moe_rows": jnp.zeros((2,), jnp.int32),
    }
    if cfg.index_topk:
        cache["idx"] = paged_lib.init_row_pool(full.layers, num_pages["bt"], page_size, cfg.index_dim, kv_dtype)
    if cfg.streams > 1:
        cache["health"] = jnp.zeros((2,), jnp.float32)
    return cache


def select_lanes(cache: dict, slots, prompt_lens) -> dict:
    """The admit batch's view of the cache for one prefill chunk: its lanes'
    rows of both tables, and how far each lane's prompt really reaches
    (`valid`): a window layer writes no page that holds only chunk padding,
    because in a ring such a page would land on one still inside the window."""
    return dict(cache, bt=cache["bt"][slots], bt_w=cache["bt_w"][slots], valid=prompt_lens)


def merge_lanes(cache: dict, sub: dict) -> dict:
    """The whole cache again: the pools carry the chunk's writes, the tables
    are the engine's."""
    out = dict(sub, bt=cache["bt"], bt_w=cache["bt_w"])
    del out["valid"]
    return out


def counters(cache: dict) -> tuple:
    """Device counters the cache carries for the engine to fetch with the
    cursors, as `(names, arrays)`: the names of the arrays' entries, in
    order. An integer array counts since the cache was made (a quantum
    reports the difference); a float array holds gauges, reported as fetched:
    `mhc_row_err_max`, the largest `|row sum - 1|` of a stream-mixing matrix
    after its Sinkhorn iterations in any decode tick and layer so far, and
    `diff_lambda_mean`, the mean lambda over live lanes, layers and signal
    heads of the last tick that had a live lane."""
    if "health" in cache:
        return (("expert_rows", "expert_rows_max", "mhc_row_err_max", "diff_lambda_mean"),
                (cache["moe_rows"], cache["health"]))
    return ("expert_rows", "expert_rows_max"), (cache["moe_rows"],)


# -- layers -------------------------------------------------------------------


def _rms_norm(x, weight, eps: float):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) * weight


def _layer_norm(x, p, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return (xf - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _mm(x, w, dtype, spec: str | None = None):
    """Matmul in the compute dtype, float32 accumulation and result."""
    x, w = x.astype(dtype), w.astype(dtype)
    if spec is None:
        return jnp.matmul(x, w, preferred_element_type=jnp.float32)
    return jnp.einsum(spec, x, w, preferred_element_type=jnp.float32)


def _rope(x, pos, theta: float):
    """Rotary embedding of the last axis of `x [..., T, (heads,) R]` at `pos
    [..., T]`, float32: the half-split pairing, `(x[i], x[i + R/2])` turned by
    `pos * theta^(-2i/R)`."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[..., None] * inv
    if x.ndim == pos.ndim + 2:  # a heads axis between T and R
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    a, b = xf[..., : r // 2], xf[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


@jax.named_scope("poly_norm")
def _poly_norm(z, w, b, cfg: LatentConfig):
    """`scale (w1 N(z) + w2 N(z^2) + w3 N(z^3) + clamp(b))` over the last axis
    of `z [..., F]` float32, `N(p) = p / sqrt(mean(p^2) + eps)`; `w [..., 3]`
    and `b [..., 1]` are one FFN's, or a row's own."""
    norm = lambda p: p * jax.lax.rsqrt(jnp.mean(p * p, axis=-1, keepdims=True) + cfg.norm_eps)  # noqa: E731
    z2 = z * z
    out = w[..., 0:1] * norm(z) + w[..., 1:2] * norm(z2) + w[..., 2:3] * norm(z2 * z)
    return cfg.poly_scale * (out + jnp.clip(b, -cfg.poly_bias_clamp, cfg.poly_bias_clamp))


def _activation(p, cfg: LatentConfig):
    """The gate's activation of the FFN `p`."""
    if cfg.activation == "silu":
        return jax.nn.silu
    return lambda z: _poly_norm(z, p["poly_w"], p["poly_b"], cfg)


def _gated_ffn(p, x, dtype, activate=jax.nn.silu):
    act = activate(_mm(x, p["gate"], dtype)) * _mm(x, p["up"], dtype)
    return _mm(act, p["down"], dtype)


@jax.named_scope("moe")
def _expert_layer(p, cfg: LatentConfig, x, row_mask, tick: bool = False):
    """`x [T, dim]` -> `(y [T, dim] float32, rows [experts_held])`; `tick`: the
    rows are a decode tick's lanes."""
    with jax.named_scope("router"):
        idx, gates = sigmoid_topk_route(x, p["router"], p.get("select_bias"), cfg.experts_per_token, cfg.route_scale)
    by_row = None
    if cfg.activation == "poly_norm":
        def by_row(z, expert):  # each sorted row under its own expert's weights (a row of no expert: the last's)
            own = jnp.minimum(expert, cfg.experts_held - 1)
            return _poly_norm(z, p["experts"]["poly_w"][own], p["experts"]["poly_b"][own], cfg)
    routed, rows = held_experts_ffn(x, idx, gates, p["experts"], cfg.expert_lo, cfg.compute_dtype, row_mask,
                                    activation=by_row, every_row=tick and cfg.tick_experts_every_row)
    with jax.named_scope("shared_expert"):
        shared = _gated_ffn(p["shared"], x, cfg.compute_dtype, _activation(p["shared"], cfg))
    return routed + shared, rows


def _latent_qkv(a, cfg: LatentConfig, spec: AttnSpec, h, pos):
    """The projections both kinds share. `h [B, T, dim]` (normed input) ->
    `c_q [B, T, q_rank]` float32, the query `[B, T, H, nope + rope]` (rotary
    applied) and the cache row `[B, T, kv_rank + rope]`, both in the compute
    dtype."""
    dt = cfg.compute_dtype
    s_q = math.sqrt(cfg.dim / spec.q_rank) if cfg.rescale_latents else 1.0
    s_kv = math.sqrt(cfg.dim / spec.kv_rank) if cfg.rescale_latents else 1.0
    with jax.named_scope("latent_q"):
        c_q = s_q * _rms_norm(_mm(h, a["q_a"], dt), a["q_norm"], cfg.norm_eps)
        q = _mm(c_q, a["q_b"], dt, "btr,rhd->bthd")
        q = jnp.concatenate([q[..., : spec.nope], _rope(q[..., spec.nope:], pos, spec.theta)], axis=-1).astype(dt)
    with jax.named_scope("latent_kv"):
        kv = _mm(h, a["kv_a"], dt)
        c_kv = s_kv * _rms_norm(kv[..., : spec.kv_rank], a["kv_norm"], cfg.norm_eps)
        row = jnp.concatenate([c_kv, _rope(kv[..., spec.kv_rank:], pos, spec.theta)], axis=-1).astype(dt)
    return c_q, q, row


def _softmax_latents(q_cat, keys, mask, spec: AttnSpec, dtype, per_query_keys: bool = False):
    """Absorbed queries `q_cat [Q, H, kv_rank + rope]` over latent rows `keys`
    (`[Q, K, R]` a set per query, or `[K, R]` shared) under `mask [Q, K]`: the
    softmax-weighted latents `[Q, H, kv_rank]` float32. The softmax is
    float32; a query with no key yet (never read) gives 0, not NaN."""
    ks = "qkr" if per_query_keys else "kr"
    scores = jnp.einsum(f"qhr,{ks}->qhk", q_cat, keys, preferred_element_type=jnp.float32)
    scores = scores * (1.0 / math.sqrt(spec.nope + spec.rope))
    scores = jnp.where(mask[:, None, :], scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jnp.where(jnp.isfinite(top), top, 0.0))
    probs = (e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)).astype(dtype)
    vs = "qkc" if per_query_keys else "kc"
    return jnp.einsum(f"qhk,{vs}->qhc", probs, keys[..., : spec.kv_rank], preferred_element_type=jnp.float32)


def _attend(q, kv_b, keys, mask, spec: AttnSpec, dtype, per_query_keys: bool):
    """Absorbed attention of the queries `q [Q, H, nope + rope]` over latent
    rows `keys` (`[Q, K, R]` a set per query, or `[K, R]` shared) under `mask
    [Q, K]`, one KV head a query head. `q_n` is carried into the latent space
    through `kv_b`'s key half (`q_n . (c W_k) = (q_n W_k^T) . c`) and the
    weighted latents come back through `kv_b`'s value half: `[Q, H, v]`. Done
    a block of queries at a time, so the `[Q, H, kv_rank]` intermediates never
    exist for a whole chunk."""
    q_abs = _mm(q[..., : spec.nope], kv_b[..., : spec.nope], dtype, "qhd,chd->qhc")
    q_cat = jnp.concatenate([q_abs.astype(dtype), q[..., spec.nope:]], axis=-1)
    o_heads = _softmax_latents(q_cat, keys, mask, spec, dtype, per_query_keys)
    return _mm(o_heads, kv_b[..., spec.nope:], dtype, "qhc,chv->qhv").astype(dtype)


def _absorb_grouped(q, kv_b, spec: AttnSpec, dtype):
    """The queries `q [..., H, nope + rope]` carried into the latent space of
    their KV group: `[..., H, kv_rank + rope]`, head `h` through the key half
    of `kv_b [kv_rank, G, nope + v]`'s group `h // (H / G)`."""
    lead, g = q.shape[:-2], spec.groups
    q_n = q[..., : spec.nope].reshape(lead + (g, spec.heads // g, spec.nope))
    q_abs = _mm(q_n, kv_b[..., : spec.nope], dtype, "...gjd,cgd->...gjc").reshape(lead + (spec.heads, spec.kv_rank))
    return jnp.concatenate([q_abs.astype(dtype), q[..., spec.nope:]], axis=-1)


def _diff_values(o_lat, lam, kv_b, spec: AttnSpec, dtype):
    """Weighted latents `o_lat [..., H, kv_rank]` float32 -> the signal heads'
    values `[..., H_signal, v]`: in each KV group the noise head's weighted
    latent, times the signal head's `lam [..., H_signal]`, is subtracted from
    the signal head's (both go through the same value projection, so the
    outputs' difference is the projected latents' difference), then the
    group's value half of `kv_b`. Without noise heads: the projection alone."""
    lead, g = o_lat.shape[:-2], spec.groups
    o_lat = o_lat.reshape(lead + (g, spec.heads // g, spec.kv_rank))
    if spec.noise_heads:
        with jax.named_scope("diff_combine"):
            signal = spec.out_heads // g
            o_lat = o_lat[..., :signal, :] - lam.reshape(lead + (g, signal, 1)) * o_lat[..., signal:, :]
    out = _mm(o_lat, kv_b[..., spec.nope:], dtype, "...gjc,cgv->...gjv")
    return out.reshape(lead + (spec.out_heads, spec.v)).astype(dtype)


def _attend_paged(q_cat, pool, li: int, bt, q_pos, spec: AttnSpec, dtype):
    """Absorbed queries `q_cat [B, Q, H, kv_rank + rope]` at positions `q_pos
    [B, Q]` over EVERY earlier key of their lane, read where it lies: the
    lane's pages of layer `li` of `pool [L, NP, P, row]`, `KEY_BLOCK` keys (a
    stretch of the block table `bt [B, MP]`) at a time, folded under a running
    max and sum. The walk ends at the last block any of these queries can
    see. Returns the weighted latents `[B, Q, H, kv_rank]` float32."""
    b, nq, h, _ = q_cat.shape
    p = pool.shape[2]
    pages = math.gcd(bt.shape[1], max(1, KEY_BLOCK // p))
    kb = pages * p
    scale = 1.0 / math.sqrt(spec.nope + spec.rope)
    offs = jnp.arange(kb, dtype=jnp.int32)

    def block(j, carry):
        m, l, acc = carry
        pids = jax.lax.dynamic_slice_in_dim(bt, j * pages, pages, axis=1)
        keys = pool[li, pids].reshape(b, kb, -1).astype(dtype)
        scores = jnp.einsum("bqhr,bkr->bqhk", q_cat, keys, preferred_element_type=jnp.float32) * scale
        seen = (j * kb + offs)[None, None, :] <= q_pos[:, :, None]
        # key 0 is every query's, so from the first block on the running max is finite and a block
        # with no key of some query adds exp(-inf) = 0 to it
        m, l, fix, e = online_softmax_update(m, l, jnp.where(seen[:, :, None, :], scores, -jnp.inf))
        acc = acc * fix + jnp.einsum("bqhk,bkc->bqhc", e.astype(dtype), keys[..., : spec.kv_rank],
                                     preferred_element_type=jnp.float32)
        return m, l, acc

    init = (jnp.full((b, nq, h, 1), -jnp.inf, jnp.float32), jnp.zeros((b, nq, h, 1), jnp.float32),
            jnp.zeros((b, nq, h, spec.kv_rank), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, jnp.max(q_pos) // kb + 1, block, init)
    return acc / l


def _finish_attention(a, cfg: LatentConfig, spec: AttnSpec, h, o):
    """Attended values `[B, T, H, v]` -> the sigmoid gate (a value a head, or
    a channel), the output projection. `[B, T, dim]` float32."""
    dt = cfg.compute_dtype
    with jax.named_scope("gate"):
        gate = jax.nn.sigmoid(_mm(h, a["gate"], dt))
        o = o * (gate.reshape(o.shape) if spec.gate == "elementwise" else gate[..., None])
    return _mm(o, a["o"], dt, "bthv,hvd->btd")


def _blocks(t: int) -> tuple[int, int]:
    """Queries of one lane attended at a time, and how many (lane, block)
    pairs run side by side: a decode tick (t = 1) takes every lane at once, a
    prefill chunk one block of one lane after the other."""
    qb = math.gcd(t, ATTEND_BLOCK)
    return qb, max(1, ATTEND_BLOCK // qb)


def index_scores(q_idx, w_idx, keys, q_pos):
    """The indexer's score of every key for every query, float32: `I[q, s] =
    sum_j w[q, j] ReLU(q_idx[q, j] . keys[s])`, `-inf` where `s > q_pos[q]`.
    `q_idx [Q, J, D]`, `w_idx [Q, J]`, `keys [S, D]` at positions `0..S-1`.
    Keys are scored `INDEX_KEY_BLOCK` at a time."""
    s = keys.shape[0]
    kb = math.gcd(s, INDEX_KEY_BLOCK)

    def block(kblk):
        sc = jnp.einsum("qjd,sd->qjs", q_idx, kblk, preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(sc) * w_idx[:, :, None], axis=1)  # float32 on the vector unit, no matmul pass

    score = jax.lax.map(block, keys.reshape(s // kb, kb, -1))  # [S/kb, Q, kb]
    score = jnp.moveaxis(score, 0, 1).reshape(q_idx.shape[0], s)
    return jnp.where(jnp.arange(s)[None, :] <= q_pos[:, None], score, -jnp.inf)


def _page_ids(bt, start, write_mask, p: int, t: int):
    """Where a chunk's rows go: its token's page `[B]` (a tick) or its whole
    pages `[B, t / p]`; a masked lane writes the null page."""
    from tpukit.serve import paged as paged_lib

    if t == 1:
        return jnp.where(write_mask, jnp.take_along_axis(bt, (start // p)[:, None], axis=1)[:, 0], 0)
    return jnp.where(write_mask[:, None], paged_lib.logical_pages(bt, start // p, t // p), 0)


def _write_rows(pool, li, pids, start, rows):
    """`rows [B, t, W]` into layer `li` of `pool` at `_page_ids`' pages."""
    from tpukit.serve import paged as paged_lib

    if rows.shape[1] == 1:
        return paged_lib.write_row(pool, li, pids, start % pool.shape[2], rows[:, 0])
    return paged_lib.write_row_pages(pool, li, pids, rows)


@jax.named_scope("attn")
def _full_attention(a, cfg: LatentConfig, h, pos, lat, idx, li, bt, start, write_mask, taps):
    """A full layer over the paged cache: write this chunk's latent rows and
    indexer keys (a token, or whole pages), then every query scores its
    lane's keys, selects, gathers the selected latents from the pool and
    attends them. `lat` / `idx` are the stacked pools, `li` this layer's
    index among the full layers. Returns `(out, lat, idx)`."""
    spec, dt = cfg.full, cfg.compute_dtype
    b, t = h.shape[0], h.shape[1]
    p = lat.shape[2]
    c_q, q, row = _latent_qkv(a, cfg, spec, h, pos)
    with jax.named_scope("indexer"):
        half = spec.rope  # rotary on the first `rope` of each indexer head, as on the key
        q_idx = _mm(c_q, a["idx_q"], dt, "btr,rjd->btjd")
        q_idx = jnp.concatenate([_rope(q_idx[..., :half], pos, spec.theta), q_idx[..., half:]], -1).astype(dt)
        k_idx = _layer_norm(_mm(h, a["idx_k"], dt), a["idx_k_norm"])
        k_idx = jnp.concatenate([_rope(k_idx[..., :half], pos, spec.theta), k_idx[..., half:]], -1).astype(dt)
        w_idx = _mm(h, a["idx_w"], dt) * (cfg.index_heads ** -0.5 * cfg.index_dim ** -0.5)

    pids = _page_ids(bt, start, write_mask, p, t)
    lat, idx = _write_rows(lat, li, pids, start, row), _write_rows(idx, li, pids, start, k_idx)

    s_max = bt.shape[1] * p
    topk = min(cfg.index_topk, s_max)
    qb, side = _blocks(t)

    rows_of_pool = lat.reshape(-1, lat.shape[-1])  # [L * NP * P, row]: a token's row by one flat index
    key_pos = jnp.arange(s_max, dtype=jnp.int32)

    def block(xs):
        bt_row, q_i, w_i, q_c, q_pos = xs
        with jax.named_scope("indexer"):
            keys = idx[li, bt_row].reshape(s_max, -1)
            score = index_scores(q_i, w_i, keys, q_pos)
        with jax.named_scope("select"):
            # the top-k as a sort that carries, with each key's position, the pool row it lives in:
            # looking 2,048 positions a query up in the block table afterwards costs more than the sort
            row_of_key = (li * lat.shape[1] + jnp.repeat(bt_row, p)) * p + key_pos % p
            carried = (row_of_key,) if taps is None else (row_of_key, key_pos)  # the positions only for who asks
            worst_first, rows, *sel = jax.lax.sort(
                (-score, *(jnp.broadcast_to(v, score.shape) for v in carried)),
                dimension=1, num_keys=1, is_stable=True)
            chosen = worst_first[:, :topk] < jnp.inf  # fewer than top-k keys so far: the rest are no keys
        with jax.named_scope("latent_gather"):
            got = rows_of_pool[rows[:, :topk]]  # [qb, topk, row]
        with jax.named_scope("attend"):
            out = _attend(q_c, a["kv_b"], got, chosen, spec, dt, per_query_keys=True)
        return out, [jnp.where(chosen, s[:, :topk], -1) for s in sel]

    per_block = lambda z: z.reshape((b * (t // qb), qb) + z.shape[2:])  # noqa: E731
    o_heads, sel = jax.lax.map(
        block, (jnp.repeat(bt, t // qb, axis=0), per_block(q_idx), per_block(w_idx),
                per_block(q), per_block(pos)), batch_size=side)
    if taps is not None:
        taps.append(sel[0].reshape(b, t, topk))
    return _finish_attention(a, cfg, spec, h, o_heads.reshape(b, t, spec.heads, spec.v)), lat, idx


def _lambda(a, cfg: LatentConfig, spec: AttnSpec, h, health):
    """`lambda [B, T, H_signal]` float32 off the layer's normed input (`None`
    without noise heads), handed to the tick's gauges where they are kept."""
    if not spec.noise_heads:
        return None
    lam = jax.nn.sigmoid(_mm(h, a["lam"], cfg.compute_dtype))
    if health is not None:
        health["lambda"].append(lam)
    return lam


@jax.named_scope("attn")
def _dense_attention(a, cfg: LatentConfig, h, pos, lat, li, bt, start, write_mask, health):
    """A full layer WITHOUT key selection over the paged cache: write this
    chunk's latent rows, then every query attends every key of its lane up to
    itself (`_attend_paged`; a prefill chunk a few lanes at a time). Returns
    `(out, lat)`."""
    spec, dt = cfg.full, cfg.compute_dtype
    b, t = h.shape[0], h.shape[1]
    _, q, row = _latent_qkv(a, cfg, spec, h, pos)
    lam = _lambda(a, cfg, spec, h, health)
    lat = _write_rows(lat, li, _page_ids(bt, start, write_mask, lat.shape[2], t), start, row)
    with jax.named_scope("attend"):
        q_cat = _absorb_grouped(q, a["kv_b"], spec, dt)
        side = b if t == 1 else math.gcd(b, max(1, ATTEND_BLOCK // t))  # lanes attended side by side
        group = lambda z: z.reshape((b // side, side) + z.shape[1:])  # noqa: E731
        o_lat = jax.lax.map(lambda xs: _attend_paged(xs[0], lat, li, xs[1], xs[2], spec, dt),
                            (group(q_cat), group(bt), group(pos))).reshape(b, t, spec.heads, spec.kv_rank)
    return _finish_attention(a, cfg, spec, h, _diff_values(o_lat, lam, a["kv_b"], spec, dt)), lat


@jax.named_scope("attn")
def _window_attention(a, cfg: LatentConfig, h, pos, win, lw, bt_w, start, write_mask, valid, health=None):
    """A window layer over its ring of pages. A decode tick writes its token
    and reads the pages the window can touch; a prefill chunk reads the
    pages before it, attends them and its own fresh rows, and then writes
    its pages (those that hold a real token) over the oldest. Returns
    `(out, win)`."""
    from tpukit.serve import paged as paged_lib

    spec, dt = cfg.window, cfg.compute_dtype
    b, t = h.shape[0], h.shape[1]
    p, ring = win.shape[2], bt_w.shape[1]
    back = _window_back_pages(cfg, p)
    _, q, row = _latent_qkv(a, cfg, spec, h, pos)
    first = start // p
    off = jnp.arange(p, dtype=jnp.int32)

    if t == 1:
        pids = jnp.where(write_mask, paged_lib.logical_pages(bt_w, first, 1, ring)[:, 0], 0)
        win = paged_lib.write_row(win, lw, pids, start % p, row[:, 0])
        n_read, read_from = back + 1, first - back
    else:
        if t // p > ring:
            raise ValueError(
                f"a prefill chunk of {t} tokens is more than a window layer's ring of "
                f"{ring} pages of {p} holds: chunks of at most {ring * p}"
            )
        n_read, read_from = back, first - back
    read_pids = paged_lib.logical_pages(bt_w, read_from, n_read, ring)  # [B, n_read]
    key_pos = ((read_from[:, None] + jnp.arange(n_read)[None, :])[..., None] * p + off).reshape(b, n_read * p)

    lam = _lambda(a, cfg, spec, h, health)

    def lane(xs):
        pids_row, kpos, q_c, q_pos, fresh, *lam_c = xs
        keys = win[lw, pids_row].reshape(n_read * p, -1)
        if t > 1:
            keys, kpos = jnp.concatenate([keys, fresh]), jnp.concatenate([kpos, q_pos])
        mask = ((kpos[None, :] <= q_pos[:, None]) & (kpos[None, :] > q_pos[:, None] - cfg.window_size)
                & (kpos[None, :] >= 0))
        with jax.named_scope("window_attend"):
            if not spec.kv_heads:
                return _attend(q_c, a["kv_b"], keys, mask, spec, dt, per_query_keys=False)
            o_lat = _softmax_latents(_absorb_grouped(q_c, a["kv_b"], spec, dt), keys.astype(dt), mask, spec, dt)
            return _diff_values(o_lat, lam_c[0] if lam_c else None, a["kv_b"], spec, dt)

    per_lane = (read_pids, key_pos, q, pos, row) + (() if lam is None else (lam,))
    o_heads = jax.lax.map(lane, per_lane, batch_size=max(1, ATTEND_BLOCK // t))
    if t > 1:
        pages = paged_lib.logical_pages(bt_w, first, t // p, ring)
        real = write_mask[:, None]
        if valid is not None:
            real = real & ((first[:, None] + jnp.arange(t // p)[None, :]) * p < valid[:, None])
        win = paged_lib.write_row_pages(win, lw, jnp.where(real, pages, 0), row)
    return _finish_attention(a, cfg, spec, h, o_heads), win


# -- the residual path ---------------------------------------------------------


def sinkhorn(m, iters: int):
    """`iters` times: each row of `m [n, n, ...]` over its sum, then each
    column over its sum (axis 0 counts the rows): doubly stochastic in the
    limit. One loop body, whatever `iters`."""
    def step(_, m):
        m = m / jnp.sum(m, axis=1, keepdims=True)
        return m / jnp.sum(m, axis=0, keepdims=True)

    return jax.lax.fori_loop(0, iters, step, m)


def _read_streams(p, cfg: LatentConfig, x, health):
    """What a sublayer reads of the residual state. One stream: the state, and
    no maps. Streams `x [B, T, n, dim]`: `u = sum_i H_pre[i] x[i]` and the
    maps `(H_res [n, n, B, T], H_post [B, T, n])` its write-back needs, all
    from the RMS-normalised `vec(x)`, float32 throughout (the projection at
    full precision: 24 outputs a token)."""
    if cfg.streams == 1:
        return x, None
    n = cfg.streams
    with jax.named_scope("mhc_pre"):
        flat = x.reshape(x.shape[:2] + (n * cfg.dim,))
        flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + cfg.norm_eps)
        proj = jnp.matmul(flat, p["phi"], precision=jax.lax.Precision.HIGHEST)
        pre = jax.nn.sigmoid(p["alpha"][0] * proj[..., :n] + p["b"][:n])
        post = 2.0 * jax.nn.sigmoid(p["alpha"][1] * proj[..., n: 2 * n] + p["b"][n: 2 * n])
        mix = jnp.exp(p["alpha"][2] * proj[..., 2 * n:] + p["b"][2 * n:])  # [B, T, n n]
        u = sum(pre[..., i, None] * x[:, :, i] for i in range(n))
    with jax.named_scope("mhc_sinkhorn"):  # tokens on the last axis: the 4 x 4 sums are adds of whole rows of lanes
        res = sinkhorn(jnp.moveaxis(mix, -1, 0).reshape((n, n) + mix.shape[:2]), cfg.sinkhorn_iters)
        if health is not None:
            health["row_err"].append(jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0), axis=0))  # [B, T]
    return u, (res, post)


def _write_streams(cfg: LatentConfig, x, maps, y):
    """The sublayer's output `y [B, T, dim]` back into the residual state: `x +
    y`, or `x'[i] = sum_j H_res[i, j] x[j] + H_post[i] clamp(y)`."""
    if maps is None:
        return x + y
    res, post = maps
    n = cfg.streams
    with jax.named_scope("mhc_post"):
        if cfg.hidden_clamp:
            y = jnp.clip(y, -cfg.hidden_clamp, cfg.hidden_clamp)
        return jnp.stack([sum(res[i, j][..., None] * x[:, :, j] for j in range(n)) + post[..., i, None] * y
                          for i in range(n)], axis=2)


# -- the forward passes -------------------------------------------------------


def _forward_cached(params: Params, cfg: LatentConfig, input_ids, position_ids, cache, start,
                    write_mask, taps: list | None):
    """The cached forward; `taps` (a list) receives the keys each full layer
    selected, `[B, T, top-k]` positions, -1 where a query had fewer."""
    if "bt" not in cache or jnp.ndim(start) != 1:
        raise ValueError("the latent family's cache is paged: a block table and a [B] vector `start`")
    b, t = input_ids.shape
    p = cache["lat"].shape[2]
    if t > 1 and t % p:
        raise ValueError(f"a chunk of {t} tokens is not whole pages of {p}")
    if write_mask is None:
        write_mask = jnp.ones((b,), bool)
    lat, idx, win, rows_seen = cache["lat"], cache.get("idx"), cache["win"], cache["moe_rows"]
    valid = cache.get("valid")
    # the decode ticks' gauges, where the cache keeps them: what the sublayers hand out
    health = {"row_err": [], "lambda": []} if t == 1 and "health" in cache else None
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(jnp.float32)
    if cfg.streams > 1:
        x = jnp.broadcast_to(x[:, :, None, :], (b, t, cfg.streams, cfg.dim))
    lf = lw = 0
    for i, kind in enumerate(cfg.layer_types):
        layer = params["layers"][i]
        u, maps = _read_streams(layer.get("mhc1"), cfg, x, health)
        with jax.named_scope("ln"):
            h = _rms_norm(u, layer["norm1"], cfg.norm_eps)
        if kind == WINDOW:
            attn, win = _window_attention(layer["attn"], cfg, h, position_ids, win, lw,
                                          cache["bt_w"], start, write_mask, valid, health)
            lw += 1
        elif cfg.index_topk:
            attn, lat, idx = _full_attention(layer["attn"], cfg, h, position_ids, lat, idx, lf,
                                             cache["bt"], start, write_mask, taps)
            lf += 1
        else:
            attn, lat = _dense_attention(layer["attn"], cfg, h, position_ids, lat, lf,
                                         cache["bt"], start, write_mask, health)
            lf += 1
        x = _write_streams(cfg, x, maps, attn)
        u, maps = _read_streams(layer.get("mhc2"), cfg, x, health)
        with jax.named_scope("ln"):
            h = _rms_norm(u, layer["norm2"], cfg.norm_eps)
        if "ffn" in layer:
            with jax.named_scope("ffn"):
                x = _write_streams(cfg, x, maps, _gated_ffn(layer["ffn"], h, cfg.compute_dtype,
                                                            _activation(layer["ffn"], cfg)))
        else:
            y, rows = _expert_layer(layer["moe"], cfg, h.reshape(b * t, -1),
                                    write_mask if t == 1 else None, tick=t == 1)
            x = _write_streams(cfg, x, maps, y.reshape(b, t, -1))
            if t == 1:  # the decode ticks' account; a prefill chunk's rows are not a tick's
                rows_seen = rows_seen + jnp.stack([jnp.sum(rows), jnp.max(rows)])
    if cfg.streams > 1:
        x = jnp.sum(x, axis=2)
    with jax.named_scope("head"):
        with jax.named_scope("ln"):
            x = _rms_norm(x, params["norm_out"], cfg.norm_eps)
        logits = _mm(x, params["lm_head"], cfg.compute_dtype).astype(cfg.compute_dtype)
    out = dict(cache, lat=lat, win=win, moe_rows=rows_seen)
    if idx is not None:
        out["idx"] = idx
    if health is not None:
        out["health"] = _health(cache["health"], health, write_mask)
    return logits, out


def _health(seen, health: dict, live):
    """The cache's two gauges after a decode tick: the largest Sinkhorn row
    error of a live lane so far, and this tick's mean lambda of the live
    lanes (`counters` says which is which)."""
    err = jnp.max(jnp.where(live[:, None], jnp.stack(health["row_err"]), 0.0))
    lam = jnp.stack(health["lambda"])  # [layers, B, 1, H_signal]
    lanes = jnp.sum(live)
    mean = jnp.sum(jnp.where(live[None, :, None, None], lam, 0.0)) / (jnp.maximum(lanes, 1) * lam.shape[0] * lam.shape[-1])
    return jnp.stack([jnp.maximum(seen[0], err), jnp.where(lanes > 0, mean, seen[1])])  # no live lane: as it was


def forward_cached(params: Params, cfg: LatentConfig, input_ids, position_ids, cache, start,
                   write_mask=None, mesh=None):
    """Forward a chunk through the paged cache: writes the rows of positions
    `[start, start + T)` and returns `(logits [B, T, vocab], cache)`. T is 1
    (a decode tick, `write_mask` the live lanes) or whole pages from a
    page-aligned `start` (a prefill chunk). `mesh` is accepted for the seam
    and unused: the family is served on one chip."""
    return _forward_cached(params, cfg, input_ids, position_ids, cache, start, write_mask, None)


def forward_cached_tapped(params: Params, cfg: LatentConfig, input_ids, position_ids, cache, start,
                          write_mask=None):
    """`forward_cached`, and the keys each full layer selected: `(logits,
    cache, [sel [B, T, top-k] per full layer])`, `-1` where a query had fewer
    keys than top-k. For the comparisons with the reference: the sort that
    selects carries the keys' positions along only here."""
    taps: list = []
    logits, cache = _forward_cached(params, cfg, input_ids, position_ids, cache, start, write_mask, taps)
    return logits, cache, taps


def forward(params: Params, cfg: LatentConfig, input_ids, position_ids=None, mask=None,
            rng=None, deterministic: bool = True, aux_out=None, page_size: int = 16):
    """Logits `[B, S, vocab]` of whole sequences, with no cache to keep: one
    chunk through a scratch paged cache in which each row owns its pages in
    order (the served mathematics, and nothing else to maintain). Sequences
    are padded to whole pages; `position_ids` must be `arange(S)` a row."""
    if not deterministic or rng is not None:
        raise ServedOnlyError("the latent family is served only: no dropout, no training forward")
    b, s = input_ids.shape
    pages = -(-s // page_size)
    pad = pages * page_size - s
    ids = jnp.pad(input_ids, ((0, 0), (0, pad)))
    pos = jnp.broadcast_to(jnp.arange(pages * page_size, dtype=jnp.int32), ids.shape)
    kv = "f32" if jnp.dtype(cfg.compute_dtype) == jnp.float32 else "bf16"
    cache = init_paged_cache(cfg, {"bt": b * pages + 1, "bt_w": b * pages + 1}, page_size, pages, b, kv)
    own = 1 + jnp.arange(b * pages, dtype=jnp.int32).reshape(b, pages)
    # a ring as wide as the sequence never wraps: the scratch window store holds every page
    cache = dict(cache, bt=own, bt_w=own, valid=jnp.full((b,), s, jnp.int32))
    logits, _ = forward_cached(params, cfg, ids, pos, cache, jnp.zeros((b,), jnp.int32))
    return logits[:, :s]
