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

The model reaches the serving stack through what every family offers
(`tpukit.model.family(cfg)`): `init_params`, `forward`, `forward_cached`,
`init_kv_cache`, `init_paged_cache`, `page_kinds`, `select_lanes` /
`merge_lanes`, `max_context`, `cached_decode_exact`. The cache is paged only.

Numerics: parameters in `param_dtype` (bf16 at the published widths: the
float32 weights of one chip's share would not fit it), matmuls in
`compute_dtype` with float32 accumulation, the residual stream, norms,
softmax, router scores and the indexer's accumulation in float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from tpukit.ops.moe_dispatch import held_experts_ffn, sigmoid_topk_route

Params = Any
FULL, WINDOW = "full_attention", "sliding_attention"
ATTEND_BLOCK = 256  # queries attended at a time: bounds the gathered latents and the scores
INDEX_KEY_BLOCK = 2048  # keys scored at a time by the indexer: bounds [queries, heads, keys]


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

    @property
    def row(self) -> int:
        """Values the cache keeps a token: the latent and the rotary key."""
        return self.kv_rank + self.rope


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
    (one chip's share of a deployment); `experts_held` overrides."""
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


# -- parameters ---------------------------------------------------------------


def _uniform(rng, shape, fan_in: int, dtype):
    bound = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(rng, shape, jnp.float32, -bound, bound).astype(dtype)


def _init_attention(rng, cfg: LatentConfig, spec: AttnSpec, indexer: bool) -> dict:
    k = iter(jax.random.split(rng, 10))
    d, dt = cfg.dim, cfg.param_dtype
    out = {
        "q_a": _uniform(next(k), (d, spec.q_rank), d, dt),
        "q_norm": jnp.ones((spec.q_rank,), jnp.float32),
        "q_b": _uniform(next(k), (spec.q_rank, spec.heads, spec.nope + spec.rope), spec.q_rank, dt),
        "kv_a": _uniform(next(k), (d, spec.row), d, dt),
        "kv_norm": jnp.ones((spec.kv_rank,), jnp.float32),
        "kv_b": _uniform(next(k), (spec.kv_rank, spec.heads, spec.nope + spec.v), spec.kv_rank, dt),
        "o": _uniform(next(k), (spec.heads, spec.v, d), spec.heads * spec.v, dt),
        "gate": _uniform(next(k), (d, spec.heads), d, dt),
    }
    if indexer:
        out.update(
            idx_q=_uniform(next(k), (spec.q_rank, cfg.index_heads, cfg.index_dim), spec.q_rank, dt),
            idx_k=_uniform(next(k), (d, cfg.index_dim), d, dt),
            idx_k_norm={"scale": jnp.ones((cfg.index_dim,), jnp.float32),
                        "bias": jnp.zeros((cfg.index_dim,), jnp.float32)},
            idx_w=_uniform(next(k), (d, cfg.index_heads), d, dt),
        )
    return out


def _init_gated_ffn(rng, d: int, width: int, dtype, experts: int = 0) -> dict:
    kg, ku, kd = jax.random.split(rng, 3)
    lead = (experts,) if experts else ()
    return {"gate": _uniform(kg, lead + (d, width), d, dtype),
            "up": _uniform(ku, lead + (d, width), d, dtype),
            "down": _uniform(kd, lead + (width, d), width, dtype)}


def init_params(rng: jax.Array, cfg: LatentConfig) -> Params:
    """Seeded weights of the layers this chip holds: a tuple of per-layer
    dicts (the kinds differ, so nothing is stacked). The router is whole and
    float32; its selection bias is drawn at a small scale so that the path
    that uses it is live."""
    k_embed, k_head, *k_layers = jax.random.split(rng, 2 + cfg.num_layers)
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        ka, kf, kr, kb, ks = jax.random.split(k_layers[i], 5)
        spec = cfg.full if kind == FULL else cfg.window
        layer = {"norm1": jnp.ones((cfg.dim,), jnp.float32), "norm2": jnp.ones((cfg.dim,), jnp.float32),
                 "attn": _init_attention(ka, cfg, spec, indexer=kind == FULL)}
        if i < cfg.first_dense:
            layer["ffn"] = _init_gated_ffn(kf, cfg.dim, cfg.dense_width, cfg.param_dtype)
        else:
            layer["moe"] = {
                "router": _uniform(kr, (cfg.dim, cfg.n_experts), cfg.dim, jnp.float32),
                "select_bias": 0.02 * jax.random.normal(kb, (cfg.n_experts,), jnp.float32),
                "experts": _init_gated_ffn(kf, cfg.dim, cfg.expert_width, cfg.param_dtype, cfg.experts_held),
                "shared": _init_gated_ffn(ks, cfg.dim, cfg.expert_width, cfg.param_dtype),
            }
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
    """Two kinds of page: a full layer's latent row and indexer key a token,
    for the whole context; a window layer's latent row, as a ring of the
    pages a window can touch (`ceil(window / P) + 1`: the window's span at
    any alignment, and the page being written)."""
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
    `num_pages`: pages of each pool, by block-table key."""
    from tpukit.serve import paged as paged_lib

    if kv_dtype == "int8":
        raise ValueError("the latent family's pages are f32 or bf16 rows: no int8 row quantizer exists")
    full, win = page_kinds(cfg, page_size, kv_dtype)
    return {
        "lat": paged_lib.init_row_pool(full.layers, num_pages["bt"], page_size, cfg.full.row, kv_dtype),
        "idx": paged_lib.init_row_pool(full.layers, num_pages["bt"], page_size, cfg.index_dim, kv_dtype),
        "win": paged_lib.init_row_pool(win.layers, num_pages["bt_w"], page_size, cfg.window.row, kv_dtype),
        "bt": jnp.zeros((slots, pages_per_slot), jnp.int32),
        "bt_w": jnp.zeros((slots, win.ring_pages), jnp.int32),
        "moe_rows": jnp.zeros((2,), jnp.int32),
    }


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
    cursors, as `(names, arrays)`: the names of what the arrays' entries
    count, in order, cumulative since the cache was made."""
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


def _gated_ffn(p, x, dtype):
    act = jax.nn.silu(_mm(x, p["gate"], dtype)) * _mm(x, p["up"], dtype)
    return _mm(act, p["down"], dtype)


@jax.named_scope("moe")
def _expert_layer(p, cfg: LatentConfig, x, row_mask):
    """`x [T, dim]` -> `(y [T, dim] float32, rows [experts_held])`."""
    idx, gates = sigmoid_topk_route(x, p["router"], p["select_bias"], cfg.experts_per_token)
    routed, rows = held_experts_ffn(x, idx, gates, p["experts"], cfg.expert_lo, cfg.compute_dtype, row_mask)
    with jax.named_scope("shared_expert"):
        shared = _gated_ffn(p["shared"], x, cfg.compute_dtype)
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


def _attend(q, kv_b, keys, mask, spec: AttnSpec, dtype, per_query_keys: bool):
    """Absorbed attention of the queries `q [Q, H, nope + rope]` over latent
    rows `keys` (`[Q, K, R]` a set per query, or `[K, R]` shared) under `mask
    [Q, K]`. `q_n` is carried into the latent space through `kv_b`'s key half
    (`q_n . (c W_k) = (q_n W_k^T) . c`), the scores' softmax is float32, and
    the weighted latents come back through `kv_b`'s value half: `[Q, H, v]`.
    Done a block of queries at a time, so the `[Q, H, kv_rank]` intermediates
    never exist for a whole chunk."""
    q_abs = _mm(q[..., : spec.nope], kv_b[..., : spec.nope], dtype, "qhd,chd->qhc")
    q_cat = jnp.concatenate([q_abs.astype(dtype), q[..., spec.nope:]], axis=-1)
    ks = "qkr" if per_query_keys else "kr"
    scores = jnp.einsum(f"qhr,{ks}->qhk", q_cat, keys, preferred_element_type=jnp.float32)
    scores = scores * (1.0 / math.sqrt(spec.nope + spec.rope))
    scores = jnp.where(mask[:, None, :], scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - jnp.where(jnp.isfinite(top), top, 0.0))  # a query with no key yet (never read) gives 0, not NaN
    probs = (e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)).astype(dtype)
    vs = "qkc" if per_query_keys else "kc"
    o_heads = jnp.einsum(f"qhk,{vs}->qhc", probs, keys[..., : spec.kv_rank],
                       preferred_element_type=jnp.float32)
    return _mm(o_heads, kv_b[..., spec.nope:], dtype, "qhc,chv->qhv").astype(dtype)


def _finish_attention(a, cfg: LatentConfig, spec: AttnSpec, h, o):
    """Attended values `[B, T, H, v]` -> the headwise gate, the output
    projection. `[B, T, dim]` float32."""
    dt = cfg.compute_dtype
    with jax.named_scope("gate"):
        o = o * jax.nn.sigmoid(_mm(h, a["gate"], dt))[..., None]
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


@jax.named_scope("attn")
def _full_attention(a, cfg: LatentConfig, h, pos, lat, idx, li, bt, start, write_mask, taps):
    """A full layer over the paged cache: write this chunk's latent rows and
    indexer keys (a token, or whole pages), then every query scores its
    lane's keys, selects, gathers the selected latents from the pool and
    attends them. `lat` / `idx` are the stacked pools, `li` this layer's
    index among the full layers. Returns `(out, lat, idx)`."""
    from tpukit.serve import paged as paged_lib

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

    if t == 1:
        pids = jnp.where(write_mask, jnp.take_along_axis(bt, (start // p)[:, None], axis=1)[:, 0], 0)
        lat = paged_lib.write_row(lat, li, pids, start % p, row[:, 0])
        idx = paged_lib.write_row(idx, li, pids, start % p, k_idx[:, 0])
    else:
        pids = jnp.where(write_mask[:, None], paged_lib.logical_pages(bt, start // p, t // p), 0)
        lat = paged_lib.write_row_pages(lat, li, pids, row)
        idx = paged_lib.write_row_pages(idx, li, pids, k_idx)

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


@jax.named_scope("attn")
def _window_attention(a, cfg: LatentConfig, h, pos, win, lw, bt_w, start, write_mask, valid):
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

    def lane(xs):
        pids_row, kpos, q_c, q_pos, fresh = xs
        keys = win[lw, pids_row].reshape(n_read * p, -1)
        if t > 1:
            keys, kpos = jnp.concatenate([keys, fresh]), jnp.concatenate([kpos, q_pos])
        mask = ((kpos[None, :] <= q_pos[:, None]) & (kpos[None, :] > q_pos[:, None] - cfg.window_size)
                & (kpos[None, :] >= 0))
        with jax.named_scope("window_attend"):
            return _attend(q_c, a["kv_b"], keys, mask, spec, dt, per_query_keys=False)

    o_heads = jax.lax.map(lane, (read_pids, key_pos, q, pos, row), batch_size=max(1, ATTEND_BLOCK // t))
    if t > 1:
        pages = paged_lib.logical_pages(bt_w, first, t // p, ring)
        real = write_mask[:, None]
        if valid is not None:
            real = real & ((first[:, None] + jnp.arange(t // p)[None, :]) * p < valid[:, None])
        win = paged_lib.write_row_pages(win, lw, jnp.where(real, pages, 0), row)
    return _finish_attention(a, cfg, spec, h, o_heads), win


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
    lat, idx, win, rows_seen = cache["lat"], cache["idx"], cache["win"], cache["moe_rows"]
    valid = cache.get("valid")
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(jnp.float32)
    lf = lw = 0
    for i, kind in enumerate(cfg.layer_types):
        layer = params["layers"][i]
        with jax.named_scope("ln"):
            h = _rms_norm(x, layer["norm1"], cfg.norm_eps)
        if kind == FULL:
            attn, lat, idx = _full_attention(layer["attn"], cfg, h, position_ids, lat, idx, lf,
                                             cache["bt"], start, write_mask, taps)
            lf += 1
        else:
            attn, win = _window_attention(layer["attn"], cfg, h, position_ids, win, lw,
                                          cache["bt_w"], start, write_mask, valid)
            lw += 1
        x = x + attn
        with jax.named_scope("ln"):
            h = _rms_norm(x, layer["norm2"], cfg.norm_eps)
        if "ffn" in layer:
            with jax.named_scope("ffn"):
                x = x + _gated_ffn(layer["ffn"], h, cfg.compute_dtype)
        else:
            y, rows = _expert_layer(layer["moe"], cfg, h.reshape(b * t, -1),
                                    write_mask if t == 1 else None)
            x = x + y.reshape(b, t, -1)
            if t == 1:  # the decode ticks' account; a prefill chunk's rows are not a tick's
                rows_seen = rows_seen + jnp.stack([jnp.sum(rows), jnp.max(rows)])
    with jax.named_scope("head"):
        with jax.named_scope("ln"):
            x = _rms_norm(x, params["norm_out"], cfg.norm_eps)
        logits = _mm(x, params["lm_head"], cfg.compute_dtype).astype(cfg.compute_dtype)
    return logits, dict(cache, lat=lat, idx=idx, win=win, moe_rows=rows_seen)


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
