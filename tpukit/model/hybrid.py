"""The hybrid state-space family: Mamba-1 selective-state layers beside a few
attention layers (Jamba, arXiv:2403.19887), SERVED ONLY (training it raises
`ServedOnlyError`). The third block family beside `gpt.py` and `latent.py`.

What a layer is (x `[T, dim]`; RMSNorm with a weight; every linear bias-free
but the convolution's and `dt_proj`'s):

    x <- x + Mixer_i(RMSNorm(x));  x <- x + W_down(silu(W_gate a) * W_up a), a = RMSNorm(x)

and then a final RMSNorm and the TIED head, `logits = x E^T`. Layer `i` is an
attention layer where `i % attn_layer_period == attn_layer_offset`, a Mamba
layer otherwise.

- **Mamba mixer** (inner width E = expand x dim, state N, `dt_rank` R,
  convolution K; `tpukit/ops/selective_scan.py`):
  `[u; z] = W_in h`, `u' = silu(conv_K(u) + b_conv)`, `[delta; B; C] = W_x
  u'` each RMSNormed with its own weight, `Delta = softplus(W_dt delta +
  b_dt)`, `A = -exp(A_log)`, the selective scan `h_t = exp(Delta_t A) h_{t-1}
  + (Delta_t u'_t) B_t`, `y_t = h_t C_t + D u'_t`, `out = W_out (y * silu(z))`.
- **Attention**: query heads over ONE K/V head (MQA), no positional encoding,
  a causal softmax at `1/sqrt(head_dim)`; every key of the lane is read off
  its pages by the latent family's key-block walk (`latent.walk_key_blocks`).

The cache is paged for the attention layers (one row kind: `k || v` a token,
`2 x head_dim` wide) and keeps, beside the pages, a RECURRENT STATE a slot for
every Mamba layer: the scan's state `ssm [L_mamba, slots, N, E]` float32 and
the convolution's last `K - 1` inputs `conv [L_mamba, K - 1, slots, E]` in the
compute dtype (the slot axis second-minor: the TPU tiles the last two axes).
The state's size is constant whatever the context (`slot_state_bytes`). A
lane whose prefill chunk starts at position 0 starts from zeros, so a reused
slot cannot leak its last request's state; a chunk's padding and a prompt's
last token leave it unchanged (the engine's first decode tick forwards that
token again: `select_lanes`); a decode tick leaves a masked lane's unchanged. A state cannot be
cut at a page, so prefix sharing and speculation have no meaning for it: the
engine refuses both by name (`serve/engine.py`), and the contiguous ring
(`init_kv_cache`).

The model reaches the serving stack through what every family offers
(`tpukit.model.family(cfg)`); beyond the others' seam it offers
`slot_state_bytes`, which the engine's byte counts add for every live lane.

Numerics: parameters in `param_dtype` (bf16 at the published widths) but for
the norm weights, `A_log`, `D` and `b_dt` (float32), matmuls in
`compute_dtype` with float32 accumulation; the residual stream, norms, the
convolution's sums, the scan and its state, and the softmax in float32; the
convolution's state holds inputs already rounded to the compute dtype, so a
decode tick reads exactly what a chunk would have.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any

import jax
import jax.numpy as jnp

from tpukit.model import latent
from tpukit.model.latent import ServedOnlyError
from tpukit.model.latent import _mm, _rms_norm, _uniform  # the families' one spelling of each
from tpukit.ops import selective_scan as ss

Params = Any
MAMBA, ATTENTION = "mamba", "attention"
ATTEND_BLOCK = 256  # queries of a prefill chunk attended at a time (lanes side by side up to it)
PREFILL_TOKENS = 4096  # tokens of a prefill chunk's lanes forwarded at a time: bounds its activations


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    dim: int
    vocab_size: int
    layer_types: tuple[str, ...]
    heads: int
    head_dim: int
    ffn_width: int
    d_state: int
    d_conv: int
    expand: int
    dt_rank: int
    norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        bad = [t for t in self.layer_types if t not in (MAMBA, ATTENTION)]
        if bad:
            raise ValueError(f"layer_types must be {MAMBA!r} or {ATTENTION!r}, got {bad}")

    @property
    def inner(self) -> int:
        return self.expand * self.dim

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def padded_vocab_size(self) -> int:
        return self.vocab_size

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)


def config_from_hf(hf: dict, *, compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16) -> HybridConfig:
    """A `HybridConfig` from the keys of a published Jamba `config.json` (as
    a benchmark configuration file carries them). What those keys can switch
    on and this family does not implement is refused by name."""
    if hf.get("model_type") != "jamba":
        raise ValueError(f"model_type {hf.get('model_type')!r}: the hybrid family reads Jamba's keys")
    refused = {"num_experts": (hf["num_experts"] != 1, "expert layers (num_experts 1 names a plain MLP)"),
               "tie_word_embeddings": (not hf["tie_word_embeddings"], "an untied head"),
               "mamba_proj_bias": (hf["mamba_proj_bias"], "biases on the mixer's projections"),
               "sliding_window": (hf.get("sliding_window") is not None, "windowed attention"),
               "num_key_value_heads": (hf["num_key_value_heads"] != 1, "more than one K/V head"),
               "hidden_act": (hf["hidden_act"] != "silu", "an MLP activation other than silu")}
    for key, (bad, what) in refused.items():
        if bad:
            raise NotImplementedError(f"{key} {hf[key]!r}: {what} is not implemented")
    if not hf["mamba_conv_bias"]:
        raise NotImplementedError("mamba_conv_bias false: the convolution keeps its bias here")
    period, offset = hf["attn_layer_period"], hf["attn_layer_offset"]
    return HybridConfig(
        dim=hf["hidden_size"], vocab_size=hf["vocab_size"],
        layer_types=tuple(ATTENTION if i % period == offset else MAMBA for i in range(hf["num_hidden_layers"])),
        heads=hf["num_attention_heads"],
        head_dim=hf["hidden_size"] // hf["num_attention_heads"], ffn_width=hf["intermediate_size"],
        d_state=hf["mamba_d_state"], d_conv=hf["mamba_d_conv"], expand=hf["mamba_expand"],
        dt_rank=hf["mamba_dt_rank"], norm_eps=hf["rms_norm_eps"],
        max_position_embeddings=hf["max_position_embeddings"],
        compute_dtype=jnp.dtype(compute_dtype), param_dtype=jnp.dtype(param_dtype),
    )


def tiny_config(**kw) -> HybridConfig:
    """The CPU tests' preset: the published pattern at toy widths (attention
    at `i % 3 == 1` of five layers: Mamba, attention, Mamba, Mamba,
    attention), float32."""
    base = dict(
        dim=64, vocab_size=97, layer_types=(MAMBA, ATTENTION, MAMBA, MAMBA, ATTENTION),
        heads=4, head_dim=16, ffn_width=96, d_state=4, d_conv=4, expand=2, dt_rank=8,
        max_position_embeddings=4096, compute_dtype=jnp.float32, param_dtype=jnp.float32,
    )
    base.update(kw)
    return HybridConfig(**base)


# -- parameters ---------------------------------------------------------------


def _init_mixer(rng, cfg: HybridConfig) -> dict:
    """Linear weights at `U(+-1/sqrt(fan_in))`; the rest as Mamba initialises
    it, so that the decay spans the published range and the state carries
    information over thousands of tokens: `A_log[e, n] = log(n + 1)`, `D = 1`,
    `b_dt = softplus^-1(dt)` with `dt` log-uniform in [1e-3, 1e-1]."""
    k = iter(jax.random.split(rng, 8))
    d, e, n, r, dt = cfg.dim, cfg.inner, cfg.d_state, cfg.dt_rank, cfg.param_dtype
    step = jnp.exp(jax.random.uniform(next(k), (e,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "in_proj": _uniform(next(k), (d, 2 * e), d, dt),
        "conv_w": _uniform(next(k), (cfg.d_conv, e), cfg.d_conv, dt),
        "conv_b": _uniform(next(k), (e,), cfg.d_conv, dt),
        "x_proj": _uniform(next(k), (e, r + 2 * n), e, dt),
        "dt_norm": jnp.ones((r,), jnp.float32), "b_norm": jnp.ones((n,), jnp.float32),
        "c_norm": jnp.ones((n,), jnp.float32),
        "dt_proj": _uniform(next(k), (r, e), r, dt),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (e, n)),
        "D": jnp.ones((e,), jnp.float32),
        "out_proj": _uniform(next(k), (e, d), e, dt),
    }


def _init_layer(rng, cfg: HybridConfig, kind: str) -> dict:
    """One layer's weights: its two norms, its mixer or attention, its MLP."""
    km, kf = jax.random.split(rng)
    d, h, hd, dt = cfg.dim, cfg.heads, cfg.head_dim, cfg.param_dtype
    layer = {"norm1": jnp.ones((d,), jnp.float32), "norm2": jnp.ones((d,), jnp.float32)}
    if kind == MAMBA:
        layer["mixer"] = _init_mixer(km, cfg)
    else:
        kq, kk, kv, ko = jax.random.split(km, 4)
        layer["attn"] = {"q": _uniform(kq, (d, h, hd), d, dt), "k": _uniform(kk, (d, hd), d, dt),
                         "v": _uniform(kv, (d, hd), d, dt), "o": _uniform(ko, (h, hd, d), h * hd, dt)}
    kg, ku, kd = jax.random.split(kf, 3)
    layer["ffn"] = {"gate": _uniform(kg, (d, cfg.ffn_width), d, dt), "up": _uniform(ku, (d, cfg.ffn_width), d, dt),
                    "down": _uniform(kd, (cfg.ffn_width, d), cfg.ffn_width, dt)}
    return layer


def init_params(rng: jax.Array, cfg: HybridConfig) -> Params:
    """Seeded weights, each layer from its own key: the Mamba layers STACKED
    (`mamba`, a leading axis of `layers_of(MAMBA)`, walked by one loop, so a
    program traces and compiles one Mamba layer whatever the depth), the
    attention layers a tuple of dicts (`attn`). The embedding, which is also
    the head, is N(0, 1/dim): logits of unit scale."""
    k_embed, *k_layers = jax.random.split(rng, 1 + cfg.num_layers)
    of = lambda kind: [k for k, t in zip(k_layers, cfg.layer_types) if t == kind]  # noqa: E731
    embed = jax.random.normal(k_embed, (cfg.vocab_size, cfg.dim), jnp.float32) / math.sqrt(cfg.dim)
    return {"embed": embed.astype(cfg.param_dtype),
            "mamba": jax.vmap(lambda k: _init_layer(k, cfg, MAMBA))(jnp.stack(of(MAMBA))),
            "attn": tuple(_init_layer(k, cfg, ATTENTION) for k in of(ATTENTION)),
            "norm_out": jnp.ones((cfg.dim,), jnp.float32)}


# -- the cache ----------------------------------------------------------------


def max_context(cfg: HybridConfig) -> int:
    """No positions: the published longest context."""
    return cfg.max_position_embeddings


def kv_heads(cfg: HybridConfig) -> int:
    """One K/V head: nothing for a mesh's `model` axis to divide."""
    return 1


def cached_decode_exact(cfg: HybridConfig) -> bool:
    """Every token's path is its own lane's: a chunk's composition changes
    nothing."""
    return True


def page_kinds(cfg: HybridConfig, page_size: int, kv_dtype: str):
    """One kind of page: an attention layer's `k || v` row a token, every
    attention layer behind the one block table."""
    from tpukit.serve import paged as paged_lib  # lazy: tpukit.serve imports tpukit.model

    item = jnp.dtype(paged_lib.storage_dtype(kv_dtype)).itemsize
    return (paged_lib.PageKind("bt", cfg.layers_of(ATTENTION), page_size * 2 * cfg.head_dim * item),)


def slot_state_bytes(cfg: HybridConfig) -> int:
    """Bytes of recurrent state one slot holds, whatever its context: the
    scan's float32 state and the convolution's inputs, every Mamba layer."""
    per_layer = cfg.d_state * cfg.inner * 4 + (cfg.d_conv - 1) * cfg.inner * jnp.dtype(cfg.compute_dtype).itemsize
    return cfg.layers_of(MAMBA) * per_layer


def init_kv_cache(cfg: HybridConfig, batch: int, max_len: int) -> dict:
    raise ServedOnlyError(
        "the hybrid family keeps a paged cache only (ServeConfig.page_size > 0): its recurrent state "
        "lives beside the pages, and the contiguous ring (and the speculation that runs on it) has no room for it"
    )


def init_paged_cache(cfg: HybridConfig, num_pages, page_size: int, pages_per_slot: int,
                     slots: int, kv_dtype: str = "bf16") -> dict:
    """The pytree the serve programs thread: `kv [La, NP, P, 2 head_dim]`
    behind block table `bt [N, MP]`, the recurrent state `ssm [Lm, N, d_state,
    E]` and `conv [Lm, d_conv - 1, N, E]`, and `live [N]`, the lanes the last
    decode tick forwarded (`counters`). `num_pages`: pages of the pool, a count
    or `{"bt": count}`."""
    from tpukit.serve import paged as paged_lib

    if kv_dtype == "int8":
        raise ValueError("the hybrid family's pages are f32 or bf16 rows: no int8 row quantizer exists")
    if isinstance(num_pages, dict):
        num_pages = num_pages["bt"]
    lm, e = cfg.layers_of(MAMBA), cfg.inner
    return {
        "kv": paged_lib.init_row_pool(cfg.layers_of(ATTENTION), num_pages, page_size, 2 * cfg.head_dim, kv_dtype),
        "bt": jnp.zeros((slots, pages_per_slot), jnp.int32),
        "ssm": jnp.zeros((lm, slots, cfg.d_state, e), jnp.float32),
        "conv": jnp.zeros((lm, cfg.d_conv - 1, slots, e), cfg.compute_dtype),
        "live": jnp.zeros((slots,), bool),
    }


def select_lanes(cache: dict, slots, prompt_lens) -> dict:
    """The admit batch's view of the cache for one prefill chunk: its lanes'
    block-table rows and recurrent state, which lanes they are, and where
    each lane's state stops (`state_end`): BEFORE the prompt's last token.
    The engine's first decode tick forwards that token again (`decode._advance`
    reads the token at cursor - 1) and samples from it; a page write of it is
    idempotent, a step of the state is not, so the state takes it then."""
    return dict(cache, bt=cache["bt"][slots], ssm=cache["ssm"][:, slots], conv=cache["conv"][:, :, slots],
                lanes=slots, state_end=prompt_lens - 1)


def merge_lanes(cache: dict, sub: dict) -> dict:
    """The whole cache again: the pool carries the chunk's writes, the lanes'
    state goes back to its slots (a lane repeated in a padded admit batch
    writes the same values twice), the table is the engine's."""
    lanes = sub["lanes"]
    out = dict(sub, bt=cache["bt"], ssm=cache["ssm"].at[:, lanes].set(sub["ssm"]),
               conv=cache["conv"].at[:, :, lanes].set(sub["conv"]))
    del out["lanes"], out["state_end"]
    return out


@jax.jit
def _gauges(ssm, conv, live):
    """`[state_bytes, ssm_state_absmax]` of the lanes `live` marks: one read
    of the scan state a sync, not one a layer and tick inside the timed
    programs."""
    n = live.shape[0]
    per_slot = (ssm.size * ssm.dtype.itemsize + conv.size * conv.dtype.itemsize) // n
    absmax = jnp.max(jnp.where(live[None, :, None, None], jnp.abs(ssm), 0.0))
    return jnp.stack([jnp.sum(live).astype(jnp.float32) * float(per_slot), absmax])


def counters(cache: dict) -> tuple:
    """Device gauges for the engine to fetch with the cursors, as `(names,
    arrays)`; float, so reported as fetched: `state_bytes`, the recurrent
    state the live lanes of the last decode tick hold (a whole number of
    bytes), and `ssm_state_absmax`, the largest |h| of those lanes' scan
    state in any Mamba layer as of this sync. Computed here, once a sync,
    by a program of its own (`_gauges`)."""
    return ("state_bytes", "ssm_state_absmax"), (_gauges(cache["ssm"], cache["conv"], cache["live"]),)


# -- layers -------------------------------------------------------------------


@jax.named_scope("mamba")
def _mamba_mixer(m, cfg: HybridConfig, h, state, conv_state, keep, n_keep):
    """`h [B, T, dim]` (normed) through one Mamba mixer from `state [B, N,
    E]` and `conv_state [K - 1, B, E]`. A decode tick (T = 1) takes one step;
    a chunk scans its tokens, the first `n_keep [B]` of them (`keep [B, T]`)
    into the state. Returns `(out [B, T, dim] float32, state, conv_state)`."""
    dt = cfg.compute_dtype
    e, n, r = cfg.inner, cfg.d_state, cfg.dt_rank
    t = h.shape[1]
    with jax.named_scope("mamba_in_proj"):
        uz = _mm(h, m["in_proj"], dt)
        u, z = uz[..., :e].astype(dt), uz[..., e:]
    lanes_first = jnp.moveaxis(conv_state, 0, 1)  # [B, K - 1, E]
    if t == 1:
        u_c, conv_state = ss.conv_step(lanes_first, u[:, 0], m["conv_w"], m["conv_b"])
        u_c = u_c[:, None]
    else:
        u_c, conv_state = ss.conv_chunk(lanes_first, u, m["conv_w"], m["conv_b"], n_keep)
    conv_state = jnp.moveaxis(conv_state, 1, 0)
    u_c = jax.nn.silu(u_c)
    with jax.named_scope("ssm_params"):
        dbc = _mm(u_c, m["x_proj"], dt)
        delta = _rms_norm(dbc[..., :r], m["dt_norm"], cfg.norm_eps)
        b = _rms_norm(dbc[..., r: r + n], m["b_norm"], cfg.norm_eps)
        c = _rms_norm(dbc[..., r + n:], m["c_norm"], cfg.norm_eps)
        delta = jax.nn.softplus(_mm(delta, m["dt_proj"], dt) + m["dt_bias"])
        a = -jnp.exp(m["A_log"]).T  # [N, E]
    if t == 1:
        state, y = ss.step(state, u_c[:, 0], delta[:, 0], b[:, 0], c[:, 0], a, m["D"])
        y = y[:, None]
    else:
        state, y = ss.scan_chunk(state, u_c, delta, b, c, a, m["D"], keep)
    with jax.named_scope("mamba_out_proj"):
        out = _mm(y * jax.nn.silu(z), m["out_proj"], dt)
    return out, state, conv_state


@jax.named_scope("attn")
def _mqa_attention(a, cfg: HybridConfig, h, pos, kv, li: int, bt, start, write_mask):
    """An attention layer over the paged cache: write this chunk's `k || v`
    rows (a token, or whole pages), then every query attends every key of
    its lane up to itself (`latent.walk_key_blocks`; a prefill chunk a few
    lanes at a time). Returns `(out [B, T, dim] float32, kv)`."""
    dt, hd = cfg.compute_dtype, cfg.head_dim
    b, t = h.shape[0], h.shape[1]
    with jax.named_scope("mqa_proj"):
        q = _mm(h, a["q"], dt, "btd,dhk->bthk").astype(dt)
        row = jnp.concatenate([_mm(h, a["k"], dt), _mm(h, a["v"], dt)], axis=-1).astype(dt)
    kv = latent._write_rows(kv, li, latent._page_ids(bt, start, write_mask, kv.shape[2], t), start, row)
    with jax.named_scope("mqa_attend"):
        side = b if t == 1 else math.gcd(b, max(1, ATTEND_BLOCK // t))  # lanes attended side by side
        group = lambda z: z.reshape((b // side, side) + z.shape[1:])  # noqa: E731
        o = jax.lax.map(
            lambda xs: latent.walk_key_blocks(xs[0], kv, li, xs[1], xs[2], dt, 1.0 / math.sqrt(hd),
                                              values=slice(hd, 2 * hd), keys=slice(None, hd)),
            (group(q), group(bt), group(pos))).reshape(b, t, cfg.heads, hd)
    return _mm(o, a["o"], dt, "bthk,hkd->btd"), kv


@jax.named_scope("ffn")
def _ffn_residual(layer, cfg: HybridConfig, x):
    """`x + W_down(silu(W_gate a) * W_up a)`, `a = RMSNorm(x)`."""
    p, dt = layer["ffn"], cfg.compute_dtype
    a = _rms_norm(x, layer["norm2"], cfg.norm_eps)
    return x + _mm(jax.nn.silu(_mm(a, p["gate"], dt)) * _mm(a, p["up"], dt), p["down"], dt)


# -- the forward passes -------------------------------------------------------


def _layers(params, cfg: HybridConfig, input_ids, position_ids, kv, bt, ssm, conv, start, write_mask, keep, n_keep):
    """Every layer over lanes `input_ids [B, T]`: `(x [B, T, dim] float32 after
    the last layer, kv, ssm, conv)`. Each run of consecutive Mamba
    layers is one `fori_loop` over their stacked weights and state."""
    t = input_ids.shape[1]
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(jnp.float32)

    def mamba(i, carry):
        x, ssm, conv = carry
        layer = jax.tree.map(lambda a: a[i], params["mamba"])
        s_in, c_in = ssm[i], conv[i]
        if t > 1:  # a lane whose chunk starts at position 0 starts from zeros
            s_in = jnp.where((start == 0)[:, None, None], 0.0, s_in)
            c_in = jnp.where((start == 0)[None, :, None], jnp.zeros((), c_in.dtype), c_in)
        y, s_new, c_new = _mamba_mixer(layer["mixer"], cfg, _rms_norm(x, layer["norm1"], cfg.norm_eps), s_in, c_in,
                                       keep, n_keep)
        if t == 1:  # a masked lane's state stays as it was
            s_new = jnp.where(write_mask[:, None, None], s_new, ssm[i])
            c_new = jnp.where(write_mask[None, :, None], c_new, conv[i])
        return _ffn_residual(layer, cfg, x + y), ssm.at[i].set(s_new), conv.at[i].set(c_new)

    lm = la = 0
    for kind, run in itertools.groupby(cfg.layer_types):
        n = len(list(run))
        if kind == MAMBA:
            x, ssm, conv = jax.lax.fori_loop(lm, lm + n, mamba, (x, ssm, conv))
            lm += n
            continue
        for layer in params["attn"][la: la + n]:
            y, kv = _mqa_attention(layer["attn"], cfg, _rms_norm(x, layer["norm1"], cfg.norm_eps), position_ids,
                                   kv, la, bt, start, write_mask)
            x = _ffn_residual(layer, cfg, x + y)
            la += 1
    return x, kv, ssm, conv


def forward_cached(params: Params, cfg: HybridConfig, input_ids, position_ids, cache, start,
                   write_mask=None, mesh=None):
    """Forward a chunk through the paged cache and the recurrent state: writes
    the attention rows of positions `[start, start + T)`, advances each
    lane's state, and returns `(logits [B, T, vocab], cache)`. T is 1 (a
    decode tick, `write_mask` the live lanes: a masked lane's state is left
    as it was) or whole pages from a page-aligned `start` (a prefill chunk,
    the cache `select_lanes`' view: a lane at `start` 0 starts from zeros).
    A chunk of more than `PREFILL_TOKENS` tokens in all runs its lanes a group
    at a time, so that its activations stay a group's. `mesh` is accepted for
    the seam and unused: the family is served on one chip."""
    if "bt" not in cache or jnp.ndim(start) != 1:
        raise ValueError("the hybrid family's cache is paged: a block table and a [B] vector `start`")
    b, t = input_ids.shape
    if t > 1 and t % cache["kv"].shape[2]:
        raise ValueError(f"a chunk of {t} tokens is not whole pages of {cache['kv'].shape[2]}")
    if write_mask is None:
        write_mask = jnp.ones((b,), bool)
    kv, ssm, conv, bt = cache["kv"], cache["ssm"], cache["conv"], cache["bt"]
    keep = n_keep = None
    if t > 1:
        n_keep = jnp.clip(cache.get("state_end", start + t) - start, 0, t).astype(jnp.int32)
        keep = jnp.arange(t, dtype=jnp.int32)[None, :] < n_keep[:, None]
    g = b if t == 1 else math.gcd(b, max(1, PREFILL_TOKENS // t))  # lanes a group
    if g == b:
        x, kv, ssm, conv = _layers(params, cfg, input_ids, position_ids, kv, bt, ssm, conv, start, write_mask, keep,
                                   n_keep)
    else:
        def group(i, carry):
            kv, ssm, conv, x = carry
            cut = lambda a, axis=0: jax.lax.dynamic_slice_in_dim(a, i * g, g, axis)  # noqa: E731
            x_g, kv, ssm_g, conv_g = _layers(
                params, cfg, cut(input_ids), cut(position_ids), kv, cut(bt), cut(ssm, 1), cut(conv, 2), cut(start),
                cut(write_mask), cut(keep), cut(n_keep))
            put = lambda a, v, axis=0: jax.lax.dynamic_update_slice_in_dim(a, v, i * g, axis)  # noqa: E731
            return kv, put(ssm, ssm_g, 1), put(conv, conv_g, 2), put(x, x_g)

        kv, ssm, conv, x = jax.lax.fori_loop(
            0, b // g, group, (kv, ssm, conv, jnp.zeros((b, t, cfg.dim), jnp.float32)))
    with jax.named_scope("head"):
        x = _rms_norm(x, params["norm_out"], cfg.norm_eps)
        logits = _mm(x, params["embed"], cfg.compute_dtype, "btd,vd->btv").astype(cfg.compute_dtype)
    out = dict(cache, kv=kv, ssm=ssm, conv=conv)
    if t == 1:
        out["live"] = write_mask
    return logits, out


def forward(params: Params, cfg: HybridConfig, input_ids, position_ids=None, mask=None,
            rng=None, deterministic: bool = True, aux_out=None, page_size: int = 16):
    """Logits `[B, S, vocab]` of whole sequences, with no cache to keep: one
    chunk through a scratch cache in which each row owns its pages in order
    and its state starts from zeros (the served mathematics, and nothing else
    to maintain). Sequences are padded to whole pages."""
    if not deterministic or rng is not None:
        raise ServedOnlyError("the hybrid family is served only: no dropout, no training forward")
    b, s = input_ids.shape
    pages = -(-s // page_size)
    ids = jnp.pad(input_ids, ((0, 0), (0, pages * page_size - s)))
    pos = jnp.broadcast_to(jnp.arange(pages * page_size, dtype=jnp.int32), ids.shape)
    kv = "f32" if jnp.dtype(cfg.compute_dtype) == jnp.float32 else "bf16"
    cache = init_paged_cache(cfg, b * pages + 1, page_size, pages, b, kv)
    own = 1 + jnp.arange(b * pages, dtype=jnp.int32).reshape(b, pages)
    cache = dict(cache, bt=own)
    logits, _ = forward_cached(params, cfg, ids, pos, cache, jnp.zeros((b,), jnp.int32))
    return logits[:, :s]
