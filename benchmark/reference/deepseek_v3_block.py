"""Plain float32 reference of the block the deepseek-v3 configuration runs.

Written from DeepSeek-V3's published `config.json` keys and the layer
equations of its official inference code (`inference/model.py`: `MLA` in its
naive form, `precompute_freqs_cis`, `apply_rotary_emb`, `Gate`, `MoE`);
imports nothing from tpukit: no cache, no pages, no absorbed attention, no
grouped matmul, no batching. Every matmul is float32 under
`default_matmul_precision("highest")`. One sequence at a time.

    x0 = embed[ids];   h = x + Attn(RMSNorm(x));   y = h + FFN(RMSNorm(h))
    logits = RMSNorm(x_L) W_head                     RMSNorm: eps, a weight

    Attn (a = RMSNorm(x); every linear bias-free), EXPANDED, every head its own K and V:
      c_q = RMSNorm(a W_qa);  [q_n; q_r]_h = c_q W_qb[h];  q_r <- RoPE(q_r)
      [c_kv; k_r] = a W_kva;  c_kv <- RMSNorm(c_kv);  k_r <- RoPE(k_r)   (one k_r for all heads)
      [k_n; v]_h = c_kv W_kvb[h];   k_h = [k_n,h; k_r]                  (192 wide)
      p[t, h, .] = softmax over s <= t of (q_h . k_h) x 192^-1/2 x mscale^2
      o[t, h] = sum_s p v_h;   out = concat_h(o) W_o
    RoPE: pairs (x[2i], x[2i+1]) turned by pos * f_i (interleaved, as `apply_rotary_emb`
      views them as complex numbers); YaRN, as `precompute_freqs_cis` does wherever the
      longest context passes original_max_position_embeddings:
        f_i = theta^(-2i/R);  d(n) = R ln(original / (2 pi n)) / (2 ln theta);
        low = max(floor(d(beta_fast)), 0), high = min(ceil(d(beta_slow)), R - 1);
        s_i = 1 - clamp((i - low) / (high - low), 0, 1);  f_i <- f_i / factor (1 - s_i) + f_i s_i
      mscale = 0.1 mscale_all_dim ln(factor) + 1, on the softmax scale squared (not on cos, sin).
    FFN, layer < first_k_dense_replace:  (silu(f W_gate) * f W_up) W_down
    FFN, later layers (`Gate`):  s = sigmoid(f W_r);  b the selection bias;
      the experts in n_group groups; a group's score the sum of its two largest s + b;
      the topk_group best groups kept, every other expert's s + b set to -inf;
      the num_experts_per_tok experts of largest s + b;  g_e = routed_scaling_factor x s_e /
      sum over the chosen of s;  y = sum over chosen AND held e of g_e E_e(f) + E_shared(f)
      (the normalisation runs over all the chosen, held here or not)

The parameter tree is the one `tpukit.model.latent.init_params` builds (a
tuple of per-layer dicts); only its layout is shared with the program. The
layers are walked one at a time on the host, each jitted function compiled
once a sequence length and reused by every layer; attention runs in groups of
heads (each group's share of the output projection summed as it goes) and
blocks of queries, the FFNs in blocks of rows, the held experts one at a
time, so that 20k tokens fit beside the served weights.

`round_to` rounds both operands of every matmul to that dtype first (the
products still accumulate in float32): the reference "computed in a lower
precision", which the comparison's limits must refuse.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256
HEAD_GROUP = 16
ROW_BLOCK = 2048


def _mm(spec, a, b, round_to):
    if round_to is not None:
        a, b = a.astype(round_to), b.astype(round_to)
    return jnp.einsum(spec, a.astype(F32), b.astype(F32))


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _blocked(s: int, block: int) -> int:
    return math.gcd(s, block) if s % block else block


def rope_freqs(hf: dict) -> np.ndarray:
    """The rotary frequencies `[qk_rope_head_dim / 2]` by the published
    formula, YaRN's where the longest context passes the original one."""
    r, theta = hf["qk_rope_head_dim"], float(hf["rope_theta"])
    freqs = 1.0 / theta ** (np.arange(0, r, 2, dtype=np.float64) / r)
    y = hf.get("rope_scaling") or {}
    if y and hf["max_position_embeddings"] > y["original_max_position_embeddings"]:
        def correction_dim(rotations):
            return r * math.log(y["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(correction_dim(y["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(y["beta_slow"])), r - 1)
        if low == high:
            high += 0.001
        smooth = 1 - np.clip((np.arange(r // 2, dtype=np.float64) - low) / (high - low), 0, 1)
        freqs = freqs / y["factor"] * (1 - smooth) + freqs * smooth
    return freqs.astype(np.float32)


def softmax_scale(hf: dict) -> float:
    """`(nope + rope)^-1/2`, times `mscale^2` under YaRN."""
    scale = (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]) ** -0.5
    y = hf.get("rope_scaling") or {}
    if y and hf["max_position_embeddings"] > y["original_max_position_embeddings"] and y["factor"] > 1:
        mscale = 0.1 * y.get("mscale_all_dim", 1) * math.log(y["factor"]) + 1.0
        scale = scale * mscale * mscale
    return scale


def _rope(x, pos, freqs):
    """x [S, ..., R] at positions pos [S], pairs (x[2i], x[2i+1])."""
    ang = pos.astype(F32)[:, None] * freqs
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1] // 2,))
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang), a * jnp.sin(ang) + b * jnp.cos(ang)], -1).reshape(x.shape)


def _sizes(hf: dict) -> tuple:
    return tuple(sorted(dict(heads=hf["num_attention_heads"], nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
                             kv_rank=hf["kv_lora_rank"], eps=float(hf["rms_norm_eps"]),
                             scale=softmax_scale(hf)).items()))


@partial(jax.jit, static_argnames=("sizes", "round_to"))
def attention(x, norm1, attn, freqs, *, sizes: tuple, round_to=None):
    """`Attn(RMSNorm(x))` for one layer (its input norm's weight and its
    `attn` weights) on `x [S, hidden]` float32, every head's keys and values
    expanded from the latent."""
    z = dict(sizes)
    heads, nope, kv_rank, eps, scale = (z[k] for k in ("heads", "nope", "kv_rank", "eps", "scale"))
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        pos = jnp.arange(s)
        a = _rms_norm(x, norm1, eps)
        c_q = _rms_norm(_mm("sd,dr->sr", a, attn["q_a"], round_to), attn["q_norm"], eps)
        kv = _mm("sd,dr->sr", a, attn["kv_a"], round_to)
        c_kv = _rms_norm(kv[:, :kv_rank], attn["kv_norm"], eps)
        k_r = _rope(kv[:, kv_rank:], pos, freqs)
        qb, hg = _blocked(s, QUERY_BLOCK), _blocked(heads, HEAD_GROUP)
        group = lambda w: jnp.moveaxis(w.reshape(w.shape[0], heads // hg, hg, w.shape[2]), 1, 0)  # noqa: E731

        def head_group(out, ws):
            q_b_w, kv_b_w, o_w = ws  # [q_rank, hg, nope + rope], [kv_rank, hg, nope + v], [hg, v, hidden]
            q = _mm("sr,rhd->shd", c_q, q_b_w, round_to)
            q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, freqs)], axis=-1)
            kvh = _mm("sc,chd->shd", c_kv, kv_b_w, round_to)
            k = jnp.concatenate([kvh[..., :nope], jnp.broadcast_to(k_r[:, None, :], (s, hg, k_r.shape[-1]))], -1)
            v = kvh[..., nope:]

            def block(xs):
                q_blk, pos_b = xs
                score = _mm("qhd,shd->qhs", q_blk, k, round_to) * scale
                allowed = pos[None, :] <= pos_b[:, None]
                p = jax.nn.softmax(jnp.where(allowed[:, None, :], score, -jnp.inf), axis=-1)
                return _mm("qhs,shv->qhv", p, v, round_to)

            o = jax.lax.map(block, (q.reshape((s // qb, qb) + q.shape[1:]), pos.reshape(s // qb, qb)))
            return out + _mm("shv,hvd->sd", o.reshape(s, hg, -1), o_w, round_to), None

        o_w = attn["o"].reshape((heads // hg, hg) + attn["o"].shape[1:])
        out, _ = jax.lax.scan(head_group, jnp.zeros((s, attn["o"].shape[-1]), F32),
                              (group(attn["q_b"]), group(attn["kv_b"]), o_w))
        return out


@partial(jax.jit, static_argnames=("round_to",))
def gated_ffn(f, p, round_to=None):
    """`(silu(f W_gate) * f W_up) W_down`, `ROW_BLOCK` rows at a time (the
    rows padded with zeros to a whole number of blocks, then dropped)."""
    s, d = f.shape
    rows = min(s, ROW_BLOCK)
    n = -(-s // rows)

    def block(x):
        act = jax.nn.silu(_mm("sd,df->sf", x, p["gate"], round_to)) * _mm("sd,df->sf", x, p["up"], round_to)
        return _mm("sf,fd->sd", act, p["down"], round_to)

    with jax.default_matmul_precision("highest"):
        x = jnp.pad(f, ((0, n * rows - s), (0, 0))).reshape(n, rows, d)
        return jax.lax.map(block, x).reshape(n * rows, -1)[:s]


@partial(jax.jit, static_argnames=("top_k", "groups", "topk_groups", "scale", "round_to"))
def route(f, router, select_bias, *, top_k: int, groups: int, topk_groups: int, scale: float, round_to=None):
    """`[S, n_experts]` gates as `Gate` gives them: g_e for the chosen experts,
    0 for the others."""
    with jax.default_matmul_precision("highest"):
        score = jax.nn.sigmoid(_mm("sd,de->se", f, router, round_to))
        s, e = score.shape
        biased = (score + select_bias.astype(F32)).reshape(s, groups, e // groups)
        group_score = jax.lax.top_k(biased, 2)[0].sum(-1)
        _, best = jax.lax.top_k(group_score, topk_groups)
        dropped = jnp.ones((s, groups), bool).at[jnp.arange(s)[:, None], best].set(False)
        biased = jnp.where(dropped[:, :, None], -jnp.inf, biased).reshape(s, e)
        _, idx = jax.lax.top_k(biased, top_k)
        chosen = jnp.zeros(score.shape, bool).at[jnp.arange(s)[:, None], idx].set(True)
        kept = jnp.where(chosen, score, 0.0)
        return scale * kept / jnp.sum(kept, axis=-1, keepdims=True)


_rms_jit = jax.jit(_rms_norm, static_argnums=2)


def _route_args(hf: dict) -> dict:
    return dict(top_k=hf["num_experts_per_tok"], groups=hf.get("n_group", 1), topk_groups=hf.get("topk_group", 1),
                scale=float(hf["routed_scaling_factor"]))


def expert_layer(f, moe, *, hf: dict, expert_lo: int = 0, round_to=None):
    """The held experts' part and the shared expert, one expert at a time."""
    gates = route(f, moe["router"], moe["select_bias"], round_to=round_to, **_route_args(hf))
    y = gated_ffn(f, moe["shared"], round_to=round_to)
    for e in range(moe["experts"]["gate"].shape[0]):
        one = {k: w[e] for k, w in moe["experts"].items()}
        y = y + gates[:, expert_lo + e, None] * gated_ffn(f, one, round_to=round_to)
    return y


def hidden_states(params, ids, *, hf: dict, expert_lo: int = 0, round_to=None):
    """The residual stream `[S, hidden]` after the last layer, for one
    sequence `ids [S]`."""
    eps, sizes, freqs = float(hf["rms_norm_eps"]), _sizes(hf), jnp.asarray(rope_freqs(hf))
    x = params["embed"][ids].astype(F32)
    for layer in params["layers"]:
        x = x + attention(x, layer["norm1"], layer["attn"], freqs, sizes=sizes, round_to=round_to)
        f = _rms_jit(x, layer["norm2"], eps)
        if "ffn" in layer:
            x = x + gated_ffn(f, layer["ffn"], round_to=round_to)
        else:
            x = x + expert_layer(f, layer["moe"], hf=hf, expert_lo=expert_lo, round_to=round_to)
    return x


@partial(jax.jit, static_argnames=("eps", "round_to"))
def head(x, norm_out, lm_head, *, eps: float, round_to=None):
    with jax.default_matmul_precision("highest"):
        return _mm("sd,dv->sv", _rms_norm(x, norm_out, eps), lm_head, round_to)


def lowered_programs(params, s: int, *, hf: dict, round_to=None) -> list:
    """The jitted functions `logits` calls for a sequence of `s` tokens, each
    lowered from shapes alone (`params` may be arrays or a tree of
    `ShapeDtypeStruct`s), one a distinct program: a caller that compiles them
    ahead (in threads, into the persistent cache) leaves `logits` only its
    fetches and the small ops between the functions."""
    sds = lambda shape, dt=F32: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
    like = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)  # noqa: E731
    x = sds((s, hf["hidden_size"]))
    dense = next(layer for layer in params["layers"] if "ffn" in layer)
    moe = next(layer for layer in params["layers"] if "moe" in layer)["moe"]
    one = {k: sds(w.shape[1:], w.dtype) for k, w in moe["experts"].items()}  # the shared expert's shapes too
    return [
        attention.lower(x, like(dense["norm1"]), like(dense["attn"]), sds((hf["qk_rope_head_dim"] // 2,)),
                        sizes=_sizes(hf), round_to=round_to),
        _rms_jit.lower(x, like(dense["norm2"]), float(hf["rms_norm_eps"])),
        gated_ffn.lower(x, like(dense["ffn"]), round_to=round_to),
        gated_ffn.lower(x, one, round_to=round_to),
        route.lower(x, like(moe["router"]), like(moe["select_bias"]), round_to=round_to, **_route_args(hf)),
        head.lower(x, like(params["norm_out"]), like(params["lm_head"]), eps=float(hf["rms_norm_eps"]),
                   round_to=round_to),
    ]


def logits(params, ids, *, hf: dict, expert_lo: int = 0, round_to=None, selected: list | None = None):
    """`[S, vocab]` float32 logits of one sequence `ids [S]` over the
    vocabulary slice the parameters hold. `selected` is `dots3_block`'s
    argument, taken so that one mode drives both: no layer here selects keys,
    so nothing is appended."""
    x = hidden_states(params, ids, hf=hf, expert_lo=expert_lo, round_to=round_to)
    return head(x, params["norm_out"], params["lm_head"], eps=float(hf["rms_norm_eps"]), round_to=round_to)
