"""Plain float32 reference of the block the dots3-note-prev configuration runs.

Written from the layer equations of ISSUE 27 / `PERF.md` section 4 and the
published `config.json` keys; imports nothing from tpukit: no cache, no
pages, no absorbed attention, no grouped matmul, no batching. Every matmul is
float32 under `default_matmul_precision("highest")`. One sequence at a time.

    x0 = embed[ids];   h = x + Attn(RMSNorm(x));   y = h + FFN(RMSNorm(h))
    logits = RMSNorm(x_L) W_head                     RMSNorm: eps, a weight

    Attn, both kinds (a = RMSNorm(x); every linear bias-free):
      c_q = s_q RMSNorm(a W_qa);  [q_n; q_r] = c_q W_qb per head;  q_r <- RoPE(q_r)
      [c_kv; k_r] = a W_kva;  c_kv <- s_kv RMSNorm(c_kv);  k_r <- RoPE(k_r)   (one k_r for all heads)
      [k_n; v] = c_kv W_kvb per head                (expanded here, for every key)
      p[t, h, .] = softmax over the allowed s of (q_n . k_n + q_r . k_r) / sqrt(nope + rope)
      o[t, h] = sigmoid(a W_g)[t, h] * sum_s p v;   out = concat_h(o) W_o
      s_q = sqrt(hidden / q_rank), s_kv = sqrt(hidden / kv_rank)  (apply_mla_qkv_lora_rescale)
    allowed keys, full layer: the index_topk keys s <= t of largest
      I[t, s] = sum_j w[t, j] ReLU(q_j[t] . k[s]),   q = c_q W_Iq (RoPE on the first rope dims of
      each head),  k = LayerNorm(a W_Ik) (same RoPE),  w = a W_Iw / sqrt(heads_I) / sqrt(dim_I);
      every s <= t while t < index_topk
    allowed keys, window layer: t - (window - 1) <= s <= t
    FFN, layer < first_k_dense_replace:  (silu(f W_gate) * f W_up) W_down
    FFN, later layers:  s = sigmoid(f W_r);  the num_experts_per_tok experts of largest s + b;
      g_e = s_e / sum over the chosen of s;  y = sum over chosen AND held e of g_e E_e(f) + E_shared(f)
      (the normalisation runs over all the chosen, held here or not)
    RoPE: pairs (x[i], x[i + R/2]) turned by pos * theta^(-2i/R).

The parameter tree is the one `tpukit.model.latent.init_params` builds (a
tuple of per-layer dicts); only its layout is shared with the program. The
layers are walked one at a time on the host, each layer's weights upcast to
float32 on their own (the whole share in float32 would be 16 GB); attention
runs in blocks of queries and groups of heads, the held experts one at a
time, so that a 16k-token sequence fits beside the served weights.

`round_to` rounds both operands of every matmul to that dtype first (the
products still accumulate in float32): the reference "computed in a lower
precision", which the comparison's limits must refuse.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
FULL = "full_attention"
QUERY_BLOCK = 256
HEAD_GROUP = 16


def _mm(spec, a, b, round_to):
    if round_to is not None:
        a, b = a.astype(round_to), b.astype(round_to)
    return jnp.einsum(spec, a.astype(F32), b.astype(F32))


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _layer_norm(x, p, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"].astype(F32) + p["bias"].astype(F32)


def _rope(x, pos, theta):
    """x [S, ..., R] at positions pos [S]."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    ang = pos.astype(F32)[:, None] * inv
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (r // 2,))
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang), a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)


def _attn_sizes(hf: dict, kind: str) -> dict:
    pre = "" if kind == FULL else "swa_"
    return dict(heads=hf[pre + "num_attention_heads"], nope=hf[pre + "qk_nope_head_dim"],
                rope=hf[pre + "qk_rope_head_dim"], q_rank=hf[pre + "q_lora_rank"],
                kv_rank=hf[pre + "kv_lora_rank"],
                theta=float(hf[pre + "rope_theta"]))


def _blocked(s: int, block: int) -> int:
    return math.gcd(s, block) if s % block else block


@partial(jax.jit, static_argnames=("topk", "index_rope", "theta", "round_to"))
def selected_keys(a, c_q, attn, *, topk: int, index_rope: int, theta: float, round_to=None):
    """The keys each query of a full layer attends: `[S, min(topk, S)]` key
    positions, -1 where the query has fewer keys than top-k."""
    with jax.default_matmul_precision("highest"):
        s = a.shape[0]
        pos = jnp.arange(s)
        heads, dim = attn["idx_q"].shape[1:]
        turn = lambda z: jnp.concatenate([_rope(z[..., :index_rope], pos, theta), z[..., index_rope:]], -1)  # noqa: E731
        q = turn(_mm("sr,rjd->sjd", c_q, attn["idx_q"], round_to))
        k = turn(_layer_norm(_mm("sd,de->se", a, attn["idx_k"], round_to), attn["idx_k_norm"]))
        w = _mm("sd,dj->sj", a, attn["idx_w"], round_to) / math.sqrt(heads) / math.sqrt(dim)
        qb = _blocked(s, QUERY_BLOCK)

        def block(xs):
            q_b, w_b, pos_b = xs
            score = jnp.sum(jax.nn.relu(_mm("qjd,sd->qjs", q_b, k, round_to)) * w_b[:, :, None], axis=1)
            score = jnp.where(pos[None, :] <= pos_b[:, None], score, -jnp.inf)
            top, sel = jax.lax.top_k(score, min(topk, s))
            return jnp.where(jnp.isfinite(top), sel, -1)

        sel = jax.lax.map(block, (q.reshape(s // qb, qb, heads, dim), w.reshape(s // qb, qb, heads),
                                  pos.reshape(s // qb, qb)))
        return sel.reshape(s, -1)


@partial(jax.jit, static_argnames=("kind", "hf_sizes", "eps", "window", "rescale", "round_to"))
def attention(x, layer, sel, *, kind: str, hf_sizes: tuple, eps: float, window: int, rescale: bool, round_to=None):
    """`Attn(RMSNorm(x))` for one layer on `x [S, hidden]` float32. `sel` is
    `selected_keys`' answer for a full layer (ignored by a window layer).
    Returns `(out, a, c_q)`."""
    z = dict(hf_sizes)
    heads, nope, rope, q_rank, kv_rank, theta = (z[k] for k in ("heads", "nope", "rope", "q_rank", "kv_rank", "theta"))
    attn = layer["attn"]
    with jax.default_matmul_precision("highest"):
        s, hidden = x.shape
        pos = jnp.arange(s)
        a = _rms_norm(x, layer["norm1"], eps)
        s_q = math.sqrt(hidden / q_rank) if rescale else 1.0
        s_kv = math.sqrt(hidden / kv_rank) if rescale else 1.0
        c_q = s_q * _rms_norm(_mm("sd,dr->sr", a, attn["q_a"], round_to), attn["q_norm"], eps)
        kv = _mm("sd,dr->sr", a, attn["kv_a"], round_to)
        c_kv = s_kv * _rms_norm(kv[:, :kv_rank], attn["kv_norm"], eps)
        k_r = _rope(kv[:, kv_rank:], pos, theta)
        gate = jax.nn.sigmoid(_mm("sd,dh->sh", a, attn["gate"], round_to))
        qb = _blocked(s, QUERY_BLOCK)
        hg = _blocked(heads, HEAD_GROUP)
        group = lambda w: jnp.moveaxis(w.reshape(w.shape[0], heads // hg, hg, w.shape[2]), 1, 0)  # noqa: E731

        def head_group(ws):
            q_b_w, kv_b_w = ws  # [q_rank, hg, nope + rope], [kv_rank, hg, nope + v]
            q = _mm("sr,rhd->shd", c_q, q_b_w, round_to)
            q_n, q_r = q[..., :nope], _rope(q[..., nope:], pos, theta)
            kvh = _mm("sc,chd->shd", c_kv, kv_b_w, round_to)
            k_n, v = kvh[..., :nope], kvh[..., nope:]

            def block(xs):
                qn_b, qr_b, pos_b, sel_b = xs
                score = (_mm("qhd,shd->qhs", qn_b, k_n, round_to) + _mm("qhd,sd->qhs", qr_b, k_r, round_to))
                score = score / math.sqrt(nope + rope)
                if kind == FULL:
                    allowed = jnp.zeros((qb, s + 1), bool).at[jnp.arange(qb)[:, None], sel_b].set(True)[:, :s]
                else:
                    allowed = (pos[None, :] <= pos_b[:, None]) & (pos[None, :] > pos_b[:, None] - window)
                p = jax.nn.softmax(jnp.where(allowed[:, None, :], score, -jnp.inf), axis=-1)
                return _mm("qhs,shv->qhv", p, v, round_to)

            per_block = lambda t: t.reshape((s // qb, qb) + t.shape[1:])  # noqa: E731
            o = jax.lax.map(block, (per_block(q_n), per_block(q_r), per_block(pos), per_block(sel)))
            return o.reshape(s, hg, -1)

        o = jax.lax.map(head_group, (group(attn["q_b"]), group(attn["kv_b"])))  # [groups, S, hg, v]
        o = jnp.moveaxis(o, 0, 1).reshape(s, heads, -1) * gate[:, :, None]
        return _mm("shv,hvd->sd", o, attn["o"], round_to), a, c_q


@partial(jax.jit, static_argnames=("round_to",))
def gated_ffn(f, p, round_to=None):
    with jax.default_matmul_precision("highest"):
        act = jax.nn.silu(_mm("sd,df->sf", f, p["gate"], round_to)) * _mm("sd,df->sf", f, p["up"], round_to)
        return _mm("sf,fd->sd", act, p["down"], round_to)


@partial(jax.jit, static_argnames=("top_k", "round_to"))
def route(f, router, select_bias, *, top_k: int, round_to=None):
    """`[S, n_experts]` gates: g_e for the chosen experts, 0 for the others."""
    with jax.default_matmul_precision("highest"):
        score = jax.nn.sigmoid(_mm("sd,de->se", f, router, round_to))
        _, idx = jax.lax.top_k(score + select_bias.astype(F32), top_k)
        chosen = jnp.zeros(score.shape, bool).at[jnp.arange(score.shape[0])[:, None], idx].set(True)
        kept = jnp.where(chosen, score, 0.0)
        return kept / jnp.sum(kept, axis=-1, keepdims=True)


_rms_jit = jax.jit(_rms_norm, static_argnums=2)


def expert_layer(f, moe, *, top_k: int, expert_lo: int, round_to=None):
    """The held experts' part and the shared expert, one expert at a time."""
    gates = route(f, moe["router"], moe["select_bias"], top_k=top_k, round_to=round_to)
    y = gated_ffn(f, moe["shared"], round_to=round_to)
    held = moe["experts"]["gate"].shape[0]
    for e in range(held):
        one = {k: w[e] for k, w in moe["experts"].items()}
        y = y + gates[:, expert_lo + e, None] * gated_ffn(f, one, round_to=round_to)
    return y


def hidden_states(params, ids, *, hf: dict, expert_lo: int = 0, round_to=None, selected: list | None = None):
    """The residual stream `[S, hidden]` after the last layer, for one
    sequence `ids [S]`. `hf`: the configuration's published keys. `selected`
    (a list) receives each full layer's `selected_keys`."""
    eps, window = hf["rms_norm_eps"], hf["sliding_window_size"]
    x = params["embed"][ids].astype(F32)
    for i, layer in enumerate(params["layers"]):
        kind = hf["layer_types"][i]
        sizes = tuple(sorted(_attn_sizes(hf, kind).items()))
        sel = jnp.zeros((x.shape[0], 1), jnp.int32)
        if kind == FULL:
            # the selection needs the normed input and c_q first
            a, c_q = _normed_and_cq(x, layer, dict(sizes)["q_rank"], eps,
                                    bool(hf.get("apply_mla_qkv_lora_rescale")), round_to)
            sel = selected_keys(a, c_q, layer["attn"], topk=hf["index_topk"], index_rope=hf["qk_rope_head_dim"],
                                theta=float(hf["rope_theta"]), round_to=round_to)
            if selected is not None:
                selected.append(sel)
        out, _, _ = attention(x, layer, sel, kind=kind, hf_sizes=sizes, eps=eps, window=window,
                              rescale=bool(hf.get("apply_mla_qkv_lora_rescale")), round_to=round_to)
        x = x + out
        f = _rms_jit(x, layer["norm2"], eps)
        if "ffn" in layer:
            x = x + gated_ffn(f, layer["ffn"], round_to=round_to)
        else:
            x = x + expert_layer(f, layer["moe"], top_k=hf["num_experts_per_tok"], expert_lo=expert_lo,
                                 round_to=round_to)
    return x


@partial(jax.jit, static_argnames=("q_rank", "eps", "rescale", "round_to"))
def _normed_and_cq(x, layer, q_rank: int, eps: float, rescale: bool, round_to=None):
    with jax.default_matmul_precision("highest"):
        a = _rms_norm(x, layer["norm1"], eps)
        s_q = math.sqrt(x.shape[1] / q_rank) if rescale else 1.0
        return a, s_q * _rms_norm(_mm("sd,dr->sr", a, layer["attn"]["q_a"], round_to), layer["attn"]["q_norm"], eps)


@partial(jax.jit, static_argnames=("eps", "round_to"))
def head(x, norm_out, lm_head, *, eps: float, round_to=None):
    with jax.default_matmul_precision("highest"):
        return _mm("sd,dv->sv", _rms_norm(x, norm_out, eps), lm_head, round_to)


def logits(params, ids, *, hf: dict, expert_lo: int = 0, round_to=None, selected: list | None = None):
    """`[S, vocab]` float32 logits of one sequence `ids [S]` over the
    vocabulary slice the parameters hold."""
    x = hidden_states(params, ids, hf=hf, expert_lo=expert_lo, round_to=round_to, selected=selected)
    return head(x, params["norm_out"], params["lm_head"], eps=hf["rms_norm_eps"], round_to=round_to)
