"""Plain float32 reference of the block the jamba2-3b configuration runs.

Written from the layer equations of ISSUE 36 / `PERF.md` section 4 and the
published `config.json` keys; imports nothing from tpukit: no cache, no pages,
no chunks, no key blocks. Every matmul is float32 under
`default_matmul_precision("highest")`. One sequence at a time.

    layer i is attention where i % attn_layer_period == attn_layer_offset, else Mamba:
      x <- x + Mixer_i(RMSNorm(x));   x <- x + W_down(silu(W_gate a) * W_up a),  a = RMSNorm(x)
    Mamba mixer (E = mamba_expand hidden, N = mamba_d_state, R = mamba_dt_rank, K = mamba_d_conv;
    every linear bias-free but the convolution's and dt_proj's):
      [u; z]    = W_in h
      u'        = silu(causal_depthwise_conv_K(u) + b_conv)      (u before position 0 is 0)
      [d; B; C] = W_x u'                                           (R, N, N)
      d, B, C   = RMSNorm_R(d), RMSNorm_N(B), RMSNorm_N(C)         (own weights, eps rms_norm_eps)
      Delta     = softplus(W_dt d + b_dt)                          (E)
      A         = -exp(A_log)                                      (E x N)
      h_t       = exp(Delta_t A) * h_{t-1} + (Delta_t u'_t) B_t    (h_{-1} = 0; one token at a time)
      y_t       = h_t C_t + D u'_t
      out       = W_out (y * silu(z))
    attention: the one K/V head expanded to every query head; no positions;
      softmax(q k^T / sqrt(head_dim)) under a dense causal mask, then W_o
    logits = RMSNorm(x) E^T (the embedding is the head)

The parameter tree is the one `tpukit.model.hybrid.init_params` builds (the
Mamba layers stacked on a leading axis, `mamba`; the attention layers a tuple
of dicts, `attn`); only its layout is shared with the program. The
layers are walked one at a time on the host, each kind's jitted function
compiled once a sequence length and reused by every layer of the kind;
attention runs in blocks of queries, so that 16k tokens fit beside the
served weights.

`round_to` rounds both operands of every matmul to that dtype first (the
products still accumulate in float32): the reference "computed in a lower
precision", which the comparison's limits must refuse. `state_round_to`
rounds the scan's state to that dtype after every step: a state kept in a
lower precision.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 256


def _mm(spec, a, b, round_to):
    if round_to is not None:
        a, b = a.astype(round_to), b.astype(round_to)
    return jnp.einsum(spec, a.astype(F32), b.astype(F32))


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _ffn(x, norm2, ffn, eps, round_to):
    a = _rms_norm(x, norm2, eps)
    act = jax.nn.silu(_mm("sd,df->sf", a, ffn["gate"], round_to)) * _mm("sd,df->sf", a, ffn["up"], round_to)
    return x + _mm("sf,fd->sd", act, ffn["down"], round_to)


@partial(jax.jit, static_argnames=("sizes", "eps", "round_to", "state_round_to"))
def mamba_layer(x, layer, *, sizes: tuple, eps: float, round_to=None, state_round_to=None):
    """One Mamba layer (mixer and MLP, both residuals) on `x [S, hidden]`."""
    z = dict(sizes)
    e, n, r, k = z["inner"], z["d_state"], z["dt_rank"], z["d_conv"]
    m = layer["mixer"]
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        h = _rms_norm(x, layer["norm1"], eps)
        uz = _mm("sd,de->se", h, m["in_proj"], round_to)
        u, gate = uz[:, :e], uz[:, e:]
        padded = jnp.concatenate([jnp.zeros((k - 1, e), F32), u])
        w = m["conv_w"].astype(F32)
        conv = sum(w[i] * padded[i: i + s] for i in range(k)) + m["conv_b"].astype(F32)
        u = jax.nn.silu(conv)
        dbc = _mm("se,ek->sk", u, m["x_proj"], round_to)
        d = _rms_norm(dbc[:, :r], m["dt_norm"], eps)
        b = _rms_norm(dbc[:, r: r + n], m["b_norm"], eps)
        c = _rms_norm(dbc[:, r + n:], m["c_norm"], eps)
        delta = jax.nn.softplus(_mm("sr,re->se", d, m["dt_proj"], round_to) + m["dt_bias"].astype(F32))
        a = -jnp.exp(m["A_log"].astype(F32))  # [E, N]

        def step(state, xs):
            u_t, d_t, b_t, c_t = xs
            state = jnp.exp(d_t[:, None] * a) * state + (d_t * u_t)[:, None] * b_t[None, :]
            if state_round_to is not None:  # a convert there and back would be folded away (excess precision)
                fi = jnp.finfo(state_round_to)
                state = jax.lax.reduce_precision(state, exponent_bits=fi.nexp, mantissa_bits=fi.nmant)
            return state, jnp.sum(state * c_t[None, :], axis=1)

        _, y = jax.lax.scan(step, jnp.zeros((e, n), F32), (u, delta, b, c), unroll=16)  # still a token a step
        y = y + m["D"].astype(F32) * u
        x = x + _mm("se,ed->sd", y * jax.nn.silu(gate), m["out_proj"], round_to)
        return _ffn(x, layer["norm2"], layer["ffn"], eps, round_to)


@partial(jax.jit, static_argnames=("eps", "round_to"))
def attention_layer(x, layer, *, eps: float, round_to=None):
    """One attention layer (one K/V head expanded to every query head, a dense
    causal mask, queries in blocks; MLP, both residuals) on `x [S, hidden]`."""
    att = layer["attn"]
    heads, head_dim = att["q"].shape[1], att["q"].shape[2]
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        h = _rms_norm(x, layer["norm1"], eps)
        q = _mm("sd,dhk->shk", h, att["q"], round_to)
        k = jnp.broadcast_to(_mm("sd,dk->sk", h, att["k"], round_to)[:, None], (s, heads, head_dim))
        v = jnp.broadcast_to(_mm("sd,dk->sk", h, att["v"], round_to)[:, None], (s, heads, head_dim))
        pos = jnp.arange(s)
        qb = math.gcd(s, QUERY_BLOCK)

        def block(xs):
            q_b, pos_b = xs
            score = _mm("qhk,shk->qhs", q_b, k, round_to) / math.sqrt(head_dim)
            p = jax.nn.softmax(jnp.where((pos[None, :] <= pos_b[:, None])[:, None, :], score, -jnp.inf), axis=-1)
            return _mm("qhs,shk->qhk", p, v, round_to)

        o = jax.lax.map(block, (q.reshape(s // qb, qb, heads, head_dim), pos.reshape(s // qb, qb)))
        x = x + _mm("shk,hkd->sd", o.reshape(s, heads, head_dim), att["o"], round_to)
        return _ffn(x, layer["norm2"], layer["ffn"], eps, round_to)


@partial(jax.jit, static_argnames=("eps", "round_to"))
def head(x, norm_out, embed, *, eps: float, round_to=None):
    with jax.default_matmul_precision("highest"):
        return _mm("sd,vd->sv", _rms_norm(x, norm_out, eps), embed, round_to)


def layer_is_attention(hf: dict, i: int) -> bool:
    return i % hf["attn_layer_period"] == hf["attn_layer_offset"]


def _sizes(hf: dict) -> tuple:
    return tuple(sorted(dict(inner=hf["mamba_expand"] * hf["hidden_size"], d_state=hf["mamba_d_state"],
                             dt_rank=hf["mamba_dt_rank"], d_conv=hf["mamba_d_conv"]).items()))


def lowered_programs(params, s: int, *, hf: dict, round_to=None, state_round_to=None) -> list:
    """The jitted functions `logits` calls for a sequence of `s` tokens, each
    lowered from shapes alone (`params` may be arrays or a tree of
    `ShapeDtypeStruct`s), one a distinct program: a caller that compiles them
    ahead (in threads, into the persistent cache) leaves `logits` only its
    fetches and the small ops between the functions."""
    like = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)  # noqa: E731
    eps = float(hf["rms_norm_eps"])
    x = jax.ShapeDtypeStruct((s, hf["hidden_size"]), F32)
    mamba = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), params["mamba"])
    return [
        mamba_layer.lower(x, mamba, sizes=_sizes(hf), eps=eps, round_to=round_to, state_round_to=state_round_to),
        attention_layer.lower(x, like(params["attn"][0]), eps=eps, round_to=round_to),
        head.lower(x, like(params["norm_out"]), like(params["embed"]), eps=eps, round_to=round_to),
    ]


def logits(params, ids, *, hf: dict, round_to=None, state_round_to=None, selected: list | None = None):
    """`[S, vocab]` float32 logits of one sequence `ids [S]`. `selected` is
    `dots3_block`'s argument, taken so that one judge drives all three: no
    layer here selects keys, so nothing is appended."""
    eps = float(hf["rms_norm_eps"])
    x = params["embed"][ids].astype(F32)
    im = ia = 0
    for i in range(hf["num_hidden_layers"]):
        if layer_is_attention(hf, i):
            x = attention_layer(x, params["attn"][ia], eps=eps, round_to=round_to)
            ia += 1
        else:
            layer = jax.tree.map(lambda a: a[im], params["mamba"])  # noqa: B023
            x = mamba_layer(x, layer, sizes=_sizes(hf), eps=eps, round_to=round_to, state_round_to=state_round_to)
            im += 1
    return head(x, params["norm_out"], params["embed"], eps=eps, round_to=round_to)
