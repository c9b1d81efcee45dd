"""Plain float32 reference of the block the motif-3-beta configuration runs.

Written from the layer equations of ISSUE 33 / `PERF.md` section 4 and the
published `config.json` keys; imports nothing from tpukit: no cache, no pages,
no absorbed attention, no grouped matmul, no batching. Every matmul is float32
under `default_matmul_precision("highest")`. One sequence at a time.

    streams:  X_0 = [embed[t]] x n;   logits = RMSNorm(sum_i X_L[i]) W_head
    a sublayer F (attention, FFN) under manifold-constrained hyper-connections,
    each with its own phi, b, alpha:
      x~ = vec(X) / rms(vec(X))                                  (n hidden wide, no weight)
      H_pre = sigmoid(alpha_pre x~ phi_pre + b_pre)              (n)
      H_post = 2 sigmoid(alpha_post x~ phi_post + b_post)        (n)
      H_res = SK(exp(alpha_res mat(x~ phi_res) + b_res))         (n x n; SK: mhc_sinkhorn_iters
              times, each row over its sum, then each column over its sum)
      u = sum_i H_pre[i] X[i];  y = clamp(F(u), +-hidden_clamp);
      X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y
    F_attn(u), a = RMSNorm(u) (every linear bias-free):
      c_q = RMSNorm(a W_qa);  [q_n; q_r]_h = c_q W_qb[h];  q_r <- RoPE(q_r)
      [c_kv; k_r] = a W_kva;  c_kv <- RMSNorm(c_kv);  k_r <- RoPE(k_r)    (one k_r for all heads)
      [k_n; v]_g = c_kv W_kvb[g] for each KV head g           (expanded here, for every key)
      query head h reads KV head g(h) = h // (heads / kv_heads); in a group the last head is the
      noise head, the others signal heads
      p_h = softmax over the allowed s of (q_n,h . k_n,g(h) + q_r,h . k_r) / sqrt(head_dim)
      o_h = p_h v_g(h);   d_i = o_i - sigmoid(a w_lambda,i) o_noise(g(i))   for each signal head i
      out = concat_i(d_i * sigmoid(a W_g)_i) W_o               (an elementwise gate)
    allowed keys: s <= t, and in a window layer t - s < sliding_window. Layer i (its published
    index) is full where (i + 1) % sliding_window_period == 0.
    F_ffn(u), a = RMSNorm(u):  down(PolyNorm(a W_gate) * (a W_up)),
      PolyNorm(z) = polynorm_output_scale (w1 N(z) + w2 N(z^2) + w3 N(z^3) + clamp(b, +-polynorm_bias_clamp)),
      N(p) = p / sqrt(mean(p^2) + eps) over the FFN's width; w, b each FFN's own.
      A layer with experts:  s = sigmoid(a W_r);  the experts_top_k experts of largest s;
      g_e = route_scale s_e / sum over the chosen of s;
      y = E_shared(a) + sum over chosen AND held e of g_e E_e(a)    (the gate on the expert's output)
    RoPE: pairs (x[i], x[i + R/2]) turned by pos * theta^(-2i/R).

The parameter tree is the one `tpukit.model.latent.init_params` builds (a
tuple of per-layer dicts); only its layout is shared with the program. The
layers are walked one at a time on the host, each jitted function compiled
once a sequence length and reused by every layer (a layer's window is an
argument: a full layer's is the whole sequence); attention runs a KV group at
a time in blocks of queries, the held experts one at a time, so that 8k
tokens fit beside the served weights.

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
QUERY_BLOCK = 256


def _mm(spec, a, b, round_to):
    if round_to is not None:
        a, b = a.astype(round_to), b.astype(round_to)
    return jnp.einsum(spec, a.astype(F32), b.astype(F32))


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, pos, theta):
    """x [S, ..., R] at positions pos [S]."""
    r = x.shape[-1]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    ang = pos.astype(F32)[:, None] * inv
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (r // 2,))
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang), a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)


def layer_is_full(hf: dict, published_index: int) -> bool:
    return (published_index + 1) % hf["sliding_window_period"] == 0


def held_layers(hf: dict) -> list[int]:
    """Published indices of the layers the parameters hold."""
    return list(hf.get("held_layers", range(hf["num_hidden_layers"])))


def sinkhorn(m, iters: int):
    """`m [S, n, n]`: `iters` times, rows over their sums, then columns."""
    for _ in range(iters):
        m = m / jnp.sum(m, axis=2, keepdims=True)
        m = m / jnp.sum(m, axis=1, keepdims=True)
    return m


@partial(jax.jit, static_argnames=("iters", "eps", "round_to"))
def stream_maps(x, mhc, *, iters: int, eps: float, round_to=None):
    """`x [S, n, hidden]` -> `(u [S, hidden], H_res [S, n, n], H_post [S, n])`."""
    with jax.default_matmul_precision("highest"):
        s, n, _ = x.shape
        flat = x.reshape(s, -1)
        flat = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
        proj = _mm("sd,dk->sk", flat, mhc["phi"], round_to)
        alpha, b = mhc["alpha"].astype(F32), mhc["b"].astype(F32)
        pre = jax.nn.sigmoid(alpha[0] * proj[:, :n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[:, n: 2 * n] + b[n: 2 * n])
        res = sinkhorn(jnp.exp(alpha[2] * proj[:, 2 * n:] + b[2 * n:]).reshape(s, n, n), iters)
        return jnp.sum(pre[:, :, None] * x, axis=1), res, post


@partial(jax.jit, static_argnames=("clamp",))
def write_back(x, res, post, y, *, clamp: float):
    if clamp:
        y = jnp.clip(y, -clamp, clamp)
    mixed = jnp.sum(res[:, :, :, None] * x[:, None, :, :], axis=2)  # elementwise: no matmul pass rounds the streams
    return mixed + post[:, :, None] * y[:, None, :]


def _blocked(s: int, block: int) -> int:
    return math.gcd(s, block) if s % block else block


@partial(jax.jit, static_argnames=("sizes", "eps", "round_to"))
def attention(u, norm1, attn, window, theta, *, sizes: tuple, eps: float, round_to=None):
    """`F_attn(u)` for one layer (its input norm's weight and its `attn`
    weights) on `u [S, hidden]` float32; `window` (a scalar) is how many keys
    back a query sees, itself counted."""
    z = dict(sizes)
    heads, groups, head_dim, rope, v_dim, kv_rank = (
        z[k] for k in ("heads", "kv_heads", "head_dim", "rope", "v", "kv_rank"))
    nope, per = head_dim - rope, heads // groups
    with jax.default_matmul_precision("highest"):
        s = u.shape[0]
        pos = jnp.arange(s)
        a = _rms_norm(u, norm1, eps)
        c_q = _rms_norm(_mm("sd,dr->sr", a, attn["q_a"], round_to), attn["q_norm"], eps)
        q = _mm("sr,rhd->shd", c_q, attn["q_b"], round_to)
        q_n, q_r = q[..., :nope], _rope(q[..., nope:], pos, theta)
        kv = _mm("sd,dr->sr", a, attn["kv_a"], round_to)
        c_kv = _rms_norm(kv[:, :kv_rank], attn["kv_norm"], eps)
        k_r = _rope(kv[:, kv_rank:], pos, theta)
        kvh = _mm("sc,cgd->sgd", c_kv, attn["kv_b"], round_to)  # every key's own K and V, per KV head
        k_n, v = kvh[..., :nope], kvh[..., nope:]
        qb = _blocked(s, QUERY_BLOCK)
        per_block = lambda t: t.reshape((s // qb, qb) + t.shape[1:])  # noqa: E731

        def kv_group(xs):
            qn_g, qr_g, kn_g, v_g = xs  # [S, per, nope], [S, per, rope], [S, nope], [S, v]

            def block(ys):
                qn_b, qr_b, pos_b = ys
                score = (_mm("qhd,sd->qhs", qn_b, kn_g, round_to) + _mm("qhd,sd->qhs", qr_b, k_r, round_to))
                score = score / math.sqrt(head_dim)
                allowed = (pos[None, :] <= pos_b[:, None]) & (pos[None, :] > pos_b[:, None] - window)
                p = jax.nn.softmax(jnp.where(allowed[:, None, :], score, -jnp.inf), axis=-1)
                return _mm("qhs,sv->qhv", p, v_g, round_to)

            return jax.lax.map(block, (per_block(qn_g), per_block(qr_g), per_block(pos))).reshape(s, per, v_dim)

        by_group = lambda t: jnp.moveaxis(t.reshape(s, groups, per, t.shape[-1]), 1, 0)  # noqa: E731
        o = jax.lax.map(kv_group, (by_group(q_n), by_group(q_r), jnp.moveaxis(k_n, 1, 0), jnp.moveaxis(v, 1, 0)))
        o = jnp.moveaxis(o, 0, 1)  # [S, groups, per, v]: the heads' outputs, each its own softmax
        if z["noise_heads"]:
            signal = per - z["noise_heads"] // groups
            lam = jax.nn.sigmoid(_mm("sd,dh->sh", a, attn["lam"], round_to)).reshape(s, groups, signal)
            o = o[:, :, :signal] - lam[..., None] * o[:, :, signal:]
        o = o.reshape(s, -1) * jax.nn.sigmoid(_mm("sd,de->se", a, attn["gate"], round_to))
        return _mm("se,ed->sd", o, attn["o"].reshape(-1, attn["o"].shape[-1]), round_to)


def _poly_norm(zz, w, b, scale: float, clamp: float, eps: float):
    norm = lambda p: p / jnp.sqrt(jnp.mean(p * p, axis=-1, keepdims=True) + eps)  # noqa: E731
    w, b = w.astype(F32), b.astype(F32)
    return scale * (w[0] * norm(zz) + w[1] * norm(zz ** 2) + w[2] * norm(zz ** 3) + jnp.clip(b[0], -clamp, clamp))


@partial(jax.jit, static_argnames=("scale", "clamp", "eps", "round_to"))
def gated_ffn(f, p, *, scale: float, clamp: float, eps: float, round_to=None):
    with jax.default_matmul_precision("highest"):
        act = _poly_norm(_mm("sd,df->sf", f, p["gate"], round_to), p["poly_w"], p["poly_b"], scale, clamp, eps)
        return _mm("sf,fd->sd", act * _mm("sd,df->sf", f, p["up"], round_to), p["down"], round_to)


@partial(jax.jit, static_argnames=("top_k", "scale", "round_to"))
def route(f, router, *, top_k: int, scale: float, round_to=None):
    """`[S, n_experts]` gates: g_e for the chosen experts, 0 for the others."""
    with jax.default_matmul_precision("highest"):
        score = jax.nn.sigmoid(_mm("sd,de->se", f, router, round_to))
        _, idx = jax.lax.top_k(score, top_k)
        chosen = jnp.zeros(score.shape, bool).at[jnp.arange(score.shape[0])[:, None], idx].set(True)
        kept = jnp.where(chosen, score, 0.0)
        return scale * kept / jnp.sum(kept, axis=-1, keepdims=True)


_rms_jit = jax.jit(_rms_norm, static_argnums=2)


def _poly(hf: dict) -> dict:
    return dict(scale=float(hf["polynorm_output_scale"]), clamp=float(hf["polynorm_bias_clamp"]),
                eps=float(hf["rms_norm_eps"]))


def expert_layer(f, moe, *, hf: dict, expert_lo: int = 0, round_to=None):
    """The held experts' part and the shared expert, one expert at a time."""
    gates = route(f, moe["router"], top_k=hf["experts_top_k"], scale=float(hf["route_scale"]), round_to=round_to)
    y = gated_ffn(f, moe["shared"], round_to=round_to, **_poly(hf))
    for e in range(moe["experts"]["gate"].shape[0]):
        one = {k: w[e] for k, w in moe["experts"].items()}
        y = y + gates[:, expert_lo + e, None] * gated_ffn(f, one, round_to=round_to, **_poly(hf))
    return y


def hidden_states(params, ids, *, hf: dict, expert_lo: int = 0, round_to=None):
    """The streams' sum `[S, hidden]` after the last layer, for one sequence `ids [S]`."""
    eps, n = float(hf["rms_norm_eps"]), hf["mhc_expansion_rate"]
    sizes = tuple(sorted(dict(
        heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"], noise_heads=hf["num_noise_heads"],
        head_dim=hf["head_dim"], rope=hf["qk_rope_head_dim"], v=hf["v_head_dim"], kv_rank=hf["kv_lora_rank"]).items()))
    maps = partial(stream_maps, iters=hf["mhc_sinkhorn_iters"], eps=eps, round_to=round_to)
    back = partial(write_back, clamp=float(hf["hidden_clamp"]))
    s = ids.shape[0]
    x = jnp.repeat(params["embed"][ids].astype(F32)[:, None, :], n, axis=1)
    for layer, index in zip(params["layers"], held_layers(hf)):
        full = layer_is_full(hf, index)
        u, res, post = maps(x, layer["mhc1"])
        y = attention(u, layer["norm1"], layer["attn"], jnp.int32(s + 1 if full else hf["sliding_window"]),
                      jnp.float32(hf["rope_theta"] if full else hf["swa_rope_theta"]),
                      sizes=sizes, eps=eps, round_to=round_to)
        x = back(x, res, post, y)
        u, res, post = maps(x, layer["mhc2"])
        f = _rms_jit(u, layer["norm2"], eps)
        if "ffn" in layer:
            y = gated_ffn(f, layer["ffn"], round_to=round_to, **_poly(hf))
        else:
            y = expert_layer(f, layer["moe"], hf=hf, expert_lo=expert_lo, round_to=round_to)
        x = back(x, res, post, y)
    return jnp.sum(x, axis=1)


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
    eps, n, hidden = float(hf["rms_norm_eps"]), hf["mhc_expansion_rate"], hf["hidden_size"]
    sizes = tuple(sorted(dict(
        heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"], noise_heads=hf["num_noise_heads"],
        head_dim=hf["head_dim"], rope=hf["qk_rope_head_dim"], v=hf["v_head_dim"], kv_rank=hf["kv_lora_rank"]).items()))
    x, u = sds((s, n, hidden)), sds((s, hidden))
    dense = next(layer for layer in params["layers"] if "ffn" in layer)
    moe = next(layer for layer in params["layers"] if "moe" in layer)["moe"]
    one = {k: sds(w.shape[1:], w.dtype) for k, w in moe["experts"].items()}  # the shared expert's shapes too
    ffn = partial(gated_ffn.lower, u, round_to=round_to, **_poly(hf))
    return [
        stream_maps.lower(x, like(dense["mhc1"]), iters=hf["mhc_sinkhorn_iters"], eps=eps, round_to=round_to),
        write_back.lower(x, sds((s, n, n)), sds((s, n)), u, clamp=float(hf["hidden_clamp"])),
        attention.lower(u, like(dense["norm1"]), like(dense["attn"]), sds((), jnp.int32), sds(()),
                        sizes=sizes, eps=eps, round_to=round_to),
        _rms_jit.lower(u, like(dense["norm2"]), eps),
        ffn(like(dense["ffn"])), ffn(one),
        route.lower(u, like(moe["router"]), top_k=hf["experts_top_k"], scale=float(hf["route_scale"]),
                    round_to=round_to),
        head.lower(u, like(params["norm_out"]), like(params["lm_head"]), eps=eps, round_to=round_to),
    ]


def logits(params, ids, *, hf: dict, expert_lo: int = 0, round_to=None, selected: list | None = None):
    """`[S, vocab]` float32 logits of one sequence `ids [S]` over the
    vocabulary slice the parameters hold. `selected` is `dots3_block`'s
    argument, taken so that one mode drives both: no layer here selects keys,
    so nothing is appended."""
    x = hidden_states(params, ids, hf=hf, expert_lo=expert_lo, round_to=round_to)
    return head(x, params["norm_out"], params["lm_head"], eps=float(hf["rms_norm_eps"]), round_to=round_to)
