"""Plain float32 reference of the block the gpt2-* configurations run.

Written from the layer equations in tpukit/model/gpt.py's docstring and
imports nothing from tpukit: no kernel, no cache, no scan, no fused
projection. Every matmul is float32 under `default_matmul_precision
("highest")` (on a TPU a float32 matmul is otherwise rounded to bf16).

    x0      = token[ids] + position[pos]
    a       = LayerNorm1(x)                      biased, eps 1e-5
    q, k, v = a Wq, a Wk, a Wv                   no bias; heads of head_dim
    s       = q k^T / sqrt(head_dim), causal     key position <= query position
    x       = x + (softmax(s) v) Wo + bo
    f       = LayerNorm2(x)
    x       = x + relu(relu(f Wup + bup) Wdown + bdown)     the double ReLU
    logits  = LayerNormOut(x) Whead              untied, no bias; columns past
                                                 vocab_size masked to -1e9
    loss    = mean over targets != -100 of logsumexp(logits) - logits[target]

The parameter tree is the one `init_params` builds (layer leaves stacked on a
leading axis); only its layout is shared with the program, none of its code.

The layers are walked by a Python loop on the host, one jitted call a layer
(every layer has the same shapes, so the compiler sees one layer, whatever
the depth). The gradient is back-propagated the same way, layer by layer
through `jax.vjp`, keeping only each layer's input: same values as one big
`jax.grad`, a fraction of its memory and compile time.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

IGNORE_INDEX = -100
F32 = jnp.float32


def _layer_norm(x, p, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"].astype(F32) + p["bias"].astype(F32)


def _dense(x, p):
    y = x @ p["kernel"].astype(F32)
    return y + p["bias"].astype(F32) if "bias" in p else y


def embed(emb, ids):
    s = ids.shape[1]
    return emb["token"].astype(F32)[ids] + emb["position"].astype(F32)[jnp.arange(s)][None]


def block(x, layer, heads: int, head_dim: int):
    """One decoder layer on `x [B, S, dim]` float32."""
    with jax.default_matmul_precision("highest"):
        b, s, _ = x.shape
        a = _layer_norm(x, layer["norm1"])
        split = lambda t: t.reshape(b, s, heads, head_dim).transpose(0, 2, 1, 3)
        q, k, v = (split(_dense(a, layer["attn"][n])) for n in ("q", "k", "v"))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(F32(head_dim))
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        mix = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
        x = x + _dense(mix.transpose(0, 2, 1, 3).reshape(b, s, heads * head_dim), layer["attn"]["out"])
        f = _layer_norm(x, layer["norm2"])
        h = jax.nn.relu(_dense(f, layer["ffn"]["up"]))
        return x + jax.nn.relu(_dense(h, layer["ffn"]["down"]))


def head(x, top, vocab_size: int):
    """Final LayerNorm and the untied head; pad columns masked."""
    with jax.default_matmul_precision("highest"):
        out = _dense(_layer_norm(x, top["norm_out"]), top["lm_head"])
        return jnp.where(jnp.arange(out.shape[-1]) < vocab_size, out, F32(-1e9))


def cross_entropy(lg, targets):
    valid = targets != IGNORE_INDEX
    safe = jnp.where(valid, targets, 0)
    nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(jnp.sum(valid), 1)


def _head_loss(x, top, targets, vocab_size: int):
    return cross_entropy(head(x, top, vocab_size), targets)


@jax.jit
def _layer_at(layers, i):
    return jax.tree_util.tree_map(lambda t: t[i], layers)


def _sq(tree):
    return sum(jnp.sum(jnp.square(g.astype(F32))) for g in jax.tree_util.tree_leaves(tree))


@partial(jax.jit, static_argnames=("heads", "head_dim"))
def _block_back(x, layer, dy, heads, head_dim):
    """Cotangent of a layer's input and the squared norm of its parameters'
    gradient, recomputing the layer from its input."""
    _, vjp = jax.vjp(lambda x_, l_: block(x_, l_, heads, head_dim), x, layer)
    dx, dlayer = vjp(dy)
    return dx, _sq(dlayer)


def _depth(params) -> int:
    return jax.tree_util.tree_leaves(params["layers"])[0].shape[0]


def _top(params):
    return {"norm_out": params["norm_out"], "lm_head": params["lm_head"]}


def hidden_states(params, ids, *, heads: int, head_dim: int):
    """The input of every layer, and the last layer's output."""
    step = jax.jit(block, static_argnames=("heads", "head_dim"))
    xs = [jax.jit(embed)(params["embeddings"], ids)]
    for i in range(_depth(params)):
        xs.append(step(xs[-1], _layer_at(params["layers"], i), heads=heads, head_dim=head_dim))
    return xs


def logits(params, ids, *, heads: int, head_dim: int, vocab_size: int):
    """`[B, S, padded_vocab]` float32 logits for token ids `[B, S]`, positions 0..S-1."""
    x = hidden_states(params, ids, heads=heads, head_dim=head_dim)[-1]
    return jax.jit(head, static_argnames="vocab_size")(x, _top(params), vocab_size=vocab_size)


def loss(params, ids, targets, *, heads: int, head_dim: int, vocab_size: int):
    """Mean cross-entropy over targets != -100."""
    x = hidden_states(params, ids, heads=heads, head_dim=head_dim)[-1]
    return jax.jit(_head_loss, static_argnames="vocab_size")(x, _top(params), targets, vocab_size=vocab_size)


def loss_and_grad_norm(params, ids, targets, *, heads: int, head_dim: int, vocab_size: int):
    """(loss, global L2 norm of d loss / d params), both float32 scalars."""
    xs = hidden_states(params, ids, heads=heads, head_dim=head_dim)

    @partial(jax.jit, static_argnames="vocab_size")
    def top_back(x, top, targets, vocab_size):
        value, (dx, dtop) = jax.value_and_grad(_head_loss, argnums=(0, 1))(x, top, targets, vocab_size)
        return value, dx, _sq(dtop)

    @jax.jit
    def embed_back(emb, ids, dx):
        return _sq(jax.vjp(lambda e: embed(e, ids), emb)[1](dx)[0])

    value, dx, sq = top_back(xs.pop(), _top(params), targets, vocab_size=vocab_size)
    for i in reversed(range(_depth(params))):
        dx, layer_sq = _block_back(xs.pop(), _layer_at(params["layers"], i), dx, heads=heads, head_dim=head_dim)
        sq = sq + layer_sq
    sq = sq + embed_back(params["embeddings"], ids, dx)
    return value, jnp.sqrt(sq)
