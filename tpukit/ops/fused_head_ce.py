"""Fused LM-head + cross-entropy (Pallas TPU kernels).

The unfused path materializes the logits `[B*S, V]` in HBM (bf16: 3.3GB at
the S=2048 bench shape), then streams them twice more through the CE custom
VJP (`ops/layers.py cross_entropy_sum`) — ~13GB of HBM traffic per step at
GPT-2 vocab, and the logits buffer is what OOMs batch 64 at long sequence.
These kernels never materialize logits: the head matmul runs tile-by-tile
([T tokens x Vc vocab] in VMEM, K=dim fills the MXU) with an online
logsumexp/argmax over vocab tiles, and the backward recomputes each tile to
produce `dh` (accumulated in VMEM across vocab tiles) and per-token-tile
`dW` partials (summed by one cheap XLA reduction).

Semantics exactly match `apply_head` + `cross_entropy_sum` +
`masked_accuracy` (reference main-single.py:95-96,128-131 twins): vocab-pad
columns are forced to -1e9 (zero probability, zero gradient), IGNORE_INDEX
targets contribute nothing, and the argmax tie-breaks to the first index.

No reference counterpart: the reference computes full logits and calls
F.cross_entropy (models/gpt.py:229-231, main-single.py:95-96) — viable at
S=256, not at the long-context shapes this framework targets.

On the CPU backend the kernels run in Pallas interpreter mode (the CPU
test mesh exercises the exact kernel code path).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from tpukit.ops.layers import IGNORE_INDEX  # one sentinel for every loss path
from tpukit.ops.pallas_attention import (
    _interpret,
    per_shard,
    tpu_compiler_params,
)

NEG_INF = -1e9  # same pad-column clamp as apply_head (model/gpt.py)

# Tile edges, env-sweepable like TPUKIT_FLASH_BLOCK. t=2048/v=2048 measured
# fastest at the S=2048 bench shape on v5e (tools/sweep_long_context.py;
# the sweep is near-flat +-4%, so these are not load-bearing). Values are
# rounded up to the hardware tile multiples (8 sublanes / 128 lanes) so a
# misaligned sweep value cannot die in Mosaic lowering.
_T_BLK = -(-max(8, int(os.environ.get("TPUKIT_CE_T_BLOCK", "2048"))) // 8) * 8
_V_BLK = -(-max(128, int(os.environ.get("TPUKIT_CE_V_BLOCK", "2048"))) // 128) * 128


def _pads(n_tokens: int, v_pad: int) -> tuple[int, int, int, int]:
    t_blk = min(_T_BLK, -(-n_tokens // 8) * 8)
    n_pad = -(-n_tokens // t_blk) * t_blk
    v_blk = _V_BLK if v_pad >= _V_BLK else -(-v_pad // 128) * 128
    v_pad2 = -(-v_pad // v_blk) * v_blk
    return t_blk, n_pad, v_blk, v_pad2


def _tile_cols(vi, v_blk):
    return vi * v_blk + jax.lax.broadcasted_iota(jnp.int32, (1, v_blk), 1)


def _fwd_kernel(tgt_ref, h_ref, w_ref, lse_ref, tgtl_ref, best_ref,
                m_scr, l_scr, tl_scr, bv_scr, bi_scr,
                *, t_blk, v_blk, num_v, vocab_size, with_argmax):
    """Per-token vectors ride as (1, t_blk) ROWS (an [N, 1] f32 column in
    HBM pads its minor dim to 128 lanes — a 128x memory expansion that cost
    1.5GB at the batch-64 bench shape); rows are reshaped to columns in
    VMEM where the math needs them. `with_argmax` is static: training steps
    (no accuracy) compile the online-argmax passes out entirely."""
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        tl_scr[:] = jnp.zeros_like(tl_scr)
        if with_argmax:
            bv_scr[:] = jnp.full_like(bv_scr, -jnp.inf)
            bi_scr[:] = jnp.zeros_like(bi_scr)

    logits = jax.lax.dot_general(
        h_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    cols = _tile_cols(vi, v_blk)  # (1, Vc) global column ids
    # NB: a closed-form pad-column correction (zero the pad columns of w,
    # skip this where, subtract pad_cnt*exp(-m) from l) was tried and
    # REVERTED: when every real logit is far below 0 the pad columns anchor
    # m at 0 and the real mass cancels below the f32 ulp of the pad mass —
    # lse collapses to -inf for any token with true logsumexp < ~-9.7.
    logits = jnp.where(cols < vocab_size, logits, NEG_INF)

    # online logsumexp over vocab tiles
    m_prev = m_scr[:, :1]
    row_max = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, row_max)  # lint: allow(online-softmax-spelling): online LOGSUMEXP for the CE loss — streams lse + argmax tie-break state, not the owner's (m, l, correction, p) contract
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[:, :1] * corr + jnp.sum(jnp.exp(logits - m_new), axis=1, keepdims=True)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # target logit: one-hot select (no in-kernel gather); cols are GLOBAL
    # column ids, so compare against the global target id — at most one
    # tile hits
    tgt_col = jnp.reshape(tgt_ref[...], (t_blk, 1))  # (T, 1)
    hit = cols == tgt_col  # (T, Vc) broadcast compare
    tl_scr[:, :1] += jnp.sum(jnp.where(hit, logits, 0.0), axis=1, keepdims=True)

    if with_argmax:
        # online argmax, first-index tie-break (matches jnp.argmax): within
        # the tile the smallest column achieving the row max; across tiles
        # strict > keeps the earliest tile's winner
        in_tile_idx = jnp.min(
            jnp.where(logits == row_max, cols, vocab_size), axis=1, keepdims=True
        )
        better = row_max > bv_scr[:, :1]
        bv_scr[:, :1] = jnp.where(better, row_max, bv_scr[:, :1])
        bi_scr[:, :1] = jnp.where(better, in_tile_idx, bi_scr[:, :1])

    @pl.when(vi == num_v - 1)
    def _():
        lse_ref[...] = jnp.reshape(m_scr[:, :1] + jnp.log(l_scr[:, :1]), (1, 1, t_blk))
        tgtl_ref[...] = jnp.reshape(tl_scr[:, :1], (1, 1, t_blk))
        if with_argmax:
            best_ref[...] = jnp.reshape(bi_scr[:, :1], (1, 1, t_blk))
        else:  # output must still be defined; the caller discards it
            best_ref[...] = jnp.zeros_like(best_ref)


def _bwd_kernel(tgt_ref, glse_ref, gtgt_ref, lse_ref, h_ref, w_ref, dhp_ref,
                dw_ref, *, t_blk, v_blk, vocab_size):
    """Grid (num_v, num_t), TOKEN axis innermost: consecutive t steps
    revisit the same dw output block, so dw accumulates IN the output
    (Pallas only keeps revisited blocks resident across consecutive grid
    steps) and never needs per-tile partials in HBM — the f32
    [num_t, dim, V_pad] partial buffer the previous (t, v) grid wrote was
    ~1.5x LARGER than the logits tensor this kernel exists to avoid. dh
    needs accumulation over the now-outer v axis instead; its per-v
    partials go to a [num_v, N_pad, dim] output in h's (bf16) dtype —
    v_blk/ (2*t_blk) ~ 8x smaller than the old dw partials — and one XLA
    reduction finishes the sum."""
    vi = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    logits = jax.lax.dot_general(
        h_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    cols = _tile_cols(vi, v_blk)
    logits = jnp.where(cols < vocab_size, logits, NEG_INF)
    lse_col = jnp.reshape(lse_ref[...], (t_blk, 1))
    p = jnp.exp(logits - lse_col)  # pad cols: exp(-1e9 - lse) == 0.0
    hit = cols == jnp.reshape(tgt_ref[...], (t_blk, 1))  # global vs global
    # d logits = softmax * d(lse) + onehot * d(tgt_logit)  (for the CE loss
    # the two cotangents are equal and opposite, but the rule is general)
    d = (
        p * jnp.reshape(glse_ref[...], (t_blk, 1))
        + hit.astype(jnp.float32) * jnp.reshape(gtgt_ref[...], (t_blk, 1))
    )
    d16 = d.astype(h_ref.dtype)

    dhp_ref[0] = jax.lax.dot_general(
        d16, w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dhp_ref.dtype)
    dw_ref[...] += jax.lax.dot_general(
        h_ref[...], d16,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _prep(h, w, targets, vocab_size):
    n, dim = h.shape
    v_pad = w.shape[1]
    t_blk, n_pad, v_blk, v_pad2 = _pads(n, v_pad)
    h_p = jnp.pad(h, ((0, n_pad - n), (0, 0)))
    w_p = jnp.pad(w.astype(h.dtype), ((0, 0), (0, v_pad2 - v_pad)))
    tgt_p = jnp.pad(
        targets.astype(jnp.int32), (0, n_pad - n), constant_values=IGNORE_INDEX
    ).reshape(n_pad // t_blk, 1, t_blk)
    return h_p, w_p, tgt_p, t_blk, n_pad, v_blk, v_pad2


def _fused_fwd_arrays(h, w, targets, vocab_size, with_argmax):
    """Returns (lse [N], tgt_logit [N], best [N] int32) — per-token values;
    the caller assembles loss/accuracy (keeping outputs token-sharded means
    GSPMD handles any batch sharding without custom partitioning rules)."""
    n, dim = h.shape
    h_p, w_p, tgt_p, t_blk, n_pad, v_blk, v_pad2 = _prep(h, w, targets, vocab_size)
    num_t, num_v = n_pad // t_blk, v_pad2 // v_blk

    lse, tgtl, best = pl.pallas_call(
        functools.partial(
            _fwd_kernel, t_blk=t_blk, v_blk=v_blk, num_v=num_v,
            vocab_size=vocab_size, with_argmax=with_argmax,
        ),
        grid=(num_t, num_v),
        in_specs=[
            pl.BlockSpec((1, 1, t_blk), lambda t, v: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((t_blk, dim), lambda t, v: (t, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((dim, v_blk), lambda t, v: (0, v), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, t_blk), lambda t, v: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, t_blk), lambda t, v: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, t_blk), lambda t, v: (t, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_t, 1, t_blk), jnp.float32),
            jax.ShapeDtypeStruct((num_t, 1, t_blk), jnp.float32),
            jax.ShapeDtypeStruct((num_t, 1, t_blk), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((t_blk, 128), jnp.float32)] * 4
        + [pltpu.VMEM((t_blk, 128), jnp.int32)],
        compiler_params=tpu_compiler_params("parallel", "arbitrary"),
        name="head_ce_fwd",
        interpret=_interpret(),
    )(tgt_p, h_p, w_p)
    return (
        lse.reshape(-1)[:n],
        tgtl.reshape(-1)[:n],
        best.reshape(-1)[:n],
    )


def _fused_bwd_arrays(h, w, targets, lse, g_lse, g_tgt, vocab_size):
    """Returns (dh [N, dim], dw [dim, V_pad]) for one token shard. dw is
    the LOCAL tokens' contribution — the partitioned wrapper psums it."""
    n, dim = h.shape
    h_p, w_p, tgt_p, t_blk, n_pad, v_blk, v_pad2 = _prep(h, w, targets, vocab_size)
    num_t, num_v = n_pad // t_blk, v_pad2 // v_blk
    lse_p = jnp.pad(lse, (0, n_pad - n)).reshape(num_t, 1, t_blk)
    glse_p = jnp.pad(g_lse.astype(jnp.float32), (0, n_pad - n)).reshape(num_t, 1, t_blk)
    gtgt_p = jnp.pad(g_tgt.astype(jnp.float32), (0, n_pad - n)).reshape(num_t, 1, t_blk)

    dhp, dw = pl.pallas_call(
        functools.partial(
            _bwd_kernel, t_blk=t_blk, v_blk=v_blk, vocab_size=vocab_size,
        ),
        grid=(num_v, num_t),
        in_specs=[
            pl.BlockSpec((1, 1, t_blk), lambda v, t: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, t_blk), lambda v, t: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, t_blk), lambda v, t: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, t_blk), lambda v, t: (t, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((t_blk, dim), lambda v, t: (t, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((dim, v_blk), lambda v, t: (0, v), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, t_blk, dim), lambda v, t: (v, t, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((dim, v_blk), lambda v, t: (0, v), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_v, n_pad, dim), h.dtype),
            jax.ShapeDtypeStruct((dim, v_pad2), jnp.float32),
        ],
        compiler_params=tpu_compiler_params("parallel", "arbitrary"),
        name="head_ce_bwd",
        interpret=_interpret(),
    )(tgt_p, glse_p, gtgt_p, lse_p, h_p, w_p)

    dh = jnp.sum(dhp.astype(jnp.float32), axis=0)
    return dh[:n].astype(h.dtype), dw[:, : w.shape[1]].astype(w.dtype)


# ---------------------------------------------------------------------------
# Sharded calls (pallas_attention.per_shard): the token axis
# (h/targets dim 0) is freely shardable — each device runs the kernels on its
# local tokens — while dim and vocab must be whole per device (the online
# logsumexp sweeps all vocab tiles and the matmul contracts all of dim). The
# forward's per-token outputs inherit the token sharding; the backward's dw
# is a sum over tokens, so each shard contributes its local partial and the
# body psums over the token mesh axes. `shard` is None (one device, or
# already inside a shard_map) or `(mesh, token_axes)` under a multi-device
# GSPMD jit: left to itself GSPMD would treat the tpu_custom_call as
# unpartitionable and all-gather the whole batch onto every device, and
# custom_partitioning does not compile on libtpu (pallas_attention.per_shard).
# The custom_vjp sits OUTSIDE the sharded calls, same layering as
# pallas_attention's _flash wrapper.
# ---------------------------------------------------------------------------


def _fwd_call(h, w, targets, vocab_size, with_argmax, shard):
    tok = shard and shard[1]
    return per_shard(
        lambda h, w, t: _fused_fwd_arrays(h, w, t, vocab_size, with_argmax),
        shard, (P(tok, None), P(None, None), P(tok)), (P(tok),) * 3,
    )(h, w, targets)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_terms(h, w, targets, vocab_size, with_argmax, shard):
    return _fwd_call(h, w, targets, vocab_size, with_argmax, shard)


def _fused_terms_fwd(h, w, targets, vocab_size, with_argmax, shard):
    lse, tgtl, best = _fwd_call(h, w, targets, vocab_size, with_argmax, shard)
    return (lse, tgtl, best), (h, w, targets, lse)


def _fused_terms_bwd(vocab_size, with_argmax, shard, residuals, g):
    h, w, targets, lse = residuals
    g_lse, g_tgt = g[0], g[1]  # best (int) has no cotangent
    tok = shard and shard[1]

    def local(h, w, t, lse, g_lse, g_tgt):
        dh, dw = _fused_bwd_arrays(h, w, t, lse, g_lse, g_tgt, vocab_size)
        if tok:  # token-sharded: dw partials live per shard
            dw = jax.lax.psum(dw, tok)
        return dh, dw

    dh, dw = per_shard(
        local, shard,
        (P(tok, None), P(None, None)) + (P(tok),) * 4,
        (P(tok, None), P(None, None)),
    )(h, w, targets, lse, g_lse, g_tgt)
    return dh, dw, np.zeros(targets.shape, jax.dtypes.float0)


_fused_terms.defvjp(_fused_terms_fwd, _fused_terms_bwd)


def fused_head_ce(h, w, targets, vocab_size, with_accuracy: bool = False,
                  shard=None):
    """(loss_sum, count, correct) of the LM head + masked CE, computed from
    hidden states `h [N, dim]` and the (vocab-padded) head kernel
    `w [dim, V_pad]` without materializing logits. `targets [N]` uses
    IGNORE_INDEX masking; `correct` is 0 unless with_accuracy.

    Equivalent to `cross_entropy_sum(apply_head-logits, targets)` (+
    masked_accuracy) — equivalence-tested against that path.

    `shard`: `(mesh, token_axes)` under a multi-device GSPMD jit — the mesh
    axes that shard the token dim of `h`/`targets`; None otherwise."""
    lse, tgt_logit, best = _fused_terms(
        h, w, targets, vocab_size, with_accuracy, shard
    )
    valid = targets != IGNORE_INDEX
    loss_sum = jnp.sum(jnp.where(valid, lse - tgt_logit, 0.0))
    count = jnp.sum(valid).astype(jnp.float32)
    if with_accuracy:
        correct = jnp.sum(jnp.where(valid, best == targets, False)).astype(jnp.float32)
    else:
        correct = jnp.float32(0)
    return loss_sum, count, correct
