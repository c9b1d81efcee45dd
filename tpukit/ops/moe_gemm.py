"""Fused grouped-expert matmul (Pallas TPU kernels) — the "pallas" MoE
dispatch.

The buffer dataflows ("xla"/"a2a", tpukit/ops/moe_dispatch.py) materialize
an `[E, B, C, D]` capacity tensor and run EVERY expert over mostly-padding
rows: at the bench e8 shape the dispatch/combine one-hot einsums plus the
~25% capacity padding are why `moe_e8` sat under the dense model in the
round-5 chip records (since deleted; ROADMAP S1). This module removes the
buffer entirely:

  1. SORT: the `[B*S*K]` top-k expert assignments are stably argsorted by
     expert id on device, giving a permutation into expert-contiguous
     segments plus per-expert offsets (one `cumsum` of a bincount).
  2. SEGMENT GEMM: a blocked kernel walks the sorted rows `BT` at a time.
     Each row block statically unrolls over the expert bank and executes
     — via `pl.when`, so non-overlapping experts cost nothing at runtime
     — the reference FFN (up -> relu -> down -> relu, the double-relu
     quirk, models/gpt.py:33-41) for exactly the experts whose segment
     intersects the block, switching weight tiles at group boundaries.
     A block away from any boundary runs precisely one expert's two
     matmuls: no capacity padding, no one-hot dispatch FLOPs.
  3. COMBINE: the inverse permutation (an argsort of the sort order)
     gathers results back to `(token, k)` order for the gated top-k sum.
     No scatter in the forward; the gather's transpose is the scatter-add
     the backward needs and XLA emits it as such.

Dropless semantics: every routed token computes (the megablocks/dropless
convention) — `moe_e8` FLOPs become exactly `top_k` expert rows per token.
Setting `cfg.moe_capacity > 0` restores capacity-drop semantics by
zeroing the gates of assignments the buffer paths would drop — the mask
is the SAME `_kept_mask` cumsum the xla path uses, so the dropped token
set is bit-identical (tests/test_moe.py::test_pallas_drop_semantics).

Backward is a custom VJP over the SAME sorted layout (no re-sort, no
GSPMD transpose guesswork): one kernel recomputes each block's hidden
activations flash-style, accumulates dW/db per expert in revisited output
blocks (expert segments are contiguous in the sorted order, so dW
accumulation is consecutive — the Pallas revisit rule), and emits dX via
the mirrored masked walk. `relu` masks come from the saved forward output
(`y > 0  <=>  z > 0`, with relu'(0) = 0 matching jax).

Under ExpertParallel the kernel composes AFTER the hand-placed all_to_all
exchange (`moe_dispatch._moe_ffn_exchange`): each device's post-exchange
`[E_local, ep*B_local, C, D]` buffer is already expert-contiguous — the
sorted layout with static equal segments — so the kernel replaces the
batched expert einsums while the collective schedule and its byte audit
are byte-for-byte the "a2a" path's. (The exchange needs static per-peer
payloads, so capacity buffers — and their drop semantics — are structural
there; the dropless win is the meshless/single-chip path, which is what
the bench `moe_e8` probe measures.)

VMEM budget: the whole expert bank (`[E, D, F]` + `[E, F, D]` + biases)
stays resident in VMEM across the row walk, and in the backward so does
its f32 gradient — at the bench e8 shape (D=256, F=1024) ~8 + 17 MiB,
well under the 100 MiB kernel budget, but it bounds this kernel to banks
that fit on-chip: at GPT-small widths (D=768, F=3072) that is E <= 2 per
device. The static expert unroll likewise targets small expert counts;
both limits are asserted at call time rather than discovered as Mosaic
errors.

On the CPU backend the kernels run in Pallas interpreter mode (the
`pallas_attention.py` convention), so the CPU tier-1 suite exercises the
exact kernel code path.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpukit.ops.moe_dispatch import (
    _aux_stats,
    _kept_mask,
    _moe_ffn_exchange,
    _route_topk,
    moe_capacity,
)
from tpukit.ops.pallas_attention import _interpret, tpu_compiler_params

# Sorted-row block edge (sublane-aligned). 512 keeps the per-block hidden
# activation [BT, F] at 2 MiB f32 for the bench shape while amortizing the
# per-block expert-switch overhead; sweepable like TPUKIT_FLASH_BLOCK.
_BLOCK_ROWS = max(8, -(-int(os.environ.get("TPUKIT_MOE_BLOCK", "512")) // 8) * 8)

# The expert bank stays VMEM-resident and the kernel unrolls over it —
# both scale with E. Fail with a named limit instead of a Mosaic OOM.
_MAX_VMEM_EXPERTS = 32

# The backward keeps the bank AND its f32 gradient VMEM-resident. 64 MiB of
# the kernels' 100 MiB limit go to those; the rest is row tiles and the
# [BT, F] hidden activations. Asked from the sandbox, the v5e compiler
# accepts 57 MB (E=2, D=768, F=3072, bf16) and refuses 85 MB (E=3) with
# "Ran out of memory in memory space vmem".
_BANK_VMEM_BYTES = 64 * 1024 * 1024


def _plan_rows(n_rows: int) -> tuple[int, int]:
    """(block_rows, padded_rows): sublane-aligned block edge and the row
    count padded to a whole number of blocks."""
    bt = min(_BLOCK_ROWS, -(-n_rows // 8) * 8)
    return bt, -(-n_rows // bt) * bt


# ---------------------------------------------------------------------------
# Kernels. Grid is (num_row_blocks,); the per-expert segment offsets ride in
# SMEM and every block statically unrolls over the expert bank with pl.when
# gating, so only experts whose segment intersects the block execute. Every
# VMEM ref read keeps rank >= 2 (bias rows are sliced `[e:e+1, :]`) — the
# Mosaic layout rule pallas_attention documents.
# ---------------------------------------------------------------------------


def _fwd_kernel(offs_ref, x_ref, wu_ref, bu_ref, wd_ref, bd_ref, y_ref, *,
                block_rows, num_experts):
    b = pl.program_id(0)
    # zero-init: rows of experts that do not reach this block (and the
    # sort-padding tail) must read as exact zeros downstream
    y_ref[...] = jnp.zeros_like(y_ref)
    base = b * block_rows
    # global row ids at the FULL [BT, D] tile shape: Mosaic has no relayout
    # that broadcasts a [BT, 1] boolean column across lanes
    rows = base + jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 0)
    x_blk = x_ref[...]
    for e in range(num_experts):
        start = offs_ref[e]
        end = offs_ref[e + 1]

        @pl.when((start < base + block_rows) & (end > base))
        def _():
            # the reference FFN for this expert over the WHOLE block (MXU
            # work is per-block; the row mask only gates the write), f32
            # accumulation, intermediates rounded to the compute dtype at
            # the same points as the einsum paths
            h = jax.lax.dot_general(
                x_blk, wu_ref[e],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            h = jnp.maximum(h + bu_ref[e:e + 1, :].astype(jnp.float32), 0.0)
            z = jax.lax.dot_general(
                h.astype(x_blk.dtype), wd_ref[e],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            z = jnp.maximum(z + bd_ref[e:e + 1, :].astype(jnp.float32), 0.0)
            mask = (rows >= start) & (rows < end)
            y_ref[...] += jnp.where(mask, z, 0.0).astype(y_ref.dtype)


def _bwd_kernel(offs_ref, x_ref, g_ref, y_ref, wu_ref, bu_ref, wd_ref,
                dx_ref, dwu_ref, dbu_ref, dwd_ref, dbd_ref, *,
                block_rows, num_experts):
    """The mirrored walk: recompute each block's hidden activations once
    (flash-style — cheaper than saving the [M, F] tensor), mask the
    incoming cotangent to the expert's segment FIRST so every downstream
    product is segment-exact, then accumulate dW/db into the
    expert-indexed output blocks (revisited consecutively: segments are
    contiguous in the sorted order) and dX into the row block. relu masks:
    y > 0 for the down relu (y is the saved forward output), h > 0 for the
    up relu (h is the recomputation)."""
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        dwu_ref[...] = jnp.zeros_like(dwu_ref)
        dbu_ref[...] = jnp.zeros_like(dbu_ref)
        dwd_ref[...] = jnp.zeros_like(dwd_ref)
        dbd_ref[...] = jnp.zeros_like(dbd_ref)

    dx_ref[...] = jnp.zeros_like(dx_ref)
    base = b * block_rows
    rows = base + jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 0)
    x_blk = x_ref[...]
    for e in range(num_experts):
        start = offs_ref[e]
        end = offs_ref[e + 1]

        @pl.when((start < base + block_rows) & (end > base))
        def _():
            mask = (rows >= start) & (rows < end)
            # two selects, not `mask & (y > 0)`: the row mask is lane-
            # replicated and Mosaic cannot relayout the other i1 operand
            # to match it; y compares in f32 (the v5e VPU has no bf16
            # compare)
            dz2 = jnp.where(
                mask,
                jnp.where(
                    y_ref[...].astype(jnp.float32) > 0,
                    g_ref[...].astype(jnp.float32),
                    0.0,
                ),
                0.0,
            )
            h = jax.lax.dot_general(
                x_blk, wu_ref[e],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            h = jnp.maximum(h + bu_ref[e:e + 1, :].astype(jnp.float32), 0.0)
            h16 = h.astype(x_blk.dtype)
            dz2_16 = dz2.astype(x_blk.dtype)
            dwd_ref[e] += jax.lax.dot_general(
                h16, dz2_16,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dbd_ref[e:e + 1, :] += jnp.sum(dz2, axis=0, keepdims=True)
            dh = jax.lax.dot_general(
                dz2_16, wd_ref[e],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dz1 = jnp.where(h > 0, dh, 0.0)
            dz1_16 = dz1.astype(x_blk.dtype)
            dwu_ref[e] += jax.lax.dot_general(
                x_blk, dz1_16,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dbu_ref[e:e + 1, :] += jnp.sum(dz1, axis=0, keepdims=True)
            dx_ref[...] += jnp.where(
                mask,
                jax.lax.dot_general(
                    dz1_16, wu_ref[e],
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ),
                0.0,
            ).astype(dx_ref.dtype)


def _check_bank(num_experts: int, d: int, f: int, bytes_per_param: int) -> None:
    """`bytes_per_param`: the bank's itemsize in the forward, plus 4 in the
    backward (its f32 gradient is resident too)."""
    if num_experts > _MAX_VMEM_EXPERTS:
        raise ValueError(
            f"moe_dispatch='pallas' keeps the whole expert bank VMEM-"
            f"resident and unrolls over it: num_experts={num_experts} "
            f"exceeds the supported {_MAX_VMEM_EXPERTS} (shard experts "
            f"over an ExpertParallel mesh, or use the buffer dispatches)"
        )
    need = num_experts * (2 * d * f + d + f) * bytes_per_param
    if need > _BANK_VMEM_BYTES:
        raise ValueError(
            f"moe_dispatch='pallas' keeps the expert bank (and, in the "
            f"backward, its f32 gradient) VMEM-resident: {num_experts} "
            f"experts x (D={d}, F={f}) need {need // (1024 * 1024)} MiB, "
            f"over the {_BANK_VMEM_BYTES // (1024 * 1024)} MiB budget (the "
            f"TPU compiler refuses such a bank with 'Ran out of memory in "
            f"memory space vmem') — shard experts over an ExpertParallel "
            f"mesh, or use the buffer dispatches"
        )


def _bank_spec(e, d, f):
    """The expert bank rides whole and constant-indexed, so Pallas fetches
    it into VMEM once and keeps it resident across the row walk."""
    return [
        pl.BlockSpec((e, d, f), lambda b: (0, 0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((e, f), lambda b: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((e, f, d), lambda b: (0, 0, 0), memory_space=pltpu.VMEM),
    ]


def _row_spec(bt, d):
    return pl.BlockSpec((bt, d), lambda b: (b, 0), memory_space=pltpu.VMEM)


def _grouped_ffn_fwd_call(xs, wu, bu, wd, bd, offsets):
    m, d = xs.shape
    e, _, f = wu.shape
    _check_bank(e, d, f, wu.dtype.itemsize)
    bt, m_pad = _plan_rows(m)
    assert m_pad == m, "caller pads the sorted rows to a block multiple"
    kernel = functools.partial(_fwd_kernel, block_rows=bt, num_experts=e)
    return pl.pallas_call(
        kernel,
        grid=(m // bt,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [_row_spec(bt, d)]
        + _bank_spec(e, d, f)
        + [pl.BlockSpec((e, d), lambda b: (0, 0), memory_space=pltpu.VMEM)],
        out_specs=_row_spec(bt, d),
        out_shape=jax.ShapeDtypeStruct((m, d), xs.dtype),
        compiler_params=tpu_compiler_params("arbitrary"),
        name="moe_ffn_fwd",
        interpret=_interpret(),
    )(offsets, xs, wu, bu, wd, bd)


def _grouped_ffn_bwd_call(xs, g, ys, wu, bu, wd, offsets):
    m, d = xs.shape
    e, _, f = wu.shape
    _check_bank(e, d, f, wu.dtype.itemsize + 4)
    bt, _ = _plan_rows(m)
    kernel = functools.partial(_bwd_kernel, block_rows=bt, num_experts=e)
    return pl.pallas_call(
        kernel,
        grid=(m // bt,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [_row_spec(bt, d)] * 3
        + _bank_spec(e, d, f),
        out_specs=[
            _row_spec(bt, d),
            pl.BlockSpec((e, d, f), lambda b: (0, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((e, f), lambda b: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((e, f, d), lambda b: (0, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((e, d), lambda b: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, d), xs.dtype),
            jax.ShapeDtypeStruct((e, d, f), jnp.float32),
            jax.ShapeDtypeStruct((e, f), jnp.float32),
            jax.ShapeDtypeStruct((e, f, d), jnp.float32),
            jax.ShapeDtypeStruct((e, d), jnp.float32),
        ],
        compiler_params=tpu_compiler_params("arbitrary"),
        name="moe_ffn_bwd",
        interpret=_interpret(),
    )(offsets, xs, g, ys, wu, bu, wd)


@jax.custom_vjp
def grouped_ffn(xs, wu, bu, wd, bd, offsets):
    """Segment FFN over expert-sorted rows.

    xs [M, D] sorted rows (M a multiple of the block edge); wu/bu/wd/bd the
    stacked expert bank in the compute dtype; offsets [E+1] int32 cumulative
    segment boundaries with offsets[-1] == M (sort-padding rows fold into
    the last segment — their cotangent is zero by construction, so they
    never pollute dW). Returns [M, D] in xs.dtype; rows outside every
    segment are exact zeros.
    """
    return _grouped_ffn_fwd_call(xs, wu, bu, wd, bd, offsets)


def _grouped_ffn_fwd(xs, wu, bu, wd, bd, offsets):
    ys = _grouped_ffn_fwd_call(xs, wu, bu, wd, bd, offsets)
    return ys, (xs, wu, bu, wd, bd, offsets, ys)


def _grouped_ffn_bwd(res, g):
    xs, wu, bu, wd, bd, offsets, ys = res
    dx, dwu, dbu, dwd, dbd = _grouped_ffn_bwd_call(
        xs, g, ys, wu, bu, wd, offsets
    )
    return (
        dx,
        dwu.astype(wu.dtype),
        dbu.astype(bu.dtype),
        dwd.astype(wd.dtype),
        dbd.astype(bd.dtype),
        np.zeros(offsets.shape, jax.dtypes.float0),
    )


grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)


# ---------------------------------------------------------------------------
# The dropless sorted dataflow (meshless path).
# ---------------------------------------------------------------------------


def sort_plan(cfg, top_idx):
    """Device-side sort plan over the flattened `[B*S*K]` assignments.

    Returns (src, inv, offsets):
      src     [M]   int32 flat token index feeding each sorted row (M = NK
                    padded to a block multiple; padding rows re-read row 0
                    — they are fed to the LAST expert's segment tail and
                    their output is never gathered, their cotangent never
                    nonzero)
      inv     [NK]  int32 position of each (token, k) pair in the sorted
                    buffer (the unsort gather)
      offsets [E+1] int32 cumulative expert segment boundaries, with the
                    sort padding folded into expert E-1 so the row space
                    [0, M) is fully covered
    """
    b, s, k = top_idx.shape
    nk = b * s * k
    _, m = _plan_rows(nk)
    ids = top_idx.reshape(nk)
    if m > nk:
        ids = jnp.concatenate(
            [ids, jnp.full((m - nk,), cfg.num_experts - 1, jnp.int32)]
        )
    # stable: within an expert, rows stay in (b, s, k) order — the same
    # order the buffer paths' causal cumsum slots them in
    order = jnp.argsort(ids, stable=True)
    counts = jnp.zeros((cfg.num_experts,), jnp.int32).at[ids].add(1)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )
    inv = jnp.argsort(order, stable=True)[:nk].astype(jnp.int32)
    src = jnp.where(order < nk, order // k, 0).astype(jnp.int32)
    return src, inv, offsets


def pallas_kept_mask(cfg, x, router_kernel):
    """[B,S,E] 0/1 mask of assignments the pallas dispatch KEEPS — the
    drop-parity test surface. Dropless (cfg.moe_capacity == 0) keeps every
    routed assignment; capacity mode reuses the xla path's `_kept_mask`
    verbatim, so the dropped token set is bit-identical."""
    _, _, _, _, assign = _route_topk(x, router_kernel, cfg)
    if cfg.moe_capacity > 0:
        return _kept_mask(assign, moe_capacity(cfg, x.shape[1]))
    return assign


def _grouped_expert_ffn(experts, expert_in, dtype):
    """`_expert_ffn` twin over the post-exchange `[E_local, R, C, D]`
    buffer, computed by the grouped kernel: the buffer is already
    expert-contiguous, i.e. the sorted layout with static equal segments
    (R*C rows per local expert; block padding folds into the last
    segment and is sliced off)."""
    e_l, r, c, d = expert_in.shape
    n = e_l * r * c
    rows = expert_in.reshape(n, d)
    _, m = _plan_rows(n)
    if m > n:
        rows = jnp.pad(rows, ((0, m - n), (0, 0)))
    offs = np.arange(e_l + 1, dtype=np.int32) * (r * c)
    offs[-1] = m
    ys = grouped_ffn(
        rows,
        experts["up"]["kernel"].astype(dtype),
        experts["up"]["bias"].astype(dtype),
        experts["down"]["kernel"].astype(dtype),
        experts["down"]["bias"].astype(dtype),
        jnp.asarray(offs),
    )
    return ys[:n].reshape(e_l, r, c, d)


def moe_ffn_pallas(layer, cfg, x, pad_mask=None):
    """The grouped-GEMM MoE FFN. Returns (out [B,S,D], aux scalar) — the
    same contract as moe_ffn_xla / moe_ffn_a2a.

    Meshless (cfg.moe_mesh is None): the dropless sorted dataflow — route,
    sort by expert, segment GEMM, unsort, gated top-k combine. With
    `cfg.moe_capacity > 0` the xla drop mask zeroes the dropped
    assignments' gates: their FFN output, their gradient to x/W and their
    router gradient are all exact zeros, reproducing the buffer paths'
    residual-passthrough bit-for-bit while the routing, aux statistics and
    kept-token math stay shared code with the other dispatches.

    Under ExpertParallel (mesh injected): the "a2a" exchange block with
    the local expert FFN swapped for the grouped kernel — collectives and
    byte audit unchanged (see module docstring).
    """
    if cfg.moe_mesh is not None:
        return _moe_ffn_exchange(
            layer, cfg, x, pad_mask, _grouped_expert_ffn, "pallas"
        )
    experts = layer["ffn"]["experts"]
    xc, top_idx, top_vals, probs, assign = _route_topk(
        x, layer["ffn"]["router"]["kernel"], cfg
    )
    b, s, d = x.shape
    k = cfg.router_top_k

    gates = top_vals  # [B,S,K] f32, raw router probability (GShard gates)
    if cfg.moe_capacity > 0:
        kept = _kept_mask(assign, moe_capacity(cfg, s))
        gates = gates * jnp.take_along_axis(kept, top_idx, axis=-1)

    src, inv, offsets = sort_plan(cfg, top_idx)
    xs = jnp.take(xc.reshape(b * s, d), src, axis=0)
    ys = grouped_ffn(
        xs,
        experts["up"]["kernel"].astype(cfg.compute_dtype),
        experts["up"]["bias"].astype(cfg.compute_dtype),
        experts["down"]["kernel"].astype(cfg.compute_dtype),
        experts["down"]["bias"].astype(cfg.compute_dtype),
        offsets,
    )
    # unsort (pure gather — its transpose is the scatter-add the backward
    # needs) and combine weighted by each (token, expert)'s gate
    y_pairs = jnp.take(ys, inv, axis=0).reshape(b, s, k, d)
    out = jnp.einsum(
        "bskd,bsk->bsd", y_pairs, gates.astype(cfg.compute_dtype)
    )
    num, den = _aux_stats(probs, assign, pad_mask, cfg)
    aux = cfg.num_experts * num / jnp.maximum(den, 1.0)
    return out, aux
