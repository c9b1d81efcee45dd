"""Fused paged-attention decode kernel (Pallas TPU, round 21 — ROADMAP #3).

The unfused paged decode path (`gpt._apply_attention_paged`) pays one
XLA gather PER LAYER to materialize the full `[N, H, MP*P, D]` view of
every slot's pages before attention — and int8 pools dequantize that
whole view up front, paying the f32 expansion in HBM for positions the
causal window then masks away. This kernel removes the materialized view
entirely: the block tables are dereferenced INSIDE the kernel (scalar-
prefetch SMEM reads feeding VMEM page-tile copies), int8 pages
dequantize tile-by-tile in VMEM via the quant_comm 256-element block
layout, and the softmax/value mix runs flash-style over the assembled
window — the only HBM traffic is the page pool itself, once per head.

Decode-only by design: one query token per slot (the serve decode tick),
no VJP — training attention is `pallas_attention.py`'s job. The pool
WRITE-BACK also stays outside (the shared `paged.write_token` spelling):
the kernel is a pure read, which is what keeps the TP comm audit the
unfused plan unchanged (see `fused_paged_attention`).

Exactness (the parity bar, tests/test_paged_attention.py): the kernel is
`gpt._attend_over_cache` over the gathered view OPERATION-FOR-OPERATION
— same dots on the same operands in the same dtypes, algebraically
identical softmax (below). The one residual is reassociation, not math:
XLA compiles the kernel's per-(head, slot) dots inside the grid program
(interpret mode scans the grid; Mosaic tiles it) while the reference
einsum is a standalone batched GEMM, and the two accumulation orders
differ at the ~1-ULP level (measured max 5e-7 on XLA:CPU f32 at test
shapes). The tests therefore pin what is actually invariant: attention
outputs within a few ULPs, and TOKEN streams (greedy and fixed-seed
sampled, through the full engine) exactly identical. Two deliberate
choices keep the math itself identical:

  - ONE online-softmax block over the whole window. The decode window is
    statically bounded (`MP * P` positions — pages_per_slot is a config
    constant), so the flash recurrence degenerates to a single call of
    the shared `online_softmax_update` helper from `-inf`/`0` state:
    `m = maximum(-inf, max(s))` IS the plain softmax max and
    `l = 0 * exp(-inf) + sum(p)` IS the plain normalizer, exactly.
    A page-blocked multi-call recurrence would trade that exactness
    for nothing here — the whole window already fits VMEM.
  - Divide BEFORE the value dot: `o = (p / l) @ v`, matching
    `jax.nn.softmax(...).astype(cdt) @ v` operation-for-operation (the
    reference casts probabilities to the compute dtype before the mix,
    and so does this kernel).

int8 pages dequantize with the exact `quant_comm.dequantize_blocks`
arithmetic (f32 cast, per-256-block scale multiply) per page tile, so
the fused int8 path is elementwise-identical to gather_view's dequant —
the existing >=90% token-agreement gate transfers unchanged.

Grid is `(H, N)` with slots innermost: the per-head pool slab
`[NP, 1, P, D]` stays VMEM-resident while every slot's window is
assembled against it — the pool is fetched H times total, not N*H.
The CPU tests run it via `interpret=_interpret()` (the pallas_attention
convention), which cannot see what Mosaic refuses: tests/test_chip_compile.py
compiles it for a described v5e and chip_smoke.py runs it compiled. What
the chip's compiler dictated: every block whole in its last two dims (the
per-slot vectors ride `[N, H, 1, D]`, the scale sidecars head-major), f32
matmul accumulators rounded once to the compute dtype, the fresh token
entered by a row select (no dynamic one-row store into a packed tile), the
dequant scale selected at tile shape (no `[nb, 256]` reshape). The VMEM
footprint of the head slab is asserted with a named error
(TPUKIT_PAGED_VMEM_MB) instead of a Mosaic OOM.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from tpukit.ops import quant_comm
from tpukit.ops.pallas_attention import (
    NEG_INF,
    _interpret,
    online_softmax_update,
    tpu_compiler_params,
)

# The per-head VMEM working set (both pool slabs + scale rows + the two
# assembled windows) is bounded with a NAMED error instead of a Mosaic
# OOM — the moe_gemm._MAX_VMEM_EXPERTS discipline. Sweepable.
_PAGED_VMEM_BYTES = (
    int(os.environ.get("TPUKIT_PAGED_VMEM_MB", "64")) * 1024 * 1024
)


def _check_vmem(num_pages, page, head_dim, mp, pool_itemsize, quant, cdt):
    window = mp * page * head_dim * jnp.dtype(cdt).itemsize
    slab = num_pages * page * head_dim * pool_itemsize
    total = 2 * slab + 2 * window
    if quant:
        nb = (page * head_dim) // quant_comm.DEFAULT_BLOCK
        total += 2 * num_pages * nb * 4
    if total > _PAGED_VMEM_BYTES:
        raise ValueError(
            f"fused paged attention keeps one head's K+V pool slab VMEM-"
            f"resident: {num_pages} pages x {page} x {head_dim} needs "
            f"{total // (1024 * 1024)} MiB, over the "
            f"{_PAGED_VMEM_BYTES // (1024 * 1024)} MiB budget "
            f"(TPUKIT_PAGED_VMEM_MB) — shrink the pool or use the "
            f"unfused path (fused_decode=False)"
        )


def _paged_kernel(bt_ref, start_ref, *refs, page, mp, head_dim, quant,
                  scale):
    """One (head, slot) step: assemble the slot's `[MP*P, D]` K/V window
    from its block-table pages (SMEM-prefetched ids -> VMEM tile copies,
    dequantizing int8 tiles in place), insert the fresh K/V at the
    cursor, and run the single-block flash softmax + value mix."""
    if quant:
        (pool_k_ref, pool_v_ref, sk_ref, sv_ref, q_ref, kn_ref, vn_ref,
         o_ref, k_win, v_win) = refs
    else:
        (pool_k_ref, pool_v_ref, q_ref, kn_ref, vn_ref, o_ref, k_win,
         v_win) = refs
    n = pl.program_id(1)
    w = mp * page
    st = start_ref[n]

    if quant:
        nb = (page * head_dim) // quant_comm.DEFAULT_BLOCK
        # which quant block each element of a (P, D) page tile belongs to,
        # in the tile's row-major order (the sidecar's flattening)
        blk = (
            jax.lax.broadcasted_iota(jnp.int32, (page, head_dim), 0) * head_dim
            + jax.lax.broadcasted_iota(jnp.int32, (page, head_dim), 1)
        ) // quant_comm.DEFAULT_BLOCK

    def load_tile(pool_ref, scale_ref, pid):
        tile = pool_ref[pid, 0]  # (P, D), pool storage dtype
        if quant:
            srow = scale_ref[0, pl.ds(pid, 1), :]  # (1, nb) f32
            # dequantize_blocks' arithmetic per (page, head) row: f32 cast,
            # one multiply by the element's block scale — elementwise-
            # identical to the gathered view's dequant. The scale tile is
            # selected at the full (P, D) shape: Mosaic has no relayout for
            # the [nb, 256] reshape the XLA spelling uses.
            sc = jnp.zeros((page, head_dim), jnp.float32)
            for j in range(nb):
                sc = jnp.where(blk == j, srow[0, j], sc)
            tile = tile.astype(jnp.float32) * sc
        return tile.astype(k_win.dtype)

    for j in range(mp):  # MP is static and small: unrolled page walk
        pid = bt_ref[n, j]
        k_win[pl.ds(j * page, page), :] = load_tile(
            pool_k_ref, sk_ref if quant else None, pid
        )
        v_win[pl.ds(j * page, page), :] = load_tile(
            pool_v_ref, sv_ref if quant else None, pid
        )

    # fresh-token insert at the cursor — the same clamp semantics as the
    # unfused path's dynamic_update_slice (start is < W for every lane
    # the engine dispatches; the clamp only guards degenerate inputs).
    # A row select over the window, not a one-row store: Mosaic cannot
    # store a single row of a packed (bf16) tile at a dynamic offset.
    at_cursor = jax.lax.broadcasted_iota(
        jnp.int32, (w, head_dim), 0
    ) == jnp.minimum(st, w - 1)
    k_all = jnp.where(at_cursor, kn_ref[0, 0], k_win[...])
    v_all = jnp.where(at_cursor, vn_ref[0, 0], v_win[...])

    # scores rounded to the COMPUTE dtype straight out of the dot (the
    # reference einsum's result dtype; Mosaic's MXU accumulates in f32, as
    # XLA's does), scale + causal mask applied in the same dtype/order as
    # _attend_over_cache, THEN the f32 cast
    s = jax.lax.dot_general(
        q_ref[0, 0], k_all,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(k_all.dtype) * scale  # (1, W)
    key_pos = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    s = jnp.where(key_pos <= st, s, jnp.asarray(NEG_INF, s.dtype))
    s32 = s.astype(jnp.float32)

    # ONE shared-helper call over the full window: degenerate flash ==
    # plain softmax exactly (module docstring); divide-before-dot matches
    # softmax(...).astype(cdt) @ v operation-for-operation
    m0 = jnp.full((1, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((1, 1), jnp.float32)
    _, l, _, p = online_softmax_update(m0, l0, s32)
    probs = (p / l).astype(v_all.dtype)
    o = jax.lax.dot_general(
        probs, v_all,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0, 0] = o.astype(o_ref.dtype)


def paged_attend(pool_k, pool_v, scale_k, scale_v, bt, start, q, k_new,
                 v_new):
    """Fused paged decode attention over one layer's pools (meshless /
    per-shard form — see `fused_paged_attention` for the TP wrapper).

    pool_k/pool_v: `[NP, H, P, D]` page pools (f32/bf16 storage, or int8
    with `scale_k`/`scale_v` `[NP, H, nb]` f32 sidecars; pass None scales
    for unquantized pools); bt `[N, MP]` int32 block tables; start `[N]`
    int32 cursors; q/k_new/v_new `[N, H, D]` in the compute dtype (the
    decode tick's single token per slot). Returns `[N, H, D]` attention
    outputs — pre-projection, `_attend_over_cache` on the gathered view
    op-for-op (module docstring: identical math, ~1-ULP dot
    reassociation, exact token parity)."""
    num_pages, heads, page, head_dim = pool_k.shape
    n, mp = bt.shape
    quant = scale_k is not None
    cdt = q.dtype
    if quant and (page * head_dim) % quant_comm.DEFAULT_BLOCK:
        raise ValueError(
            f"int8 pages need page_size x head_dim ({page} x {head_dim}) "
            f"to tile into {quant_comm.DEFAULT_BLOCK}-element quant blocks "
            f"(paged.validate_kv_layout enforces this upstream)"
        )
    _check_vmem(num_pages, page, head_dim, mp, pool_k.dtype.itemsize,
                quant, cdt)
    w = mp * page

    kernel = functools.partial(
        _paged_kernel, page=page, mp=mp, head_dim=head_dim, quant=quant,
        scale=1.0 / head_dim**0.5,
    )
    # per-head pool slab, constant across the inner slot axis — fetched
    # into VMEM once per head and reused for every slot's window
    slab = pl.BlockSpec((num_pages, 1, page, head_dim),
                        lambda h, n, *_: (0, h, 0, 0))
    # Mosaic wants a block's last two dims (8, 128)-divisible or whole, so
    # the per-slot vectors ride as [N, H, 1, D] (unit sublane axis) and the
    # scale sidecars head-major [H, NP, nb]: every block below is whole in
    # its last two dims
    vec = pl.BlockSpec((1, 1, 1, head_dim), lambda h, n, *_: (n, h, 0, 0))
    in_specs = [slab, slab]
    operands = [pool_k, pool_v]
    if quant:
        nb = (page * head_dim) // quant_comm.DEFAULT_BLOCK
        srow = pl.BlockSpec((1, num_pages, nb), lambda h, n, *_: (h, 0, 0))
        in_specs += [srow, srow]
        operands += [scale_k.transpose(1, 0, 2), scale_v.transpose(1, 0, 2)]
    in_specs += [vec, vec, vec]
    operands += [x[:, :, None, :] for x in (q, k_new, v_new)]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # bt + start ride SMEM, read per slot
            grid=(heads, n),
            in_specs=in_specs,
            out_specs=vec,
            scratch_shapes=[
                pltpu.VMEM((w, head_dim), cdt),
                pltpu.VMEM((w, head_dim), cdt),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, heads, 1, head_dim), cdt),
        compiler_params=tpu_compiler_params("parallel", "arbitrary"),
        name="paged_attend",
        interpret=_interpret(),
    )(bt, start, *operands)
    return out[:, :, 0, :]


def paged_attend_reference(pool_k, pool_v, scale_k, scale_v, bt, start, q,
                           k_new, v_new):
    """The unfused spelling of `paged_attend`'s contract, in plain XLA:
    gather_view, insert the fresh K/V at the cursor with the ring path's
    dynamic-update-slice, then `_attend_over_cache`'s math verbatim
    (pre-projection). What the kernel is held to — by the CPU tests in
    interpret mode and by chip_smoke.py compiled."""
    from tpukit.serve import paged as paged_lib  # lazy: serve imports ops

    cdt = q.dtype
    # one layer's pool here: gather_view takes the stack and a layer index
    stack = lambda z: None if z is None else z[None]
    view_k = paged_lib.gather_view(pool_k[None], stack(scale_k), 0, bt, cdt)
    view_v = paged_lib.gather_view(pool_v[None], stack(scale_v), 0, bt, cdt)
    upd = lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (0, s, 0))
    view_k = jax.vmap(upd)(view_k, k_new[:, :, None, :], start)
    view_v = jax.vmap(upd)(view_v, v_new[:, :, None, :], start)
    d = q.shape[-1]
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q[:, :, None, :], view_k
    ) * (1.0 / d**0.5)
    q_pos = (start[:, None] + jnp.arange(1))[:, None, :, None]
    key_pos = jnp.arange(view_k.shape[2])[None, None, None, :]
    scores = jnp.where(
        key_pos <= q_pos, scores, jnp.asarray(NEG_INF, scores.dtype)
    )
    probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(view_v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, view_v)[:, :, 0, :]


def fused_paged_attention(pool_k, pool_v, scale_k, scale_v, bt, start, q,
                          k_new, v_new, mesh=None):
    """`paged_attend` under the serving mesh. GSPMD cannot partition a
    pallas_call — left alone it would replicate the kernel and bolt
    resharding collectives around it, breaking the plan-exactness bar —
    so under a model axis the kernel runs inside shard_map at exactly the
    pools' serving layout: heads sharded over `model`, block tables and
    cursors replicated, zero collectives inside the body. The per-step
    comm therefore stays the unfused `decode_step_comm(paged=True)`
    closed form unchanged (the fused HLO audit, tests)."""
    if mesh is None or mesh.shape.get("model", 1) <= 1:
        return paged_attend(pool_k, pool_v, scale_k, scale_v, bt, start,
                            q, k_new, v_new)
    m = mesh.shape["model"]
    heads = pool_k.shape[1]
    if heads % m:
        raise ValueError(
            f"fused paged attention shards heads over the model axis: "
            f"heads={heads} must divide model={m} (the paged serving grid "
            f"picker guarantees this)"
        )
    pool_spec = P(None, "model", None, None)
    head_spec = P(None, "model", None)
    if scale_k is None:
        fn = lambda pk, pv, b, s, qq, kn, vn: paged_attend(
            pk, pv, None, None, b, s, qq, kn, vn
        )
        return shard_map(
            fn, mesh=mesh,
            in_specs=(pool_spec, pool_spec, P(), P(), head_spec,
                      head_spec, head_spec),
            out_specs=head_spec, check_vma=False,
        )(pool_k, pool_v, bt, start, q, k_new, v_new)
    scale_spec = P(None, "model", None)
    return shard_map(
        paged_attend, mesh=mesh,
        in_specs=(pool_spec, pool_spec, scale_spec, scale_spec, P(), P(),
                  head_spec, head_spec, head_spec),
        out_specs=head_spec, check_vma=False,
    )(pool_k, pool_v, scale_k, scale_v, bt, start, q, k_new, v_new)
