"""Ring attention: causal attention over a sequence-sharded mesh axis.

The reference has no sequence/context parallelism at all — its attention is a
dense single-device S x S matmul and the max sequence is 256 (SURVEY §2.4,
§5; reference models/gpt.py:79-99, data.py:18). tpukit makes long-context a
first-class axis: shard the *sequence* over a `seq` mesh axis and compute
exact causal attention with the classic ring schedule — each device keeps
its local Q block and online-softmax state while K/V (and the padding-mask
slice that travels with them, the CP analogue of the reference pipeline's
(x, mask) tuple threading) rotate around the ring via `lax.ppermute`, one
hop per step, P steps total. Peak memory per device is O(S/P * S/P) scores
and O(S/P) activations; the collective rides ICI.

Schedule efficiency (VERDICT r3 #3):
  - **Causally-unreachable hops are skipped.** After i hops a device holds
    the K/V block that originated at (my_index - i) mod P; blocks with
    src > my_index lie entirely in the causal future of every local query,
    so the whole [B,h,S_loc,S_loc] score/softmax/PV computation (and its
    backward) is gated off with `lax.cond` — only the ppermute runs. Across
    the ring that cuts total attention FLOPs from P^2 blocks to P(P+1)/2
    (~2x at P=8). The predicate is device-varying but the gated region is
    collective-free (the permutes happen outside it), so the cond is legal
    under shard_map.
  - **Matmuls stay in the input dtype** (bf16 under the default training
    policy) with float32 accumulation (`preferred_element_type`) — the MXU
    path — instead of upcasting Q/K to f32 first; only the softmax state
    (m, l, acc) is carried in f32, matching the dense XLA path's
    "logits in compute dtype, softmax in f32" split (ops/attention.py).
  - **Transfer/compute overlap**: each hop's ppermute depends only on the
    carried K/V, never on that hop's score math, and is issued before it —
    XLA's async collective scheduler overlaps the ICI transfer with the
    current hop's compute (double buffering by dataflow).

Masking matches tpukit/ops/attention.py: -1e9 additive causal term on
*global* positions (each device knows its ring offset), then finfo.min
overwrite for padded keys. As with the flash kernel, a fully-padded query
row attends uniformly over its causal prefix rather than over all S (the
XLA path's quirk); such rows are loss-ignored.

Runs inside `shard_map` (Manual mesh axes) — see the ContextParallel
strategy in tpukit/shardings.py. Autodiff through `ppermute`/`scan`/`cond`
gives the backward ring for free (and the cond gates the backward FLOPs of
skipped hops too).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tpukit.ops.attention import NEG_INF


def zigzag_order(seq_len: int, ring: int) -> np.ndarray:
    """Token permutation for the causally-balanced zigzag layout.

    Splits `seq_len` into 2*ring chunks and orders them so a CONTIGUOUS
    shard over `ring` devices gives device d chunks (d, 2*ring-1-d): one
    early chunk (few causal keys) and one late chunk (many) — every device
    then does the same attention work per hop, fixing the contiguous ring's
    critical-path imbalance (device P-1 saw P reachable hops, device 0 one).
    Host-side numpy; apply as `x[:, zigzag_order(S, P)]` before sharding.
    """
    if seq_len % (2 * ring):
        raise ValueError(f"zigzag needs seq_len % (2*ring) == 0, got {seq_len} over {ring}")
    c = seq_len // (2 * ring)
    idx = []
    for d in range(ring):
        idx.append(np.arange(d * c, (d + 1) * c))
        idx.append(np.arange((2 * ring - 1 - d) * c, (2 * ring - d) * c))
    return np.concatenate(idx)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float,
    axis_name: str,
    pad_mask: jax.Array | None = None,
) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses-style) context parallelism: the second
    long-context schedule next to the ring.

    Inside shard_map each device holds `[B, h, S/P, d]`. One `all_to_all`
    re-partitions from sequence-sharded to HEAD-sharded (`[B, h/P, S, d]`),
    each device runs ordinary full-sequence causal attention on its head
    subset — which on TPU is the Pallas flash kernel, the fastest attention
    path in the framework — and a second all_to_all restores the sequence
    sharding. Two collectives total per attention call (vs P ppermute hops
    for the ring), at the cost of requiring heads % P == 0 and O(S) per
    device transient activations for the exchanged heads.

    Works on the CONTIGUOUS sequence layout (positions are implicit in the
    gathered order), unlike the ring's zigzag. Exactness: the local
    computation is the standard causal attention over the full sequence —
    no online-state stitching at all.
    """
    ring = jax.lax.axis_size(axis_name)
    heads = q.shape[1]
    if heads % ring:
        raise ValueError(
            f"ulysses attention needs heads ({heads}) divisible by the "
            f"'{axis_name}' axis size ({ring}); use the ring schedule"
        )

    # One stacked exchange for q/k/v (axes shift by one under the stack):
    # a single all_to_all instead of three dependency-free launches.
    qkv = jnp.stack([q, k, v])  # [3, B, h, S/P, d]
    qkv = jax.lax.all_to_all(qkv, axis_name, split_axis=2, concat_axis=3, tiled=True)  # lint: allow(collective-spelling): ulysses head re-partition — activation re-layout inside the attention schedule (CP comm_ops audits it), not a grad/dispatch wire
    qh, kh, vh = qkv[0], qkv[1], qkv[2]  # [B, h/P, S, d] each
    if pad_mask is not None:
        pad_mask = jax.lax.all_gather(pad_mask, axis_name, axis=1, tiled=True)  # lint: allow(collective-spelling): boolean pad-mask broadcast for the gathered sequence — bytes are negligible and audited by CP comm_ops, not a payload wire

    from tpukit.ops.attention import causal_attention

    out = causal_attention(qh, kh, vh, scale=scale, pad_mask=pad_mask, impl="auto")
    # heads -> seq: the inverse exchange
    return jax.lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1, tiled=True)  # lint: allow(collective-spelling): ulysses inverse head re-partition — same activation re-layout as the forward exchange


def _online_update(m, l, acc, s, v_blk):
    """One online-softmax merge of score block `s` (f32, masks applied) into
    the running (max, denom, numerator) state. The PV matmul runs in v's
    dtype (MXU) with f32 accumulation. Shared by both ring schedules."""
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    correction = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * correction + jnp.sum(p, axis=-1)
    acc_new = acc * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk,
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def ring_causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float,
    axis_name: str,
    pad_mask: jax.Array | None = None,
    layout: str = "contiguous",
) -> jax.Array:
    """Exact causal attention over sequence shards.

    Args (all LOCAL shards, inside shard_map over `axis_name`):
      q, k, v: `[B, heads, S_local, head_dim]`.
      pad_mask: optional `[B, S_local]` bool, True = padding.
      layout: "contiguous" (device d holds global rows [d*Sl, (d+1)*Sl)) or
        "zigzag" (device d holds chunks d and 2P-1-d of 2P, i.e. the caller
        permuted the sequence with `zigzag_order` before sharding — the
        causally load-balanced schedule).

    Returns `[B, heads, S_local, head_dim]` in v's dtype.
    """
    if layout == "zigzag":
        return _zigzag_ring(q, k, v, scale=scale, axis_name=axis_name, pad_mask=pad_mask)
    if layout != "contiguous":
        raise ValueError(f"unknown ring layout {layout!r}")
    ring = jax.lax.axis_size(axis_name)
    my_index = jax.lax.axis_index(axis_name)
    batch, _, s_local, _ = q.shape
    if pad_mask is None:
        pad_mask = jnp.zeros((batch, s_local), dtype=jnp.bool_)

    rows = my_index * s_local + jnp.arange(s_local)  # global query positions

    # Each hop sends K/V/mask to the *next* device, so after i steps a device
    # holds the block that originated at (my_index - i) mod ring.
    perm = [(j, (j + 1) % ring) for j in range(ring)]

    def step(carry, _):
        m, l, acc, k_c, v_c, mask_c, src = carry

        # Rotate first: the sends depend only on the carried K/V, so the
        # collective-permute overlaps this hop's compute.
        k_next = jax.lax.ppermute(k_c, axis_name, perm)
        v_next = jax.lax.ppermute(v_c, axis_name, perm)
        mask_next = jax.lax.ppermute(mask_c, axis_name, perm)

        def hop(state):
            m, l, acc = state
            cols = src * s_local + jnp.arange(s_local)  # global key positions
            s = (
                jnp.einsum(
                    "bhqd,bhkd->bhqk", q, k_c,
                    preferred_element_type=jnp.float32,
                )
                * scale
            )
            # For strictly-lower hops (src < my_index) this compare is
            # all-true and folds to a no-op pass; only the diagonal hop
            # actually masks. One fused VPU pass either way.
            s = s + jnp.where(cols[None, :] <= rows[:, None], 0.0, NEG_INF)
            s = jnp.where(
                mask_c[:, None, None, :], jnp.finfo(jnp.float32).min, s
            )
            return _online_update(m, l, acc, s, v_c)

        # src > my_index: the whole block is in the causal future of every
        # local query — skip scores, softmax, PV and their backward.
        m, l, acc = jax.lax.cond(src <= my_index, hop, lambda s: s, (m, l, acc))
        return (m, l, acc, k_next, v_next, mask_next, (src - 1) % ring), None

    init = (
        jnp.full(q.shape[:3], -jnp.inf, jnp.float32),  # running max
        jnp.zeros(q.shape[:3], jnp.float32),  # running denom
        jnp.zeros(q.shape, jnp.float32),  # running numerator
        k,
        v,
        pad_mask,
        my_index,
    )
    (m, l, acc, *_), _ = jax.lax.scan(step, init, None, length=ring)
    return (acc / l[..., None]).astype(v.dtype)


def _zigzag_ring(q, k, v, *, scale, axis_name, pad_mask):
    """Causally load-balanced ring: the zigzag layout (see `zigzag_order`).

    Device d's local rows are chunks (a=d, b=2P-1-d) of 2P; the K/V block
    from ring source s carries chunks (s, 2P-1-s). Chunk-level causal
    reachability (row chunk >= key chunk) reduces each hop to HALF the
    dense block, the same half on every device:

      s < d : [Q_a; Q_b] x K_s           (both sub-blocks fully unmasked)
      s == d: full 2c x 2c block with the exact positional causal mask
              (the two diagonal sub-blocks plus Q_b x K_s)
      s > d : Q_b x [K_s; K_{2P-1-s}]    (both sub-blocks fully unmasked)

    so per-hop work is 2c^2 everywhere (4c^2 on the single diagonal hop) vs
    the contiguous schedule's 4c^2 on every reachable hop concentrated on
    high-index devices. Total FLOPs halve AND the critical path halves —
    the contiguous ring's skip gating couldn't shorten the critical path
    because device P-1 computed a full block every hop.

    Matmuls stay in the input dtype (MXU) with f32 accumulation; softmax
    state is f32; the ppermutes issue before the hop compute for overlap.
    """
    ring = jax.lax.axis_size(axis_name)
    my_index = jax.lax.axis_index(axis_name)
    batch, _, s_local, _ = q.shape
    if s_local % 2:
        raise ValueError(f"zigzag local sequence must be even, got {s_local}")
    c = s_local // 2
    if pad_mask is None:
        pad_mask = jnp.zeros((batch, s_local), dtype=jnp.bool_)

    ar = jnp.arange(c)
    # global positions of the local rows: chunk d then chunk 2P-1-d
    rows = jnp.concatenate([my_index * c + ar, (2 * ring - 1 - my_index) * c + ar])
    finfo_min = jnp.finfo(jnp.float32).min
    perm = [(j, (j + 1) % ring) for j in range(ring)]

    def step(carry, _):
        m, l, acc, k_c, v_c, mask_c, src = carry

        k_next = jax.lax.ppermute(k_c, axis_name, perm)
        v_next = jax.lax.ppermute(v_c, axis_name, perm)
        mask_next = jax.lax.ppermute(mask_c, axis_name, perm)

        def hop_lower(state):
            # src < d: all local rows attend the source's EARLY chunk only
            # (its late chunk 2P-1-src is in every local row's future).
            m, l, acc = state
            k_blk, v_blk, msk = k_c[:, :, :c], v_c[:, :, :c], mask_c[:, :c]
            s = (
                jnp.einsum("bhqd,bhkd->bhqk", q, k_blk, preferred_element_type=jnp.float32)
                * scale
            )
            s = jnp.where(msk[:, None, None, :], finfo_min, s)
            return _online_update(m, l, acc, s, v_blk)

        def hop_diag(state):
            # src == d: the one hop with intra-chunk causal structure —
            # full 2c x 2c block under the exact positional mask.
            m, l, acc = state
            cols = jnp.concatenate([src * c + ar, (2 * ring - 1 - src) * c + ar])
            s = (
                jnp.einsum("bhqd,bhkd->bhqk", q, k_c, preferred_element_type=jnp.float32)
                * scale
            )
            s = s + jnp.where(cols[None, :] <= rows[:, None], 0.0, NEG_INF)
            s = jnp.where(mask_c[:, None, None, :], finfo_min, s)
            return _online_update(m, l, acc, s, v_c)

        def hop_upper(state):
            # src > d: only the local LATE chunk attends, but it reaches
            # both of the source's chunks.
            m, l, acc = state
            qb = q[:, :, c:]
            s = (
                jnp.einsum("bhqd,bhkd->bhqk", qb, k_c, preferred_element_type=jnp.float32)
                * scale
            )
            s = jnp.where(mask_c[:, None, None, :], finfo_min, s)
            mb, lb, accb = _online_update(m[:, :, c:], l[:, :, c:], acc[:, :, c:], s, v_c)
            return (
                jnp.concatenate([m[:, :, :c], mb], axis=2),
                jnp.concatenate([l[:, :, :c], lb], axis=2),
                jnp.concatenate([acc[:, :, :c], accb], axis=2),
            )

        branch = jnp.clip(jnp.sign(src - my_index) + 1, 0, 2)
        m, l, acc = jax.lax.switch(branch, [hop_lower, hop_diag, hop_upper], (m, l, acc))
        return (m, l, acc, k_next, v_next, mask_next, (src - 1) % ring), None

    init = (
        jnp.full(q.shape[:3], -jnp.inf, jnp.float32),
        jnp.zeros(q.shape[:3], jnp.float32),
        jnp.zeros(q.shape, jnp.float32),
        k,
        v,
        pad_mask,
        my_index,
    )
    (m, l, acc, *_), _ = jax.lax.scan(step, init, None, length=ring)
    return (acc / l[..., None]).astype(v.dtype)
