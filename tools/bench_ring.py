"""Microbench: ring-attention schedule, optimized vs naive (VERDICT r3 #4).

Compares tpukit.ring_attention.ring_causal_attention (hop-skipping +
input-dtype MXU matmuls + permute/compute overlap) against the r3 naive
schedule (dense f32 einsum on every hop) at long-context shapes, inside the
same shard_map the ContextParallel strategy uses.

A ring needs >= 2 devices; on this machine that means the virtual CPU mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu) —
which is also where hop-skipping shows up directly in wall-clock, since one
host executes every device's compute serially. On real multi-chip TPU the
skip cuts total FLOPs/energy the same way, while the critical path (the
last device computes on every hop) is shortened by the bf16 MXU matmuls and
the transfer/compute overlap.

Usage:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python tools/bench_ring.py [--seq 8192] [--batch 1] [--grad]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from tpukit.mesh import create_mesh
from tpukit.ops.attention import NEG_INF
from tpukit.ring_attention import ring_causal_attention, zigzag_order


def naive_ring_attention(q, k, v, *, scale, axis_name, pad_mask=None):
    """The round-3 schedule: full f32 dense einsum on EVERY hop (including
    the entirely-masked ones), kept verbatim as the comparison baseline."""
    ring = jax.lax.axis_size(axis_name)
    my_index = jax.lax.axis_index(axis_name)
    batch, _, s_local, _ = q.shape
    if pad_mask is None:
        pad_mask = jnp.zeros((batch, s_local), dtype=jnp.bool_)

    rows = my_index * s_local + jnp.arange(s_local)
    qf = q.astype(jnp.float32)
    perm = [(j, (j + 1) % ring) for j in range(ring)]

    def step(carry, _):
        m, l, acc, k_c, v_c, mask_c, src = carry
        cols = src * s_local + jnp.arange(s_local)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_c.astype(jnp.float32)) * scale
        s = s + jnp.where(cols[None, :] <= rows[:, None], 0.0, NEG_INF)
        s = jnp.where(mask_c[:, None, None, :], jnp.finfo(jnp.float32).min, s)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        correction = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * correction + jnp.sum(p, axis=-1)
        acc_new = acc * correction[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_c.astype(jnp.float32)
        )
        k_next = jax.lax.ppermute(k_c, axis_name, perm)
        v_next = jax.lax.ppermute(v_c, axis_name, perm)
        mask_next = jax.lax.ppermute(mask_c, axis_name, perm)
        return (m_new, l_new, acc_new, k_next, v_next, mask_next, (src - 1) % ring), None

    init = (
        jnp.full(q.shape[:3], -jnp.inf, jnp.float32),
        jnp.zeros(q.shape[:3], jnp.float32),
        jnp.zeros(qf.shape, jnp.float32),
        k, v, pad_mask, my_index,
    )
    (m, l, acc, *_), _ = jax.lax.scan(step, init, None, length=ring)
    return (acc / l[..., None]).astype(v.dtype)


def timed(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head_dim", type=int, default=32)
    ap.add_argument("--grad", action="store_true", help="time fwd+bwd instead of fwd")
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()

    n = len(jax.devices())
    if n < 2:
        raise SystemExit(
            "ring needs >=2 devices; run with XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu"
        )
    mesh = create_mesh({"seq": n})
    scale = args.head_dim**-0.5
    dtype = jnp.bfloat16

    rng = np.random.RandomState(0)
    shape = (args.batch, args.heads, args.seq, args.head_dim)
    q, k, v = (jnp.asarray(rng.randn(*shape), dtype) for _ in range(3))
    mask = jnp.zeros((args.batch, args.seq), jnp.bool_)

    def on_mesh(impl, layout="contiguous"):
        def local(q, k, v, m):
            if impl is naive_ring_attention:
                return impl(q, k, v, scale=scale, axis_name="seq", pad_mask=m)
            return impl(q, k, v, scale=scale, axis_name="seq", pad_mask=m, layout=layout)

        f = shard_map(
            local, mesh=mesh,
            in_specs=(P(None, None, "seq"),) * 3 + (P(None, "seq"),),
            out_specs=P(None, None, "seq"),
            check_vma=False,
        )
        if args.grad:
            loss = lambda q, k, v, m: jnp.sum(f(q, k, v, m).astype(jnp.float32) ** 2)
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        return jax.jit(f)

    t_old = timed(on_mesh(naive_ring_attention), q, k, v, mask, iters=args.iters)
    t_new = timed(on_mesh(ring_causal_attention), q, k, v, mask, iters=args.iters)
    # zigzag operates on the permuted layout (ContextParallel permutes once
    # per step on [B,S] int arrays — negligible; excluded here)
    order = zigzag_order(args.seq, n)
    qz, kz, vz = (t[:, :, order] for t in (q, k, v))
    t_zz = timed(on_mesh(ring_causal_attention, "zigzag"), qz, kz, vz, mask[:, order], iters=args.iters)

    label = "fwd+bwd" if args.grad else "fwd"
    print(
        f"ring {label} S={args.seq} B={args.batch} h={args.heads} "
        f"d={args.head_dim} P={n} ({jax.devices()[0].device_kind}):"
    )
    print(f"  naive (r3)     : {t_old*1e3:8.2f} ms")
    print(f"  skip+bf16      : {t_new*1e3:8.2f} ms   speedup {t_old/t_new:.2f}x")
    print(f"  zigzag balanced: {t_zz*1e3:8.2f} ms   speedup {t_old/t_zz:.2f}x")


if __name__ == "__main__":
    main()
