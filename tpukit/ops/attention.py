"""Causal multi-head attention core.

TPU-native twin of the attention math in reference `models/gpt.py:68-105`
(`SelfAttention.forward`). Behavioral parity with two deliberate divergences,
both flagged in the reference's own TODOs (`models/gpt.py:81-82`):

- The reference materializes a full `[N, h, S, S]` additive causal mask every
  forward (`1e9 * (tril(ones) - 1)` then `repeat`, models/gpt.py:83-88) —
  O(N*h*S^2) memory traffic. Here the causal constraint is a broadcast
  `jnp.where` over a `[S, S]` boolean, which XLA fuses into the logits
  computation; no mask tensor ever hits HBM.
- Softmax runs in float32 regardless of compute dtype (torch autocast does the
  same for `F.softmax`, which the reference relies on at models/gpt.py:97).

The padding mask convention is the reference's: `mask` is `[B, S]` boolean
with **True = masked**, applied key-side with the dtype's most-negative finite
value (`masked_fill(mask[:, None, None, :], finfo.min)`, models/gpt.py:93-95).

A fused Pallas flash-attention kernel (tpukit/ops/pallas_attention.py) can be
swapped in on TPU via `causal_attention(..., impl="flash")`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e9  # twin of the reference's additive causal constant (models/gpt.py:83)


def causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float,
    pad_mask: jax.Array | None = None,
    impl: str = "xla",
    ring_axis: str = "seq",
    ring_layout: str = "contiguous",
    shard=None,
) -> jax.Array:
    """Scaled dot-product causal attention.

    Args:
      q, k, v: `[B, heads, S, head_dim]`.
      scale: `1 / sqrt(head_dim)` (reference models/gpt.py:66).
      pad_mask: optional `[B, S]` bool, True = position is padding (masked).
      impl: "xla" (fused by the compiler) or "flash" (Pallas kernel on TPU).
      shard: `(mesh, batch_axes, head_axes)` under a multi-device GSPMD jit
        (`Strategy.kernel_shard`), for the flash kernel's per-shard call.

    Returns `[B, heads, S, head_dim]` in the dtype of `v`.
    """
    if impl == "auto":
        # Measured on v5e: XLA's fused attention wins below ~512 tokens
        # (kernel grid overhead dominates tiny S x S); the flash kernel wins
        # from 512 up (+68% at S=1024, +130% at S=2048) and is the only
        # option at S >= 8k, where the materialized S x S no longer compiles.
        #
        # The kernel is safe in every sharded context: under GSPMD jit
        # (DP/FSDP/TP) the strategy names its mesh axes in `shard` and the
        # kernel runs per shard (pallas_attention.per_shard), and
        # pallas_call composes directly with shard_map Manual regions
        # (pipeline recipes).
        from tpukit.ops.pallas_attention import on_tpu_backend

        impl = "flash" if (on_tpu_backend() and q.shape[2] >= 512) else "xla"
    if impl == "flash":
        from tpukit.ops.pallas_attention import flash_causal_attention

        return flash_causal_attention(
            q, k, v, scale=scale, pad_mask=pad_mask, shard=shard
        )
    if impl == "ring":
        from tpukit.ring_attention import ring_causal_attention

        return ring_causal_attention(
            q, k, v, scale=scale, axis_name=ring_axis, pad_mask=pad_mask,
            layout=ring_layout,
        )
    if impl == "ulysses":
        from tpukit.ring_attention import ulysses_attention

        return ulysses_attention(
            q, k, v, scale=scale, axis_name=ring_axis, pad_mask=pad_mask
        )

    seq_len = q.shape[2]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale

    causal = jnp.tril(jnp.ones((seq_len, seq_len), dtype=jnp.bool_))
    logits = logits + jnp.where(causal, 0.0, NEG_INF).astype(logits.dtype)[None, None]

    if pad_mask is not None:
        logits = jnp.where(
            pad_mask[:, None, None, :],
            jnp.finfo(logits.dtype).min,
            logits,
        )

    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)
