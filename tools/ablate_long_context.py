"""Long-context component split via full-train-step ablations (a chained
step loop ending in a float() host read). Varies num_layers and sequence length at constant token count to
separate head vs trunk vs attention-S^2 time.

    PYTHONPATH=. python tools/ablate_long_context.py
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def step_time_ms(cfg, batch, seq, fused=True, iters=8):
    from tpukit.shardings import SingleDevice
    from tpukit.train import create_train_state, make_optimizer, make_step_fns

    strategy = SingleDevice()
    strategy.fused_head = fused
    optimizer = make_optimizer(1e-4)
    state = create_train_state(jax.random.PRNGKey(0), cfg, optimizer)
    shapes = jax.eval_shape(lambda: state)
    step, _, sh = make_step_fns(cfg, optimizer, strategy, shapes)
    state = jax.device_put(state, sh)
    ids = jnp.zeros((batch, seq - 1), jnp.int32)
    model_batch = {
        "input_ids": ids,
        "position_ids": jnp.broadcast_to(jnp.arange(seq - 1, dtype=jnp.int32), ids.shape),
        "mask": jnp.zeros(ids.shape, bool),
    }
    targets = jnp.zeros(ids.shape, jnp.int32)
    for _ in range(2):
        state, l = step(state, model_batch, targets)
    float(l)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            state, l = step(state, model_batch, targets)
        float(l)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e3


def main():
    from tpukit.model import GPTConfig

    base = dict(
        dim=256, head_dim=32, heads=8, vocab_size=50257,
        compute_dtype=jnp.bfloat16,
    )
    tok = 16 * 2048  # constant token budget

    rows = []
    for tag, layers, seq, batch, fused in [
        ("L8 S2048 fused", 8, 2048, 16, True),
        ("L8 S2048 unfused", 8, 2048, 16, False),
        ("L4 S2048 fused", 4, 2048, 16, True),
        ("L8 S1024 fused (b32)", 8, 1024, 32, True),
        ("L8 S512 fused (b64)", 8, 512, 64, True),
    ]:
        cfg = GPTConfig(num_layers=layers, max_position_embeddings=seq, **base)
        ms = step_time_ms(cfg, batch, seq, fused)
        tps = batch * (seq - 1) / (ms / 1e3)
        rows.append((tag, ms, tps))
        print(f"{tag:24s}: {ms:7.1f} ms  ({tps:,.0f} tok/s)", flush=True)

    by = {t: m for t, m, _ in rows}
    t8, t4 = by["L8 S2048 fused"], by["L4 S2048 fused"]
    per_layer = (t8 - t4) / 4
    head_plus = t8 - 8 * per_layer  # head + embeddings + optimizer + overhead
    print(f"\nper-layer (trunk+attn @S=2048): {per_layer:.1f} ms")
    print(f"head+emb+opt+overhead:          {head_plus:.1f} ms")
    # attention S^2 share: halving S at constant tokens halves S^2 work
    t1k = by["L8 S1024 fused (b32)"]
    print(f"S2048 -> S1024 delta (≈ half the attn-S^2 cost): {t8 - t1k:.1f} ms")


if __name__ == "__main__":
    main()
