#!/usr/bin/env python
"""Single-chip ladder benchmark: BASELINE configs 2-5 shapes (VERDICT r4 #1).

BASELINE.md names GPT-small/medium/large/XL on v4-8/16/32 pods; pod hardware
is unavailable here, so this measures the per-chip slice of each ladder rung
on the one real chip — GPT-small and GPT-medium in full (they fit), and the
16-layer stage slices of GPT-large/XL that docs/DESIGN.md §2 memory-profiles
(what one pipeline stage of the 4/8-stage recipe would execute). All rungs
use head_dim >= 64, the regime where the MXU contraction is not structurally
capped (DESIGN.md §5: head_dim=32 pins attention matmuls at ~25% of peak).

Usage: python tools/bench_ladder.py [--only NAME] [--batch N] [--steps N]
Prints one JSON line per shape; `python bench.py` imports `run_ladder`
(and the shared `make_batch`/`time_windows` harness) from here and embeds
the same measurements in the driver-facing JSON.
"""

import argparse
import json
import sys
import time

import numpy as np

LADDER = [
    # name, dim, heads, head_dim, layers, seq, batch, remat, scan
    # ("slice" = the 16-layer pipeline-stage slice DESIGN.md §2 profiles;
    #  full GPT-large/XL state does not fit one 16 GB chip at f32+Adam).
    # batch sizes + layer-stack execution swept on the real chip
    # 2026-07-30: the largest fitting batch won every rung (remat keeps
    # temp flat, so bigger batches just amortize the weight traffic
    # better); unrolled blocks beat the scanned stack on medium/large
    # (+~1% MFU) while the xl slice measured better scanned.
    ("gpt-small-dim768", 768, 12, 64, 12, 512, 64, False, False),
    ("gpt-medium-dim1024", 1024, 16, 64, 24, 512, 32, True, False),
    ("gpt-large-slice-dim1280", 1280, 20, 64, 16, 512, 32, True, False),
    ("gpt-xl-slice-dim1600", 1600, 25, 64, 16, 512, 32, True, True),
]


def make_batch(rng, vocab: int, batch: int, seq: int):
    """Synthetic (model_batch, targets) in the trainer's input format —
    the ONE batch builder every bench/probe in bench.py and this tool
    shares."""
    ids = rng.randint(0, vocab, size=(batch, seq)).astype(np.int32)
    model_batch = {
        "input_ids": ids,
        "position_ids": np.ascontiguousarray(
            np.broadcast_to(np.arange(seq, dtype=np.int32), ids.shape)
        ),
        "mask": np.zeros_like(ids, dtype=bool),
    }
    return model_batch, np.roll(ids, -1, axis=1).astype(np.int32)


def setup_step(cfg, strategy=None, lr=1e-4, seed=0):
    """State init + jitted step fns + sharded placement — the setup block
    every bench/probe repeats (bench.py's headline/long-context/offload/MoE
    probes and every ladder rung). Returns
    `(train_step, state, state_shapes, state_sharding)` ready for
    `time_windows`; warmup/compile happens there."""
    import jax

    from tpukit.shardings import SingleDevice
    from tpukit.train import create_train_state, make_optimizer, make_step_fns

    strategy = strategy if strategy is not None else SingleDevice()
    optimizer = make_optimizer(lr)
    state = create_train_state(
        jax.random.PRNGKey(seed), cfg, optimizer, strategy=strategy
    )
    shapes = jax.eval_shape(lambda: state)
    train_step, _, state_sharding = make_step_fns(cfg, optimizer, strategy, shapes)
    state = jax.device_put(state, state_sharding)
    return train_step, state, shapes, state_sharding


def time_windows(step_fn, state, model_batch, targets, steps: int,
                 windows: int, warmup: int = 3):
    """Warm up (compile), then time `windows` windows of `steps` steps.
    Returns (window_times, state, last_loss). Callers report min(times)
    as steady-state and may report the spread as the noise band. Each
    window ends in float(loss): a host read is a correct sync."""
    last = None  # warmup=0 support (ADVICE r5 #5): no sync before the loops
    for _ in range(warmup):
        state, loss = step_fn(state, model_batch, targets)
    if warmup:
        last = float(loss)  # one sync: compile + warmup finish before timing
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss = step_fn(state, model_batch, targets)
        last = float(loss)
        times.append(time.perf_counter() - t0)
    return times, state, last


def bench_shape(name, dim, heads, head_dim, layers, seq, batch, remat, scan,
                steps=8, windows=3):
    import jax.numpy as jnp

    from tpukit.model import GPTConfig
    from tpukit.obs import peak_flops_per_chip, train_flops_per_token

    cfg = GPTConfig(
        dim=dim,
        head_dim=head_dim,
        heads=heads,
        num_layers=layers,
        vocab_size=50257,
        max_position_embeddings=seq,
        compute_dtype=jnp.bfloat16,
        remat_layers=remat,
        scan_layers=scan,
    )
    train_step, state, _, _ = setup_step(cfg)

    model_batch, targets = make_batch(np.random.RandomState(0), cfg.vocab_size, batch, seq)
    times, state, _ = time_windows(
        train_step, state, model_batch, targets, steps, windows, warmup=2
    )
    best = min(times)

    tps = steps * batch * seq / best
    fpt = train_flops_per_token(cfg, seq)
    peak = peak_flops_per_chip()
    mfu = tps * fpt / peak if peak else None
    del state
    return {
        "shape": name,
        "config": f"dim{dim} hd{head_dim}x{heads} L{layers} seq{seq} b{batch}"
                  + (" remat" if remat else "")
                  + (" scanned" if scan else " unrolled"),
        "tokens_per_sec_per_chip": round(tps, 1),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "step_ms": round(best / steps * 1e3, 2),
    }


def run_ladder(steps=8, windows=3, only=None, batch=None):
    """Run every rung, never raising: failures land in the record as
    `error` (VERDICT r4 #8 — silent nulls hide regressions)."""
    out = []
    for name, dim, heads, hd, layers, seq, b, remat, scan in LADDER:
        if only and only not in name:
            continue
        try:
            out.append(bench_shape(name, dim, heads, hd, layers, seq,
                                   batch or b, remat, scan, steps, windows))
        except Exception as exc:
            out.append({"shape": name, "error": repr(exc)})
    return out


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None)
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--windows", type=int, default=3)
    args = p.parse_args()
    for rec in run_ladder(args.steps, args.windows, args.only, args.batch):
        print(json.dumps(rec))
        sys.stdout.flush()
