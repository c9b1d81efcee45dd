#!/usr/bin/env python3
"""Record a SMALL profiler trace of a few train steps of a tiny GPT on the
chip(s) this machine holds, and print what is in it: planes, lines, event
counts and names. The recorded file is what tests/benchmark_tests checks the
reduction on; the listing is how benchmark/xplane.py's layout notes were
learned. Needs a TPU.

    python3 benchmark/tools/record_trace.py --out chiprun_out/traces [--scan] [--show FILE]

`--scan` records the same toy with four layers under `scan_layers`, so that
the trace holds the `while` event a scanned stack runs inside.
"""

from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def show(path: str, top: int = 12) -> None:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            names = Counter(e.name[:90] for e in events)
            span = (min(e.start_ns for e in events), max(e.start_ns + e.duration_ns for e in events))
            print(f"  line {line.name!r}: {len(events)} events, {len(names)} names, "
                  f"ns {span[0]:.0f}..{span[1]:.0f}")
            for name, n in names.most_common(top):
                print(f"     {n:6d}  {name}")
            if plane.name.startswith("/device") and line.name == "XLA Ops":
                print("     stats of one event:", [(k, str(v)[:60]) for k, v in list(events[0].stats)[:12]])


def record(out: Path, scan: bool = False) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import common
    from tpukit import shardings
    from tpukit.mesh import create_mesh
    from tpukit.model import GPTConfig
    from tpukit.train import create_train_state, make_optimizer, make_step_fns

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    n = len(devices)
    cfg = GPTConfig(dim=256, heads=4, head_dim=64, num_layers=4 if scan else 2, vocab_size=2000,
                    max_position_embeddings=1024, compute_dtype=jnp.bfloat16, scan_layers=scan)
    strategy = (shardings.FSDP(create_mesh({"data": n})) if n > 1 else shardings.SingleDevice())
    optimizer = make_optimizer(3e-4)
    init_fn = lambda rng: create_train_state(rng, cfg, optimizer, strategy)  # noqa: E731
    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    step, _, sharding = make_step_fns(cfg, optimizer, strategy, shapes)
    state = jax.jit(init_fn, out_shardings=sharding)(jax.random.PRNGKey(0))
    rows, seq = 2 * n, 1023
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (rows, seq)).astype(np.int32)
    batch = {"input_ids": ids, "position_ids": np.broadcast_to(np.arange(seq, dtype=np.int32), ids.shape).copy(),
             "mask": np.zeros(ids.shape, bool)}
    targets = np.roll(ids, -1, axis=1)
    for _ in range(2):
        state, loss = step(state, batch, targets)
    jax.block_until_ready(loss)
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    with common.profiler_trace(Path(tmp)) as info:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench:dispatch"):
                state, loss = step(state, batch, targets)
            with jax.profiler.TraceAnnotation("bench:wait_device"):
                jax.block_until_ready(loss)
    out.mkdir(parents=True, exist_ok=True)
    dest = out / f"tiny_train_{n}chip{'_scan' if scan else ''}.xplane.pb"
    shutil.copy(info["xplane"], dest)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"recorded {dest} ({dest.stat().st_size} bytes), host window {info['t1'] - info['t0']:.4f} s")
    return str(dest)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/traces")
    ap.add_argument("--scan", action="store_true", help="four layers under scan_layers: a `while` in the trace")
    ap.add_argument("--show", default=None, help="only list this .xplane.pb")
    args = ap.parse_args()
    show(args.show or record(Path(args.out), args.scan))


if __name__ == "__main__":
    main()
