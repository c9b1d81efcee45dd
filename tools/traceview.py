#!/usr/bin/env python
"""Render a serving run's request traces (round 20) from the metrics
JSONL: per-request span-tree post-mortem in the terminal, plus a
Chrome-trace / Perfetto export (`--out trace.json`, open in
chrome://tracing or ui.perfetto.dev).

Reads the `kind="trace_event"` rows the engine/fleet tracer flushes
(tpukit/obs/trace.py module docstring has the event vocabulary) and
re-derives the span trees locally — the terminal table therefore works
on a log copied off the machine, and disagreements between it and the
run's own `kind="trace"` rows would indicate a torn flush.

Like report.py and flightview.py this tool imports NO jax (or numpy):
`tpukit/obs/trace.py` is deliberately stdlib-only and is loaded by file
path below, bypassing `tpukit/__init__` (which imports jax).

Usage:
    python tools/traceview.py run.jsonl                  # terminal table
    python tools/traceview.py run.jsonl --out trace.json # Perfetto JSON
    python tools/traceview.py run.jsonl --rid 17         # one request
Exit codes: 0 rendered, 1 no trace events in the file.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import sys
from pathlib import Path


def _load_trace_lib():
    """Import tpukit/obs/trace.py by path — `import tpukit` would pull in
    jax, which this post-mortem tool must not require."""
    path = Path(__file__).resolve().parent.parent / "tpukit" / "obs" / "trace.py"
    spec = importlib.util.spec_from_file_location("tpukit_obs_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(path: str) -> list[dict]:
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except ValueError:
                continue  # torn final line from a killed run
    return records


def _ms(s) -> str:
    return f"{1e3 * s:8.1f}" if s is not None else "       -"


def render(trees: list[dict], trace_lib) -> str:
    out: list[str] = []
    w = out.append
    w("== request traces ==")
    w(f"{'trace':>6} {'rid':>5} {'att':>3} {'quanta':>6} "
      f"{'queue':>8} {'prefill':>8} {'handoff':>8} {'decode':>8} "
      f"{'sync':>8} {'other':>8} {'e2e_ms':>8}  ok reason     replicas")
    for t in trees:
        ph = t["phases"]
        w(f"{t['trace']:>6} {t['rid']:>5} {t['attempts']:>3} "
          f"{t['quanta']:>6} {_ms(ph['queue_wait'])} {_ms(ph['prefill'])} "
          f"{_ms(ph['handoff'])} {_ms(ph['decode'])} "
          f"{_ms(ph['sync_stall'])} {_ms(ph['other'])} {_ms(t['e2e_s'])}  "
          f"{'ok' if t['complete'] else ('OPEN' if not t['closed'] else 'SUM!')}"
          f" {str(t['reason'] or '-'):<10} {','.join(t['replicas']) or '-'}")
    comp = trace_lib.completeness(trees)
    closed = sum(1 for t in trees if t["closed"])
    w(f"{len(trees)} trace(s): {closed} closed, "
      f"{100 * comp:.0f}% complete" if comp is not None else "no traces")
    p50, p99 = trace_lib.phase_stats(trees)
    if trees:
        w("phase walls (ms)   " + "  ".join(
            f"{k} p50={1e3 * p50[k]:.1f}/p99={1e3 * p99[k]:.1f}"
            for k in trace_lib.PHASES if p50.get(k) is not None))
    return "\n".join(out)


def selection_line(events: list[dict]) -> str | None:
    """The share of the context a latent model's key selection kept in the
    decode ticks (the quanta's `select_kept` / `select_visible`); None for a
    model that selects no keys."""
    quanta = [e for e in events if e.get("ev") == "quantum" and "select_visible" in e]
    visible = sum(e["select_visible"] for e in quanta)
    if not visible:
        return None
    kept = sum(e["select_kept"] for e in quanta)
    return (f"key selection: {kept} of the {visible} keys the decode ticks' queries "
            f"could see kept ({kept / visible:.3f}), {len(quanta)} quanta")


def host_lines(events: list[dict]) -> list[str]:
    """The engine's mean host account a quantum: each phase's wall, the
    thread's CPU inside it and the rest (the host waiting), then the sync
    split at the instant the device's answer was ready into the wait and
    the fetch after it. Empty for events without the CPU readings."""
    quanta = [e for e in events if e.get("ev") == "quantum" and "host_cpu" in e]
    if not quanta:
        return []
    n = len(quanta)
    mean = lambda f: 1e3 * sum(map(f, quanta)) / n  # noqa: E731
    phases = []
    for k in sorted({k for q in quanta for k in q["host"]}):
        wall = mean(lambda q: q["host"].get(k, 0.0))
        cpu = mean(lambda q: q["host_cpu"].get(k, 0.0))
        phases.append(f"{k} {wall:.2f}/{cpu:.2f}/{wall - cpu:.2f}")
    cpu = lambda part: mean(lambda q: q["sync_cpu"][part])  # noqa: E731
    return [
        f"host per quantum (ms wall/cpu/wait, {n} quanta): " + "  ".join(phases),
        f"sync per quantum (ms wall/cpu): wait {mean(lambda q: q['s_ready'] - q['s0']):.2f}/"
        f"{cpu('sync_wait'):.2f}  fetch {mean(lambda q: q['s1'] - q['s_ready']):.2f}/{cpu('sync_fetch'):.2f}",
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("log", help="metrics JSONL from a --metrics_log run")
    ap.add_argument("--out", default="",
                    help="write Chrome-trace JSON here (chrome://tracing "
                         "or ui.perfetto.dev)")
    ap.add_argument("--rid", type=int, default=None,
                    help="only the request with this rid")
    args = ap.parse_args(argv)

    trace_lib = _load_trace_lib()
    records = load(args.log)
    events = [
        {k: v for k, v in r.items() if k not in ("kind", "time")}
        for r in records if r.get("kind") == "trace_event"
    ]
    if not events:
        print(f"{args.log}: no trace_event rows (run with tracing on — "
              f"it is the default; check --no_trace was not passed)",
              file=sys.stderr)
        return 1

    trees = trace_lib.build_trees(events)
    if args.rid is not None:
        keep = {t["trace"] for t in trees if t["rid"] == args.rid}
        trees = [t for t in trees if t["trace"] in keep]
        events = [e for e in events
                  if e.get("trace") in keep or (
                      e.get("ev") == "quantum"
                      and keep & set(e.get("lanes") or ()))]
        if not trees:
            print(f"rid {args.rid}: no trace", file=sys.stderr)
            return 1

    print(render(trees, trace_lib))
    selected = selection_line(events)
    if selected:
        print(selected)
    for line in host_lines(events):
        print(line)
    if args.out:
        chrome = trace_lib.to_chrome(events)
        with open(args.out, "w") as f:
            json.dump(chrome, f)
        print(f"wrote {len(chrome['traceEvents'])} Chrome-trace events -> "
              f"{args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
