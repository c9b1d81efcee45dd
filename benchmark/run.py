#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in BENCHMARK.json, loads its configuration
(benchmark/configs/<config>.json) and traffic mix
(benchmark/traffic/<traffic>.json), and hands them to the mode the traffic
file names (benchmark/modes/<mode>.py). With --trace 1 the window is traced
and each per-layer metric of the cell is read by its own file
(benchmark/layer_metrics/<name>.py). Adding a configuration, a traffic mix, a
mode, a metric or a kernel's arithmetic is adding files and BENCHMARK.json
entries; no file here names a cell.

The last line of standard output is the result; earlier lines are JSON
objects with an "info" key (medians, counts, compiler figures, the reference
comparison's error and tolerance). One process holds the chip from its first
jax call to its exit and starts no other.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def find_cell(bench: dict, workload: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, config


def metrics_of(bench: dict, kind: str, workload: str) -> list[dict]:
    """The `kind` ("end_to_end" | "per_layer") metrics this cell reports."""
    return [m for m in bench[kind] if workload in m.get("workloads", [workload])]


def info_line(name: str, **fields) -> None:
    print(json.dumps({"info": name, **fields}, default=str), flush=True)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *, root: Path = ROOT,
             require_tpu: bool = True, keep_trace: str | None = None,
             t_process_start: float = T_PROCESS_START) -> dict:
    """Run one cell and return the result object of the last line."""
    from benchmark import common, peaks, xplane

    root = Path(root)
    bench = common.load_json(root / "BENCHMARK.json")
    cell, config_entry = find_cell(bench, workload)
    config = common.load_json(root / config_entry["file"])
    traffic = common.load_json(root / "benchmark" / "traffic" / f"{cell['traffic']}.json")

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise SystemExit(f"needs a TPU: jax found platform {platform!r}")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"{workload} needs {cell['chips']} chips, jax found {len(devices)}")
    devices = devices[: cell["chips"]]
    # an unknown TPU is an error; only a CPU rehearsal runs without peaks
    chip_peaks = peaks.peaks(devices[0].device_kind) if platform == "tpu" else None

    from tpukit.cache import enable_compilation_cache

    enable_compilation_cache()  # $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache
    mode = common.load_by_name("modes", traffic["mode"], root)
    if mode is None:
        raise SystemExit(f"no mode file for {traffic['mode']!r}")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        ctx = {
            "cell": cell, "config": config, "traffic": traffic, "seed": seed,
            "seconds": float(seconds), "chips": cell["chips"], "devices": devices,
            "root": root, "t_process_start": t_process_start, "info": info_line,
            "compiles": common.CompileCounter(), "trace_dir": trace_dir,
        }
        out = mode.run(ctx)
        record = out["record"]
        record.update(peaks=chip_peaks, device_kind=devices[0].device_kind, root=root,
                      config=config, traffic=traffic)
        device = common.device_record(devices)
        record["memory_peak_bytes"] = device["memory_peak_bytes"]
        result = {"correct": out["correct"], "attempted": out["attempted"],
                  "failed": out["failed"], "metrics": {}, "device": device}
        if not trace:
            wanted = metrics_of(bench, "end_to_end", workload)
            for m in wanted:
                result["metrics"][m["name"]] = {"value": out["end_to_end"][m["name"]], "unit": m["unit"]}
            return result

        info = record.pop("trace")
        reduced = None
        if info.get("xplane"):
            if keep_trace:
                Path(keep_trace).mkdir(parents=True, exist_ok=True)
                shutil.copy(info["xplane"], keep_trace)
            reduced = xplane.reduce(info["xplane"], n_devices=cell["chips"],
                                    host_spans=record.get("host_spans", ()))
        record["reduced"] = reduced
        record["window_s"] = info["t1"] - info["t0"]
        for m in metrics_of(bench, "per_layer", workload):
            reader = common.load_by_name("layer_metrics", m["name"], root)
            if reader is None:
                raise SystemExit(f"no reader file for per-layer metric {m['name']!r}")
            value = reader.read(record)
            if value is not None:  # a reader that finds nothing returns nothing
                result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
        if record.get("notes"):
            info_line("per_layer_notes", **record["notes"])
        if reduced is not None and reduced.busy_s > 0:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = record["window_s"]
            result["breakdown"] = xplane.breakdown(
                reduced, record.get("host_events", ()), info.get("epoch_perf"))
        return result
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the .xplane.pb into this directory (for a builder; the driver never sets it)")
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
