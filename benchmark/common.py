"""What every mode shares: reading the cell's data files, building the
program's config from a configuration file, counting compilations, taking a
profiler trace, and describing the device."""

from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_by_name(kind: str, name: str, root: Path):
    """Import `<root>/benchmark/<kind>/<name>.py` by file path: names carry
    dots and dashes, so they are files, not importable module names."""
    path = Path(root) / "benchmark" / kind / f"{name}.py"
    if not path.is_file():
        return None
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def gpt_config(config: dict):
    """tpukit's GPTConfig from a configuration file: the published sizes under
    their config.json keys, the program's own fields under `program`."""
    import jax.numpy as jnp

    from tpukit.model import GPTConfig

    prog = config["program"]
    return GPTConfig(
        dim=config["n_embd"],
        heads=config["n_head"],
        head_dim=config["n_embd"] // config["n_head"],
        num_layers=config["n_layer"],
        vocab_size=config["vocab_size"],
        max_position_embeddings=config["n_positions"],
        compute_dtype=jnp.dtype(prog["compute_dtype"]),
        param_dtype=jnp.dtype(prog["param_dtype"]),
        vocab_pad_multiple=prog["vocab_pad_multiple"],
        attention_impl=prog["attention_impl"],
        scan_layers=prog["scan_layers"],
        remat_layers=prog["remat_layers"],
    )


def reference_sizes(config: dict) -> dict:
    return dict(heads=config["n_head"], head_dim=config["n_embd"] // config["n_head"],
                vocab_size=config["vocab_size"])


def prng_key(seed: int):
    """A jax key from any non-negative whole seed, past 2**31 too."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


class CompileCounter:
    """Counts backend compilations (cache hits included: a hit still traces,
    lowers and deserialises) through jax's monitoring events, so that a run
    can show nothing compiled inside its window."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self._EVENT:
            self.count += 1
            self.seconds += duration


class Phases:
    """Seconds each phase of set-up took, for the run's `setup` info line."""

    def __init__(self, t_process_start: float):
        self.seconds = {"process_start_to_mode": time.perf_counter() - t_process_start}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = self.seconds.get(name, 0.0) + time.perf_counter() - t0


def device_record(devices) -> dict:
    """The `device` object of the last line, as jax reports it."""
    import jax

    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": jax.device_count(),
        "memory_peak_bytes": peak,
    }


@contextlib.contextmanager
def profiler_trace(out_dir: Path | None):
    """Trace the enclosed window into `out_dir` (None: no trace). Python
    frames are left out: they swamp the file and the host; TraceAnnotations
    and the device planes are what the reduction reads. Yields a dict that
    holds the window's host-clock bounds and, after exit, `xplane`."""
    info: dict = {}
    if out_dir is None:
        yield info
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:epoch"):  # ties perf_counter to the trace's clock
        info["epoch_perf"] = time.perf_counter()
    info["t0"] = time.perf_counter()
    try:
        yield info
    finally:
        info["t1"] = time.perf_counter()
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(str(out_dir), "**", "*.xplane.pb"), recursive=True),
                       key=os.path.getmtime)
        info["xplane"] = found[-1] if found else None
