"""tpukit.obs — the telemetry subsystem.

The pillars, one per module:

  - `meter`      — MFUMeter (tokens/sec, MFU), `profiler_trace`, JSONL
                   `StepLogger`.
  - `trace`      — request-scoped serving traces (round 20):
                   `TraceRecorder` span-event rings, per-request span
                   trees with phase walls (queue_wait/prefill/handoff/
                   decode/sync_stall), the completeness invariant and
                   the Chrome-trace exporter behind `tools/traceview.py`.
                   The engine's events are stamped by the span primitive
                   below; a `quantum` event carries the host walls since
                   the previous sync and the slot counters.
  - `metrics`    — mergeable fleet metrics (round 22): counters, gauges
                   and log-bucket histograms with ONE edge table
                   everywhere (merge = bucket-wise sum, exact), SLO
                   compliance + error-budget burn accounting, atomic
                   per-process snapshot files merged by process 0, and
                   the OpenMetrics textfile exporter behind
                   `tools/top.py`.
  - `spans`      — `SpanTimeline.span(name)`: THE one way the program
                   opens a host span (trainer and serve engine). One
                   `with` accumulates the phase sums behind the goodput
                   breakdown and the serve windows, enters the
                   `tpukit:<name>` profiler annotation its owner handed
                   over, and returns `(t0, t1)` on the run clock.
  - `xla`        — static analysis of compiled steps: `cost_analysis` FLOPs
                   and bytes, `memory_analysis` peak HBM, per-collective
                   comm bytes parsed from the optimized HLO, the Pallas
                   kernels in a module (`kernel_calls`), the map from
                   its instructions to the program's `jax.named_scope`
                   names (`instruction_scopes`, `SCOPES`), plus live
                   `device.memory_stats()` gauges.
  - `sentinels`  — in-jit global grad/update/param norms and the host-side
                   loss-spike/NaN `SpikeSentinel`.
  - `heartbeat`  — per-process liveness files + process-0 straggler and
                   cross-replica divergence checks for multi-host runs.
  - `recorder`   — `FlightRecorder`: always-on bounded ring of the loop's
                   recent history, serialized into diagnostics bundles.
  - `watchdog`   — `HangWatchdog` (hung-step deadline monitor + bundle
                   dumps), `AnomalyTracer` (trace-on-anomaly profiler
                   capture), `write_bundle`/`all_thread_stacks`.
  - `divergence` — periodic in-jit param/opt-state checksums compared
                   across data-parallel replicas via the heartbeat files.

The trainer (`tpukit/train.py`) wires all of it through `fit()`;
`tools/report.py` renders a run's JSONL and `tools/flightview.py` renders
a diagnostics bundle into a human-readable post-mortem.
"""

from tpukit.obs.divergence import (  # noqa: F401
    format_checksum,
    make_state_checksum,
    tree_checksum,
)
from tpukit.obs.heartbeat import Heartbeat  # noqa: F401
from tpukit.obs.meter import (  # noqa: F401
    MFUMeter,
    StepLogger,
    matmul_param_count,
    peak_flops_per_chip,
    profiler_trace,
    train_flops_per_token,
)
from tpukit.obs.metrics import (  # noqa: F401
    Histogram,
    MetricRegistry,
    SloAccountant,
    SloSpecError,
    SloTarget,
    merge_snapshot_dir,
    parse_slo,
    publish_snapshot,
    to_openmetrics,
    write_merged,
)
from tpukit.obs.recorder import FlightRecorder  # noqa: F401
from tpukit.obs.trace import (  # noqa: F401
    PHASES,
    TraceRecorder,
    build_trees,
    completeness,
    flush_to_logger,
    phase_stats,
    to_chrome,
)
from tpukit.obs.sentinels import SpikeEvent, SpikeSentinel, global_norms  # noqa: F401
from tpukit.obs.spans import GOODPUT_SPANS, SpanTimeline, format_breakdown  # noqa: F401
from tpukit.obs.watchdog import (  # noqa: F401
    AnomalyTracer,
    HangWatchdog,
    all_thread_stacks,
    write_bundle,
)
from tpukit.obs.xla import (  # noqa: F401
    COLLECTIVE_OPS,
    INVOLUNTARY_REMAT,
    SCOPES,
    capture_compiler_stderr,
    collective_bytes,
    compiled_stats,
    count_involuntary_remat,
    instruction_scopes,
    kernel_calls,
    live_memory_stats,
    wire_bytes,
)
