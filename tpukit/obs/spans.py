"""Host-side span timeline: where does the step-loop wall clock go?

The trainer's hot loop has a handful of host-visible phases per step window
(PRINT_FREQ steps): waiting on the data loader, host-side batch transforms,
global-array assembly/H2D placement, dispatching the jitted step, and the
one D2H sync that closes the window. `SpanTimeline` accumulates wall clock
into named phases and emits per-window and per-epoch breakdowns whose
seconds sum exactly to the elapsed wall clock (anything not inside a span
lands in "other") — the goodput accounting the MPMD pipeline-parallelism
work (PAPERS.md) motivates per stage, applied to the whole trainer.

Honest-accounting note: JAX dispatch is asynchronous, so the "step" span
(the time spent *calling* the jitted step) is small and the device's compute
time surfaces as the host blocking in the "sync" span at the window end.
The goodput fraction is therefore step + sync over wall clock: the share of
host time spent either feeding the device or waiting for it — everything
else (data wait, H2D assembly, checkpoint I/O) is time the device is
potentially idle. On a healthy run goodput is close to 1; a data-bound run
shows it directly.

With the round-7 input prefetcher on (`--prefetch N`, the default), the
"data"/"h2d" phases move to a background thread and the loop's only input
cost is the "prefetch_stall" span — the time the consumer actually blocked
on the buffer (docs/DESIGN.md §7).
"""

from __future__ import annotations

import contextlib
import time

# Phases whose time counts as "inside the compiled step" for goodput: the
# dispatch call itself plus the device-wait sync at the window boundary.
GOODPUT_SPANS = ("step", "sync")


def _breakdown(acc: dict[str, float], total: float) -> dict:
    """Seconds + fractions for one window/epoch; `other` absorbs wall clock
    outside any span so the seconds always sum to `total`."""
    seconds = dict(acc)
    other = total - sum(seconds.values())
    # float error can push `other` epsilon-negative; clamp for sane output
    seconds["other"] = max(other, 0.0)
    denom = total if total > 0 else 1.0
    fractions = {k: v / denom for k, v in seconds.items()}
    goodput = sum(fractions.get(k, 0.0) for k in GOODPUT_SPANS)
    return {
        "total_s": total,
        "seconds": seconds,
        "fractions": fractions,
        "goodput": goodput,
    }


class Span:
    """One open span: the context manager `SpanTimeline.span` returns. After
    the `with` block `t0` and `t1` hold its two readings of the run clock
    (seconds since the timeline's epoch), so a caller that also records the
    span elsewhere (the engine's `TraceRecorder` events) stamps it with the
    SAME readings the sums were built from. On a timeline built with
    `cpu=True`, `cpu` holds the thread's CPU seconds inside the span, read
    inside `[t0, t1]`, so it never exceeds the wall; nested spans too."""

    __slots__ = ("_timeline", "_scopes", "name", "t0", "t1", "_c0", "cpu")

    def __init__(self, timeline: "SpanTimeline", name: str, scopes: tuple):
        self._timeline = timeline
        self._scopes = scopes
        self.name = name
        self.t0 = self.t1 = self._c0 = self.cpu = 0.0

    def __enter__(self) -> "Span":
        tl = self._timeline
        for scope in self._scopes:
            scope.__enter__()
        tl._depth += 1
        self.t0 = time.perf_counter() - tl._epoch
        if tl._cpu:
            self._c0 = time.thread_time()
        return self

    def __exit__(self, *exc) -> None:
        tl = self._timeline
        if tl._cpu:
            self.cpu = time.thread_time() - self._c0
        self.t1 = time.perf_counter() - tl._epoch
        tl._depth -= 1
        if not tl._depth:  # nested: time already attributed to the outer span
            dt = self.t1 - self.t0
            for acc in (tl._window_acc, tl._epoch_acc, tl._lap_acc):
                acc[self.name] = acc.get(self.name, 0.0) + dt
            if tl._cpu:
                tl._lap_cpu_acc[self.name] = tl._lap_cpu_acc.get(self.name, 0.0) + self.cpu
        for scope in reversed(self._scopes):
            scope.__exit__(*exc)


class SpanTimeline:
    """Accumulate wall clock into named phases; report per window and epoch.

    `span(name)` is THE way the program opens a host span. One `with`:

      - accumulates the wall into the phase sums. Nested spans attribute
        their time to the OUTERMOST span only (no double counting), so
        helpers wrapped in their own spans can be called from inside a
        larger phase safely;
      - enters `annotation("tpukit:<name>")`, nested spans too, when the
        owner handed the timeline an annotation class
        (`jax.profiler.TraceAnnotation`): the span is then an event on the
        host thread's line of a profiler trace, on the device planes' clock,
        nested as the profiler nests them. Handed over rather than imported
        so this module stays importable without jax;
      - returns a `Span` whose `(t0, t1)` are on the run clock
        (`set_epoch` pins its origin; construction time until then).

    `step_annotation` (`jax.profiler.StepTraceAnnotation` with its name
    bound) is entered as well by a span that passes `step_num=`.

    `cpu=True` also reads the thread's CPU clock (`time.thread_time`) at each
    span's entry and exit, so that a wall can be told apart into the host's
    own work and its waiting (on the runtime, a lock, the device): the
    outermost spans' CPU seconds are summed by phase for `lap_cpu()`. With
    `cpu=False` no clock but the wall is read.
    """

    def __init__(self, annotation=None, step_annotation=None, cpu: bool = False):
        now = time.perf_counter()
        self._epoch = now
        self._window_start = now
        self._epoch_start = now
        self._window_acc: dict[str, float] = {}
        self._epoch_acc: dict[str, float] = {}
        self._lap_acc: dict[str, float] = {}
        self._depth = 0
        self._annotation = annotation
        self._step_annotation = step_annotation
        self._cpu = cpu
        self._lap_cpu_acc: dict[str, float] = {}
        self._lap_cpu: dict[str, float] = {}
        self._lap_cpu_from = time.thread_time() if cpu else 0.0

    def set_epoch(self, t0: float) -> None:
        """Pin the run clock: spans read `perf_counter() - t0`. A run loop
        calls this with its own t0, so span times compare directly with the
        `now` it hands its step primitives."""
        self._epoch = t0

    def span(self, name: str, step_num: int | None = None) -> Span:
        scopes = ()
        if self._annotation is not None:
            scopes = (self._annotation(f"tpukit:{name}"),)
        if step_num is not None and self._step_annotation is not None:
            scopes += (self._step_annotation(step_num=step_num),)
        return Span(self, name, scopes)

    def annotate(self, name: str):
        """The annotation alone, for work on ANOTHER thread (the prefetch
        worker): named on that thread's line of the trace, never in the
        loop's sums, which account the loop thread's wall clock only."""
        if self._annotation is None:
            return contextlib.nullcontext()
        return self._annotation(f"tpukit:{name}")

    def lap(self) -> dict[str, float]:
        """Walls by phase since the previous `lap()` (or `epoch()`), then
        reset: the per-iteration account a loop reads once a turn (the serve
        engine's per-quantum `host` walls)."""
        out, self._lap_acc = self._lap_acc, {}
        if self._cpu:
            now = time.thread_time()
            cpu, self._lap_cpu_acc = self._lap_cpu_acc, {}
            cpu["other"] = max(now - self._lap_cpu_from - sum(cpu.values()), 0.0)
            self._lap_cpu, self._lap_cpu_from = cpu, now
        return out

    def lap_cpu(self) -> dict[str, float]:
        """The thread's CPU seconds of the lap the last `lap()` closed, by
        the same phases, with `other` the CPU between the two lap points
        outside every span; `{}` on a timeline without `cpu=True`."""
        return self._lap_cpu

    def window(self) -> dict:
        """Close the current window: breakdown since the last `window()` (or
        construction/epoch reset), then reset the window accumulators."""
        now = time.perf_counter()
        out = _breakdown(self._window_acc, now - self._window_start)
        self._window_acc = {}
        self._window_start = now
        return out

    def epoch(self) -> dict:
        """Close the current epoch: breakdown since the last `epoch()` call
        (or construction). Also resets the window accumulators so a stale
        partial window does not leak into the next epoch."""
        now = time.perf_counter()
        out = _breakdown(self._epoch_acc, now - self._epoch_start)
        self._epoch_acc = {}
        self._epoch_start = now
        self._window_acc = {}
        self._window_start = now
        self._lap_acc = {}
        if self._cpu:
            self._lap_cpu_acc, self._lap_cpu = {}, {}
            self._lap_cpu_from = time.thread_time()
        return out


def format_breakdown(b: dict) -> str:
    """One-line human rendering: `goodput 83% (step 2% + sync 81%) | data 9% ...`"""
    frac = b["fractions"]
    inside = " + ".join(
        f"{k} {frac.get(k, 0.0) * 100:.0f}%" for k in GOODPUT_SPANS if k in frac
    )
    rest = " | ".join(
        f"{k} {v * 100:.0f}%"
        for k, v in sorted(frac.items(), key=lambda kv: -kv[1])
        if k not in GOODPUT_SPANS and v >= 0.005
    )
    head = f"goodput {b['goodput'] * 100:.0f}%"
    if inside:
        head += f" ({inside})"
    return head + (f" | {rest}" if rest else "")
