"""Depth-N background host-pipeline prefetcher (round-7 host overlap).

PR 1's span timeline showed the fit() loop fully synchronous on the host:
every step paid loader wait + `prepare_batch` + `make_global_batch` (H2D
device_put) inline BEFORE dispatching the compiled step, so none of that
host work overlapped the previous step's device compute — it all showed up
as the `data`/`h2d` slices of the goodput breakdown. `HostPrefetcher` moves
the whole host side of the input pipeline onto a background thread that
runs `depth` batches ahead of consumption; the training thread blocks only
when the buffer is empty, and that wait is the new `prefetch_stall` span —
the honest residual input cost after overlap, directly comparable to the
old `data + h2d` share.

Contract (tests/test_prefetch.py):
  - item order and values are EXACTLY the wrapped iterable's — the same
    `process` fn runs on the same raw batches in the same order, just
    earlier, so losses are bit-identical to the synchronous path;
  - a worker exception (in the iterable or in `process`) propagates to the
    consumer at the `next()` where the failed item would have appeared —
    never swallowed, never reordered ahead of already-buffered good items;
  - epoch boundaries flush cleanly: the iterator raises StopIteration after
    the LAST item, buffers nothing across epochs (one prefetcher per
    epoch), and `close()` releases the worker even mid-epoch;
  - depth only changes timing, never the stream (depth-1 == depth-4).

Thread-safety note: the worker calls `jax.device_put` /
`jax.make_array_from_process_local_data` — both are array-construction
APIs with no collective or dispatch-order dependency, safe to run
concurrently with the training thread's step dispatch. Nothing here may
run device COLLECTIVES off the training thread: two threads racing
enqueues onto the same devices can interleave differently across
processes and deadlock a multi-host program.
"""

from __future__ import annotations

import contextlib
import queue
import threading
from typing import Any, Callable, Iterable

_ITEM, _DONE, _ERROR = "item", "done", "error"


class HostPrefetcher:
    """Iterator pulling (and host-processing) up to `depth` batches ahead.

    `iterable` is consumed on a daemon worker thread; each raw element is
    passed through `process` (identity when None) and buffered. Iterate
    like any iterator; call `close()` to release the worker early (safe to
    call more than once, and called automatically at exhaustion/error).
    """

    def __init__(
        self,
        iterable: Iterable,
        process: Callable[[Any], Any] | None = None,
        depth: int = 2,
        name: str = "tpukit-prefetch",
        skip: int = 0,
        span: Callable[[str], Any] | None = None,
    ):
        """`span(name)` (the trainer hands over `SpanTimeline.annotate`)
        opens a context manager around each `process(raw)`, so the worker's
        production shows as `prefetch.produce` on its own thread's line of a
        profiler trace; None names nothing.

        `skip` drops the first N raw items BEFORE `process` runs (round
        9: the mid-epoch resume fast-forward) — the skipped batches never
        pay host prep or H2D placement, and the drop happens on the worker
        thread, overlapping the restore/compile the training thread is
        busy with."""
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        if skip < 0:
            raise ValueError(f"prefetch skip must be >= 0, got {skip}")
        self.depth = depth
        self._skip = skip
        self._span = span if span is not None else (lambda name: contextlib.nullcontext())
        self._queue: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._host_lock = threading.Lock()
        self._exhausted = False
        self._producer_done = False
        # window-resettable occupancy gauge (window_stats): how full the
        # buffer ran. Consumer STALL time is the caller's to measure (the
        # trainer's `prefetch_stall` span wraps next()) — one clock, not two.
        self._occ_sum = 0
        self._occ_n = 0
        self._thread = threading.Thread(
            target=self._worker, args=(iter(iterable), process),
            daemon=True, name=name,
        )
        self._thread.start()

    # -- worker ------------------------------------------------------------

    def _put(self, item) -> bool:
        """Blocking put that stays responsive to `close()`; False = closed."""
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self, it, process):
        _end = object()
        try:
            for _ in range(self._skip):
                if self._stop.is_set():
                    return
                if next(it, _end) is _end:
                    break  # skipping past the end: the stream is just empty
            for raw in it:
                if self._stop.is_set():
                    return
                if process is None:
                    item = raw
                else:
                    # serialized against quiesce(): the host pipeline ends
                    # in device_put, and a training-thread placement (a
                    # rollback's checkpoint restore) racing it can corrupt
                    # the runtime — two threads must never place at once
                    with self._host_lock, self._span("prefetch.produce"):
                        item = process(raw)
                if not self._put((_ITEM, item)):
                    return
            self._producer_done = True
            self._put((_DONE, None))
        except BaseException as exc:  # noqa: BLE001 — delivered to consumer
            self._producer_done = True
            self._put((_ERROR, exc))

    # -- consumer ----------------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        if self._exhausted:
            raise StopIteration
        kind, val = self._queue.get()
        if kind is _ITEM:
            # occupancy sampled right after an item take: batches still
            # ready beyond the one just consumed (0 = starved, up to depth
            # = producer ahead). The terminal sentinel is not a batch —
            # exclude it once the producer has finished.
            q = self._queue.qsize()
            if self._producer_done and q > 0:
                q -= 1
            self._occ_sum += q
            self._occ_n += 1
            return val
        self._exhausted = True
        self.close()
        if kind is _ERROR:
            raise val
        raise StopIteration

    @property
    def buffered(self) -> int:
        """Batches currently ready in the buffer (non-resetting gauge —
        the diagnostics-bundle probe; window_stats owns the per-window
        occupancy average)."""
        return self._queue.qsize()

    @contextlib.contextmanager
    def quiesce(self):
        """Hold the worker between host-pipeline items while the body runs
        (an in-flight item completes first — the acquire waits for it).

        Round 9: a rollback restores a checkpoint MID-stream, and its
        training-thread `device_put`s racing the worker's batch placement
        segfault the CPU runtime (observed on jax 0.4.x; resume-time
        restores never raced because they run before the first prefetcher
        exists). Any other training-thread placement concurrent with a
        live prefetcher needs the same bracket. The buffer keeps serving
        already-prepared batches throughout — quiesce pauses production,
        not consumption."""
        with self._host_lock:
            yield

    def window_stats(self) -> dict:
        """Mean buffer occupancy since the last call (the per-window JSONL
        gauge), then reset."""
        out = {
            "occupancy": self._occ_sum / self._occ_n if self._occ_n else 0.0,
        }
        self._occ_sum = 0
        self._occ_n = 0
        return out

    def close(self):
        """Release the worker (idempotent). Drains the buffer so a worker
        blocked on a full queue observes the stop flag and exits; a closed
        prefetcher iterates as exhausted rather than blocking."""
        self._exhausted = True
        self._stop.set()
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        if self._thread.is_alive():
            self._thread.join(timeout=10.0)
