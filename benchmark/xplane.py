"""Reduction from a profiler trace (.xplane.pb) to the device numbers: busy
union and idle share, per-op and per-kernel device time, collective time not
hidden by compute, per-program (jitted function) time, and the idle gaps
attributed to what the host was doing. Read with jax.profiler.ProfileData,
nothing else. Checked on a recorded trace in tests/benchmark_tests.

Layout of a TPU trace (looked at by hand, PR 23): one plane per chip named
"/device:TPU:<n>", whose line "XLA Ops" holds one event per executed HLO
instruction (named as in the HLO text, e.g. "%fusion.12 = ...", Pallas
kernels by their `name=`: "%flash_fwd.3", "%transpose_jvp_flash_bwd_.2") and
whose line "XLA Modules" holds one event per executed program
("jit_train_step(...)"); host threads are lines of the "/host:CPU" plane and
carry the TraceAnnotations. All planes share one clock, in nanoseconds.
A chip runs its ops one at a time, so the only events that overlap others on
"XLA Ops" are the loops and calls that contain them (CONTAINERS, dropped); an
asynchronous collective shows as its `-start` and `-done` ops, and the time
the chip waits for the data is the `-done` op's.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "async-collective",
               "collective-permute", "collective-broadcast")
EPOCH_MARK = "bench:epoch"
SHORT_GAP_NS = 20_000  # shorter gaps are the launch overhead between two ops
# `while`, `conditional` and `call` events span the ops they run, which are
# listed too. Counted as work they would cover every gap inside a scanned
# layer stack or a decode quantum's loop, and hide every collective there
# behind "compute". A DeviceTrace never holds them.
CONTAINERS = ("while", "conditional", "call")


def op_name(raw: str) -> str:
    """HLO instruction name without `%`, the `= ...` text, a numeric suffix
    and the wrappers autodiff puts around a kernel's name (the rule of
    tpukit/obs/xla.py:kernel_calls, copied)."""
    name = re.split(r"\s*=", raw, maxsplit=1)[0].strip().lstrip("%")
    name = re.sub(r"\.\d+$", "", name)
    return re.sub(r"^(?:transpose_|jvp_)+", "", name).rstrip("_")


_RESULT = re.compile(r"=\s*\(?([a-z0-9]+\[[\d,]*\])")


def op_label(raw: str) -> str:
    """The normalised name with the (first) result's type and shape: XLA
    numbers its fusions, so "fusion" alone says nothing and "fusion.1234"
    does not survive a recompile; "fusion bf16[8,1023,4096]" does both."""
    m = _RESULT.search(raw)
    return f"{op_name(raw)} {m.group(1)}" if m else op_name(raw)


def union(starts: np.ndarray, ends: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Merged, sorted, disjoint intervals covering the same points."""
    if len(starts) == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    order = np.argsort(starts, kind="stable")
    s, e = np.asarray(starts)[order], np.asarray(ends)[order]
    reach = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > reach[:-1]])
    idx = np.flatnonzero(new)
    return s[idx], np.concatenate([reach[idx[1:] - 1], reach[-1:]])


def covered(s: np.ndarray, e: np.ndarray, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """For disjoint sorted intervals (s, e): the length of each query
    interval [a, b) that they cover."""
    if len(s) == 0:
        return np.zeros(len(a), np.int64)
    cum = np.concatenate([[0], np.cumsum(e - s)])

    def upto(x):  # covered length in (-inf, x)
        i = np.searchsorted(s, x, side="right")
        inside = np.where(i > 0, np.minimum(x, e[np.maximum(i - 1, 0)]) - s[np.maximum(i - 1, 0)], 0)
        return cum[np.maximum(i - 1, 0)] * (i > 0) + np.where(i > 0, np.maximum(inside, 0), 0)

    return upto(np.asarray(b)) - upto(np.asarray(a))


@dataclass
class DeviceTrace:
    index: int
    names: list[str]            # normalised op name per event
    starts: np.ndarray          # ns
    ends: np.ndarray
    modules: list[tuple[str, int, int]] = field(default_factory=list)
    labels: list[str] | None = None  # op_label per event, for the breakdown

    def __post_init__(self):
        keep = np.fromiter((n not in CONTAINERS for n in self.names), bool, len(self.names))
        if not keep.all():
            self.starts, self.ends = np.asarray(self.starts)[keep], np.asarray(self.ends)[keep]
            self.names = [n for n, k in zip(self.names, keep) if k]
            if self.labels is not None:
                self.labels = [x for x, k in zip(self.labels, keep) if k]

    @property
    def busy(self) -> tuple[np.ndarray, np.ndarray]:
        return union(self.starts, self.ends)

    def seconds(self, match) -> float:
        sel = np.fromiter((match(n) for n in self.names), bool, len(self.names))
        return float((self.ends[sel] - self.starts[sel]).sum()) / 1e9

    def count(self, match) -> int:
        return sum(1 for n in self.names if match(n))

    def collective_s(self, exposed_only: bool = False) -> float:
        """Time in which a collective runs here; `exposed_only`: and no
        compute does."""
        coll = np.fromiter((n.startswith(COLLECTIVES) for n in self.names), bool, len(self.names))
        cs, ce = union(self.starts[coll], self.ends[coll])
        if not exposed_only:
            return float((ce - cs).sum()) / 1e9
        ks, ke = union(self.starts[~coll], self.ends[~coll])
        return float(((ce - cs) - covered(ks, ke, cs, ce)).sum()) / 1e9

    def exposed_collective_s(self) -> float:
        return self.collective_s(exposed_only=True)


@dataclass
class Reduced:
    devices: list[DeviceTrace]
    host: list[tuple[str, int, int]]   # (name, start ns, end ns) of host annotations
    epoch_ns: int | None               # trace clock at the EPOCH_MARK annotation

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, mean over the devices."""
        if not self.devices:
            return 0.0
        return float(np.mean([(d.busy[1] - d.busy[0]).sum() for d in self.devices])) / 1e9

    def op_seconds(self, match) -> float:
        return float(np.mean([d.seconds(match) for d in self.devices])) if self.devices else 0.0

    def op_count(self, match) -> float:
        return float(np.mean([d.count(match) for d in self.devices])) if self.devices else 0.0

    def collective_s(self, exposed_only: bool = False) -> float:
        return float(np.mean([d.collective_s(exposed_only) for d in self.devices])) if self.devices else 0.0

    def exposed_collective_s(self) -> float:
        return self.collective_s(exposed_only=True)

    def module_events(self, match) -> list[tuple[str, int, int]]:
        return [m for m in self.devices[0].modules if match(m[0])] if self.devices else []


def reduce(path: str, n_devices: int | None = None, host_spans=()) -> Reduced:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host, epoch_ns = [], [], None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            names, labels, starts, ends, modules = [], [], [], [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        names.append(op_name(ev.name))
                        labels.append(op_label(ev.name))
                        starts.append(int(ev.start_ns))
                        ends.append(int(ev.start_ns + ev.duration_ns))
                elif line.name == MODULES_LINE:
                    modules += [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                                for ev in line.events]
            if names:
                devices.append(DeviceTrace(int(m.group(1)), names, np.asarray(starts, np.int64),
                                           np.asarray(ends, np.int64), modules, labels))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == EPOCH_MARK:
                        epoch_ns = int(ev.start_ns)
                    elif ev.name in host_spans:
                        host.append((ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
    devices.sort(key=lambda d: d.index)
    if n_devices is not None:
        devices = devices[:n_devices]
    return Reduced(devices, host, epoch_ns)


def idle_gaps(red: Reduced, host_events=(), epoch_perf: float | None = None) -> list[list]:
    """Idle time of device 0 between its first and last op, by what the host
    was doing: each gap longer than SHORT_GAP_NS is shared out among the host
    spans that overlap it (annotations from the trace, and `host_events` =
    (name, perf_counter start, end) brought onto the trace's clock through
    the epoch mark); what no span covers is "host: not annotated"."""
    if not red.devices:
        return []
    bs, be = red.devices[0].busy
    ga, gb = be[:-1], bs[1:]
    length = gb - ga
    out = {"between ops (each under 20 us)": float(length[length < SHORT_GAP_NS].sum()) / 1e9}
    long = length >= SHORT_GAP_NS
    ga, gb = ga[long], gb[long]
    spans = list(red.host)
    if host_events and red.epoch_ns is not None and epoch_perf is not None:
        to_ns = lambda t: red.epoch_ns + int((t - epoch_perf) * 1e9)  # noqa: E731
        spans += [(n, to_ns(a), to_ns(b)) for n, a, b in host_events]
    left = (gb - ga).astype(np.float64)
    for name in sorted({s[0] for s in spans}):
        s, e = union(np.asarray([x[1] for x in spans if x[0] == name], np.int64),
                     np.asarray([x[2] for x in spans if x[0] == name], np.int64))
        got = covered(s, e, ga, gb).astype(np.float64)
        got = np.minimum(got, left)  # overlapping span kinds: the first named takes it
        left -= got
        out[f"host: {name}"] = float(got.sum()) / 1e9
    out["host: not annotated"] = float(left.sum()) / 1e9
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1]) if v > 0][:10]


def breakdown(red: Reduced, host_events=(), epoch_perf: float | None = None) -> dict:
    """`device_ops`: the ten operations with most device time (mean over the
    devices); `idle_gaps`: see idle_gaps()."""
    totals: dict[str, float] = {}
    for d in red.devices:
        dur = (d.ends - d.starts) / 1e9
        for label, t in zip(d.labels or d.names, dur):
            totals[label] = totals.get(label, 0.0) + float(t)
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    n = max(len(red.devices), 1)
    return {"device_ops": [[k, v / n] for k, v in ops],
            "idle_gaps": idle_gaps(red, host_events, epoch_perf)}
