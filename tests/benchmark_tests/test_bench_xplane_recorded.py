"""The trace reduction on a trace RECORDED on the chip (my chip run, PR 23:
`benchmark/tools/record_trace.py` on one TPU v5 lite): three steps of a
two-layer GPT (dim 256, 4 heads of 64, 1,023 tokens, 2 rows), traced with the
benchmark's own annotations. Each number is checked against a slower,
independent reckoning from the same file, and against what the program is
known to hold."""

import gzip
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmark import xplane

DATA = Path(__file__).resolve().parent / "data"
SPANS = ("bench:dispatch", "bench:wait_device")


@pytest.fixture(scope="module")
def path(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace") / "tiny_train_1chip.xplane.pb"
    with gzip.open(DATA / "tiny_train_1chip.xplane.pb.gz") as src, open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(out)


@pytest.fixture(scope="module")
def red(path):
    return xplane.reduce(path, n_devices=1, host_spans=SPANS)


@pytest.fixture(scope="module")
def raw_ops(path):
    """(name, start, end) of the device's XLA Ops line, read without the reducer."""
    data = jax.profiler.ProfileData.from_file(path)
    plane = next(p for p in data.planes if p.name == "/device:TPU:0")
    line = next(l for l in plane.lines if l.name == "XLA Ops")
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]


def test_one_device_and_three_steps_of_the_jitted_program(red):
    assert len(red.devices) == 1 and red.devices[0].index == 0
    modules = red.module_events(lambda n: n.startswith("jit_train_step"))
    assert len(modules) == 3 and len(red.devices[0].modules) == 3


def test_busy_union_equals_a_point_by_point_sweep(red, raw_ops):
    busy, reach = 0, None
    for _, s, e in sorted(raw_ops, key=lambda x: x[1]):
        if reach is None or s > reach:
            busy += e - s
            reach = e
        elif e > reach:
            busy += e - reach
            reach = e
    assert red.busy_s == pytest.approx(busy / 1e9, rel=1e-12)
    summed = sum(e - s for _, s, e in raw_ops) / 1e9
    assert red.busy_s <= summed  # a union, never more than the sum


def test_idle_share_of_the_traced_steps_is_a_share(red):
    d = red.devices[0]
    span = (d.ends.max() - d.starts.min()) / 1e9
    assert 0.0 < red.busy_s <= span
    # the recorder blocks after every 0.7 ms step of this toy, so the host holds the chip back
    assert 1.0 - red.busy_s / span == pytest.approx(0.5913, abs=1e-3)


def test_kernel_sums_find_the_pallas_kernels_by_name(red, raw_ops):
    # 2 layers x 3 steps of flash forward and backward; one head+CE forward and backward a step
    counts = {k: red.op_count(lambda n, k=k: n == k) for k in ("flash_fwd", "flash_bwd", "head_ce_fwd", "head_ce_bwd")}
    assert counts == {"flash_fwd": 6, "flash_bwd": 6, "head_ce_fwd": 3, "head_ce_bwd": 3}
    want = sum(e - s for n, s, e in raw_ops if "flash_fwd" in n.split("=")[0]) / 1e9
    assert red.op_seconds(lambda n: n == "flash_fwd") == pytest.approx(want, rel=1e-12)
    assert 0 < red.op_seconds(lambda n: n.startswith("flash")) < red.busy_s


def test_one_chip_exposes_no_collective(red):
    assert red.exposed_collective_s() == 0.0


def test_host_annotations_and_the_epoch_mark_are_found(red):
    names = [n for n, _, _ in red.host]
    assert names.count("bench:dispatch") == 3 and names.count("bench:wait_device") == 3
    d = red.devices[0]
    assert red.epoch_ns is not None and red.epoch_ns < d.starts.min()  # marked before the first step


def test_breakdown_of_the_recorded_trace(red):
    out = xplane.breakdown(red)
    assert 1 <= len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    seconds = [s for _, s in out["device_ops"]]
    assert seconds == sorted(seconds, reverse=True) and sum(seconds) <= red.busy_s * 1.01
    gaps = dict(out["idle_gaps"])
    d = red.devices[0]
    idle = (d.ends.max() - d.starts.min()) / 1e9 - red.busy_s
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)  # every idle nanosecond is attributed once
    # the long gaps fall between a step's end and the next dispatch reaching the chip
    assert gaps["host: bench:dispatch"] > 0.3 * idle and gaps["host: bench:wait_device"] > 0


# -- four chips: the same toy under FSDP over a data axis of four (my chip run, PR 23) --


@pytest.fixture(scope="module")
def path4(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace4") / "tiny_train_4chip.xplane.pb"
    with gzip.open(DATA / "tiny_train_4chip.xplane.pb.gz") as src, open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(out)


@pytest.fixture(scope="module")
def red4(path4):
    return xplane.reduce(path4, n_devices=4, host_spans=SPANS)


def test_four_device_planes_in_order(red4):
    assert [d.index for d in red4.devices] == [0, 1, 2, 3]
    assert all(len(d.modules) == 3 for d in red4.devices)


def test_collectives_are_found_under_every_name_the_trace_gives_them(red4):
    names = set(red4.devices[0].names)
    assert {"all-gather", "all-reduce", "all-to-all", "async-collective-start", "async-collective-done",
            "collective-permute-start", "collective-permute-done"} <= names
    assert all(n.startswith(xplane.COLLECTIVES) for n in names if "collective" in n or n.startswith("all-"))


def swept_exposed_ns(path, index, loops_are_compute=False):
    """Nanoseconds of device `index` covered by a collective op and by no
    other op, counted on a grid of every event boundary, without the reducer.
    `loops_are_compute` counts `while` / `conditional` / `call` events as
    compute, as the reducer once did."""
    data = jax.profiler.ProfileData.from_file(path)
    plane = next(p for p in data.planes if p.name == f"/device:TPU:{index}")
    line = next(l for l in plane.lines if l.name == "XLA Ops")
    events = [(xplane.op_name(e.name), int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]
    if not loops_are_compute:
        events = [ev for ev in events if ev[0] not in xplane.CONTAINERS]
    cuts = sorted({t for _, s, e in events for t in (s, e)})
    pos = {t: i for i, t in enumerate(cuts)}
    coll, comp = [0] * len(cuts), [0] * len(cuts)
    for name, s, e in events:  # difference arrays over the grid
        arr = coll if name.startswith(xplane.COLLECTIVES) else comp
        arr[pos[s]] += 1
        arr[pos[e]] -= 1
    exposed = c = k = 0
    for i in range(len(cuts) - 1):
        c += coll[i]
        k += comp[i]
        if c > 0 and k == 0:
            exposed += cuts[i + 1] - cuts[i]
    return exposed


def test_exposed_collective_time_equals_a_point_by_point_sweep(red4, path4):
    """The reducer's mean over the four devices has to match the sweep."""
    per_device = [swept_exposed_ns(path4, index) for index in range(4)]
    assert red4.exposed_collective_s() == pytest.approx(sum(per_device) / 4 / 1e9, rel=1e-12)
    assert all(x > 0 for x in per_device)  # a toy this small hides little of its communication
    # ops on one chip run one at a time: nothing overlaps a collective op here but a loop would
    assert red4.exposed_collective_s() <= red4.collective_s() < red4.busy_s


# -- four chips, scanned: the toy with four layers under scan_layers, so that the stack runs
# -- inside a `while` whose own event spans every op and every gap in it (my chip run, PR 23) --


@pytest.fixture(scope="module")
def path_scan(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace_scan") / "tiny_train_4chip_scan.xplane.pb"
    with gzip.open(DATA / "tiny_train_4chip_scan.xplane.pb.gz") as src, open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(out)


@pytest.fixture(scope="module")
def red_scan(path_scan):
    return xplane.reduce(path_scan, n_devices=4, host_spans=SPANS)


def test_the_scanned_trace_holds_loops_and_the_reducer_drops_them(path_scan, red_scan):
    data = jax.profiler.ProfileData.from_file(path_scan)
    plane = next(p for p in data.planes if p.name == "/device:TPU:0")
    raw = [xplane.op_name(e.name) for l in plane.lines if l.name == "XLA Ops" for e in l.events]
    assert raw.count("while") >= 3  # at least one loop a step, three steps
    assert len(red_scan.devices) == 4 and all("while" not in d.names for d in red_scan.devices)
    assert len(red_scan.devices[0].names) == len(raw) - sum(n in xplane.CONTAINERS for n in raw)


def test_a_scanned_stacks_collectives_are_not_hidden_by_its_loop(path_scan, red_scan):
    per_device = [swept_exposed_ns(path_scan, index) for index in range(4)]
    assert red_scan.exposed_collective_s() == pytest.approx(sum(per_device) / 4 / 1e9, rel=1e-12)
    assert all(x > 0 for x in per_device)
    # with the loop's event counted as compute, what runs inside the loop reads as hidden
    hidden_by_loop = [swept_exposed_ns(path_scan, index, loops_are_compute=True) for index in range(4)]
    assert all(h < x for h, x in zip(hidden_by_loop, per_device))


def test_a_scanned_stacks_gaps_are_idle_time(path_scan, red_scan):
    """The busy union is that of the ops alone: less than the span the loops cover."""
    data = jax.profiler.ProfileData.from_file(path_scan)
    plane = next(p for p in data.planes if p.name == "/device:TPU:0")
    loops = [(int(e.start_ns), int(e.start_ns + e.duration_ns)) for l in plane.lines if l.name == "XLA Ops"
             for e in l.events if xplane.op_name(e.name) == "while"]
    d = red_scan.devices[0]
    inside = xplane.covered(*d.busy, *(np.asarray(x, np.int64) for x in zip(*loops)))
    assert (inside <= np.asarray([e - s for s, e in loops])).all()
    assert inside.sum() < sum(e - s for s, e in loops)  # some of every loop's span is a gap between its ops
