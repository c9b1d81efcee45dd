"""The interval arithmetic of the trace reduction on hand-made events: busy
union, idle share, kernel sums, exposed-collective time, gap attribution."""

import numpy as np
import pytest

from benchmark import xplane
from benchmark.xplane import DeviceTrace, Reduced


def device(events, index=0, modules=()):
    names = [e[0] for e in events]
    return DeviceTrace(index, names, np.array([e[1] for e in events], np.int64),
                       np.array([e[2] for e in events], np.int64), list(modules))


@pytest.mark.parametrize("raw,want", [
    ("%fusion.123 = bf16[8,128]{1,0} fusion(...)", "fusion"),
    ("%flash_fwd.3", "flash_fwd"),
    ("%transpose_jvp_flash_bwd_.12 = (...) custom-call(...)", "flash_bwd"),
    ("jvp_head_ce_fwd_", "head_ce_fwd"),
    ("all-gather-start.7", "all-gather-start"),
    ("%copy.5", "copy"),
])
def test_op_names_are_normalised(raw, want):
    assert xplane.op_name(raw) == want


def test_union_merges_overlaps_and_touching_intervals():
    s, e = xplane.union(np.array([0, 5, 3, 20, 25]), np.array([4, 8, 6, 25, 26]))
    assert s.tolist() == [0, 20] and e.tolist() == [8, 26]
    s, e = xplane.union(np.array([], np.int64), np.array([], np.int64))
    assert len(s) == 0 and len(e) == 0


def test_covered_length_of_queries():
    s, e = np.array([0, 20]), np.array([8, 25])
    got = xplane.covered(s, e, np.array([0, 2, 7, 10, 0, 30]), np.array([10, 5, 22, 15, 30, 40]))
    assert got.tolist() == [8, 3, 3, 0, 13, 0]


def test_busy_is_a_union_not_a_sum():
    # two overlapping ops: 0-10 and 5-15 us; a third at 30-40: busy 25 us, not 30
    d = device([("fusion", 0, 10_000), ("copy", 5_000, 15_000), ("fusion", 30_000, 40_000)])
    red = Reduced([d], [], None)
    assert red.busy_s == pytest.approx(25e-6)
    assert red.op_seconds(lambda n: n == "fusion") == pytest.approx(20e-6)
    assert red.op_count(lambda n: n == "fusion") == 2


def test_busy_and_kernel_time_are_means_over_devices():
    a = device([("flash_fwd", 0, 10_000)], 0)
    b = device([("flash_fwd", 0, 30_000)], 1)
    red = Reduced([a, b], [], None)
    assert red.busy_s == pytest.approx(20e-6)
    assert red.op_seconds(lambda n: n.startswith("flash")) == pytest.approx(20e-6)


def test_exposed_collective_time_excludes_what_compute_hides():
    # all-gather 0-100 us; compute covers 20-50 and 90-130: exposed 100 - 30 - 10 = 60 us
    d = device([("all-gather", 0, 100_000), ("fusion", 20_000, 50_000), ("fusion", 90_000, 130_000),
                ("all-reduce", 200_000, 210_000)])
    assert d.exposed_collective_s() == pytest.approx(70e-6)  # + the 10 us all-reduce nothing hides
    assert Reduced([d], [], None).exposed_collective_s() == pytest.approx(70e-6)
    assert d.collective_s() == pytest.approx(110e-6)  # running at all, hidden or not


def test_a_device_without_collectives_exposes_none():
    assert device([("fusion", 0, 10)]).exposed_collective_s() == 0.0


def test_idle_gaps_are_attributed_to_the_host_span_that_covers_them():
    # ops at 0-100 us and 400-500 us, then a 5 us hop to 505-600: one long gap of 300 us
    d = device([("fusion", 0, 100_000), ("fusion", 400_000, 500_000), ("fusion", 505_000, 600_000)])
    host = [("bench:next_batch", 150_000, 350_000)]
    gaps = dict(xplane.idle_gaps(Reduced([d], host, None)))
    assert gaps["host: bench:next_batch"] == pytest.approx(200e-6)
    assert gaps["host: not annotated"] == pytest.approx(100e-6)
    assert gaps["between ops (each under 20 us)"] == pytest.approx(5e-6)


def test_host_events_come_onto_the_trace_clock_through_the_epoch_mark():
    d = device([("fusion", 1_000_000, 1_100_000), ("fusion", 1_400_000, 1_500_000)])
    # perf_counter 50.0 s is trace time 1_000_000 ns; the sync ran 50.0002 .. 50.0004 s
    red = Reduced([d], [], epoch_ns=1_000_000)
    gaps = dict(xplane.idle_gaps(red, [("engine sync", 50.0002, 50.0004)], epoch_perf=50.0))
    assert gaps["host: engine sync"] == pytest.approx(200e-6, rel=1e-3)
    assert gaps["host: not annotated"] == pytest.approx(100e-6, rel=1e-3)


def test_breakdown_lists_at_most_ten_ops_by_time():
    events = [(f"op{i}", i * 100, i * 100 + i + 1) for i in range(15)]
    out = xplane.breakdown(Reduced([device(events)], [], None))
    assert len(out["device_ops"]) == 10 and out["device_ops"][0][0] == "op14"
    assert all(isinstance(n, str) and isinstance(s, float) for n, s in out["device_ops"] + out["idle_gaps"])


def test_module_events_are_found_by_name():
    d = device([("fusion", 0, 10)], modules=[("jit_decode_step(123)", 0, 8_000_000), ("jit_prefill_chunk_paged(9)", 9_000_000, 9_500_000)])
    red = Reduced([d], [], None)
    assert len(red.module_events(lambda n: "decode_step" in n)) == 1


@pytest.mark.parametrize("raw,want", [
    ("%fusion.123 = bf16[8,1023,4096]{2,1,0:T(8,128)(2,1)} fusion(...)", "fusion bf16[8,1023,4096]"),
    ("%fusion.9 = (f32[1,256]{1,0:T(1,128)S(1)}, f32[1,256]{1,0}) fusion(...)", "fusion f32[1,256]"),
    ("%jvp_flash_fwd_.3 = bf16[128,1024,64]{2,1,0} custom-call(...)", "flash_fwd bf16[128,1024,64]"),
    ("%convert_reduce_fusion.10 = s32[]{:T(128)} fusion(...)", "convert_reduce_fusion s32[]"),
    ("while.2", "while"),
])
def test_op_labels_carry_the_result_shape(raw, want):
    assert xplane.op_label(raw) == want


def test_a_loop_is_not_work_only_the_ops_inside_it_are():
    # a `while` spans 0-1000 ns; inside it ops run 0-400 and 400-900: 100 ns of the loop are idle
    d = device([("while", 0, 1000), ("fusion", 0, 400), ("copy", 400, 900)])
    assert d.names == ["fusion", "copy"] and len(d.starts) == 2
    red = Reduced([d], [], None)
    ops = dict(xplane.breakdown(red)["device_ops"])
    assert "while" not in ops and ops["fusion"] == pytest.approx(400e-9)
    assert red.busy_s == pytest.approx(900e-9)
    assert red.op_seconds(lambda n: n == "while") == 0.0


@pytest.mark.parametrize("container", xplane.CONTAINERS)
def test_a_loop_around_a_collective_hides_none_of_it(container):
    # a scanned layer stack: the loop's own event covers 0-500 us. Inside it an
    # all-gather runs 0-300 us while compute covers only 100-200: 200 us exposed,
    # and a reduce-scatter 400-500 us that nothing hides: 300 us in all
    inside = [("all-gather", 0, 300_000), ("fusion", 100_000, 200_000), ("reduce-scatter", 400_000, 500_000)]
    unrolled = device(inside)
    scanned = device([(container, 0, 500_000)] + inside)
    assert unrolled.exposed_collective_s() == pytest.approx(300e-6)
    assert scanned.exposed_collective_s() == pytest.approx(300e-6)
    assert Reduced([scanned], [], None).busy_s == Reduced([unrolled], [], None).busy_s == pytest.approx(400e-6)
    # the gap at 300-400 us inside the loop is idle time, not work
    gaps = dict(xplane.idle_gaps(Reduced([scanned], [], None)))
    assert gaps["host: not annotated"] == pytest.approx(100e-6)
