"""The two per-layer metrics that read the engine's own per-quantum account
(`host` walls and slot counters on the `quantum` events): each reader on a
hand-built record whose answer is worked out here, on records from a program
that lacks the fields, and in a traced CPU rehearsal of the serving cell."""

from pathlib import Path

import pytest
from test_bench_rehearsal import cell, tiny_root  # noqa: F401  (the rehearsal's own fixture, built the same way)

from benchmark import common

ROOT = Path(__file__).resolve().parents[2]


def reader(name):
    return common.load_by_name("layer_metrics", name, ROOT)


def quantum(t0, host, delivered, steps=2, **counters):
    """A `quantum` event as the engine emits it: dispatch [t0, t0 + host.decode],
    then a 90 ms wait for the device."""
    t1 = t0 + host["decode"]
    return dict(ev="quantum", trace=-1, t0=t0, t1=t1, s0=t1, s1=t1 + 0.090, steps=steps, lanes=[1, 2, 3],
                host=host, delivered=delivered, **counters)


# Four slots, two ticks a quantum: a full quantum delivers 8 tokens.
#   q0  opens the window: the gap before it holds the profiler's start (5 s of
#       `other`), so the host reader leaves it out; all four lanes deliver 2.
#   q1  one slot is still in prefill (a prefill wall on the host, 3 x 2 = 6
#       tokens): host = .4 + .3 + .1 + 1.0 + .6 + .2 = 2.6 ms.
#   q2  a lane finished after its first tick (2 + 2 + 2 + 1 = 7 tokens) and the
#       loop slept 40 ms for an arrival, which is not host work:
#       host = .5 + .2 + .6 + .1 = 1.4 ms.
# host_ms_per_quantum = (2.6 + 1.4) / 2 = 2.0 ms; slot_occupancy = (8 + 6 + 7) / (3 x 2 x 4) = 87.5 %.
QUANTA = [
    quantum(10.000, {"decode": 0.0010, "other": 5.0}, 8, decoding=4, prefilling=0, finished=0),
    quantum(10.100, {"retire": 0.0004, "admit": 0.0003, "place": 0.0001, "prefill": 0.0010,
                     "decode": 0.0006, "other": 0.0002}, 6, decoding=3, prefilling=1, finished=0),
    quantum(10.200, {"retire": 0.0005, "window": 0.0002, "idle": 0.0400, "decode": 0.0006,
                     "other": 0.0001}, 7, decoding=4, prefilling=0, finished=1),
]
TRAFFIC = {"engine": {"slots": 4, "decode_quantum": 2}}


def test_host_ms_per_quantum_on_a_worked_record():
    rec = {"quanta": QUANTA, "traffic": TRAFFIC}
    assert reader("host_ms_per_quantum.tput").read(rec) == pytest.approx(2.0)
    notes = rec["notes"]["host_ms_per_quantum"]
    assert notes.pop("quanta") == 2 and "idle" not in notes
    assert notes == pytest.approx({"retire": 0.45, "admit": 0.15, "place": 0.05, "prefill": 0.5,
                                   "decode": 0.6, "window": 0.1, "other": 0.15})
    assert sum(notes.values()) == pytest.approx(2.0)


def test_slot_occupancy_pct_on_a_worked_record():
    assert reader("slot_occupancy_pct.tput").read({"quanta": QUANTA, "traffic": TRAFFIC}) == pytest.approx(87.5)
    # the fused window reports the ticks it really ran: an early exit after one tick with all four lanes live is full
    early = [dict(QUANTA[0], steps=1, delivered=4)]
    assert reader("slot_occupancy_pct.tput").read({"quanta": early, "traffic": TRAFFIC}) == pytest.approx(100.0)


@pytest.mark.parametrize("name", ["host_ms_per_quantum.tput", "slot_occupancy_pct.tput"])
def test_a_program_without_the_fields_reads_as_nothing(name):
    """The parent's engine emits `quantum` events with t0 t1 s0 s1 steps lanes
    only: the reader returns None, never a number, and does not raise."""
    old = [{k: q[k] for k in ("ev", "trace", "t0", "t1", "s0", "s1", "steps", "lanes")} for q in QUANTA]
    for quanta in (old, [], None):
        rec = {"quanta": quanta, "traffic": TRAFFIC}
        assert reader(name).read(rec) is None and "notes" not in rec
    assert reader(name).read({"traffic": TRAFFIC}) is None
    # a window of one quantum has no gap that lies inside it
    one = reader("host_ms_per_quantum.tput").read({"quanta": QUANTA[:1], "traffic": TRAFFIC})
    assert one is None


def test_traced_serving_rehearsal_reports_both_metrics(tiny_root, capsys):  # noqa: F811
    out = cell(tiny_root, "tiny.sat", trace=True, seconds=0.4)
    assert out["correct"] is True
    host, occupancy = out["metrics"]["host_ms_per_quantum.tput"], out["metrics"]["slot_occupancy_pct.tput"]
    assert host["unit"] == "ms" and host["value"] > 0
    assert occupancy["unit"] == "%" and 0 < occupancy["value"] <= 100
    import json

    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines() if x.startswith("{")]
    notes = next(x for x in lines if x["info"] == "per_layer_notes")["host_ms_per_quantum"]
    assert {"decode", "retire", "other"} <= set(notes) and "idle" not in notes
