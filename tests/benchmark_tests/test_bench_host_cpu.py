"""The two per-layer metrics that read the serve engine's host work apart from
its waits: `host_cpu_ms_per_quantum.tput` (the thread's CPU inside the
quanta's host walls, `host_cpu`) and `sync_fetch_ms_per_quantum.tput` (the
part of each sync after the quantum's outputs were ready, `s1 - s_ready`).
Each reader on a hand-built record whose answer is worked out here, on the
quanta of a program that reads no CPU clock and stamps no ready instant, and
in a traced CPU rehearsal of the serving cell on a copy of the benchmark that
lists both."""

import json
import shutil
from pathlib import Path

import pytest
from test_bench_rehearsal import cell, tiny_root  # noqa: F401  (the rehearsal's own fixture, built the same way)

from benchmark import common

ROOT = Path(__file__).resolve().parents[2]
CPU, FETCH = "host_cpu_ms_per_quantum.tput", "sync_fetch_ms_per_quantum.tput"


def reader(name):
    return common.load_by_name("layer_metrics", name, ROOT)


def quantum(t0, host, host_cpu, wait_s, fetch_s):
    """A `quantum` event as a traced engine emits it: dispatch [t0, t0 +
    host.decode], a sync that waits `wait_s` for the device and fetches for
    `fetch_s` after the outputs were ready."""
    t1 = t0 + host["decode"]
    return dict(ev="quantum", trace=-1, t0=t0, t1=t1, s0=t1, s_ready=t1 + wait_s, s1=t1 + wait_s + fetch_s,
                steps=2, lanes=[1, 2], host=host, host_cpu=host_cpu,
                sync_cpu={"sync_wait": 0.0001, "sync_fetch": fetch_s})


#   q0  opens the window (5 s of `other`: the profiler's start); the CPU
#       reader leaves it out. Its sync fetches for 2.0 ms.
#   q1  the chunk batch is host work, the prefill dispatch waits 1.5 of its
#       2.0 ms: CPU = .4 + .3 + 1.0 + .5 + .6 + .1 = 2.9 ms. Fetch 1.5 ms.
#   q2  the decode dispatch waits 690 ms in the runtime (5.0 ms of it CPU) and
#       the loop slept 40 ms, which is no host work:
#       CPU = .5 + .2 + 5.0 + .1 = 5.8 ms. Fetch 1.0 ms.
# host_cpu_ms_per_quantum = (2.9 + 5.8) / 2 = 4.35 ms;
# sync_fetch_ms_per_quantum = (2.0 + 1.5 + 1.0) / 3 = 1.5 ms (every quantum: no gap before a sync is read).
QUANTA = [
    quantum(10.000, {"decode": 0.0010, "other": 5.0}, {"decode": 0.0009, "other": 0.2}, 0.088, 0.0020),
    quantum(10.100, {"retire": 0.0004, "admit": 0.0003, "prefill_batch": 0.0010, "prefill": 0.0020,
                     "decode": 0.0006, "other": 0.0002},
            {"retire": 0.0004, "admit": 0.0003, "prefill_batch": 0.0010, "prefill": 0.0005,
             "decode": 0.0006, "other": 0.0001}, 0.050, 0.0015),
    quantum(11.000, {"retire": 0.0005, "window": 0.0002, "idle": 0.0400, "decode": 0.6950, "other": 0.0001},
            {"retire": 0.0005, "window": 0.0002, "idle": 0.0001, "decode": 0.0050, "other": 0.0001}, 0.010, 0.0010),
]


def test_host_cpu_ms_per_quantum_on_a_worked_record():
    rec = {"quanta": QUANTA}
    assert reader(CPU).read(rec) == pytest.approx(4.35)
    notes = rec["notes"]["host_cpu_ms_per_quantum"]
    assert notes.pop("quanta") == 2 and "idle" not in notes
    expect = {"retire": (0.45, 0.0), "admit": (0.15, 0.0), "prefill_batch": (0.5, 0.0), "prefill": (0.25, 0.75),
              "decode": (2.8, 345.0), "window": (0.1, 0.0), "other": (0.1, 0.05)}
    assert set(notes) == set(expect)
    for name, (cpu, wait) in expect.items():
        assert notes[name] == pytest.approx({"cpu": cpu, "wait": wait}), name
    assert sum(n["cpu"] for n in notes.values()) == pytest.approx(4.35)


def test_sync_fetch_ms_per_quantum_on_a_worked_record():
    assert reader(FETCH).read({"quanta": QUANTA}) == pytest.approx(1.5)
    # the wait for the device is not in it: a longer wait reads the same
    slower = [dict(q, s_ready=q["s_ready"] + 0.5, s1=q["s1"] + 0.5) for q in QUANTA]
    assert reader(FETCH).read({"quanta": slower}) == pytest.approx(1.5)


@pytest.mark.parametrize("name", [CPU, FETCH])
@pytest.mark.parametrize("quanta", ["parent", "none", "first only"])
def test_quanta_without_the_readings_give_nothing(name, quanta):
    """The parent's quanta carry `host` walls and one undivided sync: no
    `host_cpu`, no `s_ready`. An untraced record has no quanta. Nothing is
    read, no exception is raised, and no notes are left."""
    parent = [{k: v for k, v in q.items() if k not in ("host_cpu", "s_ready", "sync_cpu")} for q in QUANTA]
    rec = {"parent": {"quanta": parent}, "none": {}, "first only": {"quanta": parent[:1]}}[quanta]
    assert reader(name).read(rec) is None
    assert "notes" not in rec


@pytest.fixture(scope="module")
def listed_root(tiny_root, tmp_path_factory):  # noqa: F811
    """The rehearsal's copy of the benchmark, copied again, with the two
    metrics listed for its serving cell."""
    root = tmp_path_factory.mktemp("bench_host_cpu") / "root"
    shutil.copytree(tiny_root, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] += [{"name": name, "unit": "ms", "better": "lower", "source": "program_span",
                            "layer": "serve engine", "moves": "serve_out_tokens_per_s", "workloads": ["tiny.sat"]}
                           for name in (CPU, FETCH)]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_traced_serving_rehearsal_reports_both(listed_root, capsys):
    out = cell(listed_root, "tiny.sat", trace=True, seconds=0.4)
    assert out["correct"] is True
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    # the CPU is a part of the walls it was read in; the fetch is a part of a sync
    assert 0.0 < metrics[CPU] <= metrics["host_ms_per_quantum.tput"] + 0.1
    assert metrics[FETCH] > 0.0
    notes = next(json.loads(x) for x in capsys.readouterr().out.splitlines() if x.startswith('{"info": "per_layer_notes"'))
    cpu = notes["host_cpu_ms_per_quantum"]
    assert cpu["quanta"] > 0 and {"decode", "prefill_batch", "prefill", "other"} <= set(cpu)
    assert all(v["cpu"] >= 0.0 and v["wait"] >= -0.1 for k, v in cpu.items() if k != "quanta")


def test_the_untraced_rehearsal_is_unchanged(listed_root):
    out = cell(listed_root, "tiny.sat", seconds=0.4)
    assert out["correct"] is True and set(out["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}
