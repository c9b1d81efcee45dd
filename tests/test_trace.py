"""Request-scoped serving traces (tpukit/obs/trace, round 20).

Contracts pinned here:
  - COMPLETENESS INVARIANT: on a traced meshless serve run, every
    completed request has a CLOSED span tree (enqueue, >=1 admit,
    exactly one finish) whose named phase walls sum to its e2e latency
    within 1e-3 s — end-to-end, not on crafted events;
  - a requeue-after-replica_kill links BOTH attempts under ONE trace id
    (attempts == 2, one finish) and exactly-once delivery is checkable
    from the trace alone (every trace has exactly one finish event);
  - tracing is an OBSERVER: output tokens are bit-identical with the
    tracer on vs off, and `TraceRecorder.emit` is cheap (bounded ring,
    O(1) append — the serving-overhead budget);
  - the serve/fleet summaries carry per-phase p50/p99, trace_complete
    and the dispatch-vs-device split, and the window/summary wall split
    surfaces its residual as an explicit `other_s` >= 0;
  - `kind="trace_event"`/`kind="trace"` rows land in the metrics JSONL,
    `tools/report.py --min_trace_complete` gates on them (failing on
    trace-less logs — anti-vacuous), and `tools/traceview.py` renders
    the post-mortem + a parseable Chrome-trace export with one closed
    tree per completed request;
  - `tpukit/obs/trace.py` stays stdlib-only (no jax/numpy import), the
    property that lets traceview run anywhere the log was copied to.
"""

import importlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpukit.data import WordTokenizer, synthetic_stories
from tpukit.model import GPTConfig, init_params
from tpukit.obs import StepLogger, TraceRecorder
from tpukit.obs import trace as trace_lib
from tpukit.serve import (
    FleetConfig,
    FleetRouter,
    ServeConfig,
    ServeEngine,
    synthetic_request_stream,
)

MAX_NEW = 10


@pytest.fixture(scope="module")
def tok():
    return WordTokenizer(synthetic_stories(64))


@pytest.fixture(scope="module")
def cfg(tok):
    return GPTConfig(
        dim=32, head_dim=8, heads=4, num_layers=2, vocab_size=tok.vocab_size,
        max_position_embeddings=64, compute_dtype=jnp.float32,
    )


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(jax.random.PRNGKey(1), cfg)


@pytest.fixture(scope="module")
def host_params(params):
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), params)


def _run_traced(params, cfg, tok, n=8, logger=None, **serve_kw):
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        window_steps=4, **serve_kw)
    reqs = synthetic_request_stream(tok, n, seed=3, max_new_tokens=MAX_NEW,
                                    buckets=(8, 16))
    tracer = TraceRecorder()
    eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id),
                      tracer=tracer, logger=logger)
    comps = eng.run(list(reqs), max_wall_s=300)
    return eng, tracer, comps


# ---------------------------------------------------------------------------
# The completeness invariant, end-to-end on a real engine run.
# ---------------------------------------------------------------------------


def test_every_completion_has_a_complete_tree(tok, cfg, params):
    eng, tracer, comps = _run_traced(params, cfg, tok)
    trees = trace_lib.build_trees(tracer.snapshot())
    by_rid = {t["rid"]: t for t in trees}
    assert len(comps) == 8
    for c in comps:
        t = by_rid[c.rid]
        assert t["closed"], f"rid {c.rid}: open tree"
        assert t["complete"], (
            f"rid {c.rid}: named walls overran e2e by {t['residual_s']:.6f}s"
        )
        named = sum(v for k, v in t["phases"].items() if k != "other")
        assert named <= t["e2e_s"] + trace_lib.SUM_TOL_S
        # the walls + the residual `other` reconstruct e2e exactly
        assert sum(t["phases"].values()) == pytest.approx(t["e2e_s"], abs=1e-6)
        assert t["quanta"] > 0 and t["attempts"] == 1
        assert t["reason"] in ("eos", "length")
    assert trace_lib.completeness(trees) == 1.0
    assert tracer.dropped == 0


def test_summary_carries_phase_stats_and_attribution(tok, cfg, params):
    eng, tracer, comps = _run_traced(params, cfg, tok)
    s = eng.last_summary
    assert s["trace_complete"] == 1.0
    for key in ("phase_p50", "phase_p99"):
        assert set(s[key]) == set(trace_lib.PHASES)
    assert s["phase_p99"]["decode"] >= s["phase_p50"]["decode"] > 0
    # satellite: the wall split surfaces its residual explicitly
    assert s["other_s"] >= 0.0
    named = s["prefill_s"] + s["decode_s"] + s["sync_s"] + s["other_s"]
    assert named == pytest.approx(s["wall_s"], rel=0.05)
    # the host's work apart from its waits: every quantum's host walls come
    # with their CPU, and its sync is split where the device's answer was ready
    quanta = [e for e in tracer.snapshot() if e["ev"] == "quantum"]
    assert quanta and all(set(q["host_cpu"]) == set(q["host"]) for q in quanta)
    assert all(q["s0"] <= q["s_ready"] <= q["s1"] for q in quanta)


def test_window_records_carry_attribution(tok, cfg, params, tmp_path):
    log = tmp_path / "serve.jsonl"
    logger = StepLogger(str(log))
    _run_traced(params, cfg, tok, logger=logger)
    logger.close()
    recs = [json.loads(l) for l in log.read_text().splitlines()]
    wins = [r for r in recs if r["kind"] == "serve"]
    assert wins
    for w in wins:
        assert w["other_s"] >= 0.0
    # the quanta's CPU readings and ready stamps reach the JSONL's trace rows
    quanta = [r for r in recs if r["kind"] == "trace_event" and r["ev"] == "quantum"]
    assert quanta and all(0.0 <= sum(q["host_cpu"].values()) <= sum(q["host"].values()) + 1e-4 for q in quanta)
    assert all(q["s0"] <= q["s_ready"] <= q["s1"] for q in quanta)


# ---------------------------------------------------------------------------
# The quantum's host account, slot counters and token-delivery stamps.
# ---------------------------------------------------------------------------

HOST_SPANS = {"admit", "place", "prefill_batch", "prefill", "decode", "retire", "window", "idle", "other"}
PAGED = dict(page_size=8, prefill_chunk=8)


@pytest.mark.parametrize("serve_kw,qps", [(PAGED, 0.0), ({}, 0.0), (PAGED, 40.0)],
                         ids=["paged", "ring", "paged_open_loop"])
def test_quantum_host_walls_counters_and_deliveries(tok, cfg, params, serve_kw, qps):
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        window_steps=4, **serve_kw)
    reqs = synthetic_request_stream(tok, 8, seed=3, max_new_tokens=MAX_NEW,
                                    buckets=(8, 16), qps=qps)
    tracer = TraceRecorder()
    eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id), tracer=tracer)
    comps = eng.run(list(reqs), max_wall_s=300)
    quanta = [e for e in tracer.snapshot() if e["ev"] == "quantum"]
    assert len(quanta) >= 4 and len(comps) == 8

    s1_prev = 0.0  # the first quantum's account starts with the run
    for q in quanta:
        assert set(q["host"]) <= HOST_SPANS and "other" in q["host"] and "sync" not in q["host"]
        assert all(v >= 0.0 for v in q["host"].values())
        assert sum(q["host"].values()) == pytest.approx(q["s0"] - s1_prev, abs=1e-6)
        # the dispatch wall is one of the host walls, read from the same span
        assert q["host"]["decode"] == pytest.approx(q["t1"] - q["t0"], abs=1e-9)
        assert s1_prev <= q["t0"] <= q["t1"] <= q["s0"] <= q["s_ready"] <= q["s1"]
        # each wall's thread-CPU: the same keys, never more than the wall
        assert set(q["host_cpu"]) == set(q["host"])
        assert all(0.0 <= q["host_cpu"][k] <= q["host"][k] + 1e-4 for k in q["host"])
        assert set(q["sync_cpu"]) == {"sync_wait", "sync_fetch"}
        assert q["sync_cpu"]["sync_wait"] <= q["s_ready"] - q["s0"] + 1e-4
        assert q["sync_cpu"]["sync_fetch"] <= q["s1"] - q["s_ready"] + 1e-4
        s1_prev = q["s1"]
        # counters read at the dispatch
        assert q["decoding"] == len(q["lanes"]) >= 1
        assert 0 <= q["prefilling"] <= serve.slots - q["decoding"]
        assert q["pending"] >= 0
        assert (q["free_pages"] is None) == (not serve.paged)
        assert 0 <= q["delivered"] <= q["steps"] * q["decoding"]
        assert 0 <= q["finished"] <= q["decoding"]
    assert sum(q["delivered"] for q in quanta) == eng.generated_tokens
    assert sum(q["finished"] for q in quanta) == len(comps)
    if serve.paged:  # admission, the block-table push and the window record have names now
        assert any(q["host"].get("admit", 0) > 0 for q in quanta)
        assert any(q["host"].get("place", 0) > 0 for q in quanta)
        assert any(q["host"].get("prefill", 0) > 0 for q in quanta)
        assert any(q["host"].get("prefill_batch", 0) > 0 for q in quanta)
    # a prefill event carries the CPU of the dispatch span its wall covers
    prefills = [e for e in tracer.snapshot() if e["ev"] == "prefill"]
    assert prefills and all(0.0 <= e["cpu"] <= e["t1"] - e["t0"] + 1e-4 for e in prefills)
    assert any(q["host"].get("retire", 0) > 0 for q in quanta)
    assert any(q["host"].get("window", 0) > 0 for q in quanta)
    if qps:  # an open loop's sleeping is named, so it cannot read as host work
        assert any(q["host"].get("idle", 0) > 0 for q in quanta)

    finish_t = {e["rid"]: e["t"] for e in tracer.snapshot() if e["ev"] == "finish"}
    sync_ends = {q["s1"] for q in quanta}
    for c in comps:
        assert c.reason in ("eos", "length")
        assert sum(n for _, n in c.deliveries) == c.generated
        times = [t for t, _ in c.deliveries]
        assert times == sorted(times) and set(times) <= sync_ends  # stamped when a fetch returned
        assert c.first_token_s == times[0] and c.last_token_s == times[-1]
        assert c.first_token_s >= c.active_s >= c.admit_s >= c.arrival_s
        assert c.last_token_s >= c.done_s
        assert finish_t[c.rid] == c.last_token_s


def test_untraced_engine_keeps_no_ring_but_stamps_deliveries(tok, cfg, params, monkeypatch):
    import time

    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW, **PAGED)
    reqs = synthetic_request_stream(tok, 4, seed=3, max_new_tokens=MAX_NEW, buckets=(8, 16))
    eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id))

    def refuse():
        raise AssertionError("an untraced engine read the thread's CPU clock")

    monkeypatch.setattr(time, "thread_time", refuse)  # its spans read the wall alone
    comps = eng.run(list(reqs), max_wall_s=300)
    assert all(sum(n for _, n in c.deliveries) == c.generated for c in comps)
    assert eng._quantum is None and 0 < len(eng._periods) <= 64


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
def test_a_slow_quantum_says_so_on_logging_not_stdout(tok, cfg, params, traced,
                                                      monkeypatch, caplog, capsys):
    """A forced stall (a device_get that sleeps once, well into the run)
    yields ONE line on `logging` with the iteration's index, period and host
    walls — traced or not — and nothing on stdout, whose last line is a
    benchmark's result."""
    import time

    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW, **PAGED)
    reqs = synthetic_request_stream(tok, 12, seed=3, max_new_tokens=MAX_NEW, buckets=(8, 16))
    eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id),
                      tracer=TraceRecorder() if traced else None)
    real, fetches = jax.device_get, []

    def stalling(x):
        if isinstance(x, tuple):  # the per-quantum cursor fetch
            fetches.append(1)
            if len(fetches) == 14:
                time.sleep(0.6)
        return real(x)

    # warm every program first: a compile inside the run would be the slow quantum
    ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id)).run(list(reqs), max_wall_s=300)
    monkeypatch.setattr(jax, "device_get", stalling)
    with caplog.at_level("WARNING", logger="tpukit.serve.engine"):
        eng.run(list(reqs), max_wall_s=300)
    assert len(fetches) > 14
    lines = [r.getMessage() for r in caplog.records if "slow quantum" in r.getMessage()]
    assert len(lines) == 1, lines
    assert lines[0].startswith("slow quantum 13:") and "host ms {" in lines[0]
    assert "sync wait 6" in lines[0]  # the stall was in the wait: 600-odd ms
    assert "slow quantum" not in capsys.readouterr().out


def test_trees_and_chrome_export_hold_on_the_extended_events(tok, cfg, params):
    eng, tracer, comps = _run_traced(params, cfg, tok, **PAGED)
    events = tracer.snapshot()
    trees = trace_lib.build_trees(events)
    assert trace_lib.completeness(trees) == 1.0 and len(trees) == len(comps)
    chrome = trace_lib.to_chrome(events)
    json.dumps(chrome)  # every field is serialisable
    bars = [e for e in chrome["traceEvents"] if e.get("cat") == "quantum" and e["name"].startswith("dispatch")]
    assert bars and all({"lanes", "host", "host_cpu", "s_ready", "delivered", "finished"} <= set(b["args"])
                        for b in bars)
    # the sync is drawn as two bars: the wait for the device, then the fetch
    waits = [e for e in chrome["traceEvents"] if e.get("name") == "sync wait"]
    fetches = [e for e in chrome["traceEvents"] if e.get("name") == "sync fetch"]
    assert len(waits) == len(fetches) == len(bars)
    assert all(w["ts"] + w["dur"] == pytest.approx(f["ts"], abs=1.0) for w, f in zip(waits, fetches))
    prefill_bars = [e for e in chrome["traceEvents"] if e.get("cat") == "prefill"]
    assert prefill_bars and all({"rid", "cpu"} <= set(b["args"]) for b in prefill_bars)
    # events written before the sync was split (no host, no counters, no
    # ready stamp) still export, each sync as one bar
    old = [{k: v for k, v in e.items() if k in ("ev", "trace", "t0", "t1", "s0", "s1", "steps", "lanes", "replica")}
           if e["ev"] == "quantum" else e for e in events]
    assert trace_lib.completeness(trace_lib.build_trees(old)) == 1.0
    assert len(trace_lib.to_chrome(old)["traceEvents"]) == len(chrome["traceEvents"]) - len(fetches)


# ---------------------------------------------------------------------------
# Observer discipline: bit-identical tokens, bounded + cheap ring.
# ---------------------------------------------------------------------------


def test_tokens_bit_identical_tracer_on_off(tok, cfg, params):
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        window_steps=4, temperature=0.9, top_k=5)
    reqs = list(synthetic_request_stream(tok, 6, seed=5,
                                         max_new_tokens=MAX_NEW,
                                         buckets=(8, 16)))
    def run(tracer):
        eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id),
                          tracer=tracer)
        return {c.rid: list(map(int, c.ids))
                for c in eng.run(list(reqs), max_wall_s=300)}

    assert run(None) == run(TraceRecorder())


def test_recorder_ring_bounded_and_cheap():
    import time

    tr = TraceRecorder(capacity=256)
    t0 = time.perf_counter()
    for i in range(20_000):
        tr.emit("quantum", -1, t0=0.0, t1=1.0, s0=1.0, s1=2.0,
                steps=4, lanes=[i], replica=i % 2)
    wall = time.perf_counter() - t0
    assert wall < 1.0  # 20k emits: O(1) dict+deque appends under a lock
    assert len(tr) == 2 * 256  # bounded per ring
    assert tr.total_emitted == 20_000
    assert tr.dropped == 20_000 - 2 * 256
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)


def test_trace_module_is_stdlib_only():
    import ast

    tree = ast.parse(Path(trace_lib.__file__).read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            imported.add(node.module.split(".")[0])
    assert not imported & {"jax", "numpy", "tpukit"}, (
        f"trace.py must stay stdlib-only (traceview loads it by path with "
        f"no jax installed); imports {sorted(imported)}"
    )


# ---------------------------------------------------------------------------
# Fleet: requeue-after-kill links both attempts under ONE trace id, and
# exactly-once is checkable from the trace alone.
# ---------------------------------------------------------------------------


def test_kill_requeue_links_attempts_under_one_trace(tok, cfg, host_params):
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        window_steps=8)
    reqs = synthetic_request_stream(tok, 8, seed=3, max_new_tokens=MAX_NEW,
                                    buckets=(8, 16))
    tracer = TraceRecorder()
    router = FleetRouter(
        host_params, cfg, serve,
        FleetConfig(replicas=2, window_steps=4, kill_spec="replica_kill@1:1"),
        eos_id=int(tok.eos_token_id), tracer=tracer)
    comps = router.run(list(reqs), max_wall_s=300)
    s = router.last_summary
    assert s["kills"] == 1 and s["requeued"] >= 1
    assert len(comps) == 8

    events = tracer.snapshot()
    trees = trace_lib.build_trees(events)
    by_rid = {t["rid"]: t for t in trees}
    # every completion: a closed tree, one finish, complete walls
    assert trace_lib.completeness(trees) == 1.0
    for c in comps:
        assert by_rid[c.rid]["closed"]
    # exactly-once, FROM THE TRACE ALONE: one finish event per trace id
    fins: dict = {}
    for e in events:
        if e["ev"] == "finish":
            fins[e["trace"]] = fins.get(e["trace"], 0) + 1
    assert len(fins) == 8 and set(fins.values()) == {1}
    assert s["duplicate_completions"] == 0
    # the requeued victims: BOTH attempts live under one trace id — a
    # requeue event, two admits, still exactly one finish
    requeued_traces = {e["trace"] for e in events if e["ev"] == "requeue"}
    assert len(requeued_traces) == s["requeued"]
    for t in trees:
        if t["trace"] in requeued_traces:
            assert t["attempts"] == 2, (
                f"trace {t['trace']}: requeued but {t['attempts']} attempt(s)"
            )
            assert len(t["replicas"]) >= 1 and t["complete"]
            # its queue_wait includes the second wait-in-line
            assert t["phases"]["queue_wait"] > 0
    # the fleet summary carries the fleet-wide phase view
    assert s["trace_complete"] == 1.0
    assert set(s["phase_p50"]) == set(trace_lib.PHASES)


# ---------------------------------------------------------------------------
# Tree building on crafted events (unit-level edge cases).
# ---------------------------------------------------------------------------


def test_build_trees_requeue_accounting():
    evs = [
        dict(ev="enqueue", trace=7, rid=7, t=0.0, replica=None),
        dict(ev="admit", trace=7, rid=7, t=1.0, slot=0, replica=0),
        dict(ev="prefill_done", trace=7, rid=7, t=1.5, replica=0),
        dict(ev="quantum", trace=-1, t0=1.5, t1=1.6, s0=1.6, s1=1.8,
             steps=4, lanes=[7], replica=0),
        dict(ev="requeue", trace=7, rid=7, t=2.0, from_replica=0,
             replica="router"),
        dict(ev="admit", trace=7, rid=7, t=3.0, slot=1, replica=1),
        dict(ev="prefill_done", trace=7, rid=7, t=3.25, replica=1),
        dict(ev="quantum", trace=-1, t0=3.25, t1=3.3, s0=3.3, s1=3.5,
             steps=4, lanes=[7], replica=1),
        dict(ev="finish", trace=7, rid=7, t=3.5, reason="eos", generated=8,
             replica=1),
    ]
    (t,) = trace_lib.build_trees(evs)
    assert t["closed"] and t["complete"] and t["attempts"] == 2
    ph = t["phases"]
    assert ph["queue_wait"] == pytest.approx(1.0 + 1.0)  # both waits
    assert ph["prefill"] == pytest.approx(0.5 + 0.25)
    assert ph["decode"] == pytest.approx(0.1 + 0.05)
    assert ph["sync_stall"] == pytest.approx(0.2 + 0.2)
    assert t["e2e_s"] == pytest.approx(3.5)
    assert t["replicas"] == ["0", "1"]
    assert t["quanta"] == 2 and t["generated"] == 8


def test_build_trees_open_and_overrun_trees():
    # no finish -> open, not complete
    open_evs = [
        dict(ev="enqueue", trace=1, rid=1, t=0.0),
        dict(ev="admit", trace=1, rid=1, t=0.5, slot=0),
    ]
    (t,) = trace_lib.build_trees(open_evs)
    assert not t["closed"] and not t["complete"]
    # named walls overrunning e2e -> closed but NOT complete
    bad = [
        dict(ev="enqueue", trace=2, rid=2, t=0.0),
        dict(ev="admit", trace=2, rid=2, t=0.5, slot=0),
        dict(ev="prefill_done", trace=2, rid=2, t=0.6),
        dict(ev="quantum", trace=-1, t0=0.0, t1=5.0, s0=5.0, s1=5.0,
             steps=1, lanes=[2]),
        dict(ev="finish", trace=2, rid=2, t=1.0, reason="eos", generated=1),
    ]
    (t,) = trace_lib.build_trees(bad)
    assert t["closed"] and not t["complete"] and t["residual_s"] > 1.0


def test_percentile_matches_numpy():
    vals = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.3]
    for q in (0, 25, 50, 99, 100):
        assert trace_lib.percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q))
        )
    assert trace_lib.percentile([], 50) is None
    assert trace_lib.percentile([2.0], 99) == 2.0


# ---------------------------------------------------------------------------
# Persistence + tools: JSONL rows, the report gate, traceview + export.
# ---------------------------------------------------------------------------


def _traced_log(tok, cfg, params, tmp_path):
    log = tmp_path / "run.jsonl"
    logger = StepLogger(str(log))
    eng, tracer, comps = _run_traced(params, cfg, tok, logger=logger)
    logger.close()
    return log, comps


def test_jsonl_rows_and_report_gate(tok, cfg, params, tmp_path):
    log, comps = _traced_log(tok, cfg, params, tmp_path)
    recs = [json.loads(l) for l in log.read_text().splitlines()]
    events = [r for r in recs if r["kind"] == "trace_event"]
    trees = [r for r in recs if r["kind"] == "trace"]
    assert events and len(trees) == len(comps)
    assert all(t["complete"] for t in trees)

    report = importlib.import_module("tools.report")
    ok, msg = report.check_min_trace_complete(recs, 1.0)
    assert ok and "OK" in msg
    # anti-vacuous: a trace-less log FAILS the gate
    ok, msg = report.check_min_trace_complete(
        [r for r in recs if r["kind"] != "trace"], 1.0)
    assert not ok
    # the rendered summary carries the phase + completeness lines
    text = report.summarize(recs)
    assert "request phases p50/p99" in text
    assert "100% complete span trees" in text
    assert "/ sync " in text and "/ other " in text  # the summary's wall split
    # exit-2 wiring
    assert report.main([str(log), "--min_trace_complete", "1.0"]) == 0
    assert report.main([str(log), "--min_trace_complete", "1.1"]) == 2


def test_traceview_renders_and_exports(tok, cfg, params, tmp_path, capsys):
    log, comps = _traced_log(tok, cfg, params, tmp_path)
    traceview = importlib.import_module("tools.traceview")
    out = tmp_path / "trace.json"
    assert traceview.main([str(log), "--out", str(out)]) == 0
    text = capsys.readouterr().out
    assert "== request traces ==" in text and "100% complete" in text
    assert "host per quantum (ms wall/cpu/wait" in text and "sync per quantum (ms wall/cpu): wait " in text
    chrome = json.loads(out.read_text())
    assert chrome["traceEvents"]
    # one closed phase-bar set per completed request in the export
    phase_rows = {e["tid"] for e in chrome["traceEvents"]
                  if e.get("cat") == "phase"}
    assert len(phase_rows) == len(comps)
    # --rid filter narrows to one request
    rid = comps[0].rid
    assert traceview.main([str(log), "--rid", str(rid)]) == 0
    # a log with no trace events exits nonzero
    bare = tmp_path / "bare.jsonl"
    bare.write_text(json.dumps({"kind": "train", "step": 1}) + "\n")
    assert traceview.main([str(bare)]) == 1
