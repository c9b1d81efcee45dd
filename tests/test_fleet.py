"""Fleet serving: the request router over N engine replicas
(tpukit/serve/fleet, round 19, ROADMAP #1).

Contracts pinned here:
  - fleet output is TOKEN-IDENTICAL to a single engine consuming the same
    seeded stream — greedy and fixed-seed sampled, all-at-once and under
    staggered `--qps` arrivals — because per-request seeds ride the
    Request and every replica is the proven round-14 engine;
  - a chaos-killed replica's in-flight requests re-queue onto survivors
    (prompt reconstructed from the Request — completion-carries-prompt)
    and every request's tokens are emitted EXACTLY once, still
    token-identical to the un-killed run;
  - N replicas x model-parallel grids coexist on disjoint device subsets
    of the one process, one params placement per subset from ONE host
    copy (the shared-cold-start ledger);
  - disaggregated prefill: decode replicas never run a prefill program
    (compile budget shrinks to decode + the adopt arm), the handoff's
    decode-side registry claims survive prefill-pool pressure (refcounted
    pages are never reclaimed under a reader), and parity holds;
  - occupancy-driven autoscale grows under load and drains when idle,
    with parity throughout;
  - `kind="fleet"`/`fleet_summary` JSONL lands, `tools/report.py` renders
    the "== fleet ==" section.
"""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpukit import chaos as chaos_lib
from tpukit.data import WordTokenizer, synthetic_stories
from tpukit.model import GPTConfig, init_params
from tpukit.serve import (
    FleetConfig,
    FleetRouter,
    Request,
    ServeConfig,
    ServeEngine,
    synthetic_request_stream,
)
from tpukit.serve import decode as serve_decode
from tpukit.serve.paged import PageAllocator

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
    """ONE host-side copy — what `restore_params(..., None)` hands the
    router in production; every replica placement is a device_put of it."""
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), params)


def _tokens(comps):
    return {c.rid: list(map(int, c.ids)) for c in comps}


def _single_engine_tokens(params, cfg, tok, serve, reqs):
    eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id))
    return _tokens(eng.run(list(reqs), max_wall_s=300))


# ---------------------------------------------------------------------------
# Parity: fleet == single engine on the same stream, greedy and sampled,
# all-at-once and under staggered arrivals.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "temperature,top_k,qps",
    [(0.0, 0, 0.0), (0.9, 5, 0.0), (0.9, 5, 50.0), (0.0, 0, 50.0)],
    ids=["greedy", "sampled", "sampled_qps", "greedy_qps"],
)
def test_fleet_matches_single_engine(tok, cfg, params, host_params,
                                     temperature, top_k, qps):
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        temperature=temperature, top_k=top_k, window_steps=8)
    reqs = synthetic_request_stream(tok, 8, seed=3, max_new_tokens=MAX_NEW,
                                    buckets=(8, 16), qps=qps)
    want = _single_engine_tokens(params, cfg, tok, serve, reqs)
    router = FleetRouter(host_params, cfg, serve,
                         FleetConfig(replicas=2, window_steps=4),
                         eos_id=int(tok.eos_token_id))
    got = _tokens(router.run(list(reqs), max_wall_s=300))
    assert got.keys() == want.keys()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid], err_msg=f"rid {rid}")
    s = router.last_summary
    assert s["requests"] == 8 and s["duplicate_completions"] == 0
    assert s["kills"] == 0 and s["requeued"] == 0


# ---------------------------------------------------------------------------
# Replica failure: killed mid-stream, in-flight requests re-queue onto the
# survivor, exactly-once output, tokens unchanged.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature,top_k", [(0.0, 0), (0.9, 5)],
                         ids=["greedy", "sampled"])
def test_fleet_kill_requeues_exactly_once(tok, cfg, params, host_params,
                                          temperature, top_k):
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        temperature=temperature, top_k=top_k, window_steps=8)
    reqs = synthetic_request_stream(tok, 8, seed=3, max_new_tokens=MAX_NEW,
                                    buckets=(8, 16))
    want = _single_engine_tokens(params, cfg, tok, serve, reqs)
    router = FleetRouter(
        host_params, cfg, serve,
        FleetConfig(replicas=2, window_steps=4,
                    kill_spec="replica_kill@1:1"),
        eos_id=int(tok.eos_token_id))
    comps = router.run(list(reqs), max_wall_s=300)
    got = _tokens(comps)
    # exactly once: 8 completions, 8 distinct rids
    assert len(comps) == 8 and got.keys() == want.keys()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid], err_msg=f"rid {rid}")
    s = router.last_summary
    assert s["kills"] == 1 and s["requeued"] >= 1
    assert s["duplicate_completions"] == 0
    assert s["per_replica"][1]["fate"] == "killed"


def test_fleet_never_kills_last_replica(tok, cfg, host_params):
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=8,
                        window_steps=8)
    reqs = synthetic_request_stream(tok, 4, seed=2, max_new_tokens=8,
                                    buckets=(8, 16))
    router = FleetRouter(
        host_params, cfg, serve,
        FleetConfig(replicas=2, window_steps=4,
                    kill_spec="replica_kill@0:1,replica_kill@1:0"),
        eos_id=int(tok.eos_token_id))
    comps = router.run(list(reqs), max_wall_s=300)
    # the second kill targets the ONLY survivor and must be refused
    assert len(comps) == 4
    assert router.last_summary["kills"] == 1


# ---------------------------------------------------------------------------
# Device subsets: N replicas x model-parallel grids in one process, one
# placement per subset from one host copy.
# ---------------------------------------------------------------------------


def test_fleet_subset_meshes_coexist(tok, cfg, params, host_params):
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=6,
                        window_steps=8)
    reqs = synthetic_request_stream(tok, 6, seed=5, max_new_tokens=6,
                                    buckets=(8, 16))
    want = _single_engine_tokens(params, cfg, tok, serve, reqs)
    router = FleetRouter(host_params, cfg, serve,
                         FleetConfig(replicas=2, devices_per_replica=2,
                                     window_steps=4),
                         eos_id=int(tok.eos_token_id))
    # disjoint subsets, model-parallel grid per replica
    devs = [tuple(d.id for d in np.ravel(e.mesh.devices))
            for e in router._replicas.values()]
    assert devs[0] != devs[1] and not (set(devs[0]) & set(devs[1]))
    for e in router._replicas.values():
        assert e.mesh.shape["model"] == 2
    got = _tokens(router.run(list(reqs), max_wall_s=600))
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid], err_msg=f"rid {rid}")
    # one placement per subset, from ONE shared host copy
    assert router.last_summary["params_placements"] == 2


def test_fleet_cold_start_ledger(tok, cfg, tmp_path):
    """The shared cold start: the checkpoint is read ONCE into host
    arrays, and N replicas cost N placements (meshless replicas share a
    single committed copy — placements == 1) — never N reads."""
    from tpukit import checkpoint as ck
    from tpukit.train import create_train_state, make_optimizer

    state = create_train_state(jax.random.PRNGKey(0), cfg,
                               make_optimizer(1e-4))
    path = ck.save_auto(state, tmp_path, "checkpoint-step5",
                        format="sharded")
    template = jax.eval_shape(lambda: state).params
    # ONE read (no sharding tree): this is the fleet path — the bytes are
    # paid here and never again; every replica placement below is a pure
    # device_put of this copy
    host, info = ck.restore_params(path, template, None)
    assert info["bytes_read"] > 0 and info["bytes_skipped"] > info["bytes_read"]
    serve = ServeConfig(slots=2, buckets=(8,), max_new_tokens=4,
                        window_steps=8)
    reqs = synthetic_request_stream(tok, 3, seed=1, max_new_tokens=4,
                                    buckets=(8,))
    # meshless: all replicas SHARE one committed copy — N-1 placements free
    router = FleetRouter(host, cfg, serve, FleetConfig(replicas=3),
                         eos_id=int(tok.eos_token_id))
    assert router.placements == 1
    comps = router.run(list(reqs), max_wall_s=300)
    assert len(comps) == 3
    assert router.last_summary["params_placements"] == 1
    # meshed: one placement per subset
    router2 = FleetRouter(host, cfg, serve,
                          FleetConfig(replicas=2, devices_per_replica=2),
                          eos_id=int(tok.eos_token_id))
    assert router2.placements == 2


# ---------------------------------------------------------------------------
# Disaggregated prefill: handoff parity, the shrunk decode compile budget,
# and the write-safety of decode-side claims under pool pressure.
# ---------------------------------------------------------------------------


def test_disagg_prefill_parity_and_compile_budget(tok, cfg, params,
                                                  host_params):
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        window_steps=8, page_size=8)
    reqs = synthetic_request_stream(tok, 8, seed=3, max_new_tokens=MAX_NEW,
                                    buckets=(8, 16), shared_prefix=8)
    want = _single_engine_tokens(params, cfg, tok, serve, reqs)
    adopt0 = serve_decode.adopt_slot._cache_size()
    chunk0 = serve_decode.prefill_chunk_paged._cache_size()
    router = FleetRouter(host_params, cfg, serve,
                         FleetConfig(replicas=2, window_steps=4,
                                     disagg_prefill=True),
                         eos_id=int(tok.eos_token_id))
    replicas = list(router._replicas.values())
    got = _tokens(router.run(list(reqs), max_wall_s=600))
    assert got.keys() == want.keys()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid], err_msg=f"rid {rid}")
    s = router.last_summary
    dp = s["disagg_prefill"]
    assert dp["handoffs"] == 8 and dp["worker_admitted"] == 8
    assert dp["worker_prefix_hits"] > 0  # the shared system prompt hit
    # decode replicas NEVER ran a prefill: their compile budget is the
    # decode program + the adopt arm. The worker owns every chunk program.
    for eng in replicas:
        assert eng.spans.epoch()["seconds"].get("prefill", 0.0) == 0.0
    assert serve_decode.adopt_slot._cache_size() - adopt0 <= 1
    # chunk compiles bounded by the WORKER's power-of-two admit sizes
    worker_sizes = (router.prefill.serve.slots - 1).bit_length() + 1
    assert (serve_decode.prefill_chunk_paged._cache_size() - chunk0
            <= worker_sizes)


def test_disagg_claims_survive_prefill_pool_pressure(tok, cfg, params,
                                                     host_params):
    """The handoff safety invariant: decode-side pages backing live lanes
    are refcounted (claimed/owned) and can never be reclaimed, however
    hard the PREFILL pool is pressed — a tiny worker pool that must
    reclaim its retained prefix pages between admissions still produces
    token-exact completions on the decode side."""
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        window_steps=8, page_size=8)
    # worker pool: exactly one worst-case request + null page, so UNIQUE
    # prompts interleaved with the shared-prefix ones force the worker's
    # retained prefix pages out between admissions (reclaim pressure) —
    # while the decode side keeps claiming its own registered copies
    min_pages = -(-(16 + MAX_NEW) // 8) + 1
    shared = synthetic_request_stream(tok, 6, seed=3, max_new_tokens=MAX_NEW,
                                      buckets=(8, 16), shared_prefix=8)
    unique = synthetic_request_stream(tok, 4, seed=11, max_new_tokens=MAX_NEW,
                                      buckets=(8, 16))
    reqs = list(shared)
    for i, r in enumerate(unique):
        reqs.insert(2 * i + 1, Request(rid=100 + i, ids=r.ids,
                                       max_new_tokens=MAX_NEW, seed=11 + i))
    want = _single_engine_tokens(params, cfg, tok, serve, reqs)
    router = FleetRouter(host_params, cfg, serve,
                         FleetConfig(replicas=2, window_steps=4,
                                     disagg_prefill=True,
                                     prefill_pages=min_pages),
                         eos_id=int(tok.eos_token_id))
    replicas = list(router._replicas.values())
    got = _tokens(router.run(list(reqs), max_wall_s=600))
    assert got.keys() == want.keys()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid], err_msg=f"rid {rid}")
    # pressure actually happened on the worker pool...
    assert router.prefill.allocator.stats.reclaimed > 0
    # ...and decode-side claims still fired (registered copies survive the
    # worker's reclaims — the refcounted-reader invariant, pool-for-pool)
    assert sum(e.allocator.stats.prefix_hits for e in replicas) > 0


def test_claimed_pages_never_reclaimed_unit():
    """Allocator-level spelling of the same invariant: a claimed
    (refcount >= 1) registered page is not in the retained LRU, so pool
    pressure can only reclaim unreferenced pages — a doomed allocation
    returns None rather than stealing from a reader."""
    alloc = PageAllocator(num_pages=6, page_size=4)
    ids = tuple(range(8))
    own = alloc.alloc(2)
    alloc.register(ids, own)          # published prefix chain
    alloc.claim(own)                  # a decode-side reader claims it
    alloc.release(own)                # the writer lane evicts
    # reader still holds refcount 1 -> pages are NOT retained/reclaimable
    assert alloc.refcount[own[0]] == 1
    got = alloc.alloc(4)              # pool has 3 free pages left
    assert got is None                # refuses rather than stealing
    assert alloc.lookup_prefix(ids, 2) == own  # registry intact
    alloc.release(own)                # reader done -> retained now
    assert alloc.alloc(4) is not None  # pressure may NOW reclaim them


# ---------------------------------------------------------------------------
# Autoscale: grow under load, drain when idle, parity throughout.
# ---------------------------------------------------------------------------


def test_fleet_autoscale_up_and_down(tok, cfg, params, host_params):
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=8,
                        window_steps=8)
    burst = synthetic_request_stream(tok, 10, seed=7, max_new_tokens=8,
                                     buckets=(8, 16))
    # a trickle arrives after the burst drains: low occupancy, empty queue
    trickle = [
        Request(rid=100 + i, ids=burst[i].ids, max_new_tokens=8,
                seed=7 + i, arrival_s=1.5 + 0.4 * i)
        for i in range(4)
    ]
    reqs = burst + trickle
    want = _single_engine_tokens(params, cfg, tok, serve, reqs)
    router = FleetRouter(
        host_params, cfg, serve,
        FleetConfig(replicas=1, max_replicas=2, window_steps=2,
                    scale_up_occupancy=0.9, scale_down_occupancy=0.45),
        eos_id=int(tok.eos_token_id))
    got = _tokens(router.run(list(reqs), max_wall_s=600))
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid], err_msg=f"rid {rid}")
    s = router.last_summary
    assert s["scale_ups"] >= 1, s
    assert s["scale_downs"] >= 1, s
    assert s["replicas_peak"] == 2
    assert s["duplicate_completions"] == 0


# ---------------------------------------------------------------------------
# Telemetry: fleet JSONL + report render.
# ---------------------------------------------------------------------------


def test_fleet_jsonl_and_report_gate(tok, cfg, host_params, tmp_path):
    import importlib

    from tpukit.obs import FlightRecorder, StepLogger

    report = importlib.import_module("tools.report")
    log = tmp_path / "fleet.jsonl"
    logger = StepLogger(str(log))
    recorder = FlightRecorder(capacity=64)
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=8,
                        window_steps=4)
    reqs = synthetic_request_stream(tok, 8, seed=8, max_new_tokens=8,
                                    buckets=(8, 16))
    router = FleetRouter(host_params, cfg, serve,
                         FleetConfig(replicas=2, window_steps=2,
                                     kill_spec="replica_kill@1:1"),
                         eos_id=int(tok.eos_token_id), logger=logger,
                         recorder=recorder)
    router.run(list(reqs), max_wall_s=300)
    logger.close()

    recs = [json.loads(l) for l in log.read_text().splitlines()]
    fleet_wins = [r for r in recs if r["kind"] == "fleet"]
    fleet_sums = [r for r in recs if r["kind"] == "fleet_summary"]
    events = [r for r in recs if r["kind"] == "fleet_event"]
    serve_wins = [r for r in recs if r["kind"] == "serve"]
    serve_sums = [r for r in recs if r["kind"] == "serve_summary"]
    assert fleet_wins and len(fleet_sums) == 1
    assert any(e["event"] == "replica_kill" for e in events)
    # replica-tagged serve telemetry: every window/summary names its engine
    assert serve_wins and all("replica" in r for r in serve_wins)
    assert serve_sums and all("replica" in r for r in serve_sums)
    s = fleet_sums[0]
    assert s["requests"] == 8 and s["tokens_per_sec"] > 0
    assert s["requeued"] >= 1 and s["duplicate_completions"] == 0
    assert s["p99_e2e_s"] >= s["p50_e2e_s"]
    # the flight recorder saw the fleet records too
    ring = [r for r in recorder.snapshot() if r["kind"] == "fleet_summary"]
    assert len(ring) == 1

    text = report.summarize(recs)
    assert "== fleet ==" in text
    assert "fleet tokens/s" in text and "re-queued" in text
    assert "per-replica occupancy" in text


# ---------------------------------------------------------------------------
# Validation: named construction errors, fleet-scoped chaos grammar.
# ---------------------------------------------------------------------------


def test_fleet_config_validation(tok, cfg, host_params):
    with pytest.raises(ValueError, match="replicas"):
        FleetConfig(replicas=0)
    with pytest.raises(ValueError, match="min_replicas"):
        FleetConfig(replicas=2, min_replicas=3)
    with pytest.raises(ValueError, match="max_replicas"):
        FleetConfig(replicas=4, max_replicas=2)
    with pytest.raises(ValueError, match="oscillate"):
        FleetConfig(scale_up_occupancy=0.5, scale_down_occupancy=0.5)
    with pytest.raises(ValueError, match="prefill worker"):
        FleetConfig(prefill_slots=4)
    with pytest.raises(chaos_lib.ChaosSpecError, match="replica_kill"):
        FleetConfig(kill_spec="nan_loss@5")
    with pytest.raises(chaos_lib.ChaosSpecError, match="integer replica id"):
        chaos_lib.parse_spec("replica_kill@5:-1")
    # the training harness rejects fleet-scoped faults by name
    with pytest.raises(chaos_lib.ChaosSpecError, match="fleet-scoped"):
        chaos_lib.ChaosEngine("replica_kill@5")
    serve_ring = ServeConfig(slots=2, buckets=(8,), max_new_tokens=4)
    with pytest.raises(ValueError, match="paged cache"):
        FleetRouter(host_params, cfg, serve_ring,
                    FleetConfig(replicas=2, disagg_prefill=True), eos_id=1)
    with pytest.raises(ValueError, match="needs 16 devices"):
        FleetRouter(host_params, cfg, serve_ring,
                    FleetConfig(replicas=2, devices_per_replica=8), eos_id=1)
    moe = cfg.replace(num_experts=2, moe_dispatch="pallas")
    with pytest.raises(ValueError, match="meshless"):
        FleetRouter(host_params, moe, serve_ring,
                    FleetConfig(replicas=2, devices_per_replica=2), eos_id=1)


# ---------------------------------------------------------------------------
# Crash tolerance (round 24): durable ledger + real-process SIGKILL,
# slow-vs-dead liveness discrimination, request deadlines, backpressure,
# ledger replay, and the serving chaos grammar.
# ---------------------------------------------------------------------------

REPO = Path(__file__).resolve().parent.parent


def test_process_fleet_sigkill_requeues_and_parity(tok, cfg, params,
                                                   tmp_path):
    """THE round-24 acceptance: a real worker process SIGKILLed mid-stream
    loses nothing — its leases revoke, its requests requeue onto the
    survivor, and the durable completion set is token-identical to an
    unkilled single engine with ZERO duplicate completions across real
    process death."""
    from tpukit.obs import StepLogger
    from tpukit.serve.ledger import ProcessFleet

    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        window_steps=8)
    reqs = synthetic_request_stream(tok, 8, seed=3, max_new_tokens=MAX_NEW,
                                    buckets=(8, 16))
    want = _single_engine_tokens(params, cfg, tok, serve, reqs)
    log = tmp_path / "procs.jsonl"
    logger = StepLogger(str(log))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"

    def spawn(idx):
        return subprocess.Popen(
            [sys.executable, str(REPO / "tests" / "fleet_worker.py"),
             str(tmp_path / "fleet"), str(idx)],
            cwd=str(REPO), env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    fleet = ProcessFleet(tmp_path / "fleet", spawn=spawn, replicas=2,
                         replica_timeout=60.0, request_retries=3,
                         chaos=chaos_lib.ServingChaos("replica_sigkill@3:1"),
                         logger=logger)
    s = fleet.run(list(reqs), max_wall_s=240.0)
    logger.close()
    got = {rid: list(map(int, rec["ids"]))
           for rid, rec in fleet.ledger.completions().items()}
    assert got.keys() == want.keys()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid], err_msg=f"rid {rid}")
    assert s["kills"] == 1 and s["replicas_dead"] >= 1
    assert s["requeued"] >= 1 and s["leases_revoked"] >= 1
    assert s["duplicate_completions"] == 0
    assert s["ledger"]["duplicates"] == 0
    assert s["request_failures"] == 0
    # the death was a REAL SIGKILL: the worker's wait status says so
    assert any(d["reason"] == "exit" and d.get("code") == -9
               for d in s["deaths"])
    recs = [json.loads(l) for l in log.read_text().splitlines()]
    events = {r["event"] for r in recs if r["kind"] == "fleet_event"}
    assert "replica_sigkill" in events and "replica_dead" in events
    assert any(r["kind"] == "lease_requeue" for r in recs)
    assert any(r["kind"] == "chaos" and r.get("fault") == "replica_sigkill"
               for r in recs)


def test_liveness_discriminates_slow_from_dead(tok, cfg, params, host_params,
                                               tmp_path):
    """slow_replica@R:ms against --replica_timeout: a stall shorter than
    the timeout is a straggler and must NOT be declared dead; the SAME
    fault outliving the timeout IS death — leases revoke, work requeues
    onto the survivor, and parity holds either way."""
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        window_steps=8)
    base = synthetic_request_stream(tok, 16, seed=3, max_new_tokens=MAX_NEW,
                                    buckets=(8, 16))
    want = _single_engine_tokens(params, cfg, tok, serve, base)
    slow = FleetRouter(
        host_params, cfg, serve,
        FleetConfig(replicas=2, window_steps=4,
                    fleet_dir=str(tmp_path / "slow"), replica_timeout=5.0,
                    kill_spec="slow_replica@2:30"),
        eos_id=int(tok.eos_token_id))
    got = _tokens(slow.run(list(base), max_wall_s=300))
    assert got.keys() == want.keys()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid], err_msg=f"rid {rid}")
    s = slow.last_summary
    assert s["replicas_dead"] == 0 and s["kills"] == 0
    assert s["requeued"] == 0
    # the dead case must not ride on wall-clock racing a warm (fast) run:
    # rid 1 lands on replica 1 (least-loaded round-robin) and is PINNED
    # stuck there, so the stalled replica provably holds a lease when its
    # heartbeat age crosses the timeout; its deadline is the run's escape
    # hatch once the request requeues (still stuck) onto the survivor
    reqs = [dataclasses.replace(r, deadline_ms=800.0) if r.rid == 1 else r
            for r in base]
    dead = FleetRouter(
        host_params, cfg, serve,
        FleetConfig(replicas=2, window_steps=4,
                    fleet_dir=str(tmp_path / "dead"), replica_timeout=0.15,
                    kill_spec="slow_replica@2:60000,stuck_request@1"),
        eos_id=int(tok.eos_token_id))
    comps = dead.run(list(reqs), max_wall_s=300)
    got = _tokens(comps)
    assert got.keys() == want.keys()
    for rid in want:
        if rid != 1:
            np.testing.assert_array_equal(got[rid], want[rid],
                                          err_msg=f"rid {rid}")
    assert {c.rid: c for c in comps}[1].reason == "deadline"
    s = dead.last_summary
    assert s["replicas_dead"] == 1 and s["requeued"] >= 1
    assert s["leases_revoked"] >= 1
    assert s["duplicate_completions"] == 0
    assert s["per_replica"][1]["fate"] == "dead"
    assert s["ledger"]["duplicates"] == 0
    assert s["deadline_misses"] == 1


def test_deadline_evicts_stuck_request(tok, cfg, params, host_params,
                                       tmp_path):
    """stuck_request@RID + deadline_ms: the pinned request is evicted at
    its deadline as a reason="deadline" completion with partial output,
    every OTHER request's tokens are untouched, and the miss lands in the
    summary, the JSONL, and the --max_deadline_miss_pct gate."""
    import importlib

    from tpukit.obs import StepLogger

    report = importlib.import_module("tools.report")
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        window_steps=8)
    base = synthetic_request_stream(tok, 8, seed=3, max_new_tokens=MAX_NEW,
                                    buckets=(8, 16))
    want = _single_engine_tokens(params, cfg, tok, serve, base)
    stuck_rid = base[2].rid
    reqs = [dataclasses.replace(r, deadline_ms=600.0) if r.rid == stuck_rid
            else r for r in base]
    log = tmp_path / "deadline.jsonl"
    logger = StepLogger(str(log))
    router = FleetRouter(
        host_params, cfg, serve,
        FleetConfig(replicas=2, window_steps=4,
                    kill_spec=f"stuck_request@{stuck_rid}"),
        eos_id=int(tok.eos_token_id), logger=logger)
    comps = router.run(list(reqs), max_wall_s=120)
    logger.close()
    got = _tokens(comps)
    assert got.keys() == want.keys()
    by_rid = {c.rid: c for c in comps}
    assert by_rid[stuck_rid].reason == "deadline"
    for rid in want:
        if rid != stuck_rid:
            np.testing.assert_array_equal(got[rid], want[rid],
                                          err_msg=f"rid {rid}")
    s = router.last_summary
    assert s["deadline_misses"] == 1
    assert s["duplicate_completions"] == 0

    recs = [json.loads(l) for l in log.read_text().splitlines()]
    misses = [r for r in recs if r["kind"] == "deadline_miss"]
    assert len(misses) == 1 and misses[0]["rid"] == stuck_rid
    assert misses[0]["over_ms"] > 0
    text = report.summarize(recs)
    assert "fleet recovery" in text and "deadline miss" in text
    # the gate: 1/8 = 12.5% — passes a 50% threshold, fails 5%
    ok, msg = report.check_max_deadline_miss_pct(recs, 50.0)
    assert ok, msg
    ok, msg = report.check_max_deadline_miss_pct(recs, 5.0)
    assert not ok and "FAIL" in msg
    # no fleet summary at all -> fail, never a vacuous pass
    ok, msg = report.check_max_deadline_miss_pct(
        [r for r in recs if r["kind"] != "fleet_summary"], 50.0)
    assert not ok and "no fleet_summary" in msg
    # a pre-round-24 summary (no deadline_misses field) fails too
    forged = [{k: v for k, v in s.items() if k != "deadline_misses"}]
    ok, msg = report.check_max_deadline_miss_pct(forged, 50.0)
    assert not ok and "deadline_misses" in msg


def test_backpressure_sheds_lowest_priority(tok, cfg, params, host_params,
                                            tmp_path):
    """max_queue_depth backpressure: over-depth arrivals shed lowest
    priority first, each as a NAMED request_rejected event and a terminal
    backpressure ledger record; the admitted survivors stay token-exact."""
    from tpukit.obs import StepLogger

    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        window_steps=8)
    base = synthetic_request_stream(tok, 8, seed=3, max_new_tokens=MAX_NEW,
                                    buckets=(8, 16))
    want = _single_engine_tokens(params, cfg, tok, serve, base)
    keep = {base[0].rid, base[5].rid}
    reqs = [dataclasses.replace(r, priority=1) if r.rid in keep else r
            for r in base]
    log = tmp_path / "shed.jsonl"
    logger = StepLogger(str(log))
    router = FleetRouter(
        host_params, cfg, serve,
        FleetConfig(replicas=2, window_steps=4, max_queue_depth=2,
                    fleet_dir=str(tmp_path / "fleet")),
        eos_id=int(tok.eos_token_id), logger=logger)
    comps = router.run(list(reqs), max_wall_s=120)
    logger.close()
    got = _tokens(comps)
    assert got.keys() == keep
    for rid in keep:
        np.testing.assert_array_equal(got[rid], want[rid], err_msg=f"rid {rid}")
    s = router.last_summary
    assert s["rejected"] == 6 and s["requests"] == 2
    fails = router.ledger.failures()
    assert set(fails) == {r.rid for r in base} - keep
    assert all(f["reason"] == "backpressure" for f in fails.values())
    recs = [json.loads(l) for l in log.read_text().splitlines()]
    rej = [r for r in recs if r["kind"] == "fleet_event"
           and r["event"] == "request_rejected"]
    assert len(rej) == 6
    assert all(r["reason"] == "backpressure" for r in rej)


def test_ledger_replay_resumes_at_frontier(tok, cfg, params, host_params,
                                           tmp_path):
    """A router crashing mid-stream (a ledger I/O fault outliving the
    retry budget) leaves its completed frontier durable; a restarted
    router over the SAME directory replays it and serves only the
    remainder — the union is token-exact with zero duplicates."""
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        window_steps=8)
    reqs = synthetic_request_stream(tok, 8, seed=3, max_new_tokens=MAX_NEW,
                                    buckets=(8, 16))
    want = _single_engine_tokens(params, cfg, tok, serve, reqs)
    fdir = str(tmp_path / "fleet")
    crashed = FleetRouter(
        host_params, cfg, serve,
        FleetConfig(replicas=2, window_steps=4, fleet_dir=fdir,
                    # 9 consecutive failures of the 7th ledger operation:
                    # past the default retry budget -> fatal, mid-stream
                    kill_spec="ledger_io_fail@7:9"),
        eos_id=int(tok.eos_token_id))
    with pytest.raises(IOError, match="chaos: injected"):
        crashed.run(list(reqs), max_wall_s=300)
    durable = crashed.ledger.completions()
    assert 1 <= len(durable) < 8
    restarted = FleetRouter(
        host_params, cfg, serve,
        FleetConfig(replicas=2, window_steps=4, fleet_dir=fdir),
        eos_id=int(tok.eos_token_id))
    comps = restarted.run(list(reqs), max_wall_s=300)
    # the restarted router served ONLY the not-yet-completed frontier...
    assert {c.rid for c in comps} == set(want) - set(durable)
    s = restarted.last_summary
    assert s["ledger"]["replayed"] == len(durable)
    assert s["ledger"]["completed"] == 8
    assert s["ledger"]["duplicates"] == 0
    # ...and the durable union is the full stream, token-exact
    got = {rid: list(map(int, rec["ids"]))
           for rid, rec in restarted.ledger.completions().items()}
    assert got.keys() == want.keys()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid], err_msg=f"rid {rid}")


def test_ledger_io_fault_absorbed_by_retry(tok, cfg, params, host_params,
                                           tmp_path):
    """ledger_io_fail within the retry budget is absorbed: the run
    completes token-exact and the injected faults surface as
    kind="chaos" records, not failures."""
    from tpukit.obs import StepLogger

    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        window_steps=8)
    reqs = synthetic_request_stream(tok, 8, seed=3, max_new_tokens=MAX_NEW,
                                    buckets=(8, 16))
    want = _single_engine_tokens(params, cfg, tok, serve, reqs)
    log = tmp_path / "iofault.jsonl"
    logger = StepLogger(str(log))
    router = FleetRouter(
        host_params, cfg, serve,
        FleetConfig(replicas=2, window_steps=4,
                    fleet_dir=str(tmp_path / "fleet"),
                    kill_spec="ledger_io_fail@2:2"),
        eos_id=int(tok.eos_token_id), logger=logger)
    got = _tokens(router.run(list(reqs), max_wall_s=300))
    logger.close()
    assert got.keys() == want.keys()
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid], err_msg=f"rid {rid}")
    s = router.last_summary
    assert s["duplicate_completions"] == 0 and s["ledger"]["duplicates"] == 0
    recs = [json.loads(l) for l in log.read_text().splitlines()]
    chaos_recs = [r for r in recs if r["kind"] == "chaos"]
    assert sum(1 for r in chaos_recs if r.get("fault") == "ledger_io") == 2


def test_serving_chaos_grammar_one_path():
    """ONE grammar: every fleet fault kind parses through
    validate_fleet_spec (shared with --chaos_spec's parse_spec), malformed
    entries fail by name, and the round-24 FleetConfig knobs validate."""
    entries = chaos_lib.validate_fleet_spec(
        "replica_kill@3,replica_sigkill@4:1,slow_replica@2:50,"
        "stuck_request@7,ledger_io_fail@2:3")
    assert [e["kind"] for e in entries] == [
        "replica_kill", "replica_sigkill", "slow_replica",
        "stuck_request", "ledger_io_fail"]
    ch = chaos_lib.ServingChaos(
        "replica_sigkill@4:1,slow_replica@2:50,stuck_request@7,"
        "ledger_io_fail@2:3")
    assert ch.sigkills == {4: [1]}
    assert ch.stalls == {2: [0.05]}
    assert ch.stuck == {7}
    # FleetConfig.kill_spec rides the same path
    FleetConfig(replicas=2, kill_spec="slow_replica@2:50")
    with pytest.raises(chaos_lib.ChaosSpecError, match="stall"):
        FleetConfig(replicas=2, kill_spec="slow_replica@2")
    with pytest.raises(chaos_lib.ChaosSpecError, match="takes no param"):
        chaos_lib.validate_fleet_spec("stuck_request@7:1")
    with pytest.raises(chaos_lib.ChaosSpecError, match="1-based"):
        chaos_lib.validate_fleet_spec("ledger_io_fail@0")
    with pytest.raises(chaos_lib.ChaosSpecError, match="integer replica id"):
        chaos_lib.validate_fleet_spec("replica_sigkill@5:-1")
    # round-24 robustness knobs: named construction errors
    with pytest.raises(ValueError, match="replica_timeout"):
        FleetConfig(replicas=2, replica_timeout=-1.0)
    with pytest.raises(ValueError, match="needs fleet_dir"):
        FleetConfig(replicas=2, replica_timeout=1.0)
    with pytest.raises(ValueError, match="request_retries"):
        FleetConfig(replicas=2, request_retries=-1)
    with pytest.raises(ValueError, match="max_queue_depth"):
        FleetConfig(replicas=2, max_queue_depth=-1)


def test_serving_chaos_io_fault_occurrence_semantics():
    """A scheduled count of c fails the first c ATTEMPTS of that
    occurrence (retries re-enter without advancing the index), then the
    occurrence completes; foreign sites pass through untouched."""
    ch = chaos_lib.ServingChaos("ledger_io_fail@2:2")
    ch.io_fault("ledger")                       # occurrence 1 passes
    with pytest.raises(IOError, match="occurrence 2"):
        ch.io_fault("ledger")                   # occurrence 2, attempt 1
    with pytest.raises(IOError, match="occurrence 2"):
        ch.io_fault("ledger")                   # occurrence 2, attempt 2
    ch.io_fault("ledger")                       # attempt 3 succeeds
    ch.io_fault("ledger")                       # occurrence 3 passes
    fired = ch.drain_fired()
    assert len(fired) == 2
    assert all(f["fault"] == "ledger_io" for f in fired)
    ch2 = chaos_lib.ServingChaos("ledger_io_fail@1:1")
    ch2.io_fault("checkpoint")                  # not this plan's site


def test_fleet_decode_plan_is_standalone_plan():
    """The router adds ZERO collectives: the per-replica plan is the
    standalone decode closed form, byte for byte, on a subset mesh."""
    from tpukit.analysis import decode_comm_plan, fleet_decode_comm_plan
    from tpukit.mesh import create_mesh

    cfg = GPTConfig(dim=32, head_dim=8, heads=4, num_layers=2, vocab_size=160,
                    max_position_embeddings=64, compute_dtype=jnp.float32)
    mesh = create_mesh({"data": 1, "model": 4},
                       devices=jax.devices()[4:8])
    base = decode_comm_plan(cfg, mesh, 4)
    fleet = fleet_decode_comm_plan(cfg, mesh, 4)
    assert fleet.ops == base.ops and fleet.exhaustive
    assert fleet.label.startswith("fleet replica")
