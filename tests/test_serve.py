"""Continuous-batching serving engine (tpukit/serve, round 14, ROADMAP #1).

Contracts pinned here:
  - the batched KV-cached decode is token-for-token the SERIAL cached
    decode — greedy and fixed-seed sampling, ragged prompt lengths, and
    under mid-stream admit/evict slot reuse;
  - the scheduler's slot ring: eviction on EOS and on length, free-list
    reuse, bucket selection, admission rejection beyond the bucket set;
  - the serve path's compile budget is the DECLARED bucket set: one
    prefill program per bucket used + one decode program, asserted via
    the jit cache sizes;
  - the TP-mesh decode step's per-step collectives match the closed form
    `serve.decode_step_comm` exactly against compiled HLO, with zero
    involuntary-remat warnings (the round-10/12 audit discipline);
  - dropless-pallas MoE cached decode equals the full-reforward decode
    (the round-14 `use_cache` auto-resolve satellite);
  - `kind="serve"` / `kind="serve_summary"` JSONL records land and
    `tools/report.py` renders the serving section.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpukit.data import WordTokenizer, synthetic_stories
from tpukit.model import GPTConfig, init_params
from tpukit.sampling import _cached_decode_exact, _decode_loop_cached, generate
from tpukit.serve import (
    Request,
    ServeConfig,
    ServeEngine,
    decode_step,
    decode_step_comm,
    prefill_slots,
    synthetic_request_stream,
)
from tpukit.serve.decode import decode_loop

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


def _serial_cached(params, cfg, ids, max_new, eos_id, temperature=0.0,
                   top_k=0, seed=0):
    """Reference: the serial single-sequence cached decode on exact ids."""
    ids = np.asarray(ids, np.int32)
    buf = np.zeros((1, len(ids) + max_new), np.int32)
    buf[0, : len(ids)] = ids
    out, length = _decode_loop_cached(
        params, cfg, jnp.asarray(buf), len(ids), max_new, int(eos_id),
        temperature=float(temperature),
        top_k=min(int(top_k), cfg.padded_vocab_size),
        rng=jnp.asarray(np.asarray(jax.random.PRNGKey(seed)))
        if temperature > 0.0
        else None,
    )
    return np.asarray(out)[0, : int(length)]


# ---------------------------------------------------------------------------
# Batched cached decode (decode_loop): parity with the serial cached decode.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "temperature,top_k,seed",
    [(0.0, 0, 0), (0.9, 0, 3), (1.1, 5, 7)],
    ids=["greedy", "sampled", "sampled_topk"],
)
def test_decode_loop_matches_serial_cached(tok, cfg, params, temperature, top_k, seed):
    """Ragged prompt lengths in one [N, W] buffer: every row must decode
    the exact token sequence the serial cached decode produces for that
    prompt alone — greedy, and sampling under one fixed seed (the rows
    share the seed and fold their own cursors, like serial `generate`)."""
    prompts = ["One day, ", "The big brown cat sat on a mat ", "She said "]
    enc = tok(prompts, truncation=True, max_length=40)["input_ids"]
    lens = np.asarray([len(r) for r in enc], np.int32)
    buf = np.zeros((3, int(lens.max()) + MAX_NEW), np.int32)
    for i, r in enumerate(enc):
        buf[i, : len(r)] = r
    out, lengths = decode_loop(
        params, cfg, jnp.asarray(buf), jnp.asarray(lens), MAX_NEW,
        int(tok.eos_token_id), temperature=temperature, top_k=top_k,
        rng=jnp.asarray(np.asarray(jax.random.PRNGKey(seed)))
        if temperature > 0.0
        else None,
    )
    out, lengths = np.asarray(out), np.asarray(lengths)
    for i, ids in enumerate(enc):
        want = _serial_cached(params, cfg, ids, MAX_NEW, tok.eos_token_id,
                              temperature, top_k, seed)
        got = out[i, : int(lengths[i])]
        np.testing.assert_array_equal(got, want, err_msg=prompts[i])


# ---------------------------------------------------------------------------
# Engine: continuous batching with mid-stream admit/evict must stay serial-
# exact per request.
# ---------------------------------------------------------------------------


def _run_engine(params, cfg, tok, requests, serve):
    eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id))
    comps = eng.run(list(requests), max_wall_s=300)
    return eng, comps


def test_engine_admit_evict_parity_greedy(tok, cfg, params):
    """8 requests through 3 slots forces mid-decode eviction + slot reuse
    + admissions while other slots are mid-sequence; every completion must
    still be token-for-token the serial cached decode of its own prompt."""
    serve = ServeConfig(slots=3, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        window_steps=8)
    reqs = synthetic_request_stream(tok, 8, seed=3, max_new_tokens=MAX_NEW,
                                    buckets=(8, 16))
    eng, comps = _run_engine(params, cfg, tok, reqs, serve)
    assert len(comps) == 8
    assert eng.admitted == 8 and not eng._lanes and len(eng._free) == 3
    for c in comps:
        want = _serial_cached(params, cfg, c.ids[: c.prompt_len], MAX_NEW,
                              tok.eos_token_id)
        np.testing.assert_array_equal(c.ids, want, err_msg=f"rid {c.rid}")


def test_engine_admit_evict_parity_sampled(tok, cfg, params):
    """Same contract under per-request seeded sampling (temperature + top-k
    are engine-static; each request's key folds its own cursor), including
    arrivals spaced so admissions land mid-decode."""
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        temperature=0.9, top_k=5, window_steps=8)
    reqs = synthetic_request_stream(tok, 6, seed=11, max_new_tokens=MAX_NEW,
                                    buckets=(8, 16), qps=50.0)
    eng, comps = _run_engine(params, cfg, tok, reqs, serve)
    assert len(comps) == 6
    for c in comps:
        want = _serial_cached(
            params, cfg, c.ids[: c.prompt_len], MAX_NEW, tok.eos_token_id,
            temperature=0.9, top_k=5, seed=11 + c.rid,
        )
        np.testing.assert_array_equal(c.ids, want, err_msg=f"rid {c.rid}")


def test_engine_evicts_on_eos_and_reuses_slot(tok, cfg, params):
    """Force a real EOS eviction: pick eos_id = the 3rd token the model
    would greedily generate, and check the slot retires with reason "eos",
    exactly 3 generated tokens (stop BEFORE appending, the reference
    semantics), returns to the free list, and serves the next request."""
    ids = tok(["One day, "], truncation=True, max_length=8)["input_ids"][0]
    free_run = _serial_cached(params, cfg, ids, MAX_NEW, eos_id=-1)
    eos = int(free_run[len(ids) + 3])  # the 4th generated token
    serve = ServeConfig(slots=1, buckets=(8,), max_new_tokens=MAX_NEW,
                        window_steps=4)
    reqs = [
        Request(rid=0, ids=tuple(int(x) for x in ids), max_new_tokens=MAX_NEW),
        Request(rid=1, ids=tuple(int(x) for x in ids), max_new_tokens=2),
    ]
    eng = ServeEngine(params, cfg, serve, eos_id=eos)
    comps = eng.run(reqs, max_wall_s=300)
    by_rid = {c.rid: c for c in comps}
    assert by_rid[0].reason == "eos" and by_rid[0].generated == 3
    np.testing.assert_array_equal(
        by_rid[0].ids, free_run[: len(ids) + 3]
    )
    # the single slot was reused for rid 1, which retires on length
    assert by_rid[1].reason == "length" and by_rid[1].generated == 2
    assert eng.evicted == {"eos": 1, "length": 1, "deadline": 0}
    assert list(eng._free) == [0]


def test_scheduler_buckets_and_validation(tok, cfg, params):
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=4)
    eng = ServeEngine(params, cfg, serve, eos_id=1)
    assert eng.bucket_for(1) == 8 and eng.bucket_for(8) == 8
    assert eng.bucket_for(9) == 16 and eng.bucket_for(16) == 16
    with pytest.raises(ValueError, match="largest declared bucket"):
        eng.bucket_for(17)
    with pytest.raises(ValueError, match="ascending"):
        ServeConfig(buckets=(16, 8))
    with pytest.raises(ValueError, match="slots"):
        ServeConfig(slots=0)
    with pytest.raises(ValueError, match="smaller than the largest bucket"):
        # a ring narrower than the largest bucket would crash at prefill
        ServeConfig(buckets=(16, 32), max_len=20)
    with pytest.raises(ValueError, match="position table"):
        # width 60 + 10 = 70 > max_position_embeddings 64
        ServeEngine(params, cfg, ServeConfig(slots=1, buckets=(60,),
                                             max_new_tokens=10), eos_id=1)


def test_synthetic_stream_deterministic(tok):
    a = synthetic_request_stream(tok, 6, seed=5, qps=10.0)
    b = synthetic_request_stream(tok, 6, seed=5, qps=10.0)
    assert [(r.ids, r.arrival_s, r.seed) for r in a] == [
        (r.ids, r.arrival_s, r.seed) for r in b
    ]
    assert all(x.arrival_s <= y.arrival_s for x, y in zip(a, a[1:]))
    c = synthetic_request_stream(tok, 6, seed=6, qps=10.0)
    assert [r.ids for r in a] != [r.ids for r in c]


# ---------------------------------------------------------------------------
# Compile budget: the serve path compiles one prefill program per declared
# (bucket, power-of-two admit size) pair plus one decode step — continuous
# batching must not retrace per request, occupancy, or prompt length.
# ---------------------------------------------------------------------------


def test_compile_count_bounded_by_declared_budget(tok, cfg, params):
    buckets = (8, 16)
    serve = ServeConfig(slots=2, buckets=buckets, max_new_tokens=6,
                        window_steps=8)
    # 2 slots -> admit sizes {1, 2}: budget = 1 decode + 2 buckets x 2
    assert serve.compile_budget == 5
    prefill0 = prefill_slots._cache_size()
    decode0 = decode_step._cache_size()
    reqs = synthetic_request_stream(tok, 10, seed=2, max_new_tokens=6,
                                    buckets=buckets)
    eng, comps = _run_engine(params, cfg, tok, reqs, serve)
    assert len(comps) == 10
    assert eng.buckets_used <= set(buckets)
    # 10 requests with ragged prompts over 2 slots: serve-path compiles
    # bounded by the DECLARED budget, with exactly one decode program
    prefill_added = prefill_slots._cache_size() - prefill0
    decode_added = decode_step._cache_size() - decode0
    assert decode_added <= 1
    assert prefill_added + decode_added <= serve.compile_budget
    # a second engine over the same buckets must add ZERO compiles
    prefill1 = prefill_slots._cache_size()
    decode1 = decode_step._cache_size()
    _run_engine(params, cfg, tok, synthetic_request_stream(
        tok, 4, seed=9, max_new_tokens=6, buckets=buckets), serve)
    assert prefill_slots._cache_size() == prefill1
    assert decode_step._cache_size() == decode1


# ---------------------------------------------------------------------------
# Sharded serving: params at their TP training shardings, KV ring sharded
# (heads over `model`, slots over `data`) — per-step collectives must match
# the closed form exactly, with zero involuntary-remat warnings.
# ---------------------------------------------------------------------------


def _tp_decode_state(cfg, mesh, slots, width):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from tpukit.model import gpt
    from tpukit.shardings import TensorParallel

    strat = TensorParallel(mesh)
    params = init_params(jax.random.PRNGKey(0), cfg)
    psh = strat.state_sharding(jax.eval_shape(lambda: params))
    params = jax.tree.map(jax.device_put, params, psh)
    sh = lambda spec: NamedSharding(mesh, spec)
    da = "data" if "data" in mesh.axis_names else None
    buf = jax.device_put(np.zeros((slots, width), np.int32), sh(P(da, None)))
    cache = jax.tree.map(
        lambda c: jax.device_put(c, sh(P(None, da, "model", None, None))),
        gpt.init_kv_cache(cfg, slots, width),
    )
    cursors = jax.device_put(np.full((slots,), 5, np.int32), sh(P(da)))
    active = jax.device_put(np.ones((slots,), bool), sh(P(da)))
    limits = jax.device_put(np.full((slots,), 12, np.int32), sh(P(da)))
    keys = jax.device_put(np.zeros((slots, 2), np.uint32), sh(P(da, None)))
    return params, buf, cache, cursors, active, limits, keys


@pytest.mark.parametrize(
    "axes,slots,temperature,top_k",
    [
        ({"data": 2, "model": 4}, 4, 0.0, 0),
        ({"data": 2, "model": 4}, 4, 0.9, 5),
        ({"data": 4, "model": 2}, 8, 0.0, 0),
    ],
    ids=["d2m4_greedy", "d2m4_topk", "d4m2_greedy"],
)
def test_tp_decode_step_hlo_comm_audit(axes, slots, temperature, top_k):
    """The decode step under the TP mesh must move EXACTLY the closed-form
    collectives (`decode_step_comm`): the Megatron all-reduce pair per
    layer + the embedding-gather psum, the one deliberate logits
    all-gather, and (top-k only) lax.top_k's data-axis gather — nothing
    else, and zero GSPMD involuntary-remat fallbacks. f32 compute so the
    byte counts are exact on the CPU wire (round-12 lesson)."""
    from tpukit.mesh import create_mesh
    from tpukit.obs.xla import capture_compiler_stderr, collective_bytes

    cfg = GPTConfig(
        dim=32, head_dim=8, heads=4, num_layers=2, vocab_size=160,
        max_position_embeddings=64, compute_dtype=jnp.float32,
    )
    mesh = create_mesh(axes)
    params, buf, cache, cursors, active, limits, keys = _tp_decode_state(
        cfg, mesh, slots, width=24
    )
    # check=True raises on any involuntary-remat warning at capture exit
    with capture_compiler_stderr(check=True):
        compiled = decode_step.lower(
            params, cfg, buf, cache, cursors, active, limits, keys,
            1, temperature, top_k, mesh,
        ).compile()
    measured = collective_bytes(compiled.as_text())
    expected = decode_step_comm(cfg, mesh, slots, top_k=top_k)
    assert measured == expected, (measured, expected)


def test_tp_engine_decode_parity(tok, cfg, params):
    """Value check on top of the byte audit: the engine under the TP mesh
    (params TP-sharded, KV ring sharded over heads x slots) decodes the
    same tokens as the meshless engine."""
    from tpukit.mesh import create_mesh
    from tpukit.shardings import TensorParallel

    mesh = create_mesh({"data": 2, "model": 4})
    strat = TensorParallel(mesh)
    tp_params = jax.tree.map(
        jax.device_put, params, strat.state_sharding(jax.eval_shape(lambda: params))
    )
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=6,
                        window_steps=8)
    reqs = synthetic_request_stream(tok, 4, seed=4, max_new_tokens=6,
                                    buckets=(8, 16))
    eng_tp = ServeEngine(tp_params, cfg, serve, eos_id=int(tok.eos_token_id),
                         mesh=mesh)
    comps_tp = {c.rid: c for c in eng_tp.run(list(reqs), max_wall_s=300)}
    eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id))
    comps = {c.rid: c for c in eng.run(list(reqs), max_wall_s=300)}
    assert comps_tp.keys() == comps.keys()
    for rid in comps:
        np.testing.assert_array_equal(comps_tp[rid].ids, comps[rid].ids)


def test_engine_slot_mesh_divisibility():
    from tpukit.mesh import create_mesh

    cfg = GPTConfig(dim=32, head_dim=8, heads=4, num_layers=1, vocab_size=97,
                    max_position_embeddings=64, compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = create_mesh({"data": 4, "model": 2})
    with pytest.raises(ValueError, match="multiple of the mesh's data axis"):
        ServeEngine(params, cfg, ServeConfig(slots=3, buckets=(8,)),
                    eos_id=1, mesh=mesh)
    with pytest.raises(ValueError, match="heads"):
        decode_step_comm(cfg.replace(heads=3), mesh, 4)


# ---------------------------------------------------------------------------
# Dropless-pallas MoE: cached decode is exact (the use_cache auto-resolve
# satellite) — and the predicate's truth table.
# ---------------------------------------------------------------------------


def test_cached_decode_exact_predicate(cfg):
    assert _cached_decode_exact(cfg)  # dense
    moe = cfg.replace(num_experts=2)
    assert not _cached_decode_exact(moe)  # xla buffer dispatch
    assert not _cached_decode_exact(moe.replace(moe_dispatch="a2a"))
    assert _cached_decode_exact(moe.replace(moe_dispatch="pallas"))
    assert not _cached_decode_exact(
        moe.replace(moe_dispatch="pallas", moe_capacity=4)
    )


def test_moe_pallas_cached_equals_uncached(tok):
    """Dropless pallas MoE: per-token routing is chunk-composition-
    independent and nothing is dropped, so the KV-cached decode must equal
    the full-reforward decode token-for-token (greedy and seeded
    sampling) — the justification for lifting the num_experts==0 guard in
    generate's use_cache auto-resolve (gpt._apply_moe_ffn docstring)."""
    cfg = GPTConfig(
        dim=32, head_dim=8, heads=4, num_layers=2, vocab_size=tok.vocab_size,
        max_position_embeddings=64, compute_dtype=jnp.float32,
        num_experts=2, moe_dispatch="pallas",
    )
    params = init_params(jax.random.PRNGKey(6), cfg)
    for prompt, kw in [
        ("One day, ", {}),
        ("She said ", dict(temperature=0.9, top_k=3, seed=5)),
    ]:
        cached = generate(params, cfg, prompt, tok, max_new_tokens=6,
                          use_cache=True, **kw)
        uncached = generate(params, cfg, prompt, tok, max_new_tokens=6,
                            use_cache=False, **kw)
        assert cached == uncached, (prompt, kw)


# ---------------------------------------------------------------------------
# Serving telemetry: JSONL windows + summary land and report.py renders.
# ---------------------------------------------------------------------------


def test_serve_jsonl_windows_and_report(tok, cfg, params, tmp_path):
    from tpukit.obs import FlightRecorder, StepLogger

    log = tmp_path / "serve.jsonl"
    logger = StepLogger(str(log))
    recorder = FlightRecorder(capacity=64)
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=8,
                        window_steps=4)
    reqs = synthetic_request_stream(tok, 5, seed=8, max_new_tokens=8,
                                    buckets=(8, 16))
    eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id),
                      logger=logger, recorder=recorder)
    eng.run(reqs, max_wall_s=300)
    logger.close()

    recs = [json.loads(l) for l in log.read_text().splitlines()]
    windows = [r for r in recs if r["kind"] == "serve"]
    summaries = [r for r in recs if r["kind"] == "serve_summary"]
    assert windows and len(summaries) == 1
    for w in windows:
        assert w["steps"] > 0 and 0.0 <= w["occupancy"] <= 1.0
        assert {"prefill", "decode", "sync"} & set(w["seconds"])
    s = summaries[0]
    assert s["requests"] == 5
    assert s["generated_tokens"] == sum(
        w["new_tokens"] for w in windows
    )
    assert s["tokens_per_sec"] > 0 and s["p99_e2e_s"] >= s["p50_e2e_s"]
    assert s["p99_token_s"] >= s["p50_token_s"] > 0
    assert set(s["buckets_used"]) <= set(s["buckets"])
    assert s["decode_s"] > 0 and s["sync_s"] >= 0 and s["prefill_s"] > 0
    # the flight recorder saw the same windows
    ring = [r for r in recorder.snapshot() if r["kind"] == "serve"]
    assert len(ring) == len(windows)

    # tools/report.py renders a serving section from the same file
    import importlib

    report = importlib.import_module("tools.report")
    text = report.summarize(recs)
    assert "== serving ==" in text
    assert "tokens/s" in text and "occupancy" in text


# ---------------------------------------------------------------------------
# Device-side names: the serve programs' scopes reach the compiled module.
# ---------------------------------------------------------------------------


def test_paged_serve_programs_name_every_serving_scope(cfg, params, fresh_compiles):
    """The compiled text of a tiny paged decode quantum and of a chunked
    prefill names the serving scopes in `op_name`: together with the train
    step's (tests/test_obs.py) that is every name of `obs.SCOPES`."""
    from tpukit.obs import SCOPES, instruction_scopes
    from tpukit.serve import paged
    from tpukit.serve.decode import prefill_chunk_paged

    n, page, per_slot = 4, 8, 4
    cache = paged.init_paged_cache(cfg, n * per_slot + 1, page, per_slot, n, "f32")
    buf = jnp.zeros((n, page * per_slot), jnp.int32)
    cur, lim = jnp.ones((n,), jnp.int32), jnp.full((n,), 24, jnp.int32)
    act, keys = jnp.ones((n,), bool), jnp.zeros((n, 2), jnp.uint32)
    decode = decode_step.lower(params, cfg, buf, cache, cur, act, lim, keys,
                               0, 0.0, 0, None, steps=2).compile().as_text()
    paths = set(instruction_scopes(decode).values())
    # the paged forward writes the stacked pool by index inside each layer's
    # attention: no restack outside the layers (the ring path keeps one)
    assert "decode/kv_write" not in paths
    assert {"decode/embed", "decode/ln", "decode/attn", "decode/attn/kv_gather",
            "decode/attn/kv_write", "decode/attn/attend", "decode/ffn",
            "decode/head", "decode/head/ln", "decode/sample"} <= paths, paths
    a = 2
    z = lambda shape, dt: jnp.zeros(shape, dt)  # noqa: E731
    prefill = prefill_chunk_paged.lower(
        params, cfg, buf, cache, cur, act, lim, keys, z((a,), jnp.int32),
        z((a, page), jnp.int32), z((a,), jnp.int32), z((a,), bool),
        z((a,), jnp.int32), z((a,), jnp.int32), z((a, 2), jnp.uint32),
    ).compile().as_text()
    prefill_paths = set(instruction_scopes(prefill).values())
    assert {"prefill/attn/kv_gather", "prefill/attn/kv_write", "prefill/attn/attend",
            "prefill/ffn"} <= prefill_paths, prefill_paths
    serving = {c for p in paths | prefill_paths for c in p.split("/")}
    assert serving == set(SCOPES) - {"loss", "optimizer", "moe"}
