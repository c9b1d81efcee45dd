"""Paged KV cache subsystem (tpukit/serve/paged.py, round 15, ROADMAP #2).

Contracts pinned here:
  - paged decode is TOKEN-FOR-TOKEN the serial cached decode (and the
    round-14 ring engine) for the exact (f32-at-compute-dtype) page
    storage — greedy and fixed-seed sampling, under admit/evict
    interleaving with a pool tight enough to force mid-stream page reuse
    and retained-prefix reclaim;
  - shared-prefix reuse: prefix-hit admissions skip the shared prefill,
    and a shared page's WRITER evicting leaves its readers valid
    (refcounts), with the retained-LRU keeping a popular prefix hot;
  - chunked prefill (one page per dispatch) is equivalent to one-shot
    prefill (chunk == bucket);
  - int8 page payloads are gated by a token-level tolerance (they are
    lossy by construction — never claimed exact) at ~4x pages per HBM
    byte;
  - the decode step's per-step collectives under a model-only TP mesh
    match `decode_step_comm(..., paged=True)` EXACTLY with zero
    involuntary-remat warnings — the paged gather/write-back adds NO
    comm (the round-10/12 audit discipline extended to paging);
  - ServeConfig/engine reject bad page layouts with NAMED errors at
    construction (page size vs buckets, int8 vs the 256-element quant
    block, paged vs a data-sharded mesh), never opaque XLA shape errors;
  - the page allocator's registry can never match stale content after a
    page is reclaimed and re-issued (parent-chain purge);
  - `checkpoint.restore_params` restores the params subtree only —
    equal values to the full restore, opt_state bytes skipped (sharded),
    named errors for non-TrainState checkpoints and flag mismatches.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpukit import checkpoint as ck
from tpukit.data import WordTokenizer, synthetic_stories
from tpukit.model import GPTConfig, init_params
from tpukit.sampling import _decode_loop_cached
from tpukit.serve import (
    Request,
    ServeConfig,
    ServeEngine,
    decode_step,
    decode_step_comm,
    synthetic_request_stream,
)
from tpukit.serve import paged as paged_lib

MAX_NEW = 10


@pytest.fixture(scope="module")
def tok():
    return WordTokenizer(synthetic_stories(64))


@pytest.fixture(scope="module")
def cfg(tok):
    return GPTConfig(
        dim=32, head_dim=8, heads=4, num_layers=2, vocab_size=tok.vocab_size,
        max_position_embeddings=96, compute_dtype=jnp.float32,
    )


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(jax.random.PRNGKey(1), cfg)


def _serial(params, cfg, ids, max_new=MAX_NEW, eos_id=None, temperature=0.0,
            top_k=0, seed=0):
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
# Parity: paged engine == ring engine == serial cached decode, including a
# pool tight enough to recycle pages mid-stream.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "temperature,top_k,stream_seed",
    [(0.0, 0, 3), (0.9, 5, 11)],
    ids=["greedy", "sampled_topk"],
)
def test_paged_engine_parity_tight_pool(tok, cfg, params, temperature, top_k,
                                        stream_seed):
    """8 requests through 3 slots and a pool barely larger than one
    worst-case request set: forces mid-decode eviction, slot reuse AND
    page recycling (freed/retained pages re-issued with old garbage in
    them) while other slots are mid-sequence. Every completion must still
    be token-for-token the serial cached decode of its own prompt, and
    the ring engine must agree per request."""
    serve_kw = dict(slots=3, buckets=(8, 16), max_new_tokens=MAX_NEW,
                    temperature=temperature, top_k=top_k, window_steps=8)
    reqs = synthetic_request_stream(
        tok, 8, seed=stream_seed, max_new_tokens=MAX_NEW, buckets=(8, 16),
        qps=50.0 if temperature else 0.0,
    )
    ring = ServeEngine(params, cfg, ServeConfig(**serve_kw),
                       eos_id=int(tok.eos_token_id))
    ring_out = {c.rid: c for c in ring.run(list(reqs), max_wall_s=300)}
    # pages: width 26 -> ceil(26/4)=7 pages/slot; 11 usable pages < 3 slots'
    # worst case (21) -> admission control + recycling both exercised
    eng = ServeEngine(
        params, cfg,
        ServeConfig(**serve_kw, page_size=4, num_pages=12),
        eos_id=int(tok.eos_token_id),
    )
    comps = {c.rid: c for c in eng.run(list(reqs), max_wall_s=300)}
    assert comps.keys() == ring_out.keys() == {r.rid for r in reqs}
    assert not eng._lanes and len(eng._free) == 3
    assert eng.allocator.live_pages == 0  # every reference released
    for rid, c in comps.items():
        want = _serial(params, cfg, c.ids[: c.prompt_len], MAX_NEW,
                       tok.eos_token_id, temperature, top_k,
                       seed=stream_seed + rid)
        np.testing.assert_array_equal(c.ids, want, err_msg=f"rid {rid}")
        np.testing.assert_array_equal(c.ids, ring_out[rid].ids,
                                      err_msg=f"rid {rid} vs ring")


def test_paged_bf16_kv_parity_at_bf16_compute(tok, cfg, params):
    """bf16 pages at bf16 compute store exactly what the ring stores
    (the storage dtype == compute dtype rule): token-for-token parity
    with the serial cached decode at the same compute dtype."""
    bcfg = cfg.replace(compute_dtype=jnp.bfloat16)
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=6,
                        window_steps=8, page_size=4, kv_dtype="bf16")
    reqs = synthetic_request_stream(tok, 4, seed=6, max_new_tokens=6,
                                    buckets=(8, 16))
    eng = ServeEngine(params, bcfg, serve, eos_id=int(tok.eos_token_id))
    for c in eng.run(list(reqs), max_wall_s=300):
        want = _serial(params, bcfg, c.ids[: c.prompt_len], 6,
                       tok.eos_token_id)
        np.testing.assert_array_equal(c.ids, want, err_msg=f"rid {c.rid}")


def test_chunked_prefill_equals_one_shot(tok, cfg, params):
    """Chunked prefill (one page per dispatch) and one-shot prefill
    (chunk == bucket) must produce identical tokens — causal attention
    makes a chunk's K/V independent of how later positions arrive."""
    reqs = synthetic_request_stream(tok, 6, seed=7, max_new_tokens=MAX_NEW,
                                    buckets=(16,))
    outs = []
    for chunk in (4, 16):
        serve = ServeConfig(slots=2, buckets=(16,), max_new_tokens=MAX_NEW,
                            window_steps=8, page_size=4, prefill_chunk=chunk)
        eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id))
        outs.append({c.rid: list(map(int, c.ids))
                     for c in eng.run(list(reqs), max_wall_s=300)})
    assert outs[0] == outs[1]


def test_paged_completion_carries_prompt_on_prefix_hit(tok, cfg, params):
    """A prefix-hit admission skips its shared chunks, so the token buffer
    never holds the shared prompt segment — the completion must still
    carry the FULL prompt (regression: completions returned zeros for the
    shared prefix). Two runs on one engine: the registry (and the
    retained pages) survive between runs, so the second admission is a
    guaranteed hit."""
    ids = tuple(tok(["One day, the big cat sat"], truncation=True,
                    max_length=8)["input_ids"][0])
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=4,
                        window_steps=8, page_size=4)
    eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id))
    comps = {}
    for rid in (0, 1):
        for c in eng.run([Request(rid=rid, ids=ids, max_new_tokens=4)],
                         max_wall_s=300):
            comps[c.rid] = c
    assert eng.allocator.stats.prefix_hits >= 1
    assert comps[1].prefix_pages > 0
    for c in comps.values():
        np.testing.assert_array_equal(c.ids[: c.prompt_len], ids)
        want = _serial(params, cfg, ids, 4, tok.eos_token_id)
        np.testing.assert_array_equal(c.ids, want)


# ---------------------------------------------------------------------------
# Shared-prefix reuse: hits skip prefill; a writer's eviction never
# invalidates its readers (refcounts); retained pages serve later arrivals.
# ---------------------------------------------------------------------------


def test_prefix_reader_survives_writer_eviction(tok, cfg, params):
    """Writer A prefills + registers prompt X's pages, completes, and
    evicts — its pages retire into the retained LRU, NOT the free list.
    Readers B and C then admit the same prompt as prefix hits sharing
    those pages (refcount 2); B finishes first and releases while C is
    still mid-decode — the refcount must keep the shared pages valid for
    C, whose completion stays serial-exact."""
    ids = tuple(tok(["The big brown cat sat on a mat and then"],
                    truncation=True, max_length=16)["input_ids"][0])
    assert len(ids) == 16
    serve = ServeConfig(slots=3, buckets=(16,), max_new_tokens=MAX_NEW,
                        window_steps=8, page_size=4)
    eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id))
    budgets = {0: 2, 1: 1, 2: MAX_NEW}
    # run 1: the writer alone (registers pages 0..2 of the prompt);
    # run 2: B (evicts after 1 token, releasing its shared refs early)
    # and C (decodes on) share the writer's retained pages
    comps = {c.rid: c for c in eng.run(
        [Request(rid=0, ids=ids, max_new_tokens=budgets[0])], max_wall_s=300)}
    assert eng.allocator.registered_pages() >= 3  # writer evicted; retained
    assert eng.allocator.live_pages == 0
    for c in eng.run(
        [Request(rid=1, ids=ids, max_new_tokens=budgets[1]),
         Request(rid=2, ids=ids, max_new_tokens=budgets[2], seed=2)],
        max_wall_s=300,
    ):
        comps[c.rid] = c
    assert len(comps) == 3
    # (plen-1)//P = 3 shareable pages; both readers hit all of them
    assert eng.allocator.stats.prefix_hits >= 2
    assert comps[1].prefix_pages == 3 and comps[2].prefix_pages == 3
    for rid, c in comps.items():
        want = _serial(params, cfg, ids, budgets[rid], tok.eos_token_id)
        np.testing.assert_array_equal(c.ids, want, err_msg=f"rid {rid}")
    # all references released again; prefix pages stay RETAINED for the
    # next arrival instead of returning to the free list
    assert eng.allocator.live_pages == 0
    assert eng.allocator.registered_pages() >= 3
    assert eng.allocator.free_pages < eng.num_pages - 1
    # prefix hits deleted admission work: hit admit latency < cold
    s = eng.last_summary
    assert s["prefix_hits"] >= 1
    assert s["admit_latency_hit_s"] < s["admit_latency_cold_s"]


def test_page_allocator_refcounts_and_stale_parent_purge():
    """Allocator unit contracts: refcounted sharing, retained-LRU reuse,
    and — the correctness-critical one — a reclaimed page's registry
    subtree is purged with it, so a re-issued page id can NEVER be
    matched under its old content (stale-parent hazard)."""
    al = paged_lib.PageAllocator(num_pages=6, page_size=2)  # pages 1..5
    ids = (7, 8, 9, 10)
    pages = al.alloc(2)
    assert pages == [1, 2] and al.live_pages == 2
    al.register(ids, pages)
    assert al.lookup_prefix(ids, 2) == [1, 2]
    assert al.lookup_prefix((7, 8, 99, 100), 2) == [1]  # chain is content-exact
    # a reader shares, the writer releases: pages stay live
    al.claim(pages)
    al.release(pages)
    assert al.refcount[1] == al.refcount[2] == 1
    # last release retires REGISTERED pages into the retained LRU
    al.release(pages)
    assert al.live_pages == 0 and al.free_pages == 3
    assert al.lookup_prefix(ids, 2) == [1, 2]  # still matchable (retained)
    al.claim([1, 2])  # a hit rescues them
    assert al.refcount[1] == 1
    al.release([1, 2])
    # pool pressure reclaims the retained chain root -> whole subtree
    # purged and freed; the old registration must be gone even though the
    # page ids return to circulation
    got = al.alloc(5)
    assert sorted(got) == [1, 2, 3, 4, 5]
    assert al.lookup_prefix(ids, 2) == []
    assert al.registered_pages() == 0
    # a LIVE child whose parent is purged keeps its page but loses its
    # registration (it can only be reached through the parent)
    al2 = paged_lib.PageAllocator(num_pages=6, page_size=2)
    p = al2.alloc(2)
    al2.register(ids, p)
    al2.claim([p[1]])          # child read by someone
    al2.release([p[0], p[1]])  # writer gone: parent retained, child live
    assert al2.alloc(4) is not None  # reclaims the retained parent
    assert al2.lookup_prefix(ids, 2) == []
    al2.release([p[1]])        # last reader: unregistered -> plain free
    assert al2.refcount[p[1]] == 0
    with pytest.raises(AssertionError, match="negative"):
        al2.release([p[1]])
    # a DOOMED allocation must not purge the retained registry on its
    # way to failing: the caller retries the same admission later, and
    # every prefix hit it would have had would be gone
    al3 = paged_lib.PageAllocator(num_pages=4, page_size=2)  # pages 1..3
    p = al3.alloc(2)
    al3.register(ids, p)
    al3.release(p)  # both retained
    assert al3.alloc(4) is None  # free(1) + retained(2) < 4: infeasible
    assert al3.lookup_prefix(ids, 2) == p  # registry untouched
    assert al3.stats.reclaimed == 0


# ---------------------------------------------------------------------------
# int8 pages: tolerance-gated (lossy by construction), ~4x HBM win.
# ---------------------------------------------------------------------------


def test_int8_kv_token_tolerance_gate(tok, params):
    """The token-level tolerance gate for quantized pages (mirroring the
    round-12 loss-trajectory gate): int8 page storage must agree with the
    exact engine on >= 90% of tokens over the stream, at ~1/4 the page
    bytes. Bit parity is impossible by construction — the gate pins the
    quantizer's quality, not exactness."""
    cfg = GPTConfig(
        dim=32, head_dim=8, heads=4, num_layers=2, vocab_size=tok.vocab_size,
        max_position_embeddings=96, compute_dtype=jnp.float32,
    )
    # head_dim 8 -> page 32 makes each (page, head) row exactly one
    # 256-element quant block
    reqs = synthetic_request_stream(tok, 6, seed=4, max_new_tokens=MAX_NEW,
                                    buckets=(32,))
    outs = {}
    for dt in ("f32", "int8"):
        serve = ServeConfig(slots=2, buckets=(32,), max_new_tokens=MAX_NEW,
                            window_steps=8, page_size=32, kv_dtype=dt)
        eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id))
        outs[dt] = {c.rid: np.asarray(c.ids)
                    for c in eng.run(list(reqs), max_wall_s=300)}
        if dt == "int8":
            bytes_int8 = eng.kv_bytes
        else:
            bytes_f32 = eng.kv_bytes
    assert outs["f32"].keys() == outs["int8"].keys()
    agree = []
    for rid in outs["f32"]:
        a, b = outs["f32"][rid], outs["int8"][rid]
        m = min(len(a), len(b))
        agree.append(float(np.mean(a[:m] == b[:m])))
    assert np.mean(agree) >= 0.9, agree
    # packed int8 pages cost ~(1 + 4/256)/4 of f32 pages
    assert bytes_int8 < bytes_f32 / 3.5


def test_pool_bytes_closed_form(cfg):
    """`pool_bytes` must equal the actual device pytree footprint."""
    for dt in ("f32", "bf16", "int8"):
        page = 32 if dt == "int8" else 4
        tree = paged_lib.init_paged_cache(cfg, 7, page, 3, 2, dt)
        measured = sum(
            int(np.prod(v.shape)) * v.dtype.itemsize
            for k, v in tree.items() if k != "bt"
        )
        assert paged_lib.pool_bytes(cfg, 7, page, dt) == measured, dt


# ---------------------------------------------------------------------------
# Validation: named errors at construction, never XLA shape errors.
# ---------------------------------------------------------------------------


def test_serve_config_paged_validation(tok, cfg, params):
    with pytest.raises(ValueError, match="divide every bucket"):
        ServeConfig(buckets=(8, 12), page_size=8)
    with pytest.raises(ValueError, match="requires the paged cache"):
        ServeConfig(kv_dtype="int8")
    with pytest.raises(ValueError, match="requires the paged cache"):
        ServeConfig(num_pages=16)
    with pytest.raises(ValueError, match="multiple of.*page_size"):
        ServeConfig(buckets=(16,), page_size=4, prefill_chunk=6)
    with pytest.raises(ValueError, match="divide every bucket"):
        ServeConfig(buckets=(16, 32), page_size=4, prefill_chunk=12)
    with pytest.raises(ValueError, match="one of"):
        ServeConfig(buckets=(16,), page_size=4, kv_dtype="fp8")
    with pytest.raises(ValueError, match="cannot hold even one"):
        ServeConfig(buckets=(16,), max_new_tokens=16, page_size=4, num_pages=8)
    # int8 quant-block mismatch: page 4 x head_dim 8 = 32 elements/head,
    # not a 256 multiple — NAMED at engine construction
    with pytest.raises(ValueError, match="256-element"):
        ServeEngine(params, cfg,
                    ServeConfig(buckets=(16,), page_size=4, kv_dtype="int8"),
                    eos_id=1)
    # the same check is importable stand-alone
    with pytest.raises(ValueError, match="256-element"):
        paged_lib.validate_kv_layout(cfg, 4, "int8")
    paged_lib.validate_kv_layout(cfg, 32, "int8")  # 32*8=256: fine


def test_paged_rejects_data_sharded_mesh(cfg, params):
    from tpukit.mesh import create_mesh

    mesh = create_mesh({"data": 2, "model": 4})
    with pytest.raises(ValueError, match="model-only grid"):
        ServeEngine(params, cfg, ServeConfig(slots=4, buckets=(8,), page_size=4),
                    eos_id=1, mesh=mesh)
    with pytest.raises(ValueError, match="model-only grid"):
        decode_step_comm(cfg, mesh, 4, paged=True)


# ---------------------------------------------------------------------------
# Compile budget: chunked prefill compiles per admit size only (one chunk
# width), plus one decode program.
# ---------------------------------------------------------------------------


def test_paged_compile_budget(tok, cfg, params):
    from tpukit.serve import prefill_chunk_paged

    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=6,
                        window_steps=8, page_size=4)
    assert serve.compile_budget == 3  # 1 decode + admit sizes {1, 2}
    chunk0 = prefill_chunk_paged._cache_size()
    decode0 = decode_step._cache_size()
    reqs = synthetic_request_stream(tok, 10, seed=2, max_new_tokens=6,
                                    buckets=(8, 16))
    eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id))
    assert len(eng.run(list(reqs), max_wall_s=300)) == 10
    added = (prefill_chunk_paged._cache_size() - chunk0
             + decode_step._cache_size() - decode0)
    assert added <= serve.compile_budget
    # a second engine over the same shape adds ZERO compiles
    c1, d1 = prefill_chunk_paged._cache_size(), decode_step._cache_size()
    ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id)).run(
        synthetic_request_stream(tok, 4, seed=9, max_new_tokens=6,
                                 buckets=(8, 16)), max_wall_s=300)
    assert prefill_chunk_paged._cache_size() == c1
    assert decode_step._cache_size() == d1


# ---------------------------------------------------------------------------
# The pool is updated where it lies: the stacked `[L, NP, H, P, D]` pool goes
# through the layer loop whole and is read and written by index (ISSUE 26).
# ---------------------------------------------------------------------------


def _stack_cfg(kv_dtype):
    """Two layers, four heads of 8; pages of 32 positions so that an int8
    (page, head) row is exactly one 256-element quant block. bf16 pages at
    bf16 compute (the pair that is exact)."""
    return GPTConfig(
        dim=32, head_dim=8, heads=4, num_layers=2, vocab_size=97,
        max_position_embeddings=96,
        compute_dtype=jnp.bfloat16 if kv_dtype == "bf16" else jnp.float32,
    )


_STACK_GEOMETRY = dict(slots=3, page=32, per_slot=2, num_pages=9)  # pages 7, 8 of 1..8: no table names them


def _stack_state(cfg, kv_dtype):
    g = _STACK_GEOMETRY
    cache = paged_lib.init_paged_cache(cfg, g["num_pages"], g["page"], g["per_slot"], g["slots"], kv_dtype)
    # lanes 0 and 1 serve; lane 2 holds pages and stays masked (inactive)
    cache["bt"] = jnp.asarray([[5, 2], [1, 6], [4, 3]], jnp.int32)
    n, width = g["slots"], g["page"] * g["per_slot"]
    return (jnp.zeros((n, width), jnp.int32), cache, jnp.zeros((n,), jnp.int32),
            jnp.zeros((n,), bool), jnp.zeros((n,), jnp.int32), jnp.zeros((n, 2), jnp.uint32))


def _stack_prefill_args(cfg):
    """One chunk (a whole page) for lanes 0 and 1: a 32-token prompt and a
    20-token prompt padded to the chunk."""
    page = _STACK_GEOMETRY["page"]
    rows = np.random.RandomState(7).randint(1, cfg.vocab_size, (2, page)).astype(np.int32)
    rows[1, 20:] = 0
    lens = jnp.asarray([page, 20], jnp.int32)
    return (jnp.asarray([0, 1], jnp.int32), jnp.asarray(rows), jnp.zeros((2,), jnp.int32),
            jnp.ones((2,), bool), lens, lens + 8, jnp.zeros((2, 2), jnp.uint32))


_POOL_MOVES = ("slice", "dynamic_slice", "concatenate", "dynamic_update_slice")


def _pool_shaped_results(text, cache):
    """(op, shape) of every slice / dynamic_slice / concatenate /
    dynamic_update_slice in a lowered module whose result has the shape of a
    stacked pool (or int8 scale sidecar) or of one layer of it; and the
    number of scatters whose operand and result are a whole stack."""
    import re

    stacks = {"x".join(map(str, v.shape)) for k, v in cache.items() if k != "bt"}
    layer = {"x".join(("1", *sh.split("x")[1:])) for sh in stacks} | {sh.split("x", 1)[1] for sh in stacks}
    moves = [(m.group(1), m.group(2)) for m in re.finditer(
        r"stablehlo\.(\w+)\b[^\n]*->\s*tensor<([0-9x]+)x[a-z]\w*>", text)
        if m.group(1) in _POOL_MOVES and m.group(2) in stacks | layer]
    scatters = [m.group(1) for m in re.finditer(
        r"\}\) : \(tensor<([0-9x]+)x\w+>, tensor<[0-9x]+xi32>, tensor<[^>]+>\) -> tensor<([0-9x]+)x\w+>", text)
        if m.group(1) == m.group(2) and m.group(1) in stacks]
    return moves, len(scatters)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("program", ["decode_1", "decode_4", "prefill_chunk"])
def test_paged_programs_never_slice_or_restack_the_pool(program, kv_dtype, fresh_compiles):
    """The count that says the mechanism engages (it engages on every call
    or on none): in the module text of the decode step (one tick, and a
    quantum of four) and of a chunked prefill, no slice, dynamic_slice,
    concatenate or dynamic_update_slice has a result of the pool's shape
    `[L, NP, H, P, D]` or of one layer's `[1, NP, H, P, D]` / `[NP, H, P, D]`
    (nor of the int8 scale sidecars'); each layer writes K and V with one
    scatter on the whole stack (int8: payload and scales)."""
    from tpukit.serve.decode import prefill_chunk_paged

    cfg = _stack_cfg(kv_dtype)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    state = _stack_state(cfg, kv_dtype)
    if program == "prefill_chunk":
        lowered = prefill_chunk_paged.lower(params, cfg, *state, *_stack_prefill_args(cfg))
    else:
        lowered = decode_step.lower(params, cfg, *state, 0, 0.0, 0, None, steps=int(program[-1]))
    moves, stack_scatters = _pool_shaped_results(lowered.as_text(), state[1])
    assert moves == []
    # a quantum's loop body is in the text once, whatever `steps`
    assert stack_scatters == cfg.num_layers * (4 if kv_dtype == "int8" else 2)


def test_pool_shaped_results_sees_a_slice_and_a_restack():
    """The reader of the count above on a module that does what the parent's
    `forward_cached` did: a layer sliced out of the stack, the stack rebuilt."""
    pool = jnp.zeros((2, 9, 4, 32, 8), jnp.bfloat16)

    def old_shape(pool, val):
        layers = [pool[i].at[3, :, 0, :].set(val) for i in range(pool.shape[0])]
        return jnp.stack(layers)

    text = jax.jit(old_shape).lower(pool, jnp.ones((4, 8), jnp.bfloat16)).as_text()
    moves, stack_scatters = _pool_shaped_results(text, {"k": pool})
    assert stack_scatters == 0
    assert {op for op, _ in moves} >= {"slice", "concatenate"}
    assert {shape for _, shape in moves} == {"2x9x4x32x8", "1x9x4x32x8"}


@pytest.mark.parametrize("kv_dtype", ["f32", "bf16", "int8"])
def test_pool_holds_the_ring_paths_kv_bit_for_bit(kv_dtype):
    """Bit parity of the POOL, not only of the tokens: after one prefill
    chunk and three decode ticks (one quantum) with lane 2 masked, every
    layer's pool holds at each written logical position exactly what the
    ring path's K/V hold there; pages no block table names, lane 2's pages
    and the unwritten tail of lane 0's second page are still zero; the null
    page is the only page the masked lane wrote. bf16 pages at bf16 compute
    (the benchmark's pair) are exact; f32 pages agree to float32 rounding
    (two programs, two fusions; their tokens are equal); int8 pages are
    lossy by construction and are held to the quantizer's own step."""
    from tpukit.model import gpt
    from tpukit.ops import quant_comm
    from tpukit.serve.decode import prefill_chunk_paged

    cfg = _stack_cfg(kv_dtype)
    params = init_params(jax.random.PRNGKey(1), cfg)
    g = _STACK_GEOMETRY
    page, width, layers = g["page"], g["page"] * g["per_slot"], cfg.num_layers
    admit = _stack_prefill_args(cfg)
    buf, cache, cursors, active, limits, keys = prefill_chunk_paged(
        params, cfg, *_stack_state(cfg, kv_dtype), *admit)
    assert list(np.asarray(active)) == [True, True, False]

    # the ring path on the same rows, at the same view shapes: the chunk
    # through forward_cached's vector-cursor ring branch, then the same quantum
    rows, starts = admit[1], admit[2]
    pos = starts[:, None] + jnp.arange(page, dtype=jnp.int32)[None, :]
    _, ring2 = jax.jit(gpt.forward_cached, static_argnums=1)(
        params, cfg, rows, pos, gpt.init_kv_cache(cfg, 2, width), starts)
    ring = {k: jnp.zeros((layers, g["slots"], cfg.heads, width, cfg.head_dim), v.dtype).at[:, :2].set(v)
            for k, v in ring2.items()}
    ring_out = decode_step(params, cfg, buf, ring, cursors, active, limits, keys, 0, 0.0, 0, None, steps=3)
    buf, cache, cursors, active = decode_step(params, cfg, buf, cache, cursors, active, limits, keys,
                                              0, 0.0, 0, None, steps=3)
    ring = ring_out[1]
    if kv_dtype != "int8":  # the token streams are the same streams
        assert np.array_equal(np.asarray(buf), np.asarray(ring_out[0]))
        assert list(np.asarray(cursors)) == list(np.asarray(ring_out[2])) == [page + 3, 23, 0]

    bt = np.asarray(cache["bt"])
    written = {0: page + 2, 1: page}  # lane -> positions [0, n) written: the chunk's page, then cursor-1 of each tick
    for name, scales in (("k", "ks"), ("v", "vs")):
        pool = np.asarray(cache[name].astype(jnp.float32))
        if kv_dtype == "int8":
            sh = cache[name].shape
            pool = np.asarray(quant_comm.dequantize_blocks(
                cache[name].reshape(*sh[:3], -1), cache[scales]).reshape(sh))
        want = np.asarray(ring[name].astype(jnp.float32))
        for lane, n in written.items():
            for q in range(n):
                got = pool[:, bt[lane, q // page], :, q % page, :]  # [L, H, D]
                ref = want[:, lane, :, q, :]
                if kv_dtype == "int8":
                    # a position's error is a few quantizer steps of its (page, head) block: one rounding
                    # per requantisation of the page, and upstream layers attended over lossy K/V
                    step = np.abs(want[:, lane]).max(axis=(-1, -2))[:, :, None] / 127.0
                    assert np.all(np.abs(got - ref) <= 4 * step), (name, lane, q)
                elif kv_dtype == "f32":
                    # the ring and the paged program fuse differently on XLA:CPU: float32 rounding, the last bits
                    np.testing.assert_allclose(got, ref, rtol=2e-6, atol=2e-7, err_msg=str((name, lane, q)))
                else:
                    assert np.array_equal(got, ref), (name, lane, q)
        raw = np.asarray(cache[name].astype(jnp.float32))
        assert not raw[:, [7, 8]].any()  # pages no table names
        assert not raw[:, bt[2]].any()  # the masked lane's own pages
        assert not raw[:, bt[1, 1]].any()  # lane 1 never reached its second page
        assert not raw[:, bt[0, 1], :, 2:, :].any()  # lane 0's second page past positions 32, 33
        assert raw[:, 0].any(axis=(1, 2, 3)).all()  # every layer's null page took the masked lane's writes
        if kv_dtype == "int8":
            sc = np.asarray(cache[scales])
            assert not sc[:, [7, 8]].any() and not sc[:, bt[2]].any() and not sc[:, bt[1, 1]].any()


# ---------------------------------------------------------------------------
# Sharded serving: the paged gather must add ZERO collectives — compiled
# HLO matches decode_step_comm(paged=True) exactly, no involuntary remat.
# ---------------------------------------------------------------------------


def _tp_paged_state(cfg, mesh, slots, kv_dtype="f32", page=8, mp=3):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from tpukit.shardings import TensorParallel

    strat = TensorParallel(mesh)
    params = init_params(jax.random.PRNGKey(0), cfg)
    psh = strat.state_sharding(jax.eval_shape(lambda: params))
    params = jax.tree.map(jax.device_put, params, psh)
    sh = lambda spec: NamedSharding(mesh, spec)
    num_pages = slots * mp + 1
    tree = paged_lib.init_paged_cache(cfg, num_pages, page, mp, slots, kv_dtype)
    specs = {"k": P(None, None, "model", None, None),
             "v": P(None, None, "model", None, None),
             "ks": P(None, None, "model", None),
             "vs": P(None, None, "model", None), "bt": P()}
    cache = {k: jax.device_put(np.asarray(v), sh(specs[k]))
             for k, v in tree.items()}
    bt = np.arange(1, slots * mp + 1, dtype=np.int32).reshape(slots, mp)
    cache["bt"] = jax.device_put(bt, sh(P()))
    w = mp * page
    buf = jax.device_put(np.zeros((slots, w), np.int32), sh(P(None, None)))
    cursors = jax.device_put(np.full((slots,), 5, np.int32), sh(P(None)))
    active = jax.device_put(np.ones((slots,), bool), sh(P(None)))
    limits = jax.device_put(np.full((slots,), 12, np.int32), sh(P(None)))
    keys = jax.device_put(np.zeros((slots, 2), np.uint32), sh(P(None, None)))
    return params, buf, cache, cursors, active, limits, keys


@pytest.mark.parametrize(
    "kv_dtype,temperature,top_k",
    [("f32", 0.0, 0), ("f32", 0.9, 5), ("int8", 0.0, 0)],
    ids=["f32_greedy", "f32_topk", "int8_greedy"],
)
def test_tp_paged_decode_step_hlo_comm_audit(kv_dtype, temperature, top_k):
    """Under the model-only serving grid the paged decode step must move
    EXACTLY the ring path's closed-form collectives — the Megatron pair
    per layer + embedding psum + the one logits all-gather — with the
    page gather, the pool write-back scatter, and (int8) the
    quantize/dequantize all COMM-FREE, and zero GSPMD involuntary-remat
    fallbacks. f32 compute so byte counts are exact on the CPU wire."""
    from tpukit.mesh import create_mesh
    from tpukit.obs.xla import capture_compiler_stderr, collective_bytes

    head_dim = 32 if kv_dtype == "int8" else 8  # int8: page*head_dim == 256
    cfg = GPTConfig(
        dim=32, head_dim=head_dim, heads=4, num_layers=2, vocab_size=160,
        max_position_embeddings=64, compute_dtype=jnp.float32,
    )
    mesh = create_mesh({"model": 4})
    slots = 4
    state = _tp_paged_state(cfg, mesh, slots, kv_dtype)
    params, buf, cache, cursors, active, limits, keys = state
    # check=True raises on any involuntary-remat warning at capture exit
    with capture_compiler_stderr(check=True):
        compiled = decode_step.lower(
            params, cfg, buf, cache, cursors, active, limits, keys,
            1, temperature, top_k, mesh,
        ).compile()
    measured = collective_bytes(compiled.as_text())
    expected = decode_step_comm(cfg, mesh, slots, top_k=top_k, paged=True)
    assert measured == expected, (measured, expected)


def test_tp_paged_engine_decode_parity(tok, cfg, params):
    """Value check on top of the byte audit: the paged engine under the
    model-only TP mesh decodes the same tokens as the meshless paged
    engine (which is itself serial-exact)."""
    from tpukit.mesh import create_mesh
    from tpukit.shardings import TensorParallel

    mesh = create_mesh({"model": 4})
    strat = TensorParallel(mesh)
    tp_params = jax.tree.map(
        jax.device_put, params,
        strat.state_sharding(jax.eval_shape(lambda: params)),
    )
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=6,
                        window_steps=8, page_size=4)
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


# ---------------------------------------------------------------------------
# Telemetry: paged fields land in the JSONL windows/summary and report.py
# renders them.
# ---------------------------------------------------------------------------


def test_paged_jsonl_windows_and_report(tok, cfg, params, tmp_path):
    from tpukit.obs import StepLogger

    log = tmp_path / "serve.jsonl"
    logger = StepLogger(str(log))
    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=8,
                        window_steps=4, page_size=4)
    reqs = synthetic_request_stream(tok, 6, seed=8, max_new_tokens=8,
                                    buckets=(8, 16), shared_prefix=8)
    eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id),
                      logger=logger)
    eng.run(reqs, max_wall_s=300)
    logger.close()
    recs = [json.loads(l) for l in log.read_text().splitlines()]
    windows = [r for r in recs if r["kind"] == "serve"]
    (summary,) = [r for r in recs if r["kind"] == "serve_summary"]
    assert windows
    for w in windows:
        assert 0.0 <= w["page_occupancy"] <= 1.0
        assert w["prefix_hit_rate"] is None or 0.0 <= w["prefix_hit_rate"] <= 1.0
    assert summary["page_size"] == 4 and summary["kv_dtype"] == "f32"
    assert summary["prefix_hits"] > 0  # the shared system prompt hit
    assert summary["prefix_pages_reused"] > 0
    assert summary["pages_per_request"] > 0
    assert summary["kv_bytes"] == eng.kv_bytes
    assert summary["max_live_slots"] <= serve.slots

    import importlib

    report = importlib.import_module("tools.report")
    text = report.summarize(recs)
    assert "paged KV:" in text and "prefix hits" in text


# ---------------------------------------------------------------------------
# Satellite: params-only checkpoint restore (serve cold start).
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def train_state():
    from tpukit.train import create_train_state, make_optimizer

    cfg = GPTConfig(dim=16, head_dim=4, heads=4, num_layers=2, vocab_size=64,
                    max_position_embeddings=32, compute_dtype=jnp.float32)
    return create_train_state(jax.random.PRNGKey(0), cfg, make_optimizer(1e-4))


@pytest.mark.parametrize("fmt", ["consolidated", "sharded"])
def test_restore_params_matches_full_restore(train_state, tmp_path, fmt):
    state = train_state
    path = ck.save_auto(state, tmp_path, "checkpoint-step7", format=fmt)
    template = jax.eval_shape(lambda: state).params
    params, info = ck.restore_params(path, template)
    got = jax.tree_util.tree_leaves(params)
    want = jax.tree_util.tree_leaves(state.params)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert info["format"] == fmt
    assert info["leaves_read"] == len(want)
    assert info["leaves_skipped"] > 0  # opt_state + step never decoded
    if fmt == "sharded":
        # the 3x win: the Adam moments' blocks are never read
        assert info["bytes_skipped"] > info["bytes_read"]


def test_restore_params_named_errors(train_state, tmp_path):
    from flax import serialization

    state = train_state
    sharded = ck.save_auto(state, tmp_path, "checkpoint-step8", format="sharded")
    # template from different model flags: leaf-count mismatch, named
    cfg_big = GPTConfig(dim=16, head_dim=4, heads=4, num_layers=2,
                        vocab_size=64, max_position_embeddings=32,
                        compute_dtype=jnp.float32, num_experts=2)
    bad_template = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg_big)
    )
    with pytest.raises(ValueError, match="model flags"):
        ck.restore_params(sharded, bad_template)
    # a non-TrainState consolidated blob: named, not a KeyError
    raw = tmp_path / "raw.msgpack"
    raw.write_bytes(serialization.to_bytes(state.params))
    with pytest.raises(ValueError, match="no 'params' subtree"):
        ck.restore_params(raw, jax.eval_shape(lambda: state.params))


def test_restore_params_places_at_shardings(train_state, tmp_path):
    """With a sharding tree, leaves land directly at the target shardings
    — the serving cold-start path (any saved world, no reshard pass)."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from tpukit.mesh import create_mesh

    state = train_state
    path = ck.save_auto(state, tmp_path, "checkpoint-step9", format="sharded")
    mesh = create_mesh({"model": 4})
    template = jax.eval_shape(lambda: state).params
    shardings = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), template
    )
    params, _ = ck.restore_params(path, template, shardings)
    for leaf, want in zip(jax.tree_util.tree_leaves(params),
                          jax.tree_util.tree_leaves(state.params)):
        assert leaf.sharding.mesh.shape == mesh.shape
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want))
