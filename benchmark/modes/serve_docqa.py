"""Serving mode for document question answering on a latent model with ONE
page kind (tpukit/model/latent.py without window layers), where the prefix
registry serves each prompt's document: the cell's configuration, built from
its published keys, behind the same `ServeEngine.run` as the other serving
modes. What is family-blind is loaded by file name and not copied:
`modes/serve.py`'s observed engine, epoch recorder, warm-up and
`WindowClosed`; `modes/serve_latent.py`'s check programs, `judge` and
`check_tokens`; `modes/serve_latent_cold.py`'s `PaddedReference`;
`modes/serve_hybrid.py`'s `_first_tokens`; the
documents and questions are `benchmark/traffic_docqa.py`'s. What is written
here is about the documents:

- **Set-up prefills each document once, cold, through the engine** (a request
  of its own: the document and a few tokens of question), and the registry
  holds its pages from then on. Every request of the window is a document
  and a question; `correct` also requires that every admission of the
  traffic hit the registry for its whole document: the prompt tokens the
  engine's registry served (`prefix_tokens`) are the sum of the admitted
  requests' documents.
- **`CheckPath`**, the cached path at the engine's shapes and POOL (a page
  allocator of the engine's size, every slot's block-table row) with the
  logits handed out: documents as long as the traffic's longest prefilled
  cold in chunks in an admit batch of `setup_check.lanes` lanes and
  registered, then questions on them admitted into as many other lanes
  through `lookup_prefix` / `claim`, prefilling only their suffix, then
  ticks with every slot in the forward, past the last position any request
  of the traffic reaches. `correct` holds the checked lane's logits (the
  document's from the cold prefill, the question's and the ticks' from the
  hit) against the float32 reference's full forward, by `judge`'s
  `tolerance.logit_rms_rel`;
- **programs compile side by side**, beside the weights, each lowered from
  the shapes the engine's pool will have;
- the sampled completions: one request on each of the documents
  `check_documents` names, drawn by the seed, finished or still decoding at
  the window's close (as far as the last sync delivered it), its first
  `check_new_tokens` generated tokens served as the window served them (the
  document cold, the question through a hit) and held to the reference's
  argmax by the capped tie rule.

On a program whose latent family cannot build the configuration the mode
exits at once, non-zero, and says so. The window's info line carries the
registry's account: the share of admissions that hit, prompt tokens served
from cache, and whether they were the admitted requests' whole documents.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import time
from pathlib import Path

import numpy as np

from benchmark import common, traffic_docqa

ROOT = Path(__file__).resolve().parents[2]


class CheckPath:
    """The cached path as the engine composes it, over a pool of the
    engine's size behind its own page allocator (so a question is admitted
    through the registry as the engine admits it), with the logits handed out
    (`serve_latent._check_programs`). `shapes_only`: the programs without the
    cache (a compile tool hands them shapes)."""

    def __init__(self, cfg, eng: dict, shapes_only: bool = False):
        from tpukit.model import latent
        from tpukit.serve import paged

        self.slots, self.page, self.chunk_tokens = eng["slots"], eng["page_size"], eng["prefill_chunk"]
        self.per_slot, self.num_pages = -(-eng["max_len"] // self.page), eng["num_pages"]
        (self.kind,) = latent.page_kinds(cfg, self.page, eng["kv_dtype"])
        self._paged = paged
        self.cache = None if shapes_only else latent.init_paged_cache(
            cfg, {"bt": self.num_pages}, self.page, self.per_slot, self.slots, eng["kv_dtype"])
        self._chunk, self._tick = common.load_by_name("modes", "serve_latent", ROOT)._check_programs(cfg)

    def _prefill(self, params, lanes, seqs, start: int, prompt_len: int) -> np.ndarray:
        """`seqs[:, start:prompt_len]` in chunks, all lanes a dispatch; the
        first lane's logits of those positions."""
        import jax.numpy as jnp

        c, out = self.chunk_tokens, []
        valid = jnp.full((len(lanes),), prompt_len, jnp.int32)
        for s in range(start, prompt_len, c):
            n = min(c, prompt_len - s)
            rows = np.zeros((len(lanes), c), np.int32)
            rows[:, :n] = seqs[:, s:s + n]
            lg, self.cache, _ = self._chunk(params, self.cache, jnp.asarray(lanes), jnp.asarray(rows),
                                            jnp.full((len(lanes),), s, jnp.int32), valid)
            out.append(np.asarray(lg[:n]))
        return np.concatenate(out)

    def through(self, params, documents: list, seqs: list, prompt_len: int, keep_from: int = 0):
        """`documents` (A id arrays of one length, a document and a few
        tokens after it) prefilled cold into A lanes and registered as the
        engine registers a finished prefill; `seqs` (A id arrays of one
        length S, each starting with its document) admitted into A other
        lanes through the registry, the first `prompt_len` tokens in chunks
        from the first unshared one, the rest one tick each with every slot
        in the forward. Returns `(logits [S - keep_from, V] of the first
        lane, positions keep_from..S-1: those the registry served from the
        cold lanes' prefill, the rest from the hit; the tokens shared)`."""
        import jax.numpy as jnp

        docs, seqs = np.stack(documents), np.stack(seqs)
        a = len(seqs)
        step = self.slots // (2 * a)
        cold, hit = (np.arange(a) * 2 * step).astype(np.int32), (np.arange(a) * 2 * step + step).astype(np.int32)
        alloc = self._paged.PageAllocator(self.num_pages, self.page)
        table = np.zeros((self.slots, self.per_slot), np.int32)
        owned = []
        for lane, doc in zip(cold, docs):
            pages = alloc.alloc(self.kind.pages_for(len(doc), self.page))
            table[lane, :len(pages)] = pages
            owned.append(pages)
        self.cache["bt"] = jnp.asarray(table)
        doc_logits = self._prefill(params, cold, docs, 0, docs.shape[1])
        for doc, pages in zip(docs, owned):  # the engine's rule: the page of the last prompt token stays private
            alloc.register(tuple(doc.tolist()), pages[: (docs.shape[1] - 1) // self.page])
        shared_tokens = None
        for lane, seq in zip(hit, seqs):
            matched = alloc.lookup_prefix(tuple(seq[:prompt_len].tolist()), (prompt_len - 1) // self.page)
            s_tokens = (len(matched) * self.page // self.chunk_tokens) * self.chunk_tokens
            if shared_tokens not in (None, s_tokens):
                raise ValueError("the checked questions must share equal prefixes")
            shared_tokens = s_tokens
            shared = matched[: s_tokens // self.page]
            alloc.claim(shared)
            pages = shared + alloc.alloc(self.kind.pages_for(seqs.shape[1], self.page) - len(shared))
            table[lane] = 0
            table[lane, :len(pages)] = pages
        self.cache["bt"] = jnp.asarray(table)
        logits = [doc_logits[keep_from:shared_tokens], self._prefill(params, hit, seqs, shared_tokens, prompt_len)]
        tok, read, live = (np.zeros((self.slots,), dt) for dt in (np.int32, np.int32, bool))
        live[hit] = True
        for t in range(prompt_len, seqs.shape[1]):
            tok[hit], read[hit] = seqs[:, t], t
            lg, self.cache, _ = self._tick(params, self.cache, jnp.asarray(tok), jnp.asarray(read),
                                           jnp.asarray(live), int(hit[0]))
            logits.append(np.asarray(lg)[None])
        return np.concatenate(logits)[max(keep_from - shared_tokens, 0):], shared_tokens


def served_setup(ctx, cfg, params, path: CheckPath, seed: int) -> dict:
    """The served half of the set-up comparison: seeded documents, their
    set-up questions, and a question and continuation on each, through
    `path`; kept on the host for `judge`."""
    check = ctx["traffic"]["setup_check"]
    rng = np.random.default_rng([seed, 4])
    draw = lambda n: rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)  # noqa: E731
    docs = [draw(check["doc_tokens"]) for _ in range(check["lanes"])]
    setup_q = ctx["traffic"]["setup"]["question_tokens"]
    cold = [np.concatenate([d, draw(setup_q)]) for d in docs]
    seqs = [np.concatenate([d, draw(check["question_tokens"] + check["decode_steps"])]) for d in docs]
    prompt = check["doc_tokens"] + check["question_tokens"]
    logits, shared = path.through(params, cold, seqs, prompt)
    if shared != check["doc_tokens"]:
        raise SystemExit(f"the check's questions shared {shared} tokens of a {check['doc_tokens']}-token document")
    return {"ids": seqs[0], "logits": logits, "prompt_tokens": prompt}


def compile_side_by_side(cfg, serve_cfg, eos_id: int, check_lanes: int, pool) -> list:
    """Hand `pool` the engine's programs and the check's, each lowered from
    shapes alone (the cache as the engine holds it: `serve_cfg.num_pages`
    pages) and compiled in its thread. Returns the futures."""
    import jax

    from tpukit.model import latent
    from tpukit.serve import decode as serve_decode

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, np.dtype(dt))  # noqa: E731
    n, c = serve_cfg.slots, serve_cfg.chunk
    cache = jax.eval_shape(lambda: latent.init_paged_cache(
        cfg, {"bt": serve_cfg.num_pages}, serve_cfg.page_size, serve_cfg.pages_per_slot, n, serve_cfg.kv_dtype))
    p = jax.eval_shape(lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    state = (sds((n, serve_cfg.padded_width), np.int32), cache, sds((n,), np.int32), sds((n,), bool),
             sds((n,), np.int32), sds((n, 2), np.uint32))
    chunk, tick = common.load_by_name("modes", "serve_latent", ROOT)._check_programs(cfg)

    def prefill(a):
        return lambda: serve_decode.prefill_chunk_paged.lower(
            p, cfg, *state, sds((a,), np.int32), sds((a, c), np.int32), sds((a,), np.int32), sds((a,), bool),
            sds((a,), np.int32), sds((a,), np.int32), sds((a, 2), np.uint32))

    lowerings = [lambda: serve_decode.decode_step.lower(p, cfg, *state, eos_id, float(serve_cfg.temperature), 0, None,
                                                        steps=serve_cfg.decode_quantum),
                 lambda: tick.lower(p, cache, sds((n,), np.int32), sds((n,), np.int32), sds((n,), bool), 0),
                 lambda: chunk.lower(p, cache, sds((check_lanes,), np.int32), sds((check_lanes, c), np.int32),
                                     sds((check_lanes,), np.int32), sds((check_lanes,), np.int32))]
    lowerings += [prefill(1 << i) for i in reversed(range(n.bit_length()))]  # the largest admit batch first
    return [pool.submit(lambda lower=lower: lower().compile()) for lower in lowerings]


def live_completions(engine) -> list:
    """The engine's lanes still decoding as completions of what the last sync
    delivered of them. The prompt is the request's own: the buffer row never
    received the tokens of a prefix the registry served (the engine's
    `_retire` says the same of a finished lane)."""
    from tpukit.serve.engine import Completion

    buf = np.asarray(engine.buf)
    return [Completion(rid=lane.req.rid, prompt_len=lane.prompt_len, generated=lane.delivered, reason="live",
                       ids=np.concatenate([np.asarray(lane.req.ids, buf.dtype),
                                           buf[slot, lane.prompt_len:lane.prompt_len + lane.delivered]]),
                       arrival_s=lane.req.arrival_s, admit_s=lane.admit_s, done_s=0.0)
            for slot, lane in sorted(engine._lanes.items()) if lane.phase == "decode" and lane.delivered > 0]


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    try:
        from tpukit.model import latent
    except ImportError:  # a program from before the family existed: the cell cannot run there, and says so at once
        raise SystemExit("this program has no latent family (tpukit.model.latent): the cell cannot run on it") from None
    from tpukit.serve import decode as serve_decode
    from tpukit.serve.engine import Request, ServeConfig

    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    if traffic["arrivals"]["kind"] != "all_at_once":
        raise SystemExit("modes/serve_docqa.py runs the saturating window only (arrivals.kind all_at_once)")
    prog = config["program"]
    try:
        cfg = latent.config_from_hf(config, compute_dtype=prog["compute_dtype"], param_dtype=prog["param_dtype"])
    except KeyError as e:  # a program whose latent family does not read this configuration's keys
        raise SystemExit(f"this program's latent family cannot build the configuration: it asks for key {e}") from None
    if len(latent.page_kinds(cfg, traffic["engine"]["page_size"], traffic["engine"]["kv_dtype"])) != 1:
        raise SystemExit("this program keeps more than one page kind for the configuration: no prefix is shared")
    shared = common.load_by_name("modes", "serve", ctx["root"])
    base = common.load_by_name("modes", "serve_latent", ctx["root"])
    cold = common.load_by_name("modes", "serve_latent_cold", ctx["root"])
    check = traffic["setup_check"]
    docs_len = traffic["documents"]["tokens"]
    longest = max(docs_len[doc] + q + a for doc, q, a in traffic_docqa.block_sizes(traffic))
    if check["doc_tokens"] + check["question_tokens"] + check["decode_steps"] < longest:
        raise SystemExit(f"the set-up check stops short of the {longest} positions the traffic's longest request reaches")
    ref = cold.PaddedReference(common.load_by_name("reference", config["reference"], ctx["root"]), max(
        check["doc_tokens"] + check["question_tokens"] + check["decode_steps"],
        max(docs_len[doc] + q + min(a, traffic["check_new_tokens"]) for doc, q, a in traffic_docqa.block_sizes(traffic)
            if doc in traffic["check_documents"])))
    eng = traffic["engine"]
    tracing = ctx["trace_dir"] is not None
    phase = common.Phases(ctx["t_process_start"])
    window = min(ctx["seconds"], traffic["trace_seconds"]) if tracing else ctx["seconds"]
    serve_cfg = ServeConfig(
        slots=eng["slots"], buckets=tuple(eng["buckets"]), max_len=eng["max_len"],
        max_new_tokens=traffic["output_len"]["max"], decode_quantum=eng["decode_quantum"],
        page_size=eng["page_size"], kv_dtype=eng["kv_dtype"], prefill_chunk=eng["prefill_chunk"],
        num_pages=eng["num_pages"],
    )

    from tpukit.cache import enable_compilation_cache

    cached = enable_compilation_cache()  # run.py placed the cache: this is a view of its counters from here on
    pool = concurrent.futures.ThreadPoolExecutor(traffic["compile_workers"])
    with phase("programs_lower"):
        compiling = compile_side_by_side(cfg, serve_cfg, traffic["eos_id"], check["lanes"], pool)
    with phase("weights_init"):  # beside the compiles: they need no array
        params = jax.block_until_ready(jax.jit(lambda k: latent.init_params(k, cfg))(common.prng_key(seed)))
    with phase("programs_compile_rest"):
        for f in compiling:
            f.result()
        pool.shutdown()
        del compiling
    side_by_side = cached.stats()
    with phase("cached_path_served"):
        path = CheckPath(cfg, eng)
        setup = served_setup(ctx, cfg, params, path, seed)
        del path  # its pool's room is the engine's now

    tracer = shared._epoch_recorder(1 << 20) if tracing else None
    with phase("engine_init"):
        engine = shared._observed_engine_class()(params, cfg, serve_cfg, eos_id=traffic["eos_id"], tracer=tracer)
    ctx["info"]("engine", slots=serve_cfg.slots, num_pages=engine.num_pages, kv_bytes=engine.kv_bytes,
                chunk=serve_cfg.chunk, compile_budget=serve_cfg.compile_budget,
                weights_bytes=sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params)))
    with phase("programs_fetch"):
        shared.warm_up(engine, serve_decode)
    fetched = cached.stats()

    with phase("traffic"):
        docs = traffic_docqa.documents(traffic, cfg.vocab_size, seed)
        made = traffic_docqa.serve_requests(traffic, cfg.vocab_size, seed, window, docs)
        doc_of = [r["doc"] for r in made]
        requests = [Request(rid=r["rid"], ids=r["ids"], max_new_tokens=r["max_new_tokens"], seed=seed % (2**31),
                            arrival_s=0.0) for r in made]
    with phase("documents_prefill"):
        engine.run([Request(rid=r["rid"], ids=r["ids"], max_new_tokens=r["max_new_tokens"], seed=seed % (2**31),
                            arrival_s=0.0) for r in traffic_docqa.setup_requests(traffic, cfg.vocab_size, seed, docs)])
    registered = engine.allocator.registered_pages()
    n_setup, served0 = len(engine.completions), engine.prefix_tokens
    events0 = len(tracer.snapshot()) if tracer is not None else 0
    ctx["info"]("setup", compiles=ctx["compiles"].count, compile_or_fetch_s=ctx["compiles"].seconds,
                cache_when_compiled=side_by_side, cache_when_fetched=fetched, registered_pages=registered,
                **phase.seconds)
    ramp = traffic["ramp"]
    w = {"t_start": None, "t_end": None, "gen0": 0, "gen1": 0, "syncs": 0, "trace": None}
    tracing_scope = contextlib.ExitStack()  # entered when the window opens, closed when it closes

    def close_window(engine):
        w["t_end"], w["gen1"] = time.perf_counter(), engine.generated_tokens
        w["compiles1"] = ctx["compiles"].count
        w["admitted_at_close"] = engine.admitted
        w["tokens1"] = (engine.admitted_tokens, engine.prefix_tokens, engine.allocator.stats.prefix_hits, engine.admitted)
        tracing_scope.close()
        names, arrays = latent.counters(engine.cache)
        w["counters"] = dict(zip(names, np.concatenate([np.asarray(a).ravel() for a in arrays]).tolist()))
        # a short window can close before any request on a checked document has finished: the lanes still
        # decoding are sampled too
        w["live"] = [c for c in live_completions(engine) if c.rid < len(requests)]

    def on_sync(engine, now):
        if w["t_start"] is None:
            if len(engine.completions) - n_setup >= ramp["completions"]:
                w["trace"] = tracing_scope.enter_context(common.profiler_trace(ctx["trace_dir"]))
                w["compiles0"] = ctx["compiles"].count
                w["tokens0"] = (engine.admitted_tokens, engine.prefix_tokens, engine.allocator.stats.prefix_hits,
                                engine.admitted)
                w["t_start"], w["gen0"] = time.perf_counter(), engine.generated_tokens
            return
        if w["t_end"] is not None:
            return
        w["syncs"] += 1
        if time.perf_counter() - w["t_start"] >= window:
            close_window(engine)
            raise shared.WindowClosed

    engine.on_sync = on_sync
    t_run0 = time.perf_counter()
    with contextlib.suppress(shared.WindowClosed):
        engine.run(requests)
    if w["t_start"] is None:
        raise SystemExit("the run ended before its window opened: too few requests for this engine")
    if w["t_end"] is None:  # every request was served before the window's time was up
        close_window(engine)
    completions = list(engine.completions)[n_setup:]
    compiled_in_window = w["compiles1"] - w["compiles0"]
    measured = [c for c in completions if c.reason in ("eos", "length")]
    attempted, failed = len(completions), len(completions) - len(measured)
    elapsed = w["t_end"] - w["t_start"]
    end_to_end = {
        "serve_out_tokens_per_s": (w["gen1"] - w["gen0"]) / elapsed,
        "setup_s": w["t_start"] - ctx["t_process_start"],
    }
    admitted = w["admitted_at_close"] - n_setup
    traffic_ok = admitted < len(requests)  # the queue never emptied
    # admission is first come, first served: the traffic's first `admitted` requests. Each document is whole chunks
    # and no two questions share a page, so the registry served each its whole document exactly when it served
    # them all together
    whole_documents = sum(docs_len[doc_of[rid]] for rid in range(admitted))
    from_cache = w["tokens1"][1] - served0
    hits_ok = admitted > 0 and from_cache == whole_documents
    admitted_tokens, prefix_tokens, hits, admissions = (w["tokens1"][i] - w["tokens0"][i] for i in range(4))
    ctx["info"]("window", elapsed_s=elapsed, output_tokens=w["gen1"] - w["gen0"], syncs=w["syncs"],
                completions=len(completions), admitted=admitted, offered=len(requests),
                queue_never_empty=traffic_ok, compiled_in_window=compiled_in_window,
                ramp_s=w["t_start"] - t_run0, counters_at_close=w["counters"],
                admissions_in_window=admissions, prefix_hit_rate=hits / admissions if admissions else None,
                prompt_tokens_admitted=admitted_tokens, prompt_tokens_from_cache=prefix_tokens,
                whole_documents_served=hits_ok, documents_tokens_admitted=whole_documents,
                prompt_tokens_from_cache_since_setup=from_cache,
                completed_requests_per_s=len([c for c in measured if c.done_s >= w["t_start"] - t_run0]) / elapsed)

    host_events, quanta, prefills = [], [], []
    if tracer is not None:
        epoch = tracer.epoch_perf
        in_span = lambda ev: w["t_start"] <= epoch + ev["t0"] <= w["t_end"]  # noqa: E731
        seen = set()
        for ev in tracer.snapshot()[events0:]:  # the traffic's run: the set-up's events are on another epoch
            if ev["ev"] == "quantum":
                host_events.append(("engine dispatch", epoch + ev["t0"], epoch + ev["t1"]))
                host_events.append(("engine sync", epoch + ev["s0"], epoch + ev["s1"]))
                if in_span(ev):
                    quanta.append(ev)
            elif ev["ev"] == "prefill":
                if (ev["t0"], ev["t1"]) not in seen:  # one event a lane, one dispatch for all of them
                    seen.add((ev["t0"], ev["t1"]))
                    host_events.append(("engine prefill dispatch", epoch + ev["t0"], epoch + ev["t1"]))
                if in_span(ev):
                    prefills.append(ev)

    # after the window, off every clock the cell reports: the sampled completions through the check's path (the
    # engine's pool is not needed any more: its room is the check's), then the reference
    after = common.Phases(time.perf_counter())
    engine.cache = engine.buf = None
    rng = np.random.default_rng(seed)
    first_tokens = common.load_by_name("modes", "serve_hybrid", ctx["root"])._first_tokens
    sample, pools = [], {}
    for doc in traffic["check_documents"]:
        pool = [c for c in measured + w["live"] if c.generated > 0 and doc_of[c.rid] == doc]
        pools[doc] = len(pool)
        if pool:
            sample.append(first_tokens(pool[int(rng.integers(len(pool)))], traffic["check_new_tokens"]))
    with after("completions_served"):
        compiling = ref.compile_ahead(params, traffic["compile_workers"], hf=config)
        path = CheckPath(cfg, eng)
        setup_q = traffic["setup"]["question_tokens"]
        served = []
        for c in sample:
            doc = np.asarray(docs[doc_of[c.rid]] + tuple(rng.integers(0, cfg.vocab_size, size=setup_q).tolist()),
                             np.int32)
            lanes = check["lanes"]
            served.append(path.through(params, [doc] * lanes, [np.asarray(c.ids, np.int32)] * lanes, c.prompt_len,
                                       keep_from=c.prompt_len - 1)[0])
        del path
        for f in compiling:
            f.result()
    with after("reference"):
        exact = np.asarray(ref.logits(params, jnp.asarray(setup["ids"]), hf=config))
        setup_ok, report = base.judge(setup["logits"], [], exact, [], prompt_tokens=setup["prompt_tokens"], topk=0,
                                      tolerance=config["tolerance"])
        ctx["info"]("setup_check", ok=setup_ok, lanes=check["lanes"], **report)
        del exact
        tokens_ok, report = base.check_tokens(ctx, params, ref, sample, served)
    ctx["info"]("reference_check", ok=tokens_ok, pools=pools, **report)
    after.seconds.pop("process_start_to_mode")
    ctx["info"]("after_window", reference_padded_to=ref.pad_to, **after.seconds)

    return {
        "correct": bool(setup_ok and tokens_ok and traffic_ok and hits_ok and compiled_in_window == 0
                        and attempted > 0 and len(sample) == len(traffic["check_documents"])),
        "attempted": int(attempted),
        "failed": int(failed),
        "end_to_end": end_to_end,
        "record": {
            "mode": "serve", "cfg": cfg, "chips": ctx["chips"], "trace": w["trace"],
            "quanta": quanta, "prefills": prefills, "decode_quantum": eng["decode_quantum"],
            "prefill_chunk": eng["prefill_chunk"], "host_events": host_events, "host_spans": (),
        },
    }
