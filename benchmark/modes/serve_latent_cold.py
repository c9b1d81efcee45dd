"""Serving mode for the latent family where a whole run, programs compiled
from nothing, has to end inside the driver's limit on a run: `modes/
serve_latent.py`'s run (its `CheckPath`, `served_setup`, `judge` and
`check_tokens`, loaded by file name, as it loads `modes/serve.py`'s window and
warm-up) with three differences, each about seconds and none about what is
compared:

- **Programs compile side by side, and beside the weights.** Every program
  the run will dispatch (the decode quantum, one chunked-prefill program an
  admit size, the check's chunk and tick) is lowered from shapes before any
  array exists and handed to a pool of threads (XLA's compile releases the
  interpreter), which fills the persistent compilation cache while the
  weights are made; the calls that follow (`serve.warm_up`, the check) trace
  again and fetch. Seven admit sizes at 20-30 s each, one after the other,
  would alone pass the limit.
- **The sampled completions are bounded**: drawn from the window's
  completions of at most `check_max_tokens` tokens of which at most
  `check_max_new_tokens` were generated, every generated token against the
  reference's argmax by `check_tokens`' capped tie rule. Their served logits
  come from the check's own path, a tick a generated token, so the second
  bound is a bound on seconds after the window; the prompt goes through the
  chunk program the set-up check compiled (the completion in all
  `setup_check.lanes` lanes), so no further program compiles.
- **The reference compiles once, beside the served completions**: every
  sequence it is given (the set-up check's, the completions') is padded to
  the longest it can be given; causal, so the padding changes no kept row.
  Its jitted functions are lowered from shapes (the reference's
  `lowered_programs`) and compiled in threads while the sampled completions
  are served.

The window's info line also carries the family's counters as of its close
(`mhc_row_err_max`, `diff_lambda_mean` where the cache keeps them).
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import time

import numpy as np

from benchmark import common, traffic_gen


def compile_side_by_side(cfg, serve_cfg, eos_id: int, check_programs, check_lanes: int, pool) -> list:
    """Lower the engine's programs and the check's from shapes alone (no
    array exists yet: the run's own arrays are uncommitted, so the shapes
    carry no sharding either and the lowered modules are the ones the calls
    will ask the cache for) and hand each to `pool` to compile. Returns the
    futures."""
    import jax

    from tpukit.model import latent
    from tpukit.serve import decode as serve_decode

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, np.dtype(dt))  # noqa: E731
    n = serve_cfg.slots
    pages = {k.table: n * k.pages_for(serve_cfg.padded_width, serve_cfg.page_size) + 1
             for k in latent.page_kinds(cfg, serve_cfg.page_size, serve_cfg.kv_dtype)}
    cache = jax.eval_shape(lambda: latent.init_paged_cache(
        cfg, pages, serve_cfg.page_size, serve_cfg.pages_per_slot, n, serve_cfg.kv_dtype))
    p = jax.eval_shape(lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    state = (sds((n, serve_cfg.padded_width), np.int32), cache, sds((n,), np.int32), sds((n,), bool),
             sds((n,), np.int32), sds((n, 2), np.uint32))
    chunk, tick = check_programs
    lowered = [serve_decode.decode_step.lower(p, cfg, *state, eos_id, float(serve_cfg.temperature), 0, None,
                                              steps=serve_cfg.decode_quantum),
               chunk.lower(p, cache, sds((check_lanes,), np.int32), sds((check_lanes, serve_cfg.chunk), np.int32),
                           sds((check_lanes,), np.int32), sds((check_lanes,), np.int32)),
               tick.lower(p, cache, sds((n,), np.int32), sds((n,), np.int32), sds((n,), bool), 0)]
    a = n
    while a >= 1:  # the largest admit batch first: it compiles longest
        lowered.append(serve_decode.prefill_chunk_paged.lower(
            p, cfg, *state, sds((a,), np.int32), sds((a, serve_cfg.chunk), np.int32), sds((a,), np.int32),
            sds((a,), bool), sds((a,), np.int32), sds((a,), np.int32), sds((a, 2), np.uint32)))
        a //= 2
    return [pool.submit(low.compile) for low in lowered]


class PaddedReference:
    """The reference, every sequence padded with id 0 to one length: its
    functions compile once. Attention is causal and everything else is a
    token's own, so the kept rows are what the unpadded call gives."""

    def __init__(self, ref, pad_to: int):
        self.ref, self.pad_to = ref, pad_to

    def logits(self, params, ids, **kw):
        import jax.numpy as jnp

        n = ids.shape[0]
        if n > self.pad_to:
            raise ValueError(f"a sequence of {n} tokens is longer than the reference's padded length {self.pad_to}")
        return self.ref.logits(params, jnp.pad(ids, (0, self.pad_to - n)), **kw)[:n]

    def compile_ahead(self, params, workers: int, **kw) -> list:
        """The reference's programs at the padded length (`lowered_programs`),
        handed to threads to compile into the persistent cache. Returns the
        futures."""
        pool = concurrent.futures.ThreadPoolExecutor(workers)
        futures = [pool.submit(low.compile) for low in self.ref.lowered_programs(params, self.pad_to, **kw)]
        pool.shutdown(wait=False)
        return futures


def served_completions(params, path, completions, lanes: int) -> list:
    """The served logits of each sampled completion's generated positions, as
    `modes/serve_latent.py` takes them (the prompt in chunks, then one tick a
    generated token with every slot in the forward), but with the completion
    in all `lanes` lanes of the admit batch: the chunk program the set-up
    check compiled, and no other."""
    return [path.through(params, [np.asarray(c.ids, np.int32)] * lanes, c.prompt_len, keep_from=c.prompt_len - 1)[0]
            for c in completions]


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
        raise SystemExit("modes/serve_latent_cold.py runs the saturating window only (arrivals.kind all_at_once)")
    shared = common.load_by_name("modes", "serve", ctx["root"])
    base = common.load_by_name("modes", "serve_latent", ctx["root"])
    check = traffic["setup_check"]
    ref = PaddedReference(common.load_by_name("reference", config["reference"], ctx["root"]),
                          max(traffic["check_max_tokens"], check["prompt_tokens"] + check["decode_steps"]))
    prog = config["program"]
    try:
        cfg = latent.config_from_hf(config, compute_dtype=prog["compute_dtype"], param_dtype=prog["param_dtype"])
    except KeyError as e:  # a program whose latent family does not read this configuration's keys
        raise SystemExit(f"this program's latent family cannot build the configuration: it asks for key {e}") from None
    eng = traffic["engine"]
    tracing = ctx["trace_dir"] is not None
    phase = common.Phases(ctx["t_process_start"])
    window = min(ctx["seconds"], traffic["trace_seconds"]) if tracing else ctx["seconds"]
    serve_cfg = ServeConfig(
        slots=eng["slots"], buckets=tuple(eng["buckets"]), max_len=eng["max_len"],
        max_new_tokens=traffic["output_len"]["max"], decode_quantum=eng["decode_quantum"],
        page_size=eng["page_size"], kv_dtype=eng["kv_dtype"], prefill_chunk=eng["prefill_chunk"],
    )

    from tpukit.cache import enable_compilation_cache

    cached = enable_compilation_cache()  # run.py placed the cache: this is a view of its counters from here on
    pool = concurrent.futures.ThreadPoolExecutor(traffic["compile_workers"])
    with phase("programs_lower"):
        compiling = compile_side_by_side(cfg, serve_cfg, traffic["eos_id"], base._check_programs(cfg), check["lanes"], pool)
    with phase("weights_init"):  # beside the compiles: they need no array
        params = jax.block_until_ready(jax.jit(lambda k: latent.init_params(k, cfg))(common.prng_key(seed)))
    with phase("programs_compile_rest"):
        for f in compiling:
            f.result()
        pool.shutdown()
        del compiling
    side_by_side = cached.stats()
    with phase("cached_path_served"):
        path = base.CheckPath(cfg, eng)
        setup = base.served_setup(ctx, cfg, params, path, seed)
        del path  # its pools' room is the engine's now

    tracer = shared._epoch_recorder(1 << 20) if tracing else None
    with phase("engine_init"):
        engine = shared._observed_engine_class()(params, cfg, serve_cfg, eos_id=traffic["eos_id"], tracer=tracer)
    ctx["info"]("engine", slots=serve_cfg.slots, num_pages={k: a.num_pages for k, a in engine.allocators.items()},
                kv_bytes=engine.kv_bytes, chunk=serve_cfg.chunk, compile_budget=serve_cfg.compile_budget,
                weights_bytes=sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params)))
    with phase("programs_fetch"):
        shared.warm_up(engine, serve_decode)
    fetched = cached.stats()

    with phase("traffic"):
        requests = [Request(rid=r["rid"], ids=r["ids"], max_new_tokens=r["max_new_tokens"],
                            seed=seed % (2**31), arrival_s=r["arrival_s"])
                    for r in traffic_gen.serve_requests(traffic, cfg.vocab_size, seed, window)]
    ctx["info"]("setup", compiles=ctx["compiles"].count, compile_or_fetch_s=ctx["compiles"].seconds,
                cache_when_compiled=side_by_side, cache_when_fetched=fetched, **phase.seconds)
    ramp = traffic["ramp"]
    w = {"t_start": None, "t_end": None, "gen0": 0, "gen1": 0, "syncs": 0, "trace": None}
    tracing_scope = contextlib.ExitStack()  # entered when the window opens, closed when it closes

    def close_window(engine):
        w["t_end"], w["gen1"] = time.perf_counter(), engine.generated_tokens
        w["compiles1"] = ctx["compiles"].count
        w["admitted_at_close"] = engine.admitted
        tracing_scope.close()
        names, arrays = latent.counters(engine.cache)
        w["counters"] = dict(zip(names, np.concatenate([np.asarray(a).ravel() for a in arrays]).tolist()))

    def on_sync(engine, now):
        if w["t_start"] is None:
            if len(engine.completions) >= ramp["completions"]:
                w["trace"] = tracing_scope.enter_context(common.profiler_trace(ctx["trace_dir"]))
                w["compiles0"] = ctx["compiles"].count
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
    completions = list(engine.completions)
    compiled_in_window = w["compiles1"] - w["compiles0"]
    measured = [c for c in completions if c.reason in ("eos", "length")]
    attempted, failed = len(completions), len(completions) - len(measured)
    elapsed = w["t_end"] - w["t_start"]
    end_to_end = {
        "serve_out_tokens_per_s": (w["gen1"] - w["gen0"]) / elapsed,
        "setup_s": w["t_start"] - ctx["t_process_start"],
    }
    traffic_ok = w["admitted_at_close"] < len(requests)  # the queue never emptied
    ctx["info"]("window", elapsed_s=elapsed, output_tokens=w["gen1"] - w["gen0"], syncs=w["syncs"],
                completions=len(completions), admitted=w["admitted_at_close"], offered=len(requests),
                queue_never_empty=traffic_ok, compiled_in_window=compiled_in_window,
                ramp_s=w["t_start"] - t_run0, counters_at_close=w["counters"],
                completed_requests_per_s=len([c for c in measured if c.done_s >= w["t_start"] - t_run0]) / elapsed)

    host_events, quanta, prefills = [], [], []
    if tracer is not None:
        epoch = tracer.epoch_perf
        in_window = lambda ev: w["t_start"] <= epoch + ev["t0"] <= w["t_end"]  # noqa: E731
        seen = set()
        for ev in tracer.snapshot():
            if ev["ev"] == "quantum":
                host_events.append(("engine dispatch", epoch + ev["t0"], epoch + ev["t1"]))
                host_events.append(("engine sync", epoch + ev["s0"], epoch + ev["s1"]))
                if in_window(ev):
                    quanta.append(ev)
            elif ev["ev"] == "prefill":
                if (ev["t0"], ev["t1"]) not in seen:  # one event a lane, one dispatch for all of them
                    seen.add((ev["t0"], ev["t1"]))
                    host_events.append(("engine prefill dispatch", epoch + ev["t0"], epoch + ev["t1"]))
                if in_window(ev):
                    prefills.append(ev)

    # after the window, off every clock the cell reports: the sampled completions through the check's chunk
    # program (the engine's pools are not needed any more: their room is the check's), then the reference
    after = common.Phases(time.perf_counter())
    engine.cache = engine.buf = None
    rng = np.random.default_rng(seed)
    pool = [c for c in measured if len(c.ids) <= traffic["check_max_tokens"]
            and 0 < c.generated <= traffic["check_max_new_tokens"]]
    sample = [pool[i] for i in rng.permutation(len(pool))[: traffic["check_requests"]]]
    with after("completions_served"):
        compiling = ref.compile_ahead(params, traffic["compile_workers"], hf=config)
        path = base.CheckPath(cfg, eng)
        served = served_completions(params, path, sample, check["lanes"])
        del path
        for f in compiling:
            f.result()
    before = cached.stats()
    with after("reference"):
        exact = np.asarray(ref.logits(params, jnp.asarray(setup["ids"]), hf=config))
        setup_ok, report = base.judge(setup["logits"], setup["selections"], exact, [],
                                      prompt_tokens=check["prompt_tokens"], topk=cfg.index_topk,
                                      tolerance=config["tolerance"])
        ctx["info"]("setup_check", ok=setup_ok, lanes=check["lanes"], **report)
        del exact
        tokens_ok, report = base.check_tokens(ctx, params, ref, sample, served)
    ctx["info"]("reference_check", ok=tokens_ok, pool=len(pool), **report)
    after.seconds.pop("process_start_to_mode")
    now = cached.stats()  # what the reference still compiled: the small ops between its programs
    ctx["info"]("after_window", reference_padded_to=ref.pad_to,
                reference_cache={k: round(now[k] - before[k], 3) for k in ("requests", "hits", "misses", "compile_s")},
                **after.seconds)

    return {
        "correct": bool(setup_ok and tokens_ok and traffic_ok and compiled_in_window == 0 and attempted > 0),
        "attempted": int(attempted),
        "failed": int(failed),
        "end_to_end": end_to_end,
        "record": {
            "mode": "serve", "cfg": cfg, "chips": ctx["chips"], "trace": w["trace"],
            "quanta": quanta, "prefills": prefills, "decode_quantum": eng["decode_quantum"],
            "prefill_chunk": eng["prefill_chunk"], "host_events": host_events, "host_spans": (),
        },
    }
