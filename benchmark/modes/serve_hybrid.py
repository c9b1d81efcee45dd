"""Serving mode for the hybrid state-space family (tpukit/model/hybrid.py):
the cell's configuration, built from its published Jamba keys, behind the same
`ServeEngine.run` as the other serving modes, offered the traffic file's
requests all at once. What is family-blind is loaded by file name and not
copied: `modes/serve.py`'s observed engine, epoch recorder, warm-up and
`WindowClosed`; `modes/serve_latent.py`'s `CheckPath.through`,
`served_setup`, `judge` and `check_tokens`; `modes/serve_latent_cold.py`'s
`PaddedReference` and `served_completions`. What is written here names the
family:

- `CheckPath`: the cached path at the engine's shapes (pools, block table
  and the recurrent state for all `slots` lanes) with the logits handed out,
  the model part of `decode.prefill_chunk_paged` over an admit batch of
  `setup_check.lanes` lanes (32, a refill wave's: more than one group of
  `hybrid.PREFILL_TOKENS`, so the grouped program is the one compared, and the
  checked prompt in the batch's LAST lane, the last group), then the model
  part of `decode._advance` over every slot. `correct` holds the set-up check's **logits** over a prompt
  longer than any request the window serves and over the ticks after it
  against the float32 reference's (`reference/jamba_block.py`: the literal
  recurrence a token at a time), by `judge`'s `tolerance.logit_rms_rel`;
- `compile_side_by_side`: every program the run dispatches, lowered from
  shapes and compiled in threads beside the weights;
- the sampled completions: the first `check_new_tokens` generated tokens of
  each, prefilled in an admit batch of one lane (the ungrouped program, a
  sixteenth of the set-up check's chunk time), then a tick a token with every slot in the forward (a
  bound on seconds after the window), against the reference's argmax by the
  capped tie rule.

On a program with no hybrid family the mode exits at once, non-zero, and says
so. The window's info line carries the family's gauges as of its close
(`state_bytes`, `ssm_state_absmax`).
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import dataclasses
import functools
import time
from pathlib import Path

import numpy as np

from benchmark import common, traffic_gen

ROOT = Path(__file__).resolve().parents[2]
_latent_mode = common.load_by_name("modes", "serve_latent", ROOT)


@functools.lru_cache(maxsize=None)
def _check_programs(cfg):
    """`chunk` = the model part of `decode.prefill_chunk_paged` for an admit
    batch of A lanes, `tick` = the model part of `decode._advance` for every
    slot, each handing out ONE lane's float32 logits (the chunk's last lane,
    the tick's `lane`) and no key selections (no layer selects keys)."""
    import jax
    import jax.numpy as jnp

    from tpukit.model import hybrid

    @jax.jit
    def chunk(params, cache, lanes, rows, starts, prompt_lens):
        sub = hybrid.select_lanes(cache, lanes, prompt_lens)
        pos = starts[:, None] + jnp.arange(rows.shape[1], dtype=jnp.int32)[None]
        logits, sub = hybrid.forward_cached(params, cfg, rows, pos, sub, starts)
        return logits[-1].astype(jnp.float32), hybrid.merge_lanes(cache, sub), []

    @jax.jit
    def tick(params, cache, tok, read, live, lane):
        logits, cache = hybrid.forward_cached(params, cfg, tok[:, None], read[:, None], cache, read,
                                              write_mask=live)
        return logits[lane, -1].astype(jnp.float32), cache, []

    return chunk, tick


class CheckPath(_latent_mode.CheckPath):
    """`serve_latent.CheckPath` over this family's cache: one block table
    (each checked lane owns its pages in order, every other lane's row points
    at the null page) and every slot's recurrent state; a checked lane starts
    at position 0, so from a zero state. Its ticks begin where the engine's
    do, at the prompt's last token (`through`). `shapes_only`: the programs
    without the cache (a compile tool hands them shapes)."""

    def __init__(self, cfg, eng: dict, shapes_only: bool = False):
        from tpukit.model import hybrid

        self.slots, self.page, self.chunk_tokens = eng["slots"], eng["page_size"], eng["prefill_chunk"]
        per_slot = -(-eng["max_len"] // self.page)
        self.pages = {"bt": per_slot}
        self.cache = None if shapes_only else hybrid.init_paged_cache(
            cfg, self.slots * per_slot + 1, self.page, per_slot, self.slots, eng["kv_dtype"])
        self._chunk, self._tick = _check_programs(cfg)

    def through(self, params, seqs: list, prompt_len: int, keep_from: int = 0):
        """`seqs` (A id arrays of one length S, one a lane) through the cache
        as the engine runs them: the first `prompt_len` tokens in page-aligned
        chunks, all A lanes a dispatch, then one tick a token with every slot
        in the forward FROM THE PROMPT'S LAST TOKEN, which the engine's first
        tick forwards again and the state takes only then. Returns the first
        lane's `(logits [S - keep_from, V] for positions keep_from..S-1, [])`:
        the prompt's last position from its tick, as the engine samples it.
        The first lane's sequence goes LAST in the admit batch: in a chunk of
        more than one group of lanes (`hybrid.PREFILL_TOKENS`) it is in the
        last group, which the group loop reaches through every slice."""
        import jax.numpy as jnp

        seqs = list(seqs[1:]) + list(seqs[:1])
        lanes = self.lanes_for(len(seqs))
        n = self.pages["bt"]
        table = np.zeros((self.slots, self.cache["bt"].shape[1]), np.int32)
        table[lanes, :n] = 1 + lanes[:, None] * n + np.arange(n)[None]
        self.cache["bt"] = jnp.asarray(table)
        seqs = np.stack([np.asarray(s, np.int32) for s in seqs])
        c, logits = self.chunk_tokens, []
        valid = jnp.full((len(lanes),), prompt_len, jnp.int32)
        for start in range(0, prompt_len, c):
            rows, n = np.zeros((len(lanes), c), np.int32), min(c, prompt_len - start)
            rows[:, :n] = seqs[:, start:start + n]
            lg, self.cache, _ = self._chunk(params, self.cache, jnp.asarray(lanes), jnp.asarray(rows),
                                            jnp.full((len(lanes),), start, jnp.int32), valid)
            end = min(start + c, prompt_len - 1)  # the last prompt position's logits come from its tick
            if max(start, keep_from) < end:
                logits.append(np.asarray(lg[max(keep_from - start, 0): end - start]))
        tok, read, live = (np.zeros((self.slots,), dt) for dt in (np.int32, np.int32, bool))
        live[lanes] = True
        for t in range(prompt_len - 1, seqs.shape[1]):
            tok[lanes], read[lanes] = seqs[:, t], t
            lg, self.cache, _ = self._tick(params, self.cache, jnp.asarray(tok), jnp.asarray(read),
                                           jnp.asarray(live), int(lanes[-1]))
            if t >= keep_from:
                logits.append(np.asarray(lg)[None])
        return np.concatenate(logits), []


def compile_side_by_side(cfg, serve_cfg, eos_id: int, check_lanes: tuple, pool) -> list:
    """Hand `pool` the engine's programs and the check's, each lowered from
    shapes alone and compiled in its thread (`serve_latent_cold.
    compile_side_by_side`, for this family's cache; the lowering in the
    threads too, so that it runs beside the weights); the check's chunk at
    each admit size of `check_lanes`. Returns the futures."""
    import jax

    from tpukit.model import hybrid
    from tpukit.serve import decode as serve_decode

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, np.dtype(dt))  # noqa: E731
    n = serve_cfg.slots
    cache = jax.eval_shape(lambda: hybrid.init_paged_cache(
        cfg, n * serve_cfg.pages_per_slot + 1, serve_cfg.page_size, serve_cfg.pages_per_slot, n, serve_cfg.kv_dtype))
    p = jax.eval_shape(lambda: hybrid.init_params(jax.random.PRNGKey(0), cfg))
    state = (sds((n, serve_cfg.padded_width), np.int32), cache, sds((n,), np.int32), sds((n,), bool),
             sds((n,), np.int32), sds((n, 2), np.uint32))
    chunk, tick = _check_programs(cfg)
    c = serve_cfg.chunk

    def prefill(a):
        return lambda: serve_decode.prefill_chunk_paged.lower(
            p, cfg, *state, sds((a,), np.int32), sds((a, c), np.int32), sds((a,), np.int32), sds((a,), bool),
            sds((a,), np.int32), sds((a,), np.int32), sds((a, 2), np.uint32))

    lowerings = [lambda: serve_decode.decode_step.lower(p, cfg, *state, eos_id, float(serve_cfg.temperature), 0, None,
                                                        steps=serve_cfg.decode_quantum),
                 lambda: tick.lower(p, cache, sds((n,), np.int32), sds((n,), np.int32), sds((n,), bool), 0)]
    lowerings += [lambda a=a: chunk.lower(p, cache, sds((a,), np.int32), sds((a, c), np.int32), sds((a,), np.int32),
                                          sds((a,), np.int32)) for a in check_lanes]
    lowerings += [prefill(1 << i) for i in reversed(range(n.bit_length()))]  # the largest admit batch first
    return [pool.submit(lambda lower=lower: lower().compile()) for lower in lowerings]


def _first_tokens(completion, new_tokens: int):
    """The completion cut after its first `new_tokens` generated tokens: what
    the tie rule checks of it."""
    keep = completion.prompt_len + min(new_tokens, completion.generated)
    return dataclasses.replace(completion, ids=completion.ids[:keep], generated=keep - completion.prompt_len)


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    try:
        from tpukit.model import hybrid
    except ImportError:  # a program from before the family existed: the cell cannot run there, and says so at once
        raise SystemExit("this program has no hybrid family (tpukit.model.hybrid): the cell cannot run on it") from None
    from tpukit.serve import decode as serve_decode
    from tpukit.serve.engine import Request, ServeConfig

    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    if traffic["arrivals"]["kind"] != "all_at_once":
        raise SystemExit("modes/serve_hybrid.py runs the saturating window only (arrivals.kind all_at_once)")
    shared = common.load_by_name("modes", "serve", ctx["root"])
    cold = common.load_by_name("modes", "serve_latent_cold", ctx["root"])
    check = traffic["setup_check"]
    ref = cold.PaddedReference(common.load_by_name("reference", config["reference"], ctx["root"]),
                               check["prompt_tokens"] + check["decode_steps"])
    prog = config["program"]
    cfg = hybrid.config_from_hf(config, compute_dtype=prog["compute_dtype"], param_dtype=prog["param_dtype"])
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
        compiling = compile_side_by_side(cfg, serve_cfg, traffic["eos_id"],
                                         (check["lanes"], 1), pool)
    with phase("weights_init"):  # beside the compiles: they need no array
        params = jax.block_until_ready(jax.jit(lambda k: hybrid.init_params(k, cfg))(common.prng_key(seed)))
    with phase("programs_compile_rest"):
        for f in compiling:
            f.result()
        pool.shutdown()
        del compiling
    side_by_side = cached.stats()
    with phase("cached_path_served"):
        path = CheckPath(cfg, eng)
        setup = _latent_mode.served_setup(ctx, cfg, params, path, seed)
        del path  # its pools' room is the engine's now

    tracer = shared._epoch_recorder(1 << 20) if tracing else None
    with phase("engine_init"):
        engine = shared._observed_engine_class()(params, cfg, serve_cfg, eos_id=traffic["eos_id"], tracer=tracer)
    ctx["info"]("engine", slots=serve_cfg.slots, num_pages=engine.num_pages, kv_bytes=engine.kv_bytes,
                slot_state_bytes=hybrid.slot_state_bytes(cfg), chunk=serve_cfg.chunk,
                compile_budget=serve_cfg.compile_budget,
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
        names, arrays = hybrid.counters(engine.cache)
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

    # after the window, off every clock the cell reports: the sampled completions' first tokens through the check's
    # chunk program (the engine's pools are not needed any more: their room is the check's), then the reference
    after = common.Phases(time.perf_counter())
    engine.cache = engine.buf = None
    rng = np.random.default_rng(seed)
    pool = [c for c in measured if 0 < c.generated and c.prompt_len + traffic["check_new_tokens"] <= ref.pad_to]
    sample = [_first_tokens(pool[i], traffic["check_new_tokens"])
              for i in rng.permutation(len(pool))[: traffic["check_requests"]]]
    with after("completions_served"):
        compiling = ref.compile_ahead(params, traffic["compile_workers"], hf=config)
        path = CheckPath(cfg, eng)
        served = cold.served_completions(params, path, sample, 1)
        del path
        for f in compiling:
            f.result()
    with after("reference"):
        exact = np.asarray(ref.logits(params, jnp.asarray(setup["ids"]), hf=config))
        setup_ok, report = _latent_mode.judge(setup["logits"], setup["selections"], exact, [],
                                              prompt_tokens=check["prompt_tokens"], topk=0,
                                              tolerance=config["tolerance"])
        ctx["info"]("setup_check", ok=setup_ok, lanes=check["lanes"], **report)
        del exact
        tokens_ok, report = _latent_mode.check_tokens(ctx, params, ref, sample, served)
    ctx["info"]("reference_check", ok=tokens_ok, pool=len(pool), **report)
    after.seconds.pop("process_start_to_mode")
    ctx["info"]("after_window", reference_padded_to=ref.pad_to, **after.seconds)

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
