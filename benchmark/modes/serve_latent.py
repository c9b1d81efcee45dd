"""Serving mode for the latent family (tpukit/model/latent.py): the cell's
configuration, built from its published keys, behind the same
`ServeEngine.run` as `modes/serve.py`, offered the traffic file's requests all
at once. The saturating window, the observed engine, the epoch recorder, the
warm-up and `WindowClosed` are `modes/serve.py`'s own (loaded by file name);
what differs is the model and what `correct` holds it to:

- at the published widths and AT THE ENGINE'S SHAPES (`CheckPath`: pools,
  block tables and window rings for all `slots` lanes; the model part of
  `decode.prefill_chunk_paged` over an admit batch of `setup_check.lanes`
  lanes, then the model part of `decode._advance` over every slot, the other
  lanes live with prompts of their own): the cached path's **logits** against
  the float32 reference's full forward (`tolerance.logit_rms_rel`, over the
  prompt's positions and over the ticks' apart), and the share of the
  reference's selected keys that the served path selected too
  (`tolerance.selection_overlap_min`). The served half runs in set-up; the
  reference's forward runs after the window, on the logits kept on the host,
  so `setup_s` holds none of the reference's seconds. The timed executables
  themselves return tokens, never logits: these programs are the same
  functions at the same shapes with the logits and selections handed out;
- on the timed path: every generated token of the sampled completions (drawn
  from those whose context passed the selection's top-k) is the reference's
  argmax over the vocabulary slice, or a bf16 tie by `modes/serve.py`'s capped
  rule; nothing compiled inside the window; the queue never emptied.

`judge` is the one comparison: `tools/dots3_tolerance.py` hands it the
reference computed in fp8, which it has to refuse.
"""

from __future__ import annotations

import contextlib
import functools
import time

import numpy as np

from benchmark import common, traffic_gen

EPS_BF16 = 2.0 ** -7


@functools.lru_cache(maxsize=None)
def _check_programs(cfg):
    """`chunk` = the model part of `decode.prefill_chunk_paged` for an admit
    batch of A lanes, `tick` = the model part of `decode._advance` for every
    slot, each handing out ONE lane's float32 logits and selections (the
    chunk's first lane, the tick's `lane`). Nothing is donated
    (serve/decode.py says why). One pair a configuration: the run builds the
    path twice, before and after the window."""
    import jax
    import jax.numpy as jnp

    from tpukit.model import latent

    @jax.jit
    def chunk(params, cache, lanes, rows, starts, prompt_lens):
        sub = latent.select_lanes(cache, lanes, prompt_lens)
        pos = starts[:, None] + jnp.arange(rows.shape[1], dtype=jnp.int32)[None]
        logits, sub, sel = latent.forward_cached_tapped(params, cfg, rows, pos, sub, starts)
        return logits[0].astype(jnp.float32), latent.merge_lanes(cache, sub), [s[0] for s in sel]

    @jax.jit
    def tick(params, cache, tok, read, live, lane):
        logits, cache, sel = latent.forward_cached_tapped(
            params, cfg, tok[:, None], read[:, None], cache, read, write_mask=live)
        return logits[lane, -1].astype(jnp.float32), cache, [s[lane] for s in sel]

    return chunk, tick


class CheckPath:
    """The cached path as the engine's programs compose it, with the logits
    and each full layer's selection handed out (`_check_programs`), over a
    cache of the engine's shape: every slot's block-table row and window
    ring, pools of the engine's size; each checked lane owns its pages in
    order, every other lane's rows point at the null page, as a free lane's
    do in the engine."""

    def __init__(self, cfg, eng: dict):
        from tpukit.model import latent

        self.slots, self.page, self.chunk_tokens = eng["slots"], eng["page_size"], eng["prefill_chunk"]
        per_slot = -(-eng["max_len"] // self.page)
        ring = latent.page_kinds(cfg, self.page, eng["kv_dtype"])[1].ring_pages
        self.pages = {"bt": per_slot, "bt_w": ring}
        self.cache = latent.init_paged_cache(
            cfg, {k: self.slots * n + 1 for k, n in self.pages.items()}, self.page, per_slot, self.slots,
            eng["kv_dtype"])
        self._chunk, self._tick = _check_programs(cfg)

    def lanes_for(self, n: int) -> np.ndarray:
        """`n` slots spread over the engine's, free lanes between them."""
        step = self.slots // n
        return (np.arange(n) * step + step // 2).astype(np.int32)

    def through(self, params, seqs: list, prompt_len: int, keep_from: int = 0):
        """`seqs` (A id arrays of one length S, one a lane) through the cache
        as the engine runs them: the first `prompt_len` tokens in page-aligned
        chunks, all A lanes a dispatch, the rest one tick each with every slot
        in the forward. Returns the first lane's `(logits [S - keep_from, V]
        for positions keep_from..S-1, selections per full layer)`."""
        import jax.numpy as jnp

        lanes = self.lanes_for(len(seqs))
        for key, n in self.pages.items():  # the checked lanes own their pages in order; the rest hold the null page
            table = np.zeros((self.slots, self.cache[key].shape[1]), np.int32)
            table[lanes, :n] = 1 + lanes[:, None] * n + np.arange(n)[None]
            self.cache[key] = jnp.asarray(table)
        seqs = np.stack([np.asarray(s, np.int32) for s in seqs])
        total, c = seqs.shape[1], self.chunk_tokens
        logits: list = []
        picked: list = []  # per full layer, the kept positions' selections, dispatch by dispatch

        def take(lg, sel, first: int):
            lo = max(keep_from - first, 0)
            if lo < lg.shape[0]:
                logits.append(np.asarray(lg[lo:]))
                picked.extend([] for _ in range(len(sel) - len(picked)))
                for layer, x in enumerate(sel):
                    picked[layer].append(np.asarray(x[lo:]))

        valid = jnp.full((len(lanes),), prompt_len, jnp.int32)
        for start in range(0, prompt_len, c):
            n = min(c, prompt_len - start)
            rows = np.zeros((len(lanes), c), np.int32)
            rows[:, :n] = seqs[:, start:start + n]
            lg, self.cache, sel = self._chunk(params, self.cache, jnp.asarray(lanes), jnp.asarray(rows),
                                              jnp.full((len(lanes),), start, jnp.int32), valid)
            take(lg[:n], [x[:n] for x in sel], start)
        tok, read, live = (np.zeros((self.slots,), dt) for dt in (np.int32, np.int32, bool))
        live[lanes] = True
        for t in range(prompt_len, total):
            tok[lanes], read[lanes] = seqs[:, t], t
            lg, self.cache, sel = self._tick(params, self.cache, jnp.asarray(tok), jnp.asarray(read),
                                             jnp.asarray(live), int(lanes[0]))
            take(lg[None], [x for x in sel], t)
        return np.concatenate(logits), [np.concatenate(rows) for rows in picked]


def _selection_overlap(served: np.ndarray, exact: np.ndarray, topk: int) -> float | None:
    """Share of the reference's selected keys that the served path selected
    too, over the queries that had more keys than top-k to choose from."""
    rows = np.arange(len(exact)) >= topk
    if not rows.any():
        return None
    hit = total = 0
    for a, b in zip(served[rows], exact[rows]):
        want = set(b[b >= 0].tolist())
        hit += len(want & set(a[a >= 0].tolist()))
        total += len(want)
    return hit / total


def served_setup(ctx, cfg, params, path: CheckPath, seed: int) -> dict:
    """The served half of the set-up comparison: a seeded prompt and its
    seeded continuation in the checked lane, others of their own in the
    admit batch's other lanes, through `path`. Kept on the host for `judge`."""
    check = ctx["traffic"]["setup_check"]
    rng = np.random.default_rng(seed)
    total = check["prompt_tokens"] + check["decode_steps"]
    seqs = [rng.integers(0, cfg.vocab_size, size=total).astype(np.int32) for _ in range(check["lanes"])]
    logits, selections = path.through(params, seqs, check["prompt_tokens"])
    return {"ids": seqs[0], "logits": logits, "selections": selections}


def judge(logits: np.ndarray, selections: list, exact: np.ndarray, exact_selections: list, *,
          prompt_tokens: int, topk: int, tolerance: dict):
    """THE comparison in logits: `logits [S, V]` and the keys each full layer
    selected against the reference's. Judged: each part's (the prompt's
    positions, the ticks') rms error over the reference's logits' rms, and
    each full layer's selection overlap. Told, not judged: the worst
    position's rms and the worst single logit (a near-tie of the router's 8th
    and 9th expert moves one position by more than fp8 moves the mean: the
    float32 reference rounded to bf16 reads as much)."""
    scale = float(np.sqrt(np.mean(exact ** 2)))
    err = logits - exact
    report = {"tokens": len(exact), "prompt_tokens": prompt_tokens, "logit_rms": scale}
    ok = True
    for name, part in (("prefill", slice(0, prompt_tokens)), ("decode", slice(prompt_tokens, len(exact)))):
        rows = np.sqrt(np.mean(err[part] ** 2, axis=-1)) / scale  # each position's rms error over the vocabulary
        rms = float(np.sqrt(np.mean(rows ** 2)))
        report[f"{name}_logit_rms_rel"] = rms
        report[f"{name}_logit_row_rms_rel"] = float(rows.max())
        report[f"{name}_logit_max_rel"] = float(np.max(np.abs(err[part]))) / scale
        ok = ok and rms <= tolerance["logit_rms_rel"]
    overlaps = [_selection_overlap(np.asarray(a), np.asarray(b), topk) for a, b in zip(selections, exact_selections)]
    report["selection_overlap"] = overlaps
    ok = ok and all(o is not None and o >= tolerance["selection_overlap_min"] for o in overlaps)
    report.update(tolerance={k: tolerance[k] for k in ("logit_rms_rel", "selection_overlap_min")})
    return bool(ok), report


def served_completions(params, path: CheckPath, completions) -> list:
    """The served logits of each sampled completion's generated positions:
    alone in its admit batch (a timed shape too), every slot in the ticks."""
    return [path.through(params, [np.asarray(c.ids, np.int32)], c.prompt_len, keep_from=c.prompt_len - 1)[0]
            for c in completions]


def check_tokens(ctx, params, ref, completions, served: list):
    """`modes/serve.py`'s capped tie rule, on this model: each generated token
    is the reference's argmax over the full prefix, or within one bf16 ulp of
    it plus twice the served path's own error there, that second term capped
    at `tolerance.serve_tie_cap_ulps` ulps."""
    import jax.numpy as jnp

    config = ctx["config"]
    cap_ulps = config["tolerance"]["serve_tie_cap_ulps"]
    checked = ties = 0
    worst, widest_ulps = None, 0.0
    for c, logits in zip(completions, served):
        ids = np.asarray(c.ids, np.int32)
        exact = np.asarray(ref.logits(params, jnp.asarray(ids), hf=config))[c.prompt_len - 1:]
        best = exact.argmax(axis=-1)
        for t in range(c.prompt_len, len(ids)):
            i, tok = t - c.prompt_len, int(ids[t])  # row i holds position t - 1, which chose token t
            checked += 1
            if int(best[i]) == tok:
                continue
            e_best, e_tok = float(exact[i, best[i]]), float(exact[i, tok])
            gap = abs(e_best - e_tok)
            ulp = EPS_BF16 * max(abs(e_best), abs(e_tok))
            served_err = max(abs(float(logits[i, best[i]]) - e_best), abs(float(logits[i, tok]) - e_tok))
            room = ulp + min(2 * served_err, cap_ulps * ulp)
            ties += 1
            widest_ulps = max(widest_ulps, gap / ulp)
            if gap > room and (worst is None or gap - room > worst["gap"] - worst["room"]):
                worst = {"rid": int(c.rid), "pos": t, "gap": gap, "room": room}
    report = {"requests": len(completions), "tokens_checked": checked, "bf16_tie_positions": ties,
              "widest_tie_ulps": widest_ulps, "tie_cap_ulps": 1 + cap_ulps, "beyond_tie": worst,
              "contexts": [int(len(c.ids)) for c in completions]}
    return worst is None and checked > 0, report


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
        raise SystemExit("modes/serve_latent.py runs the saturating window only (arrivals.kind all_at_once)")
    shared = common.load_by_name("modes", "serve", ctx["root"])
    ref = common.load_by_name("reference", config["reference"], ctx["root"])
    prog = config["program"]
    cfg = latent.config_from_hf(config, compute_dtype=prog["compute_dtype"], param_dtype=prog["param_dtype"])
    eng = traffic["engine"]
    tracing = ctx["trace_dir"] is not None
    phase = common.Phases(ctx["t_process_start"])
    window = min(ctx["seconds"], traffic["trace_seconds"]) if tracing else ctx["seconds"]

    with phase("weights_init"):
        params = jax.block_until_ready(jax.jit(lambda k: latent.init_params(k, cfg))(common.prng_key(seed)))
    with phase("cached_path_served"):
        path = CheckPath(cfg, eng)
        setup = served_setup(ctx, cfg, params, path, seed)
        del path  # its pools' room is the engine's now

    serve_cfg = ServeConfig(
        slots=eng["slots"], buckets=tuple(eng["buckets"]), max_len=eng["max_len"],
        max_new_tokens=traffic["output_len"]["max"], decode_quantum=eng["decode_quantum"],
        page_size=eng["page_size"], kv_dtype=eng["kv_dtype"], prefill_chunk=eng["prefill_chunk"],
    )
    tracer = shared._epoch_recorder(1 << 20) if tracing else None
    with phase("engine_init"):
        engine = shared._observed_engine_class()(params, cfg, serve_cfg, eos_id=traffic["eos_id"], tracer=tracer)
    ctx["info"]("engine", slots=serve_cfg.slots, num_pages={k: a.num_pages for k, a in engine.allocators.items()},
                kv_bytes=engine.kv_bytes, chunk=serve_cfg.chunk, compile_budget=serve_cfg.compile_budget,
                weights_bytes=sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params)))
    with phase("programs_compile_or_fetch"):
        shared.warm_up(engine, serve_decode)

    with phase("traffic"):
        requests = [Request(rid=r["rid"], ids=r["ids"], max_new_tokens=r["max_new_tokens"],
                            seed=seed % (2**31), arrival_s=r["arrival_s"])
                    for r in traffic_gen.serve_requests(traffic, cfg.vocab_size, seed, window)]
    ctx["info"]("setup", compiles=ctx["compiles"].count, compile_or_fetch_s=ctx["compiles"].seconds,
                **phase.seconds)
    ramp = traffic["ramp"]
    w = {"t_start": None, "t_end": None, "gen0": 0, "gen1": 0, "syncs": 0, "trace": None}
    tracing_scope = contextlib.ExitStack()  # entered when the window opens, closed when it closes

    def close_window(engine):
        w["t_end"], w["gen1"] = time.perf_counter(), engine.generated_tokens
        w["compiles1"] = ctx["compiles"].count
        w["admitted_at_close"] = engine.admitted
        tracing_scope.close()

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
                ramp_s=w["t_start"] - t_run0,
                completed_requests_per_s=len([c for c in measured if c.done_s >= w["t_start"] - t_run0]) / elapsed)

    host_events, quanta, prefills = [], [], []
    if tracer is not None:
        base = tracer.epoch_perf
        in_window = lambda ev: w["t_start"] <= base + ev["t0"] <= w["t_end"]  # noqa: E731
        seen = set()
        for ev in tracer.snapshot():
            if ev["ev"] == "quantum":
                host_events.append(("engine dispatch", base + ev["t0"], base + ev["t1"]))
                host_events.append(("engine sync", base + ev["s0"], base + ev["s1"]))
                if in_window(ev):
                    quanta.append(ev)
            elif ev["ev"] == "prefill":
                if (ev["t0"], ev["t1"]) not in seen:  # one event a lane, one dispatch for all of them
                    seen.add((ev["t0"], ev["t1"]))
                    host_events.append(("engine prefill dispatch", base + ev["t0"], base + ev["t1"]))
                if in_window(ev):
                    prefills.append(ev)

    # after the window, off every clock the cell reports: the sampled completions through the check's path
    # (the engine's pools are not needed any more: their room is the check's), then the reference's forwards
    after = common.Phases(time.perf_counter())
    engine.cache = engine.buf = None
    rng = np.random.default_rng(seed)
    pool = [c for c in measured if c.generated > 0 and len(c.ids) > cfg.index_topk]
    sample = [pool[i] for i in rng.permutation(len(pool))[: traffic["check_requests"]]]
    with after("completions_served"):
        path = CheckPath(cfg, eng)
        served = served_completions(params, path, sample)
        del path
    with after("reference"):
        exact_sel: list = []
        exact = np.asarray(ref.logits(params, jnp.asarray(setup["ids"]), hf=config, selected=exact_sel))
        setup_ok, report = judge(setup["logits"], setup["selections"], exact, exact_sel,
                                 prompt_tokens=traffic["setup_check"]["prompt_tokens"], topk=cfg.index_topk,
                                 tolerance=config["tolerance"])
        ctx["info"]("setup_check", ok=setup_ok, lanes=traffic["setup_check"]["lanes"], **report)
        del exact
        tokens_ok, report = check_tokens(ctx, params, ref, sample, served)
    ctx["info"]("reference_check", ok=tokens_ok, **report)
    after.seconds.pop("process_start_to_mode")
    ctx["info"]("after_window", **after.seconds)

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
