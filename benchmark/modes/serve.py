"""Serving mode: the cell's configuration behind `ServeEngine.run`, offered
the traffic file's requests. The loop is the engine's own; the benchmark
watches it from a subclass whose `sync` (the engine's once-a-quantum host
sync) reads the engine's own token account, opens and closes the window, and
starts and stops the trace.

Two kinds of window, chosen by the traffic file's arrivals:

- "all_at_once" (past the knee): the window opens after `ramp.completions`
  completions and closes `--seconds` later; the metric is the output tokens
  the engine accounted between the two syncs over the time between them.
- "poisson" (under the knee): requests due in `[ramp.seconds, ramp.seconds +
  --seconds)` are the attempted ones, each timed from when it was due; the
  run then drains for at most `drain_limit_s`.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmark import common, stats, traffic_gen

EPS_BF16 = 2.0 ** -7  # jnp.finfo(bfloat16).eps


class WindowClosed(Exception):
    """Raised from the watcher to end a run whose queue never empties."""


def _observed_engine_class():
    from tpukit.serve.engine import ServeEngine

    class ObservedEngine(ServeEngine):
        on_sync = None

        def sync(self, now: float) -> None:
            super().sync(now)
            if self.on_sync is not None:
                self.on_sync(self, now)

    return ObservedEngine


def _epoch_recorder(capacity: int):
    from tpukit.obs.trace import TraceRecorder

    class EpochRecorder(TraceRecorder):
        """Remembers the perf_counter instant the run loop pinned as its
        epoch, so that run-relative event times map onto the host clock."""

        epoch_perf = None

        def set_epoch(self, t0: float) -> None:
            self.epoch_perf = t0
            super().set_epoch(t0)

    return EpochRecorder(capacity)


def warm_up(engine, serve_decode) -> None:
    """Compile (or fetch from the cache) every program this engine can
    dispatch: one chunked-prefill program per power-of-two admit size and the
    decode quantum. The serve programs donate nothing, so calling them on the
    engine's own arrays and dropping the results leaves the engine as it was."""
    import jax
    import jax.numpy as jnp

    sv = engine.serve
    a = 1
    while a <= sv.slots:
        z = lambda shape, dt: jnp.asarray(np.zeros(shape, dt))  # noqa: E731
        out = serve_decode.prefill_chunk_paged(
            engine.params, engine.cfg, engine.buf, engine.cache, engine.cursors,
            engine.active, engine.limits, engine.keys,
            z((a,), np.int32), z((a, sv.chunk), np.int32), z((a,), np.int32), z((a,), bool),
            z((a,), np.int32), z((a,), np.int32), z((a, 2), np.uint32),
        )
        jax.block_until_ready(out)
        del out
        a *= 2
    out = serve_decode.decode_step(
        engine.params, engine.cfg, engine.buf, engine.cache, engine.cursors,
        engine.active, engine.limits, engine.keys, engine.eos_id, float(sv.temperature),
        0, None, steps=sv.decode_quantum,
    )
    jax.block_until_ready(out)


def check_tokens(ctx, cfg, params, completions, max_len: int):
    """Each generated token of the sampled completions is the float32
    reference's argmax over the full prefix, or a tie at bf16 resolution by
    chip_smoke.py's rule (phase_serve), copied: the two tokens' float32
    logits differ by no more than one bf16 ulp of the larger plus twice the
    error the bf16 forward itself makes on those two logits there. That
    second term comes from the code under test, so it is capped at the
    configuration's `tolerance.serve_tie_cap_ulps` bf16 ulps of the larger
    logit: a forward that loses precision cannot widen its own allowance."""
    import jax
    import jax.numpy as jnp

    from tpukit.model import gpt

    config = ctx["config"]
    ref = common.load_by_name("reference", config["reference"], ctx["root"])
    sizes = common.reference_sizes(config)

    @jax.jit
    def compare(params, ids, exact):  # params as an argument: closed over, they would be baked in as constants
        exact = exact[0]
        pos = jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
        served = gpt.forward(params, cfg, ids, pos, jnp.zeros(ids.shape, bool))[0].astype(jnp.float32)
        best = jnp.argmax(exact, axis=-1)
        nxt = jnp.concatenate([ids[0, 1:], ids[0, :1]])  # the token that followed each position
        pick = lambda m, t: jnp.take_along_axis(m, t[:, None], axis=1)[:, 0]  # noqa: E731
        return best, pick(exact, best), pick(exact, nxt), pick(served, best), pick(served, nxt)

    cap_ulps = config["tolerance"]["serve_tie_cap_ulps"]
    checked = ties = 0
    worst, widest_ulps = None, 0.0
    for c in completions:
        ids = np.zeros((1, max_len), np.int32)
        seq = np.asarray(c.ids)
        ids[0, : len(seq)] = seq
        ids = jnp.asarray(ids)
        best, e_best, e_tok, s_best, s_tok = (
            np.asarray(x) for x in compare(params, ids, ref.logits(params, ids, **sizes)))
        for t in range(c.prompt_len, len(seq)):
            checked += 1
            if int(best[t - 1]) == int(seq[t]):
                continue
            gap = abs(float(e_best[t - 1]) - float(e_tok[t - 1]))
            ulp = EPS_BF16 * max(abs(float(e_best[t - 1])), abs(float(e_tok[t - 1])))
            served_err = max(abs(float(s_best[t - 1] - e_best[t - 1])), abs(float(s_tok[t - 1] - e_tok[t - 1])))
            room = ulp + min(2 * served_err, cap_ulps * ulp)
            ties += 1
            widest_ulps = max(widest_ulps, gap / ulp)
            if gap > room and (worst is None or gap - room > worst["gap"] - worst["room"]):
                worst = {"rid": int(c.rid), "pos": t, "gap": gap, "room": room}
    report = {"requests": len(completions), "tokens_checked": checked, "bf16_tie_positions": ties,
              "widest_tie_ulps": widest_ulps, "tie_cap_ulps": 1 + cap_ulps, "beyond_tie": worst}
    return worst is None and checked > 0, report


def run(ctx) -> dict:
    import jax

    from tpukit.model import gpt
    from tpukit.serve import decode as serve_decode
    from tpukit.serve.engine import Request, ServeConfig

    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    cfg = common.gpt_config(config)
    eng = traffic["engine"]
    tracing = ctx["trace_dir"] is not None
    phase = common.Phases(ctx["t_process_start"])
    window = min(ctx["seconds"], traffic["trace_seconds"]) if tracing else ctx["seconds"]

    # weights made on the device in one jitted call, in the type the engine
    # serves them from (float32 parameters, cast per matmul)
    with phase("weights_init"):
        params = jax.block_until_ready(jax.jit(lambda k: gpt.init_params(k, cfg))(common.prng_key(seed)))
    serve_cfg = ServeConfig(
        slots=eng["slots"], buckets=tuple(eng["buckets"]), max_len=eng["max_len"],
        max_new_tokens=traffic["output_len"]["max"], decode_quantum=eng["decode_quantum"],
        page_size=eng["page_size"], kv_dtype=eng["kv_dtype"], prefill_chunk=eng["prefill_chunk"],
    )
    tracer = _epoch_recorder(1 << 20) if tracing else None
    with phase("engine_init"):
        engine = _observed_engine_class()(params, cfg, serve_cfg, eos_id=traffic["eos_id"], tracer=tracer)
    ctx["info"]("engine", slots=serve_cfg.slots, num_pages=engine.num_pages,
                kv_bytes=engine.kv_bytes, chunk=serve_cfg.chunk,
                compile_budget=serve_cfg.compile_budget)
    with phase("programs_compile_or_fetch"):
        warm_up(engine, serve_decode)

    with phase("traffic"):
        reqs = traffic_gen.serve_requests(traffic, cfg.vocab_size, seed, window)
        requests = [Request(rid=r["rid"], ids=r["ids"], max_new_tokens=r["max_new_tokens"],
                            seed=seed % (2**31), arrival_s=r["arrival_s"]) for r in reqs]
    ctx["info"]("setup", compiles=ctx["compiles"].count, compile_or_fetch_s=ctx["compiles"].seconds,
                **phase.seconds)
    open_loop = traffic["arrivals"]["kind"] != "all_at_once"
    ramp = traffic["ramp"]
    w = {"t_start": None, "t_end": None, "gen0": 0, "gen1": 0, "syncs": 0, "trace": None, "marks": []}
    tracing_scope = contextlib.ExitStack()  # entered when the window opens, closed when it closes

    def open_window(engine):
        w["trace"] = tracing_scope.enter_context(common.profiler_trace(ctx["trace_dir"]))
        w["compiles0"] = ctx["compiles"].count
        w["t_start"], w["gen0"] = time.perf_counter(), engine.generated_tokens

    def close_window(engine):
        w["t_end"], w["gen1"] = time.perf_counter(), engine.generated_tokens
        w["close_rel"] = w["t_end"] - t_run0
        w["compiles1"] = ctx["compiles"].count
        w["admitted_at_close"] = engine.admitted
        tracing_scope.close()

    def on_sync(engine, now):
        if w["t_start"] is None:
            ready = (now >= ramp["seconds"]) if open_loop else (len(engine.completions) >= ramp["completions"])
            if ready:
                open_window(engine)
            return
        if w["t_end"] is not None:
            return
        w["syncs"] += 1
        elapsed = time.perf_counter() - w["t_start"]
        if elapsed >= 10.0 * (len(w["marks"]) + 1):  # the same rate over each shorter window, every 10 s
            w["marks"].append((elapsed, engine.generated_tokens - w["gen0"]))
        if elapsed >= window:
            close_window(engine)
            if not open_loop:
                raise WindowClosed

    engine.on_sync = on_sync
    t_run0 = time.perf_counter()
    timed_out = False
    try:
        max_wall = (ramp["seconds"] + window + traffic["drain_limit_s"]) if open_loop else None
        if max_wall and tracing:
            max_wall += 600  # writing the trace out stalls the loop at the window's end for up to minutes
        engine.run(requests, max_wall_s=max_wall)
    except WindowClosed:
        pass
    except TimeoutError:
        timed_out = True
    if w["t_start"] is None:
        raise SystemExit("the run ended before its window opened: too few requests for this engine")
    if w["t_end"] is None:  # every request was served before the window's time was up
        close_window(engine)
    completions = list(engine.completions)
    compiled_in_window = w["compiles1"] - w["compiles0"]

    if open_loop:
        due = [req for req, r in zip(requests, reqs) if r["segment"] == "window"]
        done = {c.rid: c for c in completions}
        measured = [done[r.rid] for r in due if r.rid in done and done[r.rid].reason in ("eos", "length")]
        attempted, failed = len(due), len(due) - len(measured)
        worst_ms = traffic["drain_limit_s"] * 1e3
        ttft = [(c.active_s - c.arrival_s) * 1e3 for c in measured] + [worst_ms] * failed
        tpot = [(c.done_s - c.active_s) / max(c.generated - 1, 1) * 1e3 for c in measured] + [worst_ms] * failed
        # traced runs: a request still queued when the trace is written out waits for that too
        queue = [(c.admit_s - c.arrival_s) * 1e3 for c in measured if not tracing or c.admit_s <= w["close_rel"]]
        # a cell names the statistic its sample supports: a p95 wants some hundreds of requests
        end_to_end = {
            "serve_ttft_mean_ms": sum(ttft) / len(ttft), "serve_ttft_p95_ms": stats.percentile(ttft, 95),
            "serve_tpot_mean_ms": sum(tpot) / len(tpot), "serve_tpot_p95_ms": stats.percentile(tpot, 95),
            "setup_s": t_run0 + ramp["seconds"] - ctx["t_process_start"],
        }
        third = max(len(queue) // 3, 1)  # a backlog that grows shows as queue wait rising through the window
        ctx["info"]("window", requests_due=attempted, completed=len(measured), timed_out=timed_out,
                    queue_wait_ms_first_third=stats.percentile(queue[:third], 50) if queue else None,
                    queue_wait_ms_last_third=stats.percentile(queue[-third:], 50) if queue else None,
                    ttft_ms_median=stats.percentile(ttft, 50), ttft_ms_p95=stats.percentile(ttft, 95),
                    tpot_ms_median=stats.percentile(tpot, 50),
                    queue_wait_ms_median=stats.percentile(queue, 50) if queue else None,
                    output_tokens=int(sum(c.generated for c in measured)),
                    offered_rate_per_s=traffic["arrivals"]["rate_per_s"],
                    compiled_in_window=compiled_in_window)
        traffic_ok = not timed_out
    else:
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
                    tokens_per_s_by_window={f"{t:.0f}": n / t for t, n in w["marks"]},
                    completed_requests_per_s=len([c for c in measured if c.done_s >= w["t_start"] - t_run0]) / elapsed)

    rng = np.random.default_rng(seed)
    pool = [c for c in measured if c.generated > 0]
    sample = [pool[i] for i in rng.permutation(len(pool))[: traffic["check_requests"]]]
    tokens_ok, report = check_tokens(ctx, cfg, params, sample, eng["max_len"])
    ctx["info"]("reference_check", ok=tokens_ok, **report)

    host_events, quanta = [], []
    if tracer is not None:
        base = tracer.epoch_perf
        for ev in tracer.snapshot():
            if ev["ev"] == "quantum":
                quanta.append(ev)
                host_events.append(("engine dispatch", base + ev["t0"], base + ev["t1"]))
                host_events.append(("engine sync", base + ev["s0"], base + ev["s1"]))
            elif ev["ev"] == "prefill":
                host_events.append(("engine prefill dispatch", base + ev["t0"], base + ev["t1"]))
        in_window = lambda q: w["t_start"] <= base + q["t0"] <= w["t_end"]  # noqa: E731
        quanta = [q for q in quanta if in_window(q)]

    return {
        "correct": bool(tokens_ok and traffic_ok and compiled_in_window == 0 and attempted > 0),
        "attempted": int(attempted),
        "failed": int(failed),
        "end_to_end": end_to_end,
        "record": {
            "mode": "serve", "cfg": cfg, "chips": ctx["chips"], "trace": w["trace"],
            "quanta": quanta, "decode_quantum": eng["decode_quantum"],
            "host_events": host_events, "host_spans": (),
        },
    }
