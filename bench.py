#!/usr/bin/env python
"""Benchmark: GPT training throughput on the available chip(s).

Trains the cookbook's GPT (reference default shape: dim 256, 8x32 heads,
8 layers, seq 256, GPT-2 vocab — main-single.py:156-162) with the full jitted
train step (fwd + bwd + AdamW) in bf16 on synthetic data, and reports
tokens/sec/chip and MFU. The reference publishes no numbers (BASELINE.md), so
`vs_baseline` is measured MFU / the driver's 35% MFU north-star.

Every probe (headline, long-context, offload, MoE, ladder rungs) shares ONE
setup helper (`tools.bench_ladder.setup_step`) and the persistent XLA
compilation cache (placed by `tpukit/cache.py`'s rule), so a
repeat bench run skips recompiles; hit/miss counts land in the JSON. The
`host_pipeline` record measures the round-7 prefetch path: the same loader
schedule + train step run synchronously and with `--prefetch`-style
depth-2 overlap, reporting the input-share both ways and loss parity. The
`obs_overhead` record measures the round-8 failure-observability layer
(flight-recorder ring + periodic in-jit divergence checksum) against the
bare loop, with the same loss-parity proof. The `moe_ep_comm` record
(round 10) audits the ExpertParallel a2a dispatch: expected-vs-measured
all-to-all bytes, involuntary-remat warning count, a2a-path throughput.
The `moe_dispatch_ladder` record (round 11, ROADMAP #3) measures the
three MoE dataflows — xla buffers, a2a exchange, pallas grouped GEMM — at
e8 top-1/top-2 with active-FLOPs-normalized MFU; `--moe_dispatch pallas`
flips the headline moe_e8 probe onto the kernel path. The `quant_comm`
record (round 12, ROADMAP #2) measures `--comm_dtype` f32 vs bf16 vs int8
per strategy rung (ddp/fsdp/ep): expected+measured bytes-on-the-wire (the
~4x int8 cut is the headline), tokens/s/chip, and the final-loss delta vs
f32 — the tolerance-gate number. The `elastic_restore` record (round 13,
ROADMAP #5) measures the reshard-on-restore pass: a sharded FSDP
checkpoint landing on a half-size world — wall-clock, bytes read, host
RSS high-water delta, and the byte-parity bit vs a direct restore. The
`serving` record (round 14, ROADMAP #1) measures the continuous-batching
engine (tpukit/serve) against serial per-request cached decode on the
same seeded synthetic stream: tokens/s (>= 2x is the acceptance bar),
p50/p99 end-to-end and per-token latency, slot occupancy. The
`spec_decode` record (round 17, ROADMAP #3) measures speculative
decoding — induction-trained target, self-spec (fused on-device n-gram)
and draft-model proposers — vs the vanilla engine on the repetitive
stream at temperature 0 and 0.8: tokens/s (self-spec t=0 >= 1.3x is the
bar), acceptance rate, and the appended-tokens/verify histogram. The
`fleet_serving` record (round 19, ROADMAP #1) measures the fleet router
(tpukit/serve/fleet) at 1 vs 2 vs 4 replicas on the same stream at equal
total devices — fleet tokens/s scaling (>1.5x at 2 replicas is the bar),
p99 under load, per-request token parity across rungs, and
disaggregated-vs-colocated prefill admit latency — with an honest
CPU-loopback caveat in-record. Round 20 adds the
`serve_dispatch_attribution` record (per-quantum dispatch-vs-device wall
split from the request tracer's quantum spans) and a `serving` rung
inside `obs_overhead` (the trace recorder on vs off on the same seeded
stream: tokens/s delta under the 1% bar, bit-identical output tokens).
The `decode_fused` record (round 21, ROADMAP #2/#4) isolates the two
`--fused_decode` wins: unfused-gather vs fused-kernel at decode_quantum=1
(the kernel delta; interpret-mode CPU states its inversion honestly) and
fused q=1 vs the on-device while-loop window (the dispatch-amortization
delta, which transfers — the kernel cost cancels), with three-way token
parity and per-quantum dispatch/device walls.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}
"""

import argparse
import json
import sys

import numpy as np


def bench_host_pipeline(cfg, strategy, batch, depth=2, steps=24):
    """Prefetch-vs-sync host input pipeline on the headline config.

    Runs the REAL input path (DataLoader -> prepare_batch -> global-batch
    assembly -> jitted train step) over an identical batch schedule twice,
    from identical initial states: once synchronous (the data+h2d spans),
    once through a depth-N HostPrefetcher (the prefetch_stall span).
    Returns the window share of each, the buffer occupancy, and whether the
    final losses are bit-identical (they must be: same batches, same order,
    same step function — the prefetcher only moves WHEN host work runs).
    """
    from tpukit.batching import prepare_batch
    from tpukit.data import ArrayDataset
    from tpukit.loader import DataLoader
    from tpukit.obs import SpanTimeline
    from tpukit.prefetch import HostPrefetcher
    from tpukit.train import make_global_batch
    from tools.bench_ladder import make_batch, setup_step

    seq = cfg.max_position_embeddings
    pad_id = 2
    rng = np.random.RandomState(7)
    # raw [B, S] rows; prepare_batch shifts to the model's S-1, matching
    # the headline step's compiled shape
    ids = rng.randint(3, cfg.vocab_size, size=(steps * batch, seq)).astype(np.int32)
    ds = ArrayDataset(ids, np.ones_like(ids))
    batch_sh = strategy.batch_sharding()

    def pipeline(raw):
        b, t = prepare_batch(raw, pad_id)
        return make_global_batch(batch_sh, b, t, place=True)

    def run(prefetched: bool):
        train_step, state, _, _ = setup_step(cfg, strategy)
        # compile + warm outside the measured window
        wb, wt = make_batch(np.random.RandomState(0), cfg.vocab_size, batch, seq - 1)
        state, _ = train_step(state, wb, wt)
        spans = SpanTimeline()
        loader = DataLoader(ds, batch)
        occupancy = None
        spans.epoch()  # reset the clock to the loop start
        if prefetched:
            pf = HostPrefetcher(loader, pipeline, depth=depth)
            try:
                while True:
                    with spans.span("prefetch_stall"):
                        try:
                            b, t = next(pf)
                        except StopIteration:
                            break
                    with spans.span("step"):
                        state, loss = train_step(state, b, t)
            finally:
                occupancy = pf.window_stats()["occupancy"]
                pf.close()
        else:
            # loader next() INSIDE the data span, mirroring fit()'s sync
            # accounting — batch assembly is real host input work and must
            # land in the share being compared against prefetch_stall
            it = iter(loader)
            while True:
                with spans.span("data"):
                    try:
                        raw = next(it)
                    except StopIteration:
                        break
                    b, t = prepare_batch(raw, pad_id)
                with spans.span("h2d"):
                    b, t = make_global_batch(batch_sh, b, t)
                with spans.span("step"):
                    state, loss = train_step(state, b, t)
        with spans.span("sync"):
            final = float(loss)
        win = spans.epoch()
        del state
        return final, win, occupancy

    loss_sync, win_sync, _ = run(prefetched=False)
    loss_pf, win_pf, occupancy = run(prefetched=True)
    frac_s, frac_p = win_sync["fractions"], win_pf["fractions"]
    return {
        "depth": depth,
        "steps": steps,
        "sync_input_share": round(
            frac_s.get("data", 0.0) + frac_s.get("h2d", 0.0), 4
        ),
        "prefetch_stall_share": round(frac_p.get("prefetch_stall", 0.0), 4),
        "prefetch_occupancy": round(occupancy, 3) if occupancy is not None else None,
        "sync_wall_s": round(win_sync["total_s"], 4),
        "prefetch_wall_s": round(win_pf["total_s"], 4),
        "loss_bit_identical": loss_sync == loss_pf,
        "final_loss": round(loss_pf, 6),
    }


def bench_obs_overhead(cfg, strategy, batch, steps=48, checksum_every=8):
    """Flight-recorder + divergence-checksum overhead on the headline step.

    Runs the same compiled train step over the same batch for `steps`
    iterations twice, from identical initial states: once bare, once with
    the round-8 observability layer active — a FlightRecorder record per
    step plus an in-jit state checksum (with its D2H sync) every
    `checksum_every` steps, the exact per-step work fit() adds with
    `--divergence_check_freq`. Reports both walls, the overhead fraction
    (the <1% claim docs/DESIGN.md makes, now measured per run), and
    whether the final losses are bit-identical (they must be: the
    recorder only observes, and the checksum is a separate jitted
    program that never touches the training state).
    """
    import time as _time

    import jax

    from tools.bench_ladder import make_batch, setup_step
    from tpukit.obs import FlightRecorder, format_checksum, make_state_checksum

    seq = cfg.max_position_embeddings
    rng = np.random.RandomState(3)
    b, t = make_batch(rng, cfg.vocab_size, batch, seq - 1)

    def run(instrumented: bool):
        train_step, state, _, _ = setup_step(cfg, strategy)
        state, loss = train_step(state, b, t)  # compile + warm, untimed
        jax.block_until_ready(loss)
        rec = FlightRecorder() if instrumented else None
        checksum_fn = make_state_checksum() if instrumented else None
        if checksum_fn is not None:
            # compile the checksum program outside the timed window, the
            # same one-off cost fit() pays at its first check step
            jax.block_until_ready(checksum_fn(state)["params"])
        last_ck = pending = None
        t0 = _time.perf_counter()
        for i in range(1, steps + 1):
            state, loss = train_step(state, b, t)
            if rec is not None:
                rec.record("step", step=i)
                if i % checksum_every == 0:
                    pending = (i, checksum_fn(state))  # async dispatch
            if i % checksum_every == 0:
                float(loss)  # the PRINT_FREQ window sync BOTH paths pay
                if pending is not None:
                    # fit's deferred D2H read at the window boundary
                    last_ck = format_checksum(pending[1])
                    rec.record("divergence_check", step=pending[0], checksum=last_ck)
                    pending = None
        final = float(loss)  # drains the dispatch pipeline inside the timing
        wall = _time.perf_counter() - t0
        del state
        return final, wall, last_ck

    loss_off, wall_off, _ = run(False)
    loss_on, wall_on, last_ck = run(True)
    return {
        "steps": steps,
        "checksum_every": checksum_every,
        "baseline_wall_s": round(wall_off, 4),
        "instrumented_wall_s": round(wall_on, 4),
        "overhead_frac": round((wall_on - wall_off) / wall_off, 4),
        "loss_bit_identical": loss_off == loss_on,
        "final_loss": round(loss_on, 6),
        "last_checksum": last_ck,
    }


def bench_moe_ep_comm(cfg, n_dev, num_experts=8, steps=8):
    """Expert-parallel a2a dispatch audit + throughput on the available
    chips (round 10).

    Builds the moe_e8 shape on an ExpertParallel `(data, expert)` mesh with
    the explicit all_to_all dispatch, compiles the train step under a
    compiler-stderr capture, and reports:
      - expected vs measured per-device all-to-all payload (the closed-form
        `ExpertParallel.dispatch_comm` number against the optimized HLO) —
        hand-scheduling a collective means being able to predict its bytes;
      - the count of `[SPMD] Involuntary full rematerialization` warnings
        (zero is the bar — the round-5 einsum dispatch emitted a wall of
        them; meaningful on cold compiles, a cache hit emits none);
      - tokens/sec/chip through the a2a path, next to the xla-dispatch
        `moe_e8` headline so the two spellings stay comparable.
    On one chip the expert axis is 1 and no traffic crosses devices —
    expected == measured == 0 keeps the record honest rather than faked.
    """
    import math

    import jax

    from tools.bench_ladder import make_batch, setup_step, time_windows
    from tpukit.mesh import create_mesh
    from tpukit.obs import capture_compiler_stderr, collective_bytes
    from tpukit.shardings import ExpertParallel

    expert = math.gcd(n_dev, num_experts)
    grid = {"data": n_dev // expert, "expert": expert}
    strat = ExpertParallel(create_mesh(grid), dispatch="a2a")
    cfg_m = cfg.replace(num_experts=num_experts)
    seq = cfg.max_position_embeddings
    batch = 32 * n_dev
    b, t = make_batch(np.random.RandomState(5), cfg.vocab_size, batch, seq - 1)
    with capture_compiler_stderr() as cap:
        step, state, shapes, _ = setup_step(cfg_m, strat)
        struct = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
        compiled = step.lower(
            shapes, jax.tree.map(struct, b), struct(t)
        ).compile()
    measured = collective_bytes(compiled.as_text()).get(
        "all-to-all", {"count": 0, "bytes": 0}
    )
    backend = jax.default_backend()
    # dtype-aware expectation (round 12): the closed form prices in the
    # backend's wire dtype (XLA:CPU upcasts bf16 payloads to f32), so the
    # byte comparison is EXACT on every backend — the old cpu 2x allowance
    # is gone, a drift is a drift.
    expected = strat.dispatch_comm(
        cfg_m, global_batch=batch, seq=seq - 1, backend=backend
    )["train"]
    # time the COMPILED executable: the AOT path does not
    # populate the jit call cache, so timing `step` would recompile
    times, state, loss = time_windows(
        compiled, state, b, t, steps=steps, windows=3, warmup=2
    )
    del state
    bytes_match = (
        measured["count"] == expected["count"]
        and measured["bytes"] == expected["bytes"]
    )
    return {
        "mesh": grid,
        "dispatch": "a2a",
        "backend": backend,
        "expected_a2a": {"count": expected["count"], "bytes": expected["bytes"]},
        "measured_a2a": measured,
        "bytes_match": bytes_match,
        "involuntary_remat_warnings": cap["involuntary_remat"],
        "tokens_per_sec_per_chip": round(steps * batch * (seq - 1) / min(times) / n_dev, 1),
        "final_loss": round(loss, 6),
    }


def bench_moe_dispatch_ladder(cfg, n_dev, num_experts=8, steps=8):
    """FLOP-normalized MoE dispatch ladder (ROADMAP #3, round 11): xla vs
    a2a vs pallas at the e8 shape, top-1 AND top-2. Each rung reports
    tokens/s/chip and an MFU normalized by ACTIVE FLOPs
    (`obs.moe_active_flops_per_token`: top_k routed experts + router per
    token — the dropless convention), so a dataflow that burns MXU cycles
    on capacity padding or one-hot dispatch einsums shows as LOST MFU at
    equal tokens/s instead of hiding inside a bigger FLOP count. "xla" and
    "pallas" run meshless (the single-chip spellings); "a2a" runs through
    ExpertParallel, whose 1-way expert axis on one chip keeps the same
    capacity-buffer dataflow without collectives. Per-rung failures land
    as {"dispatch", "top_k", "error"} entries — a broken rung cannot hide
    behind a clean rc=0."""
    import math

    from tools.bench_ladder import make_batch, setup_step, time_windows
    from tpukit.mesh import create_mesh
    from tpukit.obs import moe_active_flops_per_token, peak_flops_per_chip
    from tpukit.shardings import DataParallel, ExpertParallel, SingleDevice

    seq = cfg.max_position_embeddings
    batch = 32 * n_dev
    peak = peak_flops_per_chip()
    rows = []
    for top_k in (1, 2):
        for dispatch in ("xla", "a2a", "pallas"):
            cfg_m = cfg.replace(num_experts=num_experts, router_top_k=top_k)
            try:
                if dispatch == "a2a":
                    expert = math.gcd(n_dev, num_experts)
                    strat = ExpertParallel(
                        create_mesh(
                            {"data": n_dev // expert, "expert": expert}
                        ),
                        dispatch="a2a",
                    )
                else:
                    cfg_m = cfg_m.replace(moe_dispatch=dispatch)
                    strat = DataParallel() if n_dev > 1 else SingleDevice()
                step, state, _, _ = setup_step(cfg_m, strat)
                b, t = make_batch(
                    np.random.RandomState(5), cfg.vocab_size, batch, seq - 1
                )
                times, state, loss = time_windows(
                    step, state, b, t, steps=steps, windows=3, warmup=2
                )
                del state
                tps_chip = steps * batch * (seq - 1) / min(times) / n_dev
                flops = moe_active_flops_per_token(cfg_m, seq - 1)
                rows.append({
                    "dispatch": dispatch,
                    "top_k": top_k,
                    "tokens_per_sec_per_chip": round(tps_chip, 1),
                    "active_flops_per_token": flops,
                    "mfu_active": (
                        round(tps_chip * flops / peak, 4) if peak else None
                    ),
                    "final_loss": round(loss, 6),
                })
            except Exception as exc:
                rows.append(
                    {"dispatch": dispatch, "top_k": top_k, "error": repr(exc)}
                )
                print(
                    f"moe ladder rung {dispatch}/top{top_k} failed: {exc!r}",
                    file=sys.stderr,
                )
    return rows


def bench_elastic_restore(cfg, n_dev):
    """Elastic restore probe (round 13, ROADMAP #5): save a sharded FSDP
    checkpoint over all chips, then restore it two ways — direct (same
    world) and RESHARDED onto a half-size mesh (tpukit/reshard.py) — and
    record what an elastic relaunch costs:

      - restore+reshard wall-clock and bytes/blocks read (the streaming
        reader should read each byte once);
      - peak host RSS delta across the reshard (ru_maxrss high-water),
        plus `rss_overhead_bytes` = delta minus the state's own bytes:
        on CPU backends the restored arrays themselves live in process
        heap, so the DELTA is ~state_bytes on every healthy run — the
        OVERHEAD is the signal. The streaming pass bounds scratch memory
        by one leaf's blocks, so overhead near zero is healthy and
        overhead near +state_bytes means a second full copy was
        materialized (the regression this probe exists to catch);
      - a parity bit: the resharded state's leaves must be BYTE-identical
        to the direct restore's (resharding moves data, never math).

    Needs >= 2 chips to have a smaller world to land on; on one chip the
    record carries an honest error instead of a faked number."""
    import resource
    import shutil
    import tempfile

    import jax

    from tools.bench_ladder import setup_step
    from tpukit import checkpoint as ckpt_lib
    from tpukit import reshard as reshard_lib
    from tpukit.mesh import create_mesh
    from tpukit.shardings import FSDP

    if n_dev < 2:
        return {"error": "needs >= 2 chips (no smaller world to reshard onto)"}
    src = FSDP(create_mesh({"data": n_dev}))
    _, state, shapes, _ = setup_step(cfg, src)
    state_bytes = sum(
        l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(state)
    )
    ckdir = tempfile.mkdtemp(prefix="tpukit-bench-resize-")
    try:
        path = ckpt_lib.save_sharded(
            state, ckdir, meta={"world": reshard_lib.current_world(src)}
        )
        tgt = FSDP(create_mesh({"data": n_dev // 2}, jax.devices()[: n_dev // 2]))
        t_sharding = tgt.state_sharding(shapes)
        # reshard FIRST, bracketed by the RSS high-water reads, so the
        # direct (parity-reference) restore's allocations cannot inflate
        # the delta attributed to the streaming pass
        rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        resized, info = reshard_lib.reshard_restore(path, shapes, t_sharding)
        rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        direct, _ = reshard_lib.reshard_restore(
            path, shapes, src.state_sharding(shapes)
        )
        parity = all(
            np.asarray(jax.device_get(a)).tobytes()
            == np.asarray(jax.device_get(b)).tobytes()
            for a, b in zip(
                jax.tree_util.tree_leaves(resized),
                jax.tree_util.tree_leaves(direct),
            )
        )
        del state, direct, resized
        # ru_maxrss is KiB on Linux, bytes on macOS; normalize to bytes
        rss_delta = int(
            (rss1 - rss0) * (1 if sys.platform == "darwin" else 1024)
        )
        return {
            "from_world": {"strategy": "fsdp", "devices": n_dev},
            "to_world": {"strategy": "fsdp", "devices": n_dev // 2},
            "state_bytes": int(state_bytes),
            "restore_wall_s": round(info["wall_s"], 4),
            "bytes_read": int(info["bytes_read"]),
            "blocks_read": int(info["blocks_read"]),
            "peak_rss_delta_bytes": rss_delta,
            # the signal: scratch above the restored state's own residency
            # (on CPU the restored arrays ARE host RAM; on TPU they are
            # not, and overhead simply reads lower — still comparable
            # across rounds on the same backend)
            "rss_overhead_bytes": rss_delta - int(state_bytes),
            "parity_ok": bool(parity),
        }
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)


def bench_serving(cfg, n_dev, requests=32, slots=8, max_new=16):
    """Continuous batching vs serial per-request `generate` on the SAME
    seeded synthetic stream (round 14, ROADMAP #1 — the >= 2x bar).

    All sides serve identical requests from identical params: the engine
    admits into `slots` KV-ring lanes mid-decode (batched bucketed
    prefills, quantum cached decode steps); the baselines decode one
    request at a time, each waiting for every request before it — the
    pre-round-14 serving story. TWO serial baselines are reported so the
    headline can't hide behind baseline choice:

      - "serial": per-request `generate` AS SHIPPED — its use_cache
        auto-resolve picks the naive full-re-forward loop at these
        buffer widths (the v5e-tuned threshold), exactly what serving
        through the training-era API costs.
      - "serial_cached": the STRONGEST serial spelling — the fused
        single-sequence KV-cached while_loop (`use_cache=True`), zero
        host round-trips per token.

    Each side runs twice (warm-up absorbs compiles — the stream's prompt
    lengths are drawn from a fixed set so the serial paths' per-length
    compiles are bounded); the measured run reports tokens/s, end-to-end
    p50/p99 (arrivals all at t=0, so serial queue wait IS the latency
    story), per-token p50/p99 and slot occupancy. `speedup` is
    continuous vs "serial" (the acceptance bar's baseline);
    `speedup_vs_cached` is the honest harder ratio."""
    import time

    import jax
    import jax.numpy as jnp

    from tpukit.data import get_tokenizer
    from tpukit.model import init_params
    from tpukit.sampling import _decode_loop, _decode_loop_cached
    from tpukit.serve import ServeConfig, ServeEngine, synthetic_request_stream

    tokenizer = get_tokenizer()
    tokenizer.pad_token_id = 2
    cfg = cfg.replace(vocab_size=tokenizer.vocab_size)
    params = init_params(jax.random.PRNGKey(0), cfg)
    # buckets == the drawn length set: prompts prefill at their exact
    # length, so the comparison shows scheduling wins, not padding losses
    buckets = lengths = (8, 16, 24, 32)
    eos = int(tokenizer.eos_token_id)
    stream = synthetic_request_stream(
        tokenizer, requests, seed=0, max_new_tokens=max_new,
        buckets=buckets, lengths=lengths,
    )
    serve = ServeConfig(slots=slots, buckets=buckets, max_new_tokens=max_new,
                        window_steps=10**9)  # no window records in the bench

    def run_continuous():
        eng = ServeEngine(params, cfg, serve, eos_id=eos)
        t0 = time.perf_counter()
        comps = eng.run(list(stream), max_wall_s=900)
        wall = time.perf_counter() - t0
        gen = sum(c.generated for c in comps)
        e2e = np.asarray([c.e2e_s for c in comps])
        tok = np.asarray([c.per_token_s for c in comps])
        s = eng.last_summary
        return dict(
            tokens_per_sec=round(gen / wall, 1), wall_s=round(wall, 3),
            generated_tokens=gen,
            p50_e2e_s=round(float(np.percentile(e2e, 50)), 4),
            p99_e2e_s=round(float(np.percentile(e2e, 99)), 4),
            p50_token_s=round(float(np.percentile(tok, 50)), 5),
            p99_token_s=round(float(np.percentile(tok, 99)), 5),
            mean_occupancy=round(s["mean_occupancy"], 3),
            prefill_s=round(s["prefill_s"], 3),
            decode_s=round(s["decode_s"], 3),
        )

    def run_serial(decode_fn):
        t0 = time.perf_counter()
        gen, finish = 0, []
        for r in stream:
            ids = np.asarray(r.ids, np.int32)
            buf = np.zeros((1, len(ids) + max_new), np.int32)
            buf[0, : len(ids)] = ids
            out, length = decode_fn(
                params, cfg, jnp.asarray(buf), len(ids), max_new, eos
            )
            gen += int(length) - len(ids)
            finish.append(time.perf_counter() - t0)
        wall = time.perf_counter() - t0
        e2e = np.asarray(finish)  # arrivals at t=0: wait-in-line included
        return dict(
            tokens_per_sec=round(gen / wall, 1), wall_s=round(wall, 3),
            generated_tokens=gen,
            p50_e2e_s=round(float(np.percentile(e2e, 50)), 4),
            p99_e2e_s=round(float(np.percentile(e2e, 99)), 4),
        )

    run_continuous()  # warm: bucket prefills + the decode step compile
    cont = run_continuous()
    run_serial(_decode_loop)  # warm: one compile per distinct prompt length
    ser = run_serial(_decode_loop)
    run_serial(_decode_loop_cached)
    ser_cached = run_serial(_decode_loop_cached)
    return {
        "requests": requests, "slots": slots, "buckets": list(buckets),
        "max_new_tokens": max_new,
        "generated_tokens": cont["generated_tokens"],
        "decode_quantum": serve.decode_quantum,
        "continuous": cont, "serial": ser, "serial_cached": ser_cached,
        "speedup": round(cont["tokens_per_sec"] / ser["tokens_per_sec"], 2)
        if ser["tokens_per_sec"] else None,
        "speedup_vs_cached": round(
            cont["tokens_per_sec"] / ser_cached["tokens_per_sec"], 2
        ) if ser_cached["tokens_per_sec"] else None,
    }


def bench_serve_trace_overhead(cfg, n_dev, requests=32, slots=8, max_new=16):
    """Request-trace recorder overhead on the serving engine (round 20):
    the SAME seeded stream served twice, tracer off then on, after a warm
    pass that absorbs compiles. The tracer is host-side only — a dict +
    deque append per span event — so the acceptance bar is a tokens/s
    delta under 1% AND bit-identical output tokens per request (the
    recorder observes, it never schedules). Also reports the event count
    and ring drops so capacity sizing stays honest."""
    import time

    import jax

    from tpukit.data import get_tokenizer
    from tpukit.model import init_params
    from tpukit.obs import TraceRecorder
    from tpukit.serve import ServeConfig, ServeEngine, synthetic_request_stream

    tokenizer = get_tokenizer()
    tokenizer.pad_token_id = 2
    cfg = cfg.replace(vocab_size=tokenizer.vocab_size)
    params = init_params(jax.random.PRNGKey(0), cfg)
    buckets = lengths = (8, 16, 24, 32)
    eos = int(tokenizer.eos_token_id)
    stream = list(synthetic_request_stream(
        tokenizer, requests, seed=0, max_new_tokens=max_new,
        buckets=buckets, lengths=lengths,
    ))
    serve = ServeConfig(slots=slots, buckets=buckets, max_new_tokens=max_new,
                        window_steps=10**9)

    def run(traced: bool):
        tracer = TraceRecorder() if traced else None
        eng = ServeEngine(params, cfg, serve, eos_id=eos, tracer=tracer)
        t0 = time.perf_counter()
        comps = eng.run(list(stream), max_wall_s=900)
        wall = time.perf_counter() - t0
        gen = sum(c.generated for c in comps)
        toks = {c.rid: [int(x) for x in np.asarray(c.ids)] for c in comps}
        return gen / wall, toks, tracer

    run(False)  # warm: bucket prefills + the decode step compile
    tps_off, toks_off, _ = run(False)
    tps_on, toks_on, tracer = run(True)
    return {
        "requests": requests, "slots": slots, "max_new_tokens": max_new,
        "tokens_per_sec_off": round(tps_off, 1),
        "tokens_per_sec_on": round(tps_on, 1),
        "overhead_frac": round((tps_off - tps_on) / tps_off, 4)
        if tps_off else None,
        "tokens_bit_identical": toks_off == toks_on,
        "events_emitted": tracer.total_emitted,
        "events_dropped": tracer.dropped,
    }


def bench_metrics_overhead(cfg, n_dev, requests=32, slots=8, max_new=16):
    """Metrics-plane overhead on the serving engine (round 22): the SAME
    seeded stream served twice, registry off (--no_metrics) then on,
    after a warm pass that absorbs compiles. The metrics plane is a pure
    observer — counters/gauges/histograms DERIVED from completions the
    engine computes anyway — so the acceptance bar is the round-20
    discipline verbatim: tokens/s delta under 1% AND bit-identical
    output tokens per request. The atomic snapshot publish + merge (the
    only new I/O) is timed separately so dir-publish cost can't hide
    inside the throughput delta."""
    import tempfile
    import time

    import jax

    from tpukit.data import get_tokenizer
    from tpukit.model import init_params
    from tpukit.obs import MetricRegistry, merge_snapshot_dir, publish_snapshot
    from tpukit.serve import ServeConfig, ServeEngine, synthetic_request_stream

    tokenizer = get_tokenizer()
    tokenizer.pad_token_id = 2
    cfg = cfg.replace(vocab_size=tokenizer.vocab_size)
    params = init_params(jax.random.PRNGKey(0), cfg)
    buckets = lengths = (8, 16, 24, 32)
    eos = int(tokenizer.eos_token_id)
    stream = list(synthetic_request_stream(
        tokenizer, requests, seed=0, max_new_tokens=max_new,
        buckets=buckets, lengths=lengths,
    ))
    serve = ServeConfig(slots=slots, buckets=buckets, max_new_tokens=max_new,
                        window_steps=10**9)

    def run(with_metrics: bool):
        metrics = MetricRegistry() if with_metrics else None
        eng = ServeEngine(params, cfg, serve, eos_id=eos, metrics=metrics)
        t0 = time.perf_counter()
        comps = eng.run(list(stream), max_wall_s=900)
        wall = time.perf_counter() - t0
        gen = sum(c.generated for c in comps)
        toks = {c.rid: [int(x) for x in np.asarray(c.ids)] for c in comps}
        return gen / wall, toks, metrics

    run(False)  # warm: bucket prefills + the decode step compile
    tps_off, toks_off, _ = run(False)
    tps_on, toks_on, metrics = run(True)
    snap = metrics.snapshot()
    series = (len(snap["counters"]) + len(snap["gauges"])
              + len(snap["hists"]))
    with tempfile.TemporaryDirectory() as d:
        t0 = time.perf_counter()
        publish_snapshot(d, 0, metrics, time_s=time.time())
        merge_snapshot_dir(d)
        publish_s = time.perf_counter() - t0
    return {
        "requests": requests, "slots": slots, "max_new_tokens": max_new,
        "tokens_per_sec_off": round(tps_off, 1),
        "tokens_per_sec_on": round(tps_on, 1),
        "overhead_frac": round((tps_off - tps_on) / tps_off, 4)
        if tps_off else None,
        "tokens_bit_identical": toks_off == toks_on,
        "series": series,
        "snapshot_publish_s": round(publish_s, 6),
    }


def bench_serve_dispatch_attribution(cfg, n_dev, requests=32, slots=8,
                                     max_new=16):
    """Per-quantum dispatch-vs-device attribution on a traced serving run
    (round 20): where does a decode quantum's wall actually go — the
    host-side async-dispatch loop (`dispatch_overhead_s`, the [t0,t1]
    walls of the trace's quantum events) or waiting for the device at the
    per-quantum sync (`device_s`, the [s0,s1] walls)? Derived from spans
    the engine times anyway, so the record costs nothing beyond the
    traced run itself. On CPU loopback the "device" is the host too, so
    the split reads as loop-vs-XLA-compute; the per-quantum means are the
    transferable numbers."""
    import time

    import jax

    from tpukit.data import get_tokenizer
    from tpukit.model import init_params
    from tpukit.obs import TraceRecorder, build_trees, completeness
    from tpukit.serve import ServeConfig, ServeEngine, synthetic_request_stream

    tokenizer = get_tokenizer()
    tokenizer.pad_token_id = 2
    cfg = cfg.replace(vocab_size=tokenizer.vocab_size)
    params = init_params(jax.random.PRNGKey(0), cfg)
    buckets = lengths = (8, 16, 24, 32)
    eos = int(tokenizer.eos_token_id)
    stream = list(synthetic_request_stream(
        tokenizer, requests, seed=0, max_new_tokens=max_new,
        buckets=buckets, lengths=lengths,
    ))
    serve = ServeConfig(slots=slots, buckets=buckets, max_new_tokens=max_new,
                        window_steps=10**9)

    def run():
        tracer = TraceRecorder()
        eng = ServeEngine(params, cfg, serve, eos_id=eos, tracer=tracer)
        t0 = time.perf_counter()
        comps = eng.run(list(stream), max_wall_s=900)
        wall = time.perf_counter() - t0
        return eng, tracer, comps, wall

    run()  # warm: absorbs compiles so the split reflects steady state
    eng, tracer, comps, wall = run()
    s = eng.last_summary or {}
    quanta = [e for e in tracer.snapshot() if e.get("ev") == "quantum"]
    disp = sum(q["t1"] - q["t0"] for q in quanta)
    dev = sum(q["s1"] - q["s0"] for q in quanta if "s1" in q)
    tot = disp + dev
    trees = build_trees(tracer.snapshot())
    return {
        "requests": requests, "slots": slots, "max_new_tokens": max_new,
        "decode_quantum": serve.decode_quantum,
        "quanta": len(quanta),
        "wall_s": round(wall, 3),
        "dispatch_overhead_s": round(disp, 4),
        "device_s": round(dev, 4),
        "dispatch_frac": round(disp / tot, 4) if tot else None,
        "mean_dispatch_ms_per_quantum": round(1e3 * disp / len(quanta), 3)
        if quanta else None,
        "mean_device_ms_per_quantum": round(1e3 * dev / len(quanta), 3)
        if quanta else None,
        # the summary's span-derived split must agree with the trace's
        "summary_dispatch_overhead_s": round(s.get("dispatch_overhead_s", 0.0), 4),
        "summary_device_s": round(s.get("device_s", 0.0), 4),
        "trace_complete": completeness(trees),
        "completed": len(comps),
    }


def bench_decode_fused(cfg, n_dev, requests=24, slots=4, max_new=12,
                       window=8):
    """Fused-decode ladder (round 21, ROADMAP #2/#4): the two wins behind
    `--fused_decode`, measured SEPARATELY so neither can hide behind the
    other:

      - "unfused_q1" vs "fused_q1" (both at decode_quantum=1): the pure
        KERNEL delta — the per-layer XLA gather+attend against the fused
        paged-attention pallas_call, with the host dispatch cadence held
        identical. On a real TPU this is the no-materialized-view win; on
        CPU loopback the kernel runs in pallas INTERPRET mode (a scan
        over the grid) and is honestly SLOWER — the ratio still lands in
        the record because hiding it would defeat the point.
      - "fused_q1" vs "fused_loop" (decode_quantum=window): the
        DISPATCH-AMORTIZATION delta — the same kernel, but the scheduler
        state machine lives on device and one `while_loop` dispatch
        covers the whole window. The round-20 attribution priced the
        per-quantum host overhead at ~0.3 ms against ~0.7 ms device
        work; this ratio is that attribution cashed in, and because the
        kernel cost is IDENTICAL in numerator and denominator the
        interpret-mode slowness cancels — the amortization number
        transfers from this container.

    Every rung reruns the round-20 trace plumbing (quantum spans carry
    the device-reported tick count for the loop rung), so the record
    cross-checks mean per-quantum dispatch/device walls against the
    `serve_dispatch_attribution` record, and `parity_ok` pins all three
    rungs token-identical per request."""
    import time

    import jax

    from tpukit.data import get_tokenizer
    from tpukit.model import init_params
    from tpukit.obs import TraceRecorder, build_trees, completeness
    from tpukit.serve import ServeConfig, ServeEngine, synthetic_request_stream

    import jax.numpy as jnp

    tokenizer = get_tokenizer()
    tokenizer.pad_token_id = 2
    # f32 compute: the parity bit across rungs is exact-token equality
    cfg = cfg.replace(vocab_size=tokenizer.vocab_size,
                      compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    buckets = lengths = (8, 16)
    page = 8
    eos = int(tokenizer.eos_token_id)
    stream = list(synthetic_request_stream(
        tokenizer, requests, seed=0, max_new_tokens=max_new,
        buckets=buckets, lengths=lengths,
    ))
    pages = slots * (-(-(max(buckets) + max_new) // page)) + 1

    def run(fused, quantum):
        serve = ServeConfig(
            slots=slots, buckets=buckets, max_new_tokens=max_new,
            window_steps=10**9, page_size=page, num_pages=pages,
            fused_decode=fused, decode_quantum=quantum,
        )
        ServeEngine(params, cfg, serve, eos_id=eos).run(
            list(stream), max_wall_s=900)  # warm: absorbs compiles
        tracer = TraceRecorder()
        eng = ServeEngine(params, cfg, serve, eos_id=eos, tracer=tracer)
        t0 = time.perf_counter()
        comps = eng.run(list(stream), max_wall_s=900)
        wall = time.perf_counter() - t0
        gen = sum(c.generated for c in comps)
        quanta = [e for e in tracer.snapshot() if e.get("ev") == "quantum"]
        disp = sum(q["t1"] - q["t0"] for q in quanta)
        dev = sum(q["s1"] - q["s0"] for q in quanta if "s1" in q)
        rec = {
            "tokens_per_sec": round(gen / wall, 1),
            "wall_s": round(wall, 3),
            "generated_tokens": gen,
            "quanta": len(quanta),
            "decode_steps": eng.steps,
            "mean_dispatch_ms_per_quantum": round(1e3 * disp / len(quanta), 3)
            if quanta else None,
            "mean_device_ms_per_quantum": round(1e3 * dev / len(quanta), 3)
            if quanta else None,
            "trace_complete": completeness(build_trees(tracer.snapshot())),
        }
        return rec, {c.rid: list(map(int, c.ids)) for c in comps}

    unfused, toks_u = run(False, 1)
    fused_q1, toks_f1 = run(True, 1)
    fused_loop, toks_fl = run(True, window)
    return {
        "requests": requests, "slots": slots, "max_new_tokens": max_new,
        "page_size": page, "window_quanta": window,
        "unfused_q1": unfused, "fused_q1": fused_q1,
        "fused_loop": fused_loop,
        "parity_ok": bool(toks_u == toks_f1 == toks_fl),
        # the kernel win (interpret-mode CPU: expect < 1, stated honestly)
        "kernel_speedup": round(
            fused_q1["tokens_per_sec"] / unfused["tokens_per_sec"], 3)
        if unfused["tokens_per_sec"] else None,
        # the dispatch-amortization win (kernel cost cancels: transfers)
        "amortization_speedup": round(
            fused_loop["tokens_per_sec"] / fused_q1["tokens_per_sec"], 3)
        if fused_q1["tokens_per_sec"] else None,
    }


def bench_paged_kv(cfg, n_dev, requests=24, max_new=12, slots=4):
    """Paged-KV ladder (round 15, ROADMAP #2): ring vs paged vs paged+int8
    at EQUAL KV HBM, on the same seeded stream.

    The ring rung is the round-14 engine (per-slot full-width KV). The
    paged rungs get a page pool sized to the ring's exact byte budget
    (`serve.paged.pool_bytes`), so every difference is layout, not a
    bigger memory grant:

      - "paged" (f32 pages, same slot count): the parity rung — tokens
        must be identical to the ring rung per request (`parity_ok`, the
        acceptance bar's exactness bit) at ~equal throughput.
      - "paged_int8": pages cost ~1/4 the bytes (int8 payload + packed
        f32 block scales), so the same HBM holds ~4x pages; lanes are
        raised to 4x the ring slots and `max_live_slots` measures how
        many requests actually decode CONCURRENTLY — the >= 2x
        slots-at-equal-HBM acceptance bar, with `int8_token_agreement`
        (mean per-request match vs the exact paged rung) as the honest
        quality sidecar.

    The prefix rung re-serves the paged config on a stream whose requests
    share one system prompt: admissions that hit the prefix registry skip
    the shared prefill chunks, and the record carries measured
    hit-vs-cold admit latency plus the hit count."""
    import time

    import jax

    from tpukit.data import get_tokenizer
    from tpukit.model import init_params
    from tpukit.serve import ServeConfig, ServeEngine, synthetic_request_stream
    from tpukit.serve import paged as paged_lib

    import jax.numpy as jnp

    tokenizer = get_tokenizer()
    tokenizer.pad_token_id = 2
    # f32 compute for the whole ladder: the ring stores the COMPUTE dtype
    # while pages store kv_dtype, so a bf16 ring against f32 pages would
    # dtype-confound the equal-HBM sizing (half the token capacity for
    # the parity rung, ~2x instead of ~4x pages for int8) — at f32 the
    # ring and the f32-page rung are byte-comparable and the int8 ratio
    # is the honest payload win.
    cfg = cfg.replace(vocab_size=tokenizer.vocab_size,
                      compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    buckets = lengths = (8, 16)
    page = 8  # page * head_dim is a 256 multiple at the ladder head_dim=32
    eos = int(tokenizer.eos_token_id)
    stream = synthetic_request_stream(
        tokenizer, requests, seed=0, max_new_tokens=max_new,
        buckets=buckets, lengths=lengths,
    )

    def run(serve, reqs):
        ServeEngine(params, cfg, serve, eos_id=eos).run(list(reqs), max_wall_s=900)
        eng = ServeEngine(params, cfg, serve, eos_id=eos)  # measured: warm jits
        t0 = time.perf_counter()
        comps = eng.run(list(reqs), max_wall_s=900)
        wall = time.perf_counter() - t0
        gen = sum(c.generated for c in comps)
        s = eng.last_summary
        rec = dict(
            tokens_per_sec=round(gen / wall, 1), wall_s=round(wall, 3),
            generated_tokens=gen, slots=serve.slots,
            max_live_slots=s["max_live_slots"], kv_bytes=s["kv_bytes"],
        )
        return rec, {c.rid: list(map(int, c.ids)) for c in comps}, s

    ring_cfg = ServeConfig(slots=slots, buckets=buckets,
                           max_new_tokens=max_new, window_steps=10**9)
    ring, ring_toks, _ = run(ring_cfg, stream)

    per_page_f32 = paged_lib.pool_bytes(cfg, 1, page, "f32")
    per_page_int8 = paged_lib.pool_bytes(cfg, 1, page, "int8")
    min_pages = -(-(max(buckets) + max_new) // page) + 1  # one request + null
    paged_cfg = ServeConfig(
        slots=slots, buckets=buckets, max_new_tokens=max_new,
        window_steps=10**9, page_size=page,
        num_pages=max(ring["kv_bytes"] // per_page_f32, min_pages),
    )
    paged, paged_toks, _ = run(paged_cfg, stream)
    parity = ring_toks == paged_toks

    int8_cfg = ServeConfig(
        slots=4 * slots, buckets=buckets, max_new_tokens=max_new,
        window_steps=10**9, page_size=page, kv_dtype="int8",
        num_pages=max(ring["kv_bytes"] // per_page_int8, min_pages),
    )
    int8, int8_toks, _ = run(int8_cfg, stream)
    agree = [
        float(np.mean(np.asarray(int8_toks[r][:m]) == np.asarray(paged_toks[r][:m])))
        for r in paged_toks
        for m in [min(len(int8_toks[r]), len(paged_toks[r]))]
        if m
    ]

    shared = synthetic_request_stream(
        tokenizer, requests, seed=0, max_new_tokens=max_new,
        buckets=buckets, lengths=lengths, shared_prefix=page,
    )
    _, _, psum = run(paged_cfg, shared)
    return {
        "requests": requests, "buckets": list(buckets), "page_size": page,
        "max_new_tokens": max_new,
        "ring": ring, "paged": paged, "paged_int8": int8,
        "parity_ok": bool(parity),
        "int8_token_agreement": round(float(np.mean(agree)), 4) if agree else None,
        "slots_at_equal_hbm_ratio": round(
            int8["max_live_slots"] / max(ring["max_live_slots"], 1), 2
        ),
        "prefix": {
            "hits": psum.get("prefix_hits"),
            "hit_rate": psum.get("prefix_hit_rate"),
            "pages_reused": psum.get("prefix_pages_reused"),
            "admit_latency_hit_s": psum.get("admit_latency_hit_s"),
            "admit_latency_cold_s": psum.get("admit_latency_cold_s"),
        },
    }


def bench_fleet_serving(cfg, n_dev, requests=32, slots=4, max_new=12):
    """Fleet scaling curve (round 19, ROADMAP #1): 1 vs 2 vs 4 engine
    replicas on the SAME seeded stream at EQUAL total devices — the
    router's capacity story. Each rung carves the device list into
    disjoint per-replica subsets (8 devices = 1x8, 2x4, 4x2; grids from
    `fleet.pick_serve_grid`), serves the identical stream, and reports
    fleet tokens/s, p99 e2e under load, and per-request token parity vs
    the 1-replica rung (the fleet bar: routing must never change a
    token). The 2-replica rung is the acceptance rung (>1.5x the
    1-replica tokens/s at equal total devices).

    The second half measures DISAGGREGATED vs COLOCATED prefill on the
    2-replica paged configuration over a shared-system-prompt stream:
    mean admit latency (slot-assignment to decode-ready — what moving
    prefill off the decode replicas buys them) plus handoff/prefix-hit
    counts.

    HONEST CPU CAVEAT (in-record as `caveat`, the comm_overlap
    discipline): on virtual CPU devices the per-replica "grids" share
    host cores and collectives are loopback memcpys, so the scaling
    curve measures the ROUTER (scheduling, admission, dispatch overlap
    across subsets), not interconnect physics; on real chips the
    per-replica model-parallel speedup stacks on top. With fewer than 4
    devices the rungs run meshless replicas (router identical, grids
    trivial)."""
    import time

    import jax

    from tpukit.data import get_tokenizer
    from tpukit.model import init_params
    from tpukit.serve import (
        FleetConfig,
        FleetRouter,
        ServeConfig,
        synthetic_request_stream,
    )

    import jax.numpy as jnp

    tokenizer = get_tokenizer()
    tokenizer.pad_token_id = 2
    cfg = cfg.replace(vocab_size=tokenizer.vocab_size,
                      compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), params)
    buckets = lengths = (8, 16)
    eos = int(tokenizer.eos_token_id)
    stream = synthetic_request_stream(
        tokenizer, requests, seed=0, max_new_tokens=max_new,
        buckets=buckets, lengths=lengths,
    )
    serve = ServeConfig(slots=slots, buckets=buckets, max_new_tokens=max_new,
                        window_steps=10**9)
    meshed = n_dev >= 4

    def run_fleet(n_replicas, fleet_kw=None, serve_cfg=None, reqs=None):
        fc = FleetConfig(
            replicas=n_replicas,
            devices_per_replica=(n_dev // n_replicas) if meshed else 0,
            window_steps=10**9, **(fleet_kw or {}),
        )
        sv = serve_cfg or serve
        FleetRouter(host, cfg, sv, fc, eos_id=eos).run(
            list(reqs or stream), max_wall_s=900)  # warm compiles
        router = FleetRouter(host, cfg, sv, fc, eos_id=eos)
        t0 = time.perf_counter()
        comps = router.run(list(reqs or stream), max_wall_s=900)
        wall = time.perf_counter() - t0
        gen = sum(c.generated for c in comps)
        e2e = np.asarray([c.e2e_s for c in comps])
        admit = [c.admit_latency_s for c in comps]
        return dict(
            replicas=n_replicas,
            devices_per_replica=fc.devices_per_replica,
            tokens_per_sec=round(gen / wall, 1), wall_s=round(wall, 3),
            generated_tokens=gen,
            p50_e2e_s=round(float(np.percentile(e2e, 50)), 4),
            p99_e2e_s=round(float(np.percentile(e2e, 99)), 4),
            mean_admit_latency_s=round(float(np.mean(admit)), 5),
        ), {c.rid: list(map(int, c.ids)) for c in comps}, router.last_summary

    rungs, toks = [], {}
    for n_replicas in (1, 2, 4):
        if n_replicas > max(requests, 1):
            continue
        try:
            rec, t, _ = run_fleet(n_replicas)
            rungs.append(rec)
            toks[n_replicas] = t
        except Exception as exc:  # per-rung failures land in-record
            rungs.append({"replicas": n_replicas, "error": repr(exc)})
    parity = (1 in toks) and all(toks[n] == toks[1] for n in toks)
    by_n = {r["replicas"]: r for r in rungs if "error" not in r}
    scaling = (
        round(by_n[2]["tokens_per_sec"] / by_n[1]["tokens_per_sec"], 2)
        if 1 in by_n and 2 in by_n and by_n[1]["tokens_per_sec"] else None
    )

    # disaggregated vs colocated prefill: 2 replicas, paged pools, one
    # shared system prompt — what a dedicated prefill worker buys the
    # decode replicas' admit latency
    disagg = None
    try:
        page = 8
        paged_cfg = ServeConfig(
            slots=slots, buckets=buckets, max_new_tokens=max_new,
            window_steps=10**9, page_size=page,
        )
        shared = synthetic_request_stream(
            tokenizer, requests, seed=0, max_new_tokens=max_new,
            buckets=buckets, lengths=lengths, shared_prefix=page,
        )
        colo, _, _ = run_fleet(2, serve_cfg=paged_cfg, reqs=shared)
        dis, _, dsum = run_fleet(
            2, fleet_kw=dict(disagg_prefill=True), serve_cfg=paged_cfg,
            reqs=shared,
        )
        dp = (dsum or {}).get("disagg_prefill") or {}
        disagg = dict(
            colocated_admit_latency_s=colo["mean_admit_latency_s"],
            disagg_admit_latency_s=dis["mean_admit_latency_s"],
            colocated_tokens_per_sec=colo["tokens_per_sec"],
            disagg_tokens_per_sec=dis["tokens_per_sec"],
            handoffs=dp.get("handoffs"),
            worker_prefix_hits=dp.get("worker_prefix_hits"),
        )
    except Exception as exc:
        disagg = {"error": repr(exc)}

    return {
        "requests": requests, "slots_per_replica": slots,
        "buckets": list(buckets), "max_new_tokens": max_new,
        "total_devices": n_dev, "meshed": meshed,
        "rungs": rungs,
        "parity_ok": bool(parity),
        "scaling_2x_vs_1": scaling,
        "disagg_prefill": disagg,
        "caveat": (
            "CPU virtual devices: per-replica grids share host cores and "
            "collectives are loopback memcpys — the curve measures router "
            "scheduling + dispatch overlap, not interconnect physics"
            + ("" if meshed else "; <4 devices, so rungs ran MESHLESS "
               "replicas (trivial grids)")
        ),
    }


def _induction_train(cfg, tokenizer, steps, row_len, lr=3e-3, seed=7,
                     batch=8):
    """Train `cfg` on tiled-phrase rows — the `repetitive` stream profile
    as training data — so greedy decode learns induction (continue the
    repetition). Three details are load-bearing, all measured in
    round 17: (1) 2+ layers are the induction-head minimum; (2) `row_len`
    must cover the SERVING position range (prompt + decode budget +
    verify scratch) — position embeddings beyond the trained range are
    noise, and greedy continuations wander exactly there (acceptance
    0.34 vs 0.85 with the range covered); (3) the phrases must come from
    the DISTRIBUTION the serving stream tiles — short heads of the
    corpus stories, the templated-traffic family — not uniform random
    tokens: the acceptance rate is 0.30 (speedup 0.76x, speculation
    loses) with random-token phrases vs 0.99 (2.1x) in-domain, because
    greedy continuation of a repetition the model has never seen the
    token statistics of is exactly where it wanders. The training draws
    use their own seed, not the stream's — in-domain, not
    memorize-the-eval. Returns (state, final_loss) — the full train
    state so `tools/train_induction.py` can checkpoint it for the CI
    spec serve-smoke; bench rungs read `state.params`."""
    import optax

    from tools.bench_ladder import setup_step
    from tpukit.data import synthetic_stories

    # cosine decay to ~0: at a constant lr the greedy loops this probe
    # depends on stay fragile — the loss bounces around 0.1 and the
    # acceptance rate with it (measured 0.54..0.85 across retrains); a
    # decayed finish converges the induction behavior reproducibly
    step_fn, state, _, _ = setup_step(
        cfg, lr=optax.cosine_decay_schedule(lr, steps)
    )
    rng0 = np.random.RandomState(seed)
    enc = tokenizer(synthetic_stories(128), truncation=True,
                    max_length=8)["input_ids"]
    rows = []
    while len(rows) < 512:
        head = enc[rng0.randint(len(enc))]
        plen = min(int(rng0.randint(2, 5)), len(head))
        if plen < 2:
            continue
        phrase = np.asarray(head[:plen], np.int32)
        rows.append(np.tile(phrase, -(-(row_len + 1) // plen))[: row_len + 1])
    data = np.asarray(rows, np.int32)
    pos = np.ascontiguousarray(np.broadcast_to(
        np.arange(row_len, dtype=np.int32), (batch, row_len)))
    rng = np.random.RandomState(0)
    for _ in range(steps):
        idx = rng.randint(0, len(data), size=batch)
        mb = {"input_ids": data[idx, :row_len], "position_ids": pos,
              "mask": np.zeros((batch, row_len), dtype=bool)}
        state, loss = step_fn(state, mb, data[idx, 1 : row_len + 1])
    return state, float(loss)


def bench_spec_decode(cfg, n_dev, requests=24, slots=4, max_new=48, k=10):
    """Speculative decoding vs the vanilla engine (round 17, ROADMAP #3),
    end to end on the SAME seeded `repetitive` synthetic stream.

    Speculation is an optimization exactly when the target's next tokens
    are predictable, so the probe first makes them predictable the honest
    way: it TRAINS the target (and a smaller draft) into the regime
    templated/structured serving traffic puts a real model in — greedy
    loops that prompt-lookup drafting predicts (`_induction_train`). A
    random-init target accepts ~nothing and speculation rightly LOSES;
    that regime is visible in the CI serve smoke, not benched here.

    Rungs at temperature 0 and 0.8, each proposer vs the vanilla engine
    (all warm — engines constructed twice, second run measured, the
    round-14 serving-bench pattern): end-to-end tokens/s, acceptance
    rate, the appended-tokens-per-verify histogram, and the draft/verify
    wall split. `speedup` per rung is vs the SAME-temperature vanilla
    run. The acceptance bar is self-spec (ngram) at temperature 0
    >= 1.3x: the fused on-device proposal (spec.spec_ngram_step) keeps
    the host rhythm of one dispatch + one sync per quantum, so the win
    is k+1 tokens of emission capacity per target forward.

    k=10 because the verify dispatch is FIXED-COST dominated at bench
    shape on this backend (measured: 4.5 ms at k=8 vs 4.9 ms at k=12,
    vs 0.9 ms per one-token decode dispatch and the vanilla engine's
    decode_quantum=4 amortization) — a narrow window (k=6) caps the
    arithmetic at ~1.1x however high acceptance goes, while the
    induction-trained target's ~0.97 per-token greedy-match rate keeps
    the accepted prefix long enough for a wide window to pay."""
    import time

    import jax
    import jax.numpy as jnp

    from tpukit.data import get_tokenizer
    from tpukit.model import init_params
    from tpukit.serve import ServeConfig, ServeEngine, synthetic_request_stream

    tokenizer = get_tokenizer()
    tokenizer.pad_token_id = 2
    buckets = (16, 32)
    # serving positions: bucket 32 + 48 new + k scratch = 86
    row_len = max(buckets) + max_new + k + 2
    tgt_cfg = cfg.replace(
        dim=128, head_dim=32, heads=4, num_layers=4,
        vocab_size=tokenizer.vocab_size, max_position_embeddings=128,
        compute_dtype=jnp.float32, num_experts=0,
    )
    draft_cfg = tgt_cfg.replace(dim=32, head_dim=16, heads=2, num_layers=2)
    t0 = time.perf_counter()
    tgt_state, tgt_loss = _induction_train(tgt_cfg, tokenizer, 900, row_len)
    params = tgt_state.params
    draft_state, draft_loss = _induction_train(
        draft_cfg, tokenizer, 1500, row_len
    )
    draft_params = draft_state.params
    train_s = time.perf_counter() - t0
    eos = int(tokenizer.eos_token_id)
    stream = synthetic_request_stream(
        tokenizer, requests, seed=3, max_new_tokens=max_new,
        buckets=buckets, stream_profile="repetitive",
    )

    def run(draft, temperature):
        serve = ServeConfig(
            slots=slots, buckets=buckets, max_new_tokens=max_new,
            temperature=temperature, window_steps=10**9,
            draft=draft, spec_k=k,
        )
        kw = (dict(draft_params=draft_params, draft_cfg=draft_cfg)
              if draft == "model" else {})
        ServeEngine(params, tgt_cfg, serve, eos_id=eos, **kw).run(
            list(stream), max_wall_s=900)  # warm: compiles absorbed
        # steady state = best of 3 measured runs (the time_windows
        # min-of-windows convention — this shared CPU shows double-digit
        # run-to-run variance, and a ratio of two noisy walls is noisier
        # still); token streams are seed-deterministic, so every run
        # generates the identical tokens and only the wall moves
        walls = []
        for _ in range(3):
            eng = ServeEngine(params, tgt_cfg, serve, eos_id=eos, **kw)
            t0 = time.perf_counter()
            comps = eng.run(list(stream), max_wall_s=900)
            walls.append(time.perf_counter() - t0)
        wall = min(walls)
        gen = sum(c.generated for c in comps)
        out = dict(tokens_per_sec=round(gen / wall, 1),
                   wall_s=round(wall, 3),
                   wall_spread_s=round(max(walls) - wall, 3),
                   generated_tokens=gen, verify_steps=eng.steps)
        if draft:
            s = (eng.last_summary or {}).get("spec") or {}
            out.update(
                accept_rate=round(s["accept_rate"], 4)
                if s.get("accept_rate") is not None else None,
                proposed=s.get("proposed"), accepted=s.get("accepted"),
                accepted_hist=s.get("accepted_hist"),
                draft_s=round((eng.last_summary or {}).get("draft_s", 0.0), 3),
                verify_s=round((eng.last_summary or {}).get("verify_s", 0.0), 3),
            )
        return out

    rec = {
        "requests": requests, "slots": slots, "spec_k": k,
        "max_new_tokens": max_new, "buckets": list(buckets),
        "stream_profile": "repetitive",
        "train": {
            "target_loss": round(tgt_loss, 4),
            "draft_loss": round(draft_loss, 4),
            "train_s": round(train_s, 1),
        },
    }
    for label, temp in (("t0", 0.0), ("t0.8", 0.8)):
        van = run("", temp)
        rung = {"vanilla": van}
        for d in ("ngram", "model"):
            r = run(d, temp)
            r["speedup"] = (round(r["tokens_per_sec"] / van["tokens_per_sec"], 2)
                            if van["tokens_per_sec"] else None)
            rung[d] = r
        rec[label] = rung
    rec["speedup_ngram_t0"] = rec["t0"]["ngram"]["speedup"]
    return rec


def bench_quant_comm(cfg, n_dev, num_experts=8, steps=8):
    """Quantized-collective ladder (round 12, ROADMAP #2): f32 vs bf16 vs
    int8 `--comm_dtype` on each strategy with hand-wired quantized
    collectives — ddp (grad all-reduce), fsdp (grad reduce-scatter), ep
    (a2a dispatch payload). Each rung compiles the train step under a
    compiler-stderr capture and reports:

      - expected vs measured quantized payload bytes (the closed-form
        `grad_comm`/`dispatch_comm` numbers against the optimized HLO) and
        whether they match exactly;
      - ring-model bytes-on-the-wire (`obs.wire_bytes` — result payloads
        are not comparable across op KINDS, an all-reduce moves ~2x its
        result) plus the ratio vs the rung's f32 baseline: the ~4x cut is
        THE headline this record exists to publish;
      - involuntary-remat warning count (zero = the schedule did not
        change, only the payload — meaningful on cold compiles);
      - tokens/s/chip and the final-loss delta vs the f32 rung after
        `steps` identical steps — the tolerance-gate number (bit parity is
        impossible by construction; a small bounded delta is the
        correctness contract).

    On one chip the data/expert axes are 1-way: the wrappers keep the
    quantize/dequantize numerics but skip the collectives, so expected
    bytes are honestly zero rather than faked."""
    import math

    import jax

    from tools.bench_ladder import make_batch, setup_step, time_windows
    from tpukit.mesh import create_mesh
    from tpukit.obs import (
        capture_compiler_stderr,
        collective_bytes,
        wire_bytes,
    )
    from tpukit.shardings import DataParallel, ExpertParallel, FSDP

    seq = cfg.max_position_embeddings
    batch = 32 * n_dev
    expert = math.gcd(n_dev, num_experts)
    backend = jax.default_backend()
    struct = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731

    rungs = [
        ("ddp", lambda: DataParallel(create_mesh({"data": n_dev})),
         lambda dt: cfg.replace(comm_dtype=dt), n_dev),
        ("fsdp", lambda: FSDP(create_mesh({"data": n_dev})),
         lambda dt: cfg.replace(comm_dtype=dt), n_dev),
        ("ep", lambda: ExpertParallel(
            create_mesh({"data": n_dev // expert, "expert": expert}),
            dispatch="a2a"),
         lambda dt: cfg.replace(comm_dtype=dt, num_experts=num_experts),
         expert),
    ]
    rows = []
    for name, strat_fn, cfg_fn, world in rungs:
        f32_loss = f32_wire = None
        for dtype in ("f32", "bf16", "int8"):
            try:
                c = cfg_fn(dtype)
                strat = strat_fn()
                strat.validate_config(c)
                b, t = make_batch(
                    np.random.RandomState(5), cfg.vocab_size, batch, seq - 1
                )
                with capture_compiler_stderr() as cap:
                    step, state, shapes, _ = setup_step(c, strat)
                    compiled = step.lower(
                        shapes, jax.tree.map(struct, b), struct(t)
                    ).compile()
                coll = collective_bytes(compiled.as_text())
                if name == "ep":
                    # the EP rung's wire number AND its expectation isolate
                    # the a2a dispatch payload: the trunk's FSDP comm is
                    # identical across rungs (full precision by design) and
                    # would bury the dispatch cut in a shared constant
                    wire = wire_bytes(
                        {"all-to-all": coll.get("all-to-all")
                         or {"count": 0, "bytes": 0}},
                        world,
                    )
                    audit = strat.dispatch_comm(
                        c, global_batch=batch, seq=seq - 1, backend=backend
                    )
                    expected = (
                        {"all-to-all": {
                            "count": audit["train"]["count"],
                            "bytes": audit["train"]["bytes"],
                        }}
                        if audit
                        else None
                    )
                else:
                    wire = wire_bytes(coll, world)
                    expected = strat.grad_comm(c, shapes.params, backend=backend)
                exact = None
                if expected:
                    exact = all(
                        (coll.get(op) or {"count": 0, "bytes": 0}) == rec
                        for op, rec in expected.items()
                    )
                times, state, loss = time_windows(
                    compiled, state, b, t, steps=steps, windows=3, warmup=2
                )
                del state
                row = {
                    "strategy": name,
                    "comm_dtype": dtype,
                    "wire_bytes": wire,
                    "expected": expected,
                    "measured": {
                        op: coll.get(op)
                        for op in (expected or {})
                        if coll.get(op)
                    } or None,
                    "bytes_match": exact,
                    "involuntary_remat_warnings": cap["involuntary_remat"],
                    "tokens_per_sec_per_chip": round(
                        steps * batch * (seq - 1) / min(times) / n_dev, 1
                    ),
                    "final_loss": round(loss, 6),
                }
                if dtype == "f32":
                    f32_loss, f32_wire = loss, wire
                else:
                    row["loss_delta_vs_f32"] = (
                        round(loss - f32_loss, 6) if f32_loss is not None else None
                    )
                    row["wire_ratio_vs_f32"] = (
                        round(wire / f32_wire, 4) if f32_wire else None
                    )
                rows.append(row)
            except Exception as exc:
                rows.append(
                    {"strategy": name, "comm_dtype": dtype, "error": repr(exc)}
                )
                print(
                    f"quant comm rung {name}/{dtype} failed: {exc!r}",
                    file=sys.stderr,
                )
    return rows


def bench_comm_overlap(cfg, n_dev, num_experts=8, steps=8):
    """Overlap-scheduled collectives ladder (round 18, ROADMAP #5):
    step-time at f32 (serial) vs int8 (serial — the round-12 wire cut)
    vs int8 + --grad_buckets 4 (the overlap schedule) on the DDP, FSDP
    and EP worlds, so the wire cut and the overlap win are SEPARATELY
    visible. Each rung compiles cold under a compiler-stderr capture and
    reports:

      - step_time_s (best window / steps) and tokens/s/chip — the
        wall-clock observable. NOTE the honest caveat: on CPU virtual
        devices the collectives are loopback memcpys, so the overlap
        rung's wall win is noise-bounded; the schedule PROPERTY is the
        gated signal (below), the times are the observable a real
        multi-chip run compares;
      - the promoted hlolint `overlap` verdict on the overlap rung:
        declared vs overlappable bucket wires and `overlap_frac` =
        overlappable/declared (1.0 = every bucket wire independently
        schedulable) — the number tools/report.py's --min_overlap_frac
        gate checks;
      - bytes_match: measured collectives == the per-bucket closed form;
      - involuntary-remat warnings (zero = schedule intact, cold only);
      - final loss + delta vs the rung's f32 serial baseline (the
        round-12 tolerance-gate number; the f32 bucket schedule itself
        is bit-identical across bucket counts, tests/test_overlap.py).
    """
    import math

    import jax

    from tools.bench_ladder import make_batch, setup_step, time_windows
    from tpukit.analysis import (
        collective_summary, lint_module, parse_hlo, summarize,
        train_comm_plan,
    )
    from tpukit.mesh import create_mesh
    from tpukit.obs import capture_compiler_stderr
    from tpukit.shardings import DataParallel, ExpertParallel, FSDP

    seq = cfg.max_position_embeddings
    batch = 32 * n_dev
    expert = math.gcd(n_dev, num_experts)
    backend = jax.default_backend()
    struct = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731

    rungs = [
        ("ddp", lambda: DataParallel(create_mesh({"data": n_dev}))),
        ("fsdp", lambda: FSDP(create_mesh({"data": n_dev}))),
        ("ep", lambda: ExpertParallel(
            create_mesh({"data": n_dev // expert, "expert": expert}),
            dispatch="a2a")),
    ]
    rows = []
    for name, strat_fn in rungs:
        f32_loss = f32_step = None
        for dtype, buckets in (("f32", 0), ("int8", 0), ("int8", 4)):
            try:
                c = cfg.replace(
                    comm_dtype=dtype, grad_buckets=buckets,
                    num_experts=num_experts if name == "ep" else 0,
                )
                strat = strat_fn()
                strat.validate_config(c)
                b, t = make_batch(
                    np.random.RandomState(5), cfg.vocab_size, batch, seq - 1
                )
                with capture_compiler_stderr() as cap:
                    step, state, shapes, _ = setup_step(c, strat)
                    compiled = step.lower(
                        shapes, jax.tree.map(struct, b), struct(t)
                    ).compile()
                # render + parse ONCE (the round-16 discipline): the byte
                # audit and the lint share one module
                module = parse_hlo(compiled.as_text())
                coll = collective_summary(module)
                plan = train_comm_plan(
                    strat, c, param_shapes=shapes.params,
                    global_batch=batch, seq=seq - 1, backend=backend,
                )
                exact = None
                if plan is not None and plan.ops:
                    exact = all(
                        (coll.get(op) or {"count": 0, "bytes": 0}) == rec
                        for op, rec in plan.ops.items()
                    )
                overlap = None
                if plan is not None and plan.overlap:
                    verdict = summarize(lint_module(
                        module, plan=plan,
                        compiler_stderr=cap["text"], backend=backend,
                    ))
                    gate = verdict.get("overlap_gate") or {}
                    declared = gate.get("declared") or 0
                    overlap = {
                        "declared": declared,
                        "overlappable": gate.get("overlappable", 0),
                        # capped at 1.0: EP measures MORE overlappable
                        # wires than its (backward-hops-only) declaration
                        "overlap_frac": (
                            round(min(
                                1.0, gate.get("overlappable", 0) / declared
                            ), 4)
                            if declared else None
                        ),
                        "gate_ok": gate.get("ok"),
                        "clean": verdict["clean"],
                    }
                times, state, loss = time_windows(
                    compiled, state, b, t, steps=steps, windows=3, warmup=2
                )
                del state
                step_time = min(times) / steps
                row = {
                    "strategy": name,
                    "comm_dtype": dtype,
                    "grad_buckets": buckets,
                    "step_time_s": round(step_time, 6),
                    "tokens_per_sec_per_chip": round(
                        batch * (seq - 1) / step_time / n_dev, 1
                    ),
                    "bytes_match": exact,
                    "overlap": overlap,
                    "involuntary_remat_warnings": cap["involuntary_remat"],
                    "final_loss": round(loss, 6),
                }
                if dtype == "f32" and buckets == 0:
                    f32_loss, f32_step = loss, step_time
                else:
                    row["loss_delta_vs_f32"] = (
                        round(loss - f32_loss, 6)
                        if f32_loss is not None else None
                    )
                    row["step_time_vs_f32"] = (
                        round(step_time / f32_step, 4) if f32_step else None
                    )
                rows.append(row)
            except Exception as exc:
                rows.append({
                    "strategy": name, "comm_dtype": dtype,
                    "grad_buckets": buckets, "error": repr(exc),
                })
                print(
                    f"comm overlap rung {name}/{dtype}/b{buckets} failed: "
                    f"{exc!r}",
                    file=sys.stderr,
                )
    return rows


def bench_pipe_interleave(n_dev, steps=3, micro=8):
    """Interleaved-1F1B ladder (round 25, --virtual_stages): the flat
    1F1B tick machine vs V=2 and V=4 virtual chunks per device at EQUAL
    micro-batch count. Two kinds of numbers, kept apart on purpose:

      - `bubble_table` + per-rung `bubble_frac`: weighted idle-phase
        accounting straight off the tick table (pipeline_schedule.py,
        backward at 2x forward cost; the V=1 row is the closed form
        (2S-2)/(M+2S-2)). Deterministic, backend-free — the numbers
        tools/report.py's --min_bubble_gain gate pins, because on CPU
        virtual devices wall-clock is loopback noise (the
        --min_overlap_frac discipline).
      - per-rung step time / tokens/s/chip and `wall_ratio_vs_flat` vs
        `predicted_ratio_vs_flat` (schedule cost in forward-units, a
        chunk being 1/V of a flat stage pass): the wall cross-check a
        real multi-chip run compares. On CPU the unrolled machine's
        per-tick dispatch overhead dilutes the predicted win.

    Rungs that fail land as {"virtual_stages": V, "error": ...} so a
    machine that stops compiling cannot hide behind the pure-math table
    (the gate fails on errored rungs)."""
    import jax.numpy as jnp

    from tools.bench_ladder import make_batch, setup_step, time_windows
    from tpukit.mesh import create_mesh
    from tpukit.model import GPTConfig
    from tpukit.pipeline import Pipeline1F1B
    from tpukit.pipeline_schedule import (
        bubble_table, cached_schedule, flat_1f1b_bubble,
    )

    stages = 4 if n_dev >= 4 else 2
    layers = 4 * stages  # V=4 needs S*V chunks <= layers
    seq = 128
    cfg_p = GPTConfig(
        dim=128, head_dim=32, heads=4, num_layers=layers, vocab_size=8192,
        max_position_embeddings=seq, compute_dtype=jnp.bfloat16,
    )
    batch = 2 * micro  # two rows per micro-batch
    record = {
        "stages": stages,
        "microbatches": micro,
        "layers": layers,
        # the measured-bubble grid the gate checks: V x M, tick-table
        # accounting (V=1 rows are the closed form)
        "bubble_table": bubble_table(stages),
        "rungs": [],
        "caveat": (
            "CPU loopback: per-tick dispatch overhead dilutes the "
            "schedule win; bubble_frac/predicted_ratio are the "
            "backend-transferable numbers"
        ),
    }
    # schedule cost in forward-units: flat runs fwd+bwd EVERY tick (its
    # idle ticks still compute garbage), interleaved only on live phases
    # at 1/V the per-tick work
    flat_cost = 3.0 * (micro + 2 * stages - 2)
    flat_step = None
    for v in (1, 2, 4):
        try:
            if v == 1:
                bubble = flat_1f1b_bubble(stages, micro)
                cost = flat_cost
            else:
                st = cached_schedule(stages, v, micro).stats
                bubble = st["bubble_frac"]
                cost = (st["fwd_phase_ticks"]
                        + 2.0 * st["bwd_phase_ticks"]) / v
            strat = Pipeline1F1B(
                create_mesh({"stage": stages}), num_microbatches=micro
            )
            c = cfg_p.replace(virtual_stages=v)
            strat.validate_config(c)
            b, t = make_batch(np.random.RandomState(5), c.vocab_size,
                              batch, seq)
            step, state, _, _ = setup_step(c, strat)
            times, state, loss = time_windows(
                step, state, b, t, steps=steps, windows=3, warmup=2
            )
            del state
            step_time = min(times) / steps
            row = {
                "virtual_stages": v,
                "bubble_frac": round(bubble, 4),
                "sched_cost_units": round(cost, 2),
                "predicted_ratio_vs_flat": round(cost / flat_cost, 4),
                "step_time_s": round(step_time, 6),
                "tokens_per_sec_per_chip": round(
                    batch * seq / step_time / stages, 1
                ),
                "final_loss": round(loss, 6),
            }
            if v == 1:
                flat_step = step_time
            else:
                row["wall_ratio_vs_flat"] = (
                    round(step_time / flat_step, 4) if flat_step else None
                )
            record["rungs"].append(row)
        except Exception as exc:
            record["rungs"].append(
                {"virtual_stages": v, "error": repr(exc)}
            )
            print(f"pipe interleave rung V={v} failed: {exc!r}",
                  file=sys.stderr)
    return record


def bench_pipe_moe(n_dev, micro=4, steps=3):
    """Pipeline x MoE composition rung (round 25): the interleaved 1F1B
    machine with 8 experts through the meshless dropless pallas dispatch
    — the ONE legal pipeline MoE dataflow — against the single-device
    run of the identical per-micro objective (CE + aux, f32). The
    parity bit is the record's point; tokens/s/chip rides along as the
    observable. A buffer dispatch leaking in shows up as an hlolint
    a2a-free violation (pipe_moe world), not here."""
    import jax.numpy as jnp

    from tools.bench_ladder import make_batch, setup_step, time_windows
    from tpukit.mesh import create_mesh
    from tpukit.model import GPTConfig
    from tpukit.pipeline import Pipeline1F1B
    from tpukit.shardings import SingleDevice

    stages = 2
    if n_dev < stages:
        raise ValueError("pipe_moe rung needs >= 2 devices")
    seq = 64
    cfg_m = GPTConfig(
        dim=64, head_dim=16, heads=4, num_layers=8, vocab_size=1024,
        max_position_embeddings=seq, compute_dtype=jnp.float32,
        num_experts=8, moe_dispatch="pallas", virtual_stages=2,
    )
    batch = 2 * micro
    b, t = make_batch(np.random.RandomState(5), cfg_m.vocab_size, batch, seq)

    # single-device reference: same params (same init key), same
    # objective — the pipeline's per-micro CE+aux at f32 must match to
    # float tolerance
    step_ref, state_ref, _, _ = setup_step(
        cfg_m.replace(virtual_stages=1), SingleDevice()
    )
    state_ref, ref_loss = step_ref(state_ref, b, t)
    ref_loss = float(ref_loss)
    del state_ref

    strat = Pipeline1F1B(
        create_mesh({"stage": stages}), num_microbatches=micro,
        moe_dispatch="pallas",
    )
    step, state, _, _ = setup_step(cfg_m, strat)
    state, loss = step(state, b, t)
    loss = float(loss)
    times, state, _ = time_windows(
        step, state, b, t, steps=steps, windows=2, warmup=1
    )
    del state
    delta = abs(loss - ref_loss)
    return {
        "stages": stages,
        "virtual_stages": 2,
        "microbatches": micro,
        "num_experts": 8,
        "dispatch": "pallas",
        "loss": round(loss, 6),
        "ref_loss": round(ref_loss, 6),
        "loss_delta": round(delta, 8),
        "parity_ok": bool(delta < 1e-4),
        "tokens_per_sec_per_chip": round(
            steps * batch * seq / min(times) / stages, 1
        ),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--compilation_cache_dir",
        default="",
        help="explicit persistent XLA compile cache location; empty = "
        "$JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache "
        "(tpukit/cache.py). Repeat runs skip recompiles and the JSON "
        "reports hits/misses",
    )
    ap.add_argument(
        "--moe_dispatch",
        choices=("xla", "pallas"),
        default="xla",
        help="dataflow for the headline moe_e8 probe (default xla so the "
        "number stays comparable across rounds; the moe_dispatch_ladder "
        "record always measures xla, a2a and pallas side by side)",
    )
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from tools.bench_ladder import make_batch, run_ladder, setup_step, time_windows
    from tpukit.model import GPTConfig
    from tpukit.obs import peak_flops_per_chip, train_flops_per_token
    from tpukit.shardings import DataParallel, SingleDevice

    from tpukit.cache import enable_compilation_cache

    cache_stats = enable_compilation_cache(args.compilation_cache_dir)

    n_dev = len(jax.devices())
    strategy = DataParallel() if n_dev > 1 else SingleDevice()

    seq = 256
    per_chip_batch = 64
    batch = per_chip_batch * n_dev
    cfg = GPTConfig(
        dim=256,
        head_dim=32,
        heads=8,
        num_layers=8,
        vocab_size=50257,
        max_position_embeddings=seq,
        compute_dtype=jnp.bfloat16,
    )

    train_step, state, shapes, _ = setup_step(cfg, strategy)

    rng = np.random.RandomState(0)
    model_batch, targets = make_batch(rng, cfg.vocab_size, batch, seq - 1)

    # XLA static analysis of the exact executable the timing loop runs
    # (tpukit.obs round 6): the AOT lower/compile shares the jit caches, so
    # this is not a second compile; FLOPs/bytes come from cost_analysis and
    # comm bytes are parsed from the compiled HLO's collectives.
    from tpukit.obs import compiled_stats

    struct = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    xla_stats = compiled_stats(
        train_step, shapes, jax.tree.map(struct, model_batch), struct(targets)
    )

    # Best of four timing windows; all window times are kept so the JSON
    # can report the spread (VERDICT r4: a headline that sits on the target
    # bar needs its noise band stated).
    steps = 12
    windows, state, final_loss = time_windows(
        train_step, state, model_batch, targets, steps=steps, windows=4
    )
    best = min(windows)

    tokens = steps * batch * (seq - 1)
    tps = tokens / best
    tps_chip = tps / n_dev
    flops_per_token = train_flops_per_token(cfg, seq - 1)
    peak = peak_flops_per_chip()
    mfu = (tps_chip * flops_per_token / peak) if peak else None

    # Secondary: long-context throughput (S=2048) through the Pallas flash
    # attention kernel — a regime where the materialized-mask attention the
    # reference uses (models/gpt.py:83-88) stops being viable.
    long_tps, long_err = None, None
    try:
        # batch 16/chip measured best on v5e with the fused head+CE path
        # (8 underfills the chip; 64 OOMs on trunk activations even with
        # no logits buffer — remat didn't pay for itself at 32/64)
        long_seq, long_batch = 2048, 16 * n_dev
        cfg_long = cfg.replace(max_position_embeddings=long_seq)
        train_step_l, state, _, _ = setup_step(cfg_long, strategy)
        long_b, long_t = make_batch(rng, cfg.vocab_size, long_batch, long_seq)
        # best-of-4 windows of 8
        times_l, state, _ = time_windows(
            train_step_l, state, long_b, long_t, steps=8, windows=4, warmup=2
        )
        long_tps = 8 * long_batch * long_seq / min(times_l) / n_dev
    except Exception as exc:  # stdout is reserved for the JSON line; the
        # error lands in the JSON and main() exits non-zero on any recorded
        # error, so a kernel regression cannot hide behind rc=0
        long_err = repr(exc)
        print(f"long-context bench failed: {exc!r}", file=sys.stderr)

    # FSDP --cpu_offload proof (VERDICT r3 #6): run the donated train step
    # with params/opt state pinned to HOST memory on the real chip and
    # record that the state is still host-pinned afterwards — the positive
    # path that CPU tests can only fake (they assert the degrade warning).
    offload_ok, offload_tps, offload_err = None, None, None
    try:
        from tpukit.mesh import create_mesh
        from tpukit.shardings import FSDP

        strat_o = FSDP(mesh=create_mesh({"data": n_dev}), cpu_offload=True)
        if strat_o._offload_supported():
            step_o, state_o, _, _ = setup_step(cfg, strat_o)
            kinds = {l.sharding.memory_kind for l in jax.tree.leaves(state_o.params)}
            assert kinds == {"pinned_host"}, kinds
            times_o, state_o, _ = time_windows(
                step_o, state_o, model_batch, targets, steps=6, windows=1, warmup=2
            )
            kinds = {l.sharding.memory_kind for l in jax.tree.leaves(state_o.params)}
            assert kinds == {"pinned_host"}, kinds
            offload_ok = True
            offload_tps = 6 * batch * (seq - 1) / times_o[0] / n_dev
            del state_o
    except Exception as exc:
        offload_ok = False
        offload_err = repr(exc)
        print(f"fsdp cpu_offload probe failed: {exc!r}", file=sys.stderr)

    # MoE probe (round 5): the Switch-style expert path on the real chip —
    # reference shape with 8 experts, full train step (routing + dispatch
    # einsums + aux loss + AdamW).
    moe_tps, moe_err = None, None
    try:
        cfg_moe = cfg.replace(num_experts=8, moe_dispatch=args.moe_dispatch)
        step_m, state_m, _, _ = setup_step(cfg_moe, strategy)
        moe_batch = 32 * n_dev
        b_m, t_m = make_batch(rng, cfg.vocab_size, moe_batch, seq - 1)
        times_m, state_m, _ = time_windows(
            step_m, state_m, b_m, t_m, steps=8, windows=3, warmup=2
        )
        moe_tps = 8 * moe_batch * (seq - 1) / min(times_m) / n_dev
        del state_m
    except Exception as exc:
        moe_err = repr(exc)
        print(f"moe probe failed: {exc!r}", file=sys.stderr)

    # EP a2a dispatch audit (round 10): expected-vs-measured all-to-all
    # payload + remat-warning count + a2a-path throughput. The xla-dispatch
    # moe probe above is untouched, so moe_e8_tokens_per_sec_per_chip stays
    # comparable across rounds.
    moe_ep_comm, moe_ep_comm_err = None, None
    try:
        moe_ep_comm = bench_moe_ep_comm(cfg, n_dev)
    except Exception as exc:
        moe_ep_comm_err = repr(exc)
        print(f"moe ep comm probe failed: {exc!r}", file=sys.stderr)

    # MoE dispatch ladder (round 11, ROADMAP #3): xla vs a2a vs pallas at
    # e8 top-1/top-2, tokens/s/chip + active-FLOPs-normalized MFU. Per-rung
    # errors land inside the record itself.
    moe_dispatch_ladder = None
    try:
        moe_dispatch_ladder = bench_moe_dispatch_ladder(cfg, n_dev)
    except Exception as exc:
        moe_dispatch_ladder = [{"dispatch": "ladder", "error": repr(exc)}]
        print(f"moe dispatch ladder failed: {exc!r}", file=sys.stderr)

    # Quantized collectives (round 12, ROADMAP #2): f32 vs bf16 vs int8
    # --comm_dtype per strategy rung — expected+measured bytes on the wire,
    # tokens/s/chip, final-loss delta vs f32. Per-rung errors land inside
    # the record itself.
    quant_comm_rec = None
    try:
        quant_comm_rec = bench_quant_comm(cfg, n_dev)
    except Exception as exc:
        quant_comm_rec = [{"strategy": "quant_comm", "error": repr(exc)}]
        print(f"quant comm ladder failed: {exc!r}", file=sys.stderr)

    # Overlap-scheduled collectives (round 18, ROADMAP #5): f32 vs int8
    # vs int8 + --grad_buckets 4 per strategy — step time, the promoted
    # overlap-gate verdict (overlap_frac), per-bucket byte match.
    comm_overlap_rec = None
    try:
        comm_overlap_rec = bench_comm_overlap(cfg, n_dev)
    except Exception as exc:
        comm_overlap_rec = [{"strategy": "comm_overlap", "error": repr(exc)}]
        print(f"comm overlap ladder failed: {exc!r}", file=sys.stderr)

    # Interleaved pipeline (round 25, --virtual_stages): flat 1F1B vs
    # V=2/V=4 at equal micro count — the tick-table bubble grid (the
    # --min_bubble_gain gated numbers) plus wall cross-checks; and the
    # pipeline x MoE pallas-dispatch parity rung.
    pipe_interleave_rec = None
    try:
        pipe_interleave_rec = bench_pipe_interleave(n_dev)
    except Exception as exc:
        pipe_interleave_rec = {"error": repr(exc)}
        print(f"pipe interleave ladder failed: {exc!r}", file=sys.stderr)
    pipe_moe_rec = None
    try:
        pipe_moe_rec = bench_pipe_moe(n_dev)
    except Exception as exc:
        pipe_moe_rec = {"error": repr(exc)}
        print(f"pipe moe probe failed: {exc!r}", file=sys.stderr)

    # Elastic restore (round 13, ROADMAP #5): restore+reshard wall-clock,
    # bytes read, RSS high-water delta and the parity bit for a sharded
    # checkpoint landing on a half-size world.
    elastic_restore = None
    try:
        elastic_restore = bench_elastic_restore(cfg, n_dev)
    except Exception as exc:
        elastic_restore = {"error": repr(exc)}
        print(f"elastic restore probe failed: {exc!r}", file=sys.stderr)

    # Serving (round 14, ROADMAP #1): continuous batching vs serial
    # per-request decode on the same seeded stream — tokens/s (the >= 2x
    # bar), p50/p99 end-to-end + per-token latency, slot occupancy.
    serving_rec = None
    try:
        serving_rec = bench_serving(cfg, n_dev)
    except Exception as exc:
        serving_rec = {"error": repr(exc)}
        print(f"serving probe failed: {exc!r}", file=sys.stderr)

    # Paged KV (round 15, ROADMAP #2): ring vs paged vs paged+int8 at
    # equal KV HBM — tokens/s, measured max concurrent slots (the >= 2x
    # bar with int8 pages), the exact-parity bit, and prefix-hit vs cold
    # admit latency on a shared-system-prompt stream.
    paged_kv_rec = None
    try:
        paged_kv_rec = bench_paged_kv(cfg, n_dev)
    except Exception as exc:
        paged_kv_rec = {"error": repr(exc)}
        print(f"paged kv probe failed: {exc!r}", file=sys.stderr)

    # Speculative decoding (round 17, ROADMAP #3): draft-and-verify vs
    # the vanilla engine on the repetitive stream — tokens/s (>= 1.3x
    # self-spec at temperature 0 is the bar), acceptance rate, the
    # appended-tokens/verify histogram, at temperature 0 and 0.8.
    spec_decode_rec = None
    try:
        spec_decode_rec = bench_spec_decode(cfg, n_dev)
    except Exception as exc:
        spec_decode_rec = {"error": repr(exc)}
        print(f"spec decode probe failed: {exc!r}", file=sys.stderr)

    # Dispatch-vs-device attribution (round 20): where a decode quantum's
    # wall goes — host async-dispatch loop vs waiting at the per-quantum
    # sync — from the request tracer's quantum spans on a traced run.
    serve_dispatch_rec = None
    try:
        serve_dispatch_rec = bench_serve_dispatch_attribution(cfg, n_dev)
    except Exception as exc:
        serve_dispatch_rec = {"error": repr(exc)}
        print(f"serve dispatch attribution probe failed: {exc!r}",
              file=sys.stderr)

    # Fused decode (round 21, ROADMAP #2/#4): the kernel win (unfused vs
    # fused at quantum=1) and the dispatch-amortization win (fused q=1 vs
    # the on-device while-loop window) measured separately, with parity
    # and per-quantum dispatch/device walls cross-checking the round-20
    # attribution record.
    decode_fused_rec = None
    try:
        decode_fused_rec = bench_decode_fused(cfg, n_dev)
    except Exception as exc:
        decode_fused_rec = {"error": repr(exc)}
        print(f"fused decode probe failed: {exc!r}", file=sys.stderr)

    # Fleet serving (round 19, ROADMAP #1): 1 vs 2 vs 4 replicas on the
    # same stream at equal total devices — fleet tokens/s scaling (>1.5x
    # at 2 replicas is the bar), p99 under load, per-request parity, and
    # disaggregated-vs-colocated prefill admit latency.
    fleet_serving_rec = None
    try:
        fleet_serving_rec = bench_fleet_serving(cfg, n_dev)
    except Exception as exc:
        fleet_serving_rec = {"error": repr(exc)}
        print(f"fleet serving probe failed: {exc!r}", file=sys.stderr)

    # Host input pipeline (round 7): sync data+h2d share vs the depth-2
    # prefetcher's residual stall share, with loss-parity proof.
    host_pipeline, host_pipeline_err = None, None
    try:
        host_pipeline = bench_host_pipeline(cfg, strategy, batch)
    except Exception as exc:
        host_pipeline_err = repr(exc)
        print(f"host pipeline probe failed: {exc!r}", file=sys.stderr)

    # Failure-observability overhead (round 8): recorder + periodic
    # checksum cost vs the bare loop, with loss-parity proof.
    obs_overhead, obs_overhead_err = None, None
    try:
        obs_overhead = bench_obs_overhead(cfg, strategy, batch)
    except Exception as exc:
        obs_overhead_err = repr(exc)
        print(f"obs overhead probe failed: {exc!r}", file=sys.stderr)

    # Round-20 serving rung of the obs-overhead story: the request-trace
    # recorder on vs off on the same seeded stream — tokens/s delta
    # (<1% bar) and bit-identical output tokens.
    try:
        serving_rung = bench_serve_trace_overhead(cfg, n_dev)
    except Exception as exc:
        serving_rung = {"error": repr(exc)}
        print(f"serve trace overhead probe failed: {exc!r}", file=sys.stderr)
    if obs_overhead is None:
        obs_overhead = {}
    obs_overhead["serving"] = serving_rung

    # Round-22 metrics-plane rung of the same story: the registry on vs
    # --no_metrics on the same seeded stream — tokens/s delta (<1% bar),
    # bit-identical tokens, and the snapshot-publish wall timed apart.
    try:
        metrics_overhead_rec = bench_metrics_overhead(cfg, n_dev)
    except Exception as exc:
        metrics_overhead_rec = {"error": repr(exc)}
        print(f"metrics overhead probe failed: {exc!r}", file=sys.stderr)

    # Ladder rungs (VERDICT r4 #1): single-chip measurements of the
    # BASELINE configs 2-5 shapes at head_dim=64 — GPT-small/medium full,
    # GPT-large/XL as the 16-layer stage slices DESIGN.md §2 profiles.
    # Per-rung failures land as {"shape": ..., "error": ...} entries.
    ladder = None
    if n_dev == 1:  # rung batch sizes are tuned per chip
        try:
            ladder = run_ladder(steps=6, windows=3)
        except Exception as exc:
            ladder = [{"shape": "ladder", "error": repr(exc)}]

    result = {
        "metric": "gpt_train_tokens_per_sec_per_chip",
        "value": round(tps_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.35, 4) if mfu is not None else None,
        "mfu": round(mfu, 4) if mfu is not None else None,
        # spread across the four timing windows: the slowest window's MFU
        # (lower bound seen THIS run) vs the reported best — the noise band
        # around the headline number
        "mfu_window_min": (
            round(mfu * best / max(windows), 4) if mfu is not None else None
        ),
        "tokens_per_sec_total": round(tps, 1),
        "long_context_s2048_tokens_per_sec_per_chip": round(long_tps, 1) if long_tps else None,
        "long_context_error": long_err,
        "fsdp_cpu_offload_ok": offload_ok,
        "fsdp_cpu_offload_tokens_per_sec_per_chip": round(offload_tps, 1) if offload_tps else None,
        "fsdp_cpu_offload_error": offload_err,
        "moe_e8_tokens_per_sec_per_chip": round(moe_tps, 1) if moe_tps else None,
        "moe_e8_dispatch": args.moe_dispatch,
        "moe_error": moe_err,
        "moe_ep_comm": moe_ep_comm,
        "moe_ep_comm_error": moe_ep_comm_err,
        "moe_dispatch_ladder": moe_dispatch_ladder,
        "quant_comm": quant_comm_rec,
        "comm_overlap": comm_overlap_rec,
        "pipe_interleave": pipe_interleave_rec,
        "pipe_moe": pipe_moe_rec,
        "elastic_restore": elastic_restore,
        "serving": serving_rec,
        "paged_kv": paged_kv_rec,
        "spec_decode": spec_decode_rec,
        "serve_dispatch_attribution": serve_dispatch_rec,
        "decode_fused": decode_fused_rec,
        "fleet_serving": fleet_serving_rec,
        "host_pipeline": host_pipeline,
        "host_pipeline_error": host_pipeline_err,
        "obs_overhead": obs_overhead,
        "obs_overhead_error": obs_overhead_err,
        "metrics_overhead": metrics_overhead_rec,
        "ladder": ladder,
        "chips": n_dev,
        "device": jax.devices()[0].device_kind,
        "config": f"GPT-20M dim256 L8 seq256 bf16 batch{batch}, fused train step",
        "final_loss": round(final_loss, 4),
        # roofline + comm-volume telemetry for the headline step (tpukit.obs)
        "xla_train_step": xla_stats,
        "compile_cache": cache_stats.stats(),
    }
    print(json.dumps(result))
    failed = _recorded_errors(result)
    if failed:
        print(f"bench: {len(failed)} probe(s) recorded an error: "
              f"{', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


def _recorded_errors(node, path="result") -> list[str]:
    """Paths of every non-empty `error` / `*_error` field in the record: a
    probe that failed is in the JSON line AND in the exit code."""
    found = []
    if isinstance(node, dict):
        for key, val in node.items():
            here = f"{path}.{key}"
            if key == "error" or key.endswith("_error"):
                if val:
                    found.append(here)
            else:
                found += _recorded_errors(val, here)
    elif isinstance(node, list):
        for i, val in enumerate(node):
            found += _recorded_errors(val, f"{path}[{i}]")
    return found


if __name__ == "__main__":
    sys.exit(main())
