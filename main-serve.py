#!/usr/bin/env python
"""Recipe 9 (tpukit extension): continuous-batching inference serving.

The extension ladder after the reference's five recipes is 6 = TP
(main-tp.py), 7 = ring/CP (main-ring.py), 8 = MoE/EP (main-moe.py),
9 = serving — the "millions of users" half of the north star (ROADMAP #1).
Everything upstream of this recipe decodes as a training-loop side effect;
this is the standalone serving path: restore ANY checkpoint the training
recipes saved (reshard-on-restore handles a different world — round 13),
shard it over a (data x model) serving mesh with params at their
TensorParallel training shardings and the per-slot KV ring sharded heads
over `model` / slots over `data`, and drive a seeded synthetic request
stream through the continuous-batching engine (tpukit/serve): requests
admit into free slots mid-decode at bucketed prompt lengths (the whole
compile budget is the declared bucket set), evict on EOS/length, and the
`kind="serve"` JSONL windows — tokens/s, p50/p99 per-token and end-to-end
latency, slot occupancy, prefill/decode wall split — flow through the same
StepLogger/flight-recorder/report stack that covers training
(`python tools/report.py serve.jsonl`, with `--min_serve_tps` as the CI
throughput gate).

Round 15 (ROADMAP #2): `--page_size P` swaps the per-slot ring for the
PAGED KV cache (tpukit/serve/paged.py) — fixed-size pages + per-slot
block tables, request-granular allocation, shared-prefix reuse
(admissions hitting the page-granular prefix registry skip the shared
prefill entirely; `--shared_prefix N` gives the synthetic stream one
system prompt), chunked prefill (`--prefill_chunk`), and int8 page
payloads (`--kv_dtype int8`, ~4x pages per HBM byte, tolerance-gated).
Paged serving picks a model-only grid (the page pool replicates over
`data`); the checkpoint restore is params-ONLY either way
(`checkpoint.restore_params`: the Adam moments — ~2/3 of the bytes —
are never read, and any saved world lands at the serving shardings).

Round 17 (ROADMAP #3): `--draft {ngram,model}` turns on SPECULATIVE
DECODING (tpukit/serve/spec.py) — a proposer guesses `--spec_k` tokens
per slot per quantum and the target scores all k+1 positions in ONE
batched forward, rejection sampling keeping the output distribution
EXACT (greedy output token-identical to vanilla decode). "ngram" is
self-speculation: on-device prompt-lookup drafting fused into the
verify program, no second model — near-free, and a big win on
repetitive/templated traffic (`--stream_profile repetitive`). "model"
runs a small tpukit GPT draft (`--draft_checkpoint` + `--draft_*` shape
flags, params-only restore with its own ledger line) with its own
replicated KV ring. Speculation needs the ring cache (page_size 0).

Round 19 (ROADMAP #1, tpukit/serve/fleet.py): `--replicas N` routes the
stream through a FLEET — N engine replicas, each on its own disjoint
device subset (`--devices_per_replica`, model-parallel grid per
replica), behind one least-loaded router. The checkpoint is read ONCE
(host-side params-only restore) and placed per replica; fleet output is
token-identical to a single engine on the same stream, including when
`--fleet_kill replica_kill@R[:idx]` chaos-kills a replica mid-stream
(in-flight requests re-queue onto survivors, exactly-once output).
`--disagg_prefill` dedicates a prefill worker that hands finished
prefixes to decode replicas as pages; `--scale_up_occupancy` /
`--scale_down_occupancy` autoscale the replica count between fleet
windows. `kind="fleet"` telemetry renders via tools/report.py
"== fleet ==" with `--min_fleet_tps` as the CI gate.

Round 21, and PR 28: a paged decode tick on a TPU reads the page pool
through ONE Pallas kernel a layer (tpukit/ops/paged_attention.py): the
block table is scalar-prefetched and dereferenced INSIDE the kernel, which
visits the pages at or below each slot's cursor in the stacked pool: no
per-layer XLA gather materializing a [slots, window] contiguous KV view,
and int8 pages dequantize tile-by-tile in VMEM on the quant_comm block
layout. No flag selects it (gpt.decode_read_in_kernel: the backend, the
pool); elsewhere the XLA gather runs. `--fused_decode` is the other axis:
per quantum, the scheduler inner state (cursors, EOS flags, length limits,
freed-page account) lives on device and `--decode_quantum` steps run as
one `lax.while_loop` (decode.decode_loop_window), so the ~0.3 ms host
dispatch the round-20 traces measured per step is paid once per quantum
instead of once per step; the host syncs only at window boundaries (or
early, when EOS activity frees enough pages for the head-of-queue admit).
Token streams are exactly those of the per-step engine (greedy and seeded
sampling); whether the window is faster than the per-step quantum is not
measured on the chip (ROADMAP D16). Needs the paged cache (`--page_size`).

Round 24 (tpukit/serve/ledger.py): CRASH-TOLERANT fleet serving. With
`--fleet_dir` the request lifecycle is durable — write-ahead lease
records before dispatch, exactly-once completion records after, full
stream replay on router restart (a restarted router serves only the
not-yet-completed frontier; `duplicate_completions` stays 0 across
process death). Replicas publish heartbeat files; `--replica_timeout`
declares silent replicas dead and requeues their leases on survivors
under the `--request_retries` budget with jittered backoff.
`--fleet_procs` runs each replica as a real worker PROCESS (this recipe
re-exec'd with `--fleet_worker i`) so `--fleet_kill
replica_sigkill@R` chaos delivers a real SIGKILL; the serving chaos
grammar also takes slow_replica@R:ms (heartbeat stall — slowness the
liveness check must NOT confuse with death), stuck_request@N (pair with
`--deadline_ms`), and ledger_io_fail@k:c (transient IOError on ledger
I/O, absorbed by retry_io). `--deadline_ms` evicts over-deadline lanes
with their partial tokens as reason="deadline" (kind="deadline_miss"
records, gated by report.py --max_deadline_miss_pct);
`--max_queue_depth` sheds over-depth arrivals lowest-priority-first as
named request_rejected events.

Run examples:
  python main-serve.py --requests 64 --slots 8 --metrics_log serve.jsonl
  python main-serve.py --checkpoint latest --temperature 0.8 --top_k 40
  python main-serve.py --checkpoint checkpoints/step-200.msgpack \\
      --num_experts 8 --moe_dispatch pallas   # dropless MoE: exact cached
  python main-serve.py --page_size 8 --shared_prefix 16 --requests 128 \\
      --kv_dtype int8 --metrics_log serve.jsonl   # paged + prefix + int8
  python main-serve.py --draft ngram --spec_k 6 \\
      --stream_profile repetitive --metrics_log serve.jsonl  # self-spec
  python main-serve.py --draft model \\
      --draft_checkpoint ckpts_draft/checkpoint-step000002000.msgpack \\
      --draft_dim 64 --draft_num_layers 2   # draft-model speculation
  python main-serve.py --replicas 2 --devices_per_replica 4 \\
      --fleet_kill replica_kill@40:1 \\
      --metrics_log fleet.jsonl   # fleet router + chaos replica kill
  python main-serve.py --replicas 2 --fleet_procs --fleet_dir /tmp/fleet \\
      --replica_timeout 3 --fleet_kill replica_sigkill@6:1 \\
      --metrics_log fleet.jsonl   # real worker procs + real SIGKILL
"""

import argparse
import sys
import time
from functools import partial

import numpy as np


def parse_serve_flags(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    # model shape — must match the checkpoint being served
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--head_dim", type=int, default=32)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--num_layers", type=int, default=8)
    ap.add_argument("--sequence_length", type=int, default=256,
                    help="position table size; the KV ring (max bucket + "
                    "max_new_tokens) must fit inside it")
    ap.add_argument("--disable_amp", action="store_true")
    ap.add_argument("--num_experts", type=int, default=0)
    ap.add_argument("--moe_top_k", type=int, default=1)
    ap.add_argument("--moe_dispatch", choices=("xla", "pallas"), default="xla",
                    help="meshless decode dataflow for MoE checkpoints; "
                    "'pallas' (dropless) makes the cached decode exact")
    ap.add_argument("--model", choices=("gpt", "latent"), default="gpt",
                    help="block family: 'gpt' (tpukit/model/gpt.py, the flags "
                    "above) or 'latent' (tpukit/model/latent.py: latent "
                    "attention with a learned key selection, window layers, a "
                    "sigmoid-routed expert share; served only, paged cache "
                    "only, fresh seeded weights on one device)")
    ap.add_argument("--model_config", type=str, default="",
                    help="--model latent: a configuration file with the "
                    "published config.json keys (benchmark/configs/"
                    "dots3-note-prev.json); empty = the tiny preset at the "
                    "tokenizer's vocabulary")
    # checkpoint
    ap.add_argument("--checkpoint", type=str, default="",
                    help="path or 'latest'; empty serves fresh seeded params "
                    "(smoke mode)")
    ap.add_argument("--seed", type=int, default=0)
    # engine shape (tpukit.flags.add_serve_flags)
    from tpukit.flags import add_fleet_flags, add_serve_flags

    add_serve_flags(ap)
    # fleet router (round 19): --replicas N routes the stream over N
    # engine replicas on disjoint device subsets; 0 = single engine
    add_fleet_flags(ap)
    # stream
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--qps", type=float, default=0.0,
                    help="0 = offered up front (saturation); >0 = seeded "
                    "exponential arrivals at this rate")
    ap.add_argument("--shared_prefix", type=int, default=0,
                    help="prepend the SAME n-token system prompt to every "
                    "request (the shared-prefix-reuse shape; with "
                    "--page_size the engine skips the shared prefill on "
                    "prefix hits)")
    ap.add_argument("--stream_profile",
                    choices=("uniform", "repetitive", "shared_prefix"),
                    default="uniform",
                    help="synthetic-stream workload shape: 'repetitive' "
                    "tiles a short phrase per prompt (where "
                    "self-speculation wins), 'shared_prefix' gives every "
                    "request one system prompt (the paged prefix-reuse "
                    "shape)")
    # draft model (--draft model): restored params-only like the target,
    # with its own shape flags — a draft checkpoint is just a smaller
    # tpukit training run sharing the target's tokenizer
    ap.add_argument("--draft_checkpoint", type=str, default="",
                    help="checkpoint PATH for the --draft model proposer "
                    "(no 'latest' — it would resolve the same shared "
                    "directory as --checkpoint latest); empty with "
                    "--draft model serves fresh seeded draft params "
                    "(smoke mode)")
    ap.add_argument("--draft_dim", type=int, default=64)
    ap.add_argument("--draft_head_dim", type=int, default=16)
    ap.add_argument("--draft_heads", type=int, default=4)
    ap.add_argument("--draft_num_layers", type=int, default=2)
    # telemetry
    ap.add_argument("--metrics_log", type=str, default="")
    ap.add_argument("--compilation_cache_dir", type=str, default="",
                    help="explicit compile-cache location; empty = "
                    "$JAX_COMPILATION_CACHE_DIR if set, else "
                    "<checkout>/.jax_cache (tpukit/cache.py)")
    return ap.parse_args(argv)


def pick_serve_grid(n_devices: int, heads: int, slots: int,
                    paged: bool = False) -> dict:
    """The grid picker moved to tpukit/serve/fleet.py in round 19 (the
    fleet builds one grid PER REPLICA over each replica's device subset,
    so it is shared infrastructure now); this thin delegate keeps the
    name callers and docs know, and the lazy import keeps this module's
    import side-effect-free like the rest of the recipe CLI."""
    from tpukit.serve.fleet import pick_serve_grid as _pick

    return _pick(n_devices, heads, slots, paged=paged)


def main(argv=None):
    flags = parse_serve_flags(argv)
    import jax
    import jax.numpy as jnp

    from tpukit import checkpoint as ckpt_lib
    from tpukit import reshard as reshard_lib
    from tpukit.cache import enable_compilation_cache
    from tpukit.data import get_tokenizer
    from tpukit.mesh import create_mesh, initialize_runtime, is_process_zero
    from tpukit.model import GPTConfig
    from tpukit.obs import (
        FlightRecorder,
        MetricRegistry,
        StepLogger,
        TraceRecorder,
        parse_slo,
    )
    from tpukit.serve import ServeConfig, ServeEngine, synthetic_request_stream
    from tpukit.shardings import DataParallel, SingleDevice, TensorParallel
    from tpukit.train import TrainState, create_train_state, make_optimizer

    initialize_runtime()
    enable_compilation_cache(flags.compilation_cache_dir)

    tokenizer = get_tokenizer()
    tokenizer.pad_token_id = 2  # every recipe pins pad to 2 (main-single.py:23)
    cfg = GPTConfig(
        dim=flags.dim,
        head_dim=flags.head_dim,
        heads=flags.heads,
        num_layers=flags.num_layers,
        vocab_size=tokenizer.vocab_size,
        max_position_embeddings=flags.sequence_length,
        compute_dtype=jnp.float32 if flags.disable_amp else jnp.bfloat16,
        num_experts=flags.num_experts,
        router_top_k=flags.moe_top_k,
        moe_dispatch=flags.moe_dispatch if flags.num_experts > 0 else "xla",
    )
    buckets = tuple(sorted({int(b) for b in flags.buckets.split(",") if b}))

    # ---- the latent family (--model latent): served only, one device -----
    if flags.model == "latent":
        cfg, params = _latent_model(flags, tokenizer)
        return _serve_stream(flags, cfg, params, None, tokenizer, buckets,
                             StepLogger(flags.metrics_log), FlightRecorder())

    # ---- fleet mode (round 19, --replicas >= 1) --------------------------
    if flags.replicas > 0:
        return _run_fleet(flags, cfg, tokenizer, buckets)

    # ---- serving mesh + params at their training shardings ---------------
    # Dense models serve TensorParallel (heads over `model`); MoE
    # checkpoints serve replicated over a data-only grid — the Megatron
    # rules don't cover expert banks, and the meshless MoE decode dataflow
    # (xla buffers / dropless pallas) needs no expert axis.
    n_dev = len(jax.devices())
    if flags.num_experts > 0:
        data = n_dev
        while data > 1 and flags.slots % data:
            data -= 1
        mesh = create_mesh({"data": data})
        strategy = DataParallel(mesh) if data > 1 else SingleDevice()
    else:
        mesh = create_mesh(pick_serve_grid(n_dev, flags.heads, flags.slots,
                                           paged=flags.page_size > 0))
        strategy = TensorParallel(mesh)
    strategy.validate_config(cfg)

    # Shapes only — serving never steps, so only the params subtree of the
    # TrainState is ever materialized (the optimizer here exists solely to
    # derive the state's tree structure for the sharding specs).
    optimizer = make_optimizer(1e-4)
    init_fn = partial(create_train_state, cfg=cfg, optimizer=optimizer,
                      strategy=strategy)
    state_shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(flags.seed))
    state_sharding = strategy.state_sharding(state_shapes)

    logger = StepLogger(flags.metrics_log)
    recorder = FlightRecorder()
    p0 = is_process_zero()

    if flags.checkpoint:
        path = (ckpt_lib.latest_any() if flags.checkpoint == "latest"
                else flags.checkpoint)
        if path is None:
            raise FileNotFoundError("--checkpoint latest: no checkpoint found")
        ok, detail = ckpt_lib.verify_checkpoint(path)
        if not ok:
            raise RuntimeError(f"--checkpoint {path}: failed integrity "
                               f"verification ({detail})")
        saved_w = reshard_lib.saved_world(path)
        run_world = reshard_lib.current_world(strategy)
        mismatch = reshard_lib.describe_mismatch(saved_w, run_world)
        # Round 15: params-ONLY restore — the full-TrainState restore read
        # params + both Adam moments (~3x the params bytes; the documented
        # round-14 future optimization). `restore_params` filters the
        # sharded manifest to the `.params` leaves from npy headers alone
        # and places them straight at the serving shardings; because
        # leaves are assembled whole and placed at the TARGET shardings, a
        # training world that differs from the serving grid needs no
        # reshard pass for a params-only read.
        try:
            params, rs_info = ckpt_lib.restore_params(
                path, state_shapes.params, state_sharding.params
            )
        except ValueError as exc:
            # flax's structure mismatch is deep and unnamed — say what
            # it almost always means at this surface
            raise ValueError(
                f"--checkpoint {path}: state structure does not match "
                f"the model flags (--dim/--heads/--num_layers/"
                f"--num_experts... must equal the training run's). "
                f"Original error: {exc}"
            ) from exc
        rec = dict(kind="ckpt_restore", params_only=True,
                   checkpoint=str(path), mismatch=mismatch or "",
                   world=run_world, **rs_info)
        logger.log(**rec)
        recorder.record("ckpt_restore", params_only=True,
                        mismatch=mismatch or "")
        if p0:
            step = ckpt_lib._step_of(ckpt_lib.Path(path))
            skipped = rs_info.get("bytes_skipped", 0)
            print(f"serving checkpoint {path} ("
                  + (f"step {step}, " if step >= 0 else "")
                  + f"params-only restore: {rs_info['bytes_read']} B read"
                  + (f", {skipped} B of opt state skipped" if skipped else "")
                  + (f"; cross-world: {mismatch}" if mismatch else "") + ")")
    else:
        # smoke mode: fresh seeded params directly at the shardings
        params = jax.jit(
            lambda r: init_fn(r).params, out_shardings=state_sharding.params
        )(jax.random.PRNGKey(flags.seed))
        if p0:
            print("serving fresh seeded params (no --checkpoint)")

    # ---- the draft model (--draft model, round 17) -----------------------
    # The draft is restored by the SAME params-only reader as the target,
    # replicated (its forward is not the audited program — replication
    # keeps any head count legal whatever the model axis), with its own
    # kind="ckpt_restore" ledger so the report's restore accounting sees
    # both reads.
    draft_params = draft_cfg = None
    if flags.draft == "model":
        from jax.sharding import NamedSharding, PartitionSpec
        from tpukit.model.gpt import init_params as gpt_init_params

        draft_cfg = GPTConfig(
            dim=flags.draft_dim, head_dim=flags.draft_head_dim,
            heads=flags.draft_heads, num_layers=flags.draft_num_layers,
            vocab_size=tokenizer.vocab_size,
            max_position_embeddings=flags.sequence_length,
            compute_dtype=cfg.compute_dtype,
        )
        d_shapes = jax.eval_shape(
            partial(gpt_init_params, cfg=draft_cfg),
            jax.random.PRNGKey(flags.seed),
        )
        repl = NamedSharding(mesh, PartitionSpec())
        d_sharding = jax.tree.map(lambda _: repl, d_shapes)
        if flags.draft_checkpoint:
            # path-only, deliberately NO "latest": latest_any() scans one
            # shared directory, so "latest" here and on --checkpoint would
            # always resolve to the SAME (newest) save — there is no way
            # to say "latest draft" vs "latest target" from one ledger
            d_path = flags.draft_checkpoint
            if d_path == "latest":
                raise ValueError(
                    "--draft_checkpoint takes an explicit path: 'latest' "
                    "would resolve through the same checkpoint directory "
                    "as --checkpoint latest and pick the identical "
                    "(newest) save for both models"
                )
            ok, detail = ckpt_lib.verify_checkpoint(d_path)
            if not ok:
                raise RuntimeError(
                    f"--draft_checkpoint {d_path}: failed integrity "
                    f"verification ({detail})")
            try:
                draft_params, d_info = ckpt_lib.restore_params(
                    d_path, d_shapes, d_sharding
                )
            except ValueError as exc:
                raise ValueError(
                    f"--draft_checkpoint {d_path}: state structure does "
                    f"not match the draft shape flags (--draft_dim/"
                    f"--draft_heads/--draft_num_layers... must equal the "
                    f"draft training run's). Original error: {exc}"
                ) from exc
            rec = dict(kind="ckpt_restore", params_only=True, draft=True,
                       checkpoint=str(d_path), **d_info)
            logger.log(**rec)
            recorder.record("ckpt_restore", params_only=True, draft=True)
            if p0:
                print(f"draft model {d_path} (params-only restore: "
                      f"{d_info['bytes_read']} B read)")
        else:
            draft_params = jax.jit(
                partial(gpt_init_params, cfg=draft_cfg),
                out_shardings=d_sharding,
            )(jax.random.PRNGKey(flags.seed + 1))
            if p0:
                print("draft model: fresh seeded params "
                      "(no --draft_checkpoint)")

    return _serve_stream(flags, cfg, params, mesh, tokenizer, buckets, logger,
                         recorder, draft_params, draft_cfg)


def _serve_stream(flags, cfg, params, mesh, tokenizer, buckets, logger, recorder,
                  draft_params=None, draft_cfg=None):
    """The engine, the seeded synthetic stream through it, and the summary:
    the same for every block family (the engine reaches the model through
    `tpukit.model.family(cfg)`)."""
    from tpukit.mesh import is_process_zero
    from tpukit.obs import MetricRegistry, TraceRecorder, parse_slo
    from tpukit.serve import ServeConfig, ServeEngine, synthetic_request_stream

    p0 = is_process_zero()
    # ---- the engine + the stream -----------------------------------------
    serve = ServeConfig(
        slots=flags.slots, buckets=buckets,
        max_new_tokens=flags.max_new_tokens,
        temperature=flags.temperature, top_k=flags.top_k,
        window_steps=flags.window_steps,
        decode_quantum=flags.decode_quantum,
        page_size=flags.page_size, num_pages=flags.num_pages,
        kv_dtype=flags.kv_dtype, prefill_chunk=flags.prefill_chunk,
        draft=flags.draft, spec_k=flags.spec_k, ngram_max=flags.ngram_max,
        fused_decode=flags.fused_decode,
    )
    # Request-scoped tracing (round 20): on by default — the recorder is a
    # bounded ring of host-side span events, asserted <1% overhead and
    # token-bit-identical on/off by tests/test_trace.py.
    tracer = (None if flags.no_trace
              else TraceRecorder(capacity=flags.trace_capacity))
    # Metrics plane (round 22): on by default; --slo parses NOW so a
    # typo'd objective fails the launch, not silently never gates
    # (chaos-grammar discipline; SloSpecError is a clean startup error).
    metrics = None if flags.no_metrics else MetricRegistry()
    slo = parse_slo(flags.slo) if flags.slo else None
    engine = ServeEngine(params, cfg, serve, eos_id=int(tokenizer.eos_token_id),
                         mesh=mesh, logger=logger, recorder=recorder,
                         tracer=tracer, metrics=metrics, slo=slo,
                         metrics_dir=flags.metrics_dir or None,
                         draft_params=draft_params, draft_cfg=draft_cfg)
    requests = synthetic_request_stream(
        tokenizer, flags.requests, seed=flags.seed,
        max_new_tokens=flags.max_new_tokens, buckets=buckets, qps=flags.qps,
        shared_prefix=flags.shared_prefix,
        stream_profile=flags.stream_profile,
    )
    t0 = time.perf_counter()
    completions = engine.run(requests)
    wall = time.perf_counter() - t0

    if p0:
        gen = sum(c.generated for c in completions)
        e2e = sorted(c.e2e_s for c in completions)
        occ = (engine.last_summary or {}).get("mean_occupancy") or 0.0
        print(f"served {len(completions)} requests / {gen} tokens in "
              f"{wall:.2f}s ({gen / wall:.1f} tokens/s, occupancy "
              f"{100 * occ:.0f}%)")
        if serve.paged:
            s = engine.last_summary or {}
            print(f"paged KV: {s.get('num_pages')} pages x "
                  f"{s.get('page_size')} tokens ({s.get('kv_dtype')}), "
                  f"prefix hits {s.get('prefix_hits', 0)}/"
                  f"{s.get('admitted', 0)} admissions, "
                  f"{s.get('prefix_pages_reused', 0)} pages of prefill "
                  f"skipped")
        if serve.draft:
            sp = (engine.last_summary or {}).get("spec") or {}
            rate = sp.get("accept_rate")
            print(f"speculative decoding ({serve.draft}, k={serve.spec_k}): "
                  f"accepted {sp.get('accepted', 0)}/{sp.get('proposed', 0)} "
                  f"draft tokens"
                  + (f" ({100 * rate:.0f}%)" if rate is not None else "")
                  + f", appended/verify histogram "
                  f"{sp.get('accepted_hist', [])}")
        if e2e:
            print(f"e2e latency p50 {1e3 * e2e[len(e2e) // 2]:.1f} ms  "
                  f"p99 {1e3 * e2e[min(len(e2e) - 1, int(len(e2e) * 0.99))]:.1f} ms")
        s = engine.last_summary or {}
        if s.get("trace_complete") is not None:
            p50p = s.get("phase_p50") or {}
            print(f"traces: {100 * s['trace_complete']:.0f}% complete span "
                  f"trees; phase p50 (ms) "
                  + "  ".join(f"{k} {1e3 * v:.1f}"
                              for k, v in p50p.items() if v)
                  + (f" (view: python tools/traceview.py {flags.metrics_log})"
                     if flags.metrics_log else ""))
        if s.get("trace_dropped"):
            print(f"WARNING: {s['trace_dropped']} trace events evicted "
                  f"(ring saturated) — phase aggregates above are built "
                  f"from an incomplete history; grow --trace_capacity")
        if s.get("slo_overall_compliance") is not None:
            print(f"SLO compliance {100 * s['slo_overall_compliance']:.2f}% "
                  f"(worst target, cumulative) for --slo {flags.slo!r}")
        if flags.metrics_dir:
            print(f"metric snapshots -> {flags.metrics_dir} "
                  f"(live: python tools/top.py {flags.metrics_log or '-'} "
                  f"--metrics_dir {flags.metrics_dir})")
        for c in completions[:3]:
            print(f"  [{c.rid}] " + tokenizer.decode(
                np.asarray(c.ids), skip_special_tokens=True))
        if flags.metrics_log:
            print(f"serve telemetry -> {flags.metrics_log} "
                  f"(render: python tools/report.py {flags.metrics_log})")
    logger.close()
    # like the training recipes' FitResult: what came out, for a caller that
    # drives main(argv) in-process (chip_smoke.py); run_recipe ignores it
    return completions


def _latent_model(flags, tokenizer):
    """`--model latent`: the config (a configuration file's published keys,
    or the tiny preset at the tokenizer's vocabulary) and fresh seeded
    weights. The family has no training path, so there is no checkpoint to
    restore, no strategy and no mesh; speculation and the fleet speak of the
    GPT block's cache and are refused by name."""
    import json

    import jax
    import jax.numpy as jnp

    from tpukit.model import ServedOnlyError, latent

    for flag, why in (("checkpoint", "it is served only: nothing trains it, so nothing saves one"),
                      ("draft", "speculative decoding verifies on the ring cache"),
                      ("replicas", "the fleet's page handoff copies the GPT block's K and V pools")):
        if getattr(flags, flag):
            raise ServedOnlyError(f"--model latent takes no --{flag}: {why}")
    if not flags.page_size:
        raise ServedOnlyError("--model latent needs the paged cache (--page_size > 0): "
                              "its window layers keep a ring of pages")
    dtype = jnp.float32 if flags.disable_amp else jnp.bfloat16
    if flags.model_config:
        with open(flags.model_config) as f:
            cfg = latent.config_from_hf(json.load(f), compute_dtype=dtype, param_dtype=dtype)
    else:
        cfg = latent.tiny_config(vocab_size=tokenizer.vocab_size, compute_dtype=dtype, param_dtype=dtype)
    params = jax.jit(lambda r: latent.init_params(r, cfg))(jax.random.PRNGKey(flags.seed))
    print(f"serving fresh seeded params of the latent family "
          f"({cfg.num_layers} layers, {cfg.experts_held} of {cfg.n_experts} experts held)")
    return cfg, params


def _apply_request_knobs(requests, flags):
    """Apply the stream-wide request robustness knobs (round 24):
    `--deadline_ms` stamps every synthetic request with a completion
    deadline (the engine evicts over-deadline lanes with their partial
    tokens as reason=\"deadline\")."""
    if not flags.deadline_ms:
        return requests
    import dataclasses

    return [dataclasses.replace(r, deadline_ms=flags.deadline_ms)
            for r in requests]


def _run_fleet_worker(flags, cfg, tokenizer, buckets) -> int:
    """INTERNAL (`--fleet_worker N`, set by the --fleet_procs supervisor
    re-execing this recipe): run ONE replica engine as a real process
    driven entirely through the durable ledger under `--fleet_dir` —
    claim leases addressed to this replica, decode, publish exactly-once
    completion records, beat the heartbeat file, exit on the
    supervisor's stop record. The worker does its OWN params cold start
    (processes share no memory; the ledger directory is the only
    channel) and never writes the supervisor's JSONL."""
    import jax
    from functools import partial

    from tpukit import checkpoint as ckpt_lib
    from tpukit.serve import ServeConfig, ServeEngine, serve_from_ledger
    from tpukit.serve.fleet import place_replica_params
    from tpukit.shardings import SingleDevice
    from tpukit.train import create_train_state, make_optimizer

    serve = ServeConfig(
        slots=flags.slots, buckets=buckets,
        max_new_tokens=flags.max_new_tokens,
        temperature=flags.temperature, top_k=flags.top_k,
        window_steps=flags.window_steps,
        decode_quantum=flags.decode_quantum,
        page_size=flags.page_size, num_pages=flags.num_pages,
        kv_dtype=flags.kv_dtype, prefill_chunk=flags.prefill_chunk,
        draft=flags.draft, spec_k=flags.spec_k, ngram_max=flags.ngram_max,
        fused_decode=flags.fused_decode,
    )
    optimizer = make_optimizer(1e-4)
    init_fn = partial(create_train_state, cfg=cfg, optimizer=optimizer,
                      strategy=SingleDevice())
    state_shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(flags.seed))
    if flags.checkpoint:
        path = (ckpt_lib.latest_any() if flags.checkpoint == "latest"
                else flags.checkpoint)
        if path is None:
            raise FileNotFoundError("--checkpoint latest: no checkpoint found")
        params_host, _ = ckpt_lib.restore_params(
            path, state_shapes.params, None
        )
        params = place_replica_params(params_host, None)
    else:
        params = jax.jit(lambda r: init_fn(r).params)(
            jax.random.PRNGKey(flags.seed)
        )
    engine = ServeEngine(
        params, cfg, serve, eos_id=int(tokenizer.eos_token_id), mesh=None,
        logger=None, recorder=None, replica=flags.fleet_worker,
    )
    comps = serve_from_ledger(engine, flags.fleet_dir, flags.fleet_worker)
    print(f"fleet worker {flags.fleet_worker}: {len(comps)} completion(s) "
          f"published")
    return 0


def _run_fleet_procs(flags, cfg, tokenizer, buckets) -> int:
    """Process fleet (`--fleet_procs`, round 24): each replica is a real
    worker PROCESS (this recipe re-exec'd with `--fleet_worker i`)
    coordinated only through the durable ledger under `--fleet_dir`.
    `--fleet_kill replica_sigkill@R` delivers a REAL SIGKILL mid-stream;
    liveness (process exit + heartbeat age) revokes the victim's leases
    and requeues its in-flight requests on survivors with the
    `--request_retries` budget — the crash-consistency claim the
    in-process router can only simulate."""
    import os
    import subprocess

    from tpukit import chaos as chaos_lib
    from tpukit.obs import FlightRecorder, StepLogger
    from tpukit.serve import (
        ProcessFleet,
        local_tpu_chips,
        synthetic_request_stream,
        worker_chip_env,
    )

    if not flags.fleet_dir:
        raise ValueError(
            "--fleet_procs requires --fleet_dir: the ledger directory is "
            "the only channel between supervisor and worker processes"
        )
    # One process per chip: this supervisor never initialises a backend,
    # and worker i is bound to chip i by the TPU runtime's own environment.
    # Built for every worker BEFORE any spawn, so more workers than chips
    # is a named error here, not a hang later.
    n_chips = local_tpu_chips()
    chip_env = [worker_chip_env(i, flags.replicas, n_chips)
                for i in range(flags.replicas)]
    print(f"process fleet: {flags.replicas} worker process(es); "
          + (f"{n_chips} TPU chip(s) on this host, worker i bound to chip i"
             if n_chips else "no TPU on this host, nothing to bind"))
    logger = StepLogger(flags.metrics_log)
    recorder = FlightRecorder()

    def spawn(idx):
        argv = ([sys.executable, sys.argv[0]] + list(sys.argv[1:])
                + ["--fleet_worker", str(idx)])
        return subprocess.Popen(argv, env={**os.environ, **chip_env[idx]})

    requests = _apply_request_knobs(
        synthetic_request_stream(
            tokenizer, flags.requests, seed=flags.seed,
            max_new_tokens=flags.max_new_tokens, buckets=buckets,
            qps=flags.qps, shared_prefix=flags.shared_prefix,
            stream_profile=flags.stream_profile,
        ),
        flags,
    )
    pf = ProcessFleet(
        flags.fleet_dir, spawn=spawn, replicas=flags.replicas,
        replica_timeout=flags.replica_timeout or 5.0,
        request_retries=flags.request_retries,
        chaos=chaos_lib.ServingChaos(flags.fleet_kill),
        logger=logger, recorder=recorder,
    )
    rec = pf.run(requests)
    print(f"process fleet served {rec['requests']} requests / "
          f"{rec['generated_tokens']} tokens in {rec['wall_s']:.2f}s over "
          f"{flags.replicas} worker process(es)")
    if rec["replicas_dead"] or rec["kills"]:
        print(f"  failures: {rec['kills']} SIGKILL(s), "
              f"{rec['replicas_dead']} replica death(s), "
              f"{rec['leases_revoked']} lease(s) revoked, "
              f"{rec['requeued']} request(s) re-queued, "
              f"{rec['duplicate_completions']} duplicate completion(s)")
    if rec["request_failures"] or rec["deadline_misses"]:
        print(f"  requests: {rec['request_failures']} terminal failure(s), "
              f"{rec['deadline_misses']} deadline miss(es)")
    if rec["retry_total"]:
        print(f"  {rec['retry_total']} transient I/O error(s) retried")
    if flags.metrics_log:
        print(f"fleet telemetry -> {flags.metrics_log} "
              f"(render: python tools/report.py {flags.metrics_log})")
    logger.close()
    return 0


def _run_fleet(flags, cfg, tokenizer, buckets) -> int:
    """Fleet serving (round 19, ROADMAP #1): route the stream over
    `--replicas` ServeEngine replicas on disjoint device subsets via
    `tpukit/serve/fleet.FleetRouter`. The checkpoint cold start is SHARED:
    `checkpoint.restore_params(..., sharding_tree=None)` reads the bytes
    ONCE into host arrays, and every replica placement is a device_put of
    that one copy — the `kind="ckpt_restore"` ledger records bytes_read
    once with the placement count alongside, so N replicas never imply
    N checkpoint reads. Round 24 adds the crash-tolerance plane: worker
    (`--fleet_worker`) and process-fleet (`--fleet_procs`) modes dispatch
    before the in-process router below."""
    if flags.fleet_worker >= 0:
        return _run_fleet_worker(flags, cfg, tokenizer, buckets)
    if flags.fleet_procs:
        return _run_fleet_procs(flags, cfg, tokenizer, buckets)
    import time
    from functools import partial

    import jax
    import numpy as np

    from tpukit import checkpoint as ckpt_lib
    from tpukit.mesh import is_process_zero
    from tpukit.obs import (
        FlightRecorder,
        MetricRegistry,
        StepLogger,
        TraceRecorder,
        parse_slo,
    )
    from tpukit.serve import (
        FleetConfig,
        FleetRouter,
        ServeConfig,
        synthetic_request_stream,
    )
    from tpukit.shardings import SingleDevice
    from tpukit.train import create_train_state, make_optimizer

    if flags.draft == "model":
        raise ValueError(
            "--replicas with --draft model is a future round (the draft "
            "params would need their own per-replica placement); "
            "--draft ngram (self-speculation, no second model) runs per "
            "replica today"
        )
    serve = ServeConfig(
        slots=flags.slots, buckets=buckets,
        max_new_tokens=flags.max_new_tokens,
        temperature=flags.temperature, top_k=flags.top_k,
        window_steps=flags.window_steps,
        decode_quantum=flags.decode_quantum,
        page_size=flags.page_size, num_pages=flags.num_pages,
        kv_dtype=flags.kv_dtype, prefill_chunk=flags.prefill_chunk,
        draft=flags.draft, spec_k=flags.spec_k, ngram_max=flags.ngram_max,
        fused_decode=flags.fused_decode,
    )
    fleet = FleetConfig(
        replicas=flags.replicas,
        devices_per_replica=flags.devices_per_replica,
        min_replicas=flags.min_replicas, max_replicas=flags.max_replicas,
        scale_up_occupancy=flags.scale_up_occupancy,
        scale_down_occupancy=flags.scale_down_occupancy,
        window_steps=flags.fleet_window_steps,
        disagg_prefill=flags.disagg_prefill,
        prefill_slots=flags.prefill_slots, prefill_pages=flags.prefill_pages,
        kill_spec=flags.fleet_kill,
        fleet_dir=flags.fleet_dir,
        replica_timeout=flags.replica_timeout,
        request_retries=flags.request_retries,
        max_queue_depth=flags.max_queue_depth,
    )
    logger = StepLogger(flags.metrics_log)
    recorder = FlightRecorder()
    p0 = is_process_zero()

    # Shapes only (strategy-independent): the template for the params-only
    # host read. Nothing is materialized here.
    optimizer = make_optimizer(1e-4)
    init_fn = partial(create_train_state, cfg=cfg, optimizer=optimizer,
                      strategy=SingleDevice())
    state_shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(flags.seed))

    path = rs_info = None
    if flags.checkpoint:
        path = (ckpt_lib.latest_any() if flags.checkpoint == "latest"
                else flags.checkpoint)
        if path is None:
            raise FileNotFoundError("--checkpoint latest: no checkpoint found")
        ok, detail = ckpt_lib.verify_checkpoint(path)
        if not ok:
            raise RuntimeError(f"--checkpoint {path}: failed integrity "
                               f"verification ({detail})")
        try:
            # sharding_tree=None keeps the leaves on HOST — the one read
            params_host, rs_info = ckpt_lib.restore_params(
                path, state_shapes.params, None
            )
        except ValueError as exc:
            raise ValueError(
                f"--checkpoint {path}: state structure does not match "
                f"the model flags (--dim/--heads/--num_layers/"
                f"--num_experts... must equal the training run's). "
                f"Original error: {exc}"
            ) from exc
    else:
        params_host = jax.tree.map(
            lambda x: np.asarray(jax.device_get(x)),
            jax.jit(lambda r: init_fn(r).params)(jax.random.PRNGKey(flags.seed)),
        )
        if p0:
            print("serving fresh seeded params (no --checkpoint)")

    # One shared TraceRecorder across router + replicas + prefill worker:
    # span events land in per-replica rings and merge into one event stream.
    tracer = (None if flags.no_trace
              else TraceRecorder(capacity=flags.trace_capacity))
    # One shared MetricRegistry too (round 22): replica engines observe
    # replica-labeled series into it; the router accounts the declared
    # --slo fleet-wide and owns the --metrics_dir snapshot publish/merge.
    metrics = None if flags.no_metrics else MetricRegistry()
    slo = parse_slo(flags.slo) if flags.slo else None
    router = FleetRouter(params_host, cfg, serve, fleet,
                         eos_id=int(tokenizer.eos_token_id),
                         logger=logger, recorder=recorder, tracer=tracer,
                         metrics=metrics, slo=slo,
                         metrics_dir=flags.metrics_dir or None)
    if path is not None:
        rec = dict(kind="ckpt_restore", params_only=True, fleet=True,
                   checkpoint=str(path), replicas=flags.replicas,
                   placements=router.placements, **rs_info)
        logger.log(**rec)
        recorder.record("ckpt_restore", params_only=True, fleet=True,
                        placements=router.placements)
        if p0:
            print(f"fleet cold start from {path}: "
                  f"{rs_info['bytes_read']} B read ONCE, "
                  f"{router.placements} placement(s) for "
                  f"{flags.replicas} replica(s)"
                  + (" + prefill worker" if fleet.disagg_prefill else ""))

    requests = _apply_request_knobs(
        synthetic_request_stream(
            tokenizer, flags.requests, seed=flags.seed,
            max_new_tokens=flags.max_new_tokens, buckets=buckets,
            qps=flags.qps, shared_prefix=flags.shared_prefix,
            stream_profile=flags.stream_profile,
        ),
        flags,
    )
    t0 = time.perf_counter()
    completions = router.run(requests)
    wall = time.perf_counter() - t0

    if p0:
        s = router.last_summary or {}
        gen = sum(c.generated for c in completions)
        print(f"fleet served {len(completions)} requests / {gen} tokens in "
              f"{wall:.2f}s ({gen / wall:.1f} tokens/s) over "
              f"{s.get('replicas_final', '?')} replica(s) "
              f"(peak {s.get('replicas_peak', '?')})")
        if s.get("kills") or s.get("requeued"):
            print(f"  failures: {s.get('kills', 0)} replica kill(s) "
                  f"({s.get('replicas_dead', 0)} by liveness), "
                  f"{s.get('leases_revoked', 0)} lease(s) revoked, "
                  f"{s.get('requeued', 0)} request(s) re-queued, "
                  f"{s.get('duplicate_completions', 0)} duplicate "
                  f"completion(s)")
        if (s.get("deadline_misses") or s.get("rejected")
                or s.get("request_failures")):
            print(f"  requests: {s.get('deadline_misses', 0)} deadline "
                  f"miss(es), {s.get('rejected', 0)} shed by backpressure, "
                  f"{s.get('request_failures', 0)} terminal failure(s)")
        if s.get("ledger"):
            led = s["ledger"]
            print(f"  ledger: {led.get('completed', 0)} durable completion "
                  f"record(s), {led.get('replayed', 0)} replayed, "
                  f"{led.get('duplicates', 0)} duplicate(s) "
                  f"-> {flags.fleet_dir}")
        if s.get("scale_ups") or s.get("scale_downs"):
            print(f"  autoscale: {s.get('scale_ups', 0)} up / "
                  f"{s.get('scale_downs', 0)} down")
        if fleet.disagg_prefill:
            d = s.get("disagg_prefill") or {}
            print(f"  disaggregated prefill: {d.get('handoffs', 0)} "
                  f"handoffs, {d.get('worker_prefix_hits', 0)} worker "
                  f"prefix hits, {d.get('worker_pages_reused', 0)} pages "
                  f"of prefill skipped")
        p50, p99 = s.get("p50_e2e_s"), s.get("p99_e2e_s")
        if p50 is not None:
            print(f"  e2e latency p50 {1e3 * p50:.1f} ms  "
                  f"p99 {1e3 * p99:.1f} ms")
        if s.get("trace_complete") is not None:
            p50p = s.get("phase_p50") or {}
            print(f"  traces: {100 * s['trace_complete']:.0f}% complete "
                  f"span trees; phase p50 (ms) "
                  + "  ".join(f"{k} {1e3 * v:.1f}"
                              for k, v in p50p.items() if v))
        if s.get("trace_dropped"):
            print(f"  WARNING: {s['trace_dropped']} trace events evicted "
                  f"(per replica {s.get('trace_dropped_by_replica')}) — "
                  f"grow --trace_capacity")
        if s.get("slo_overall_compliance") is not None:
            print(f"  SLO compliance "
                  f"{100 * s['slo_overall_compliance']:.2f}% (worst "
                  f"target, cumulative) for --slo {flags.slo!r}")
        if flags.metrics_log:
            print(f"fleet telemetry -> {flags.metrics_log} "
                  f"(render: python tools/report.py {flags.metrics_log})")
    logger.close()
    return 0


if __name__ == "__main__":
    from tpukit.recovery import run_recipe

    # Exit-code contract (docs/DESIGN.md "recovery", README): 0 clean,
    # 75 preempted-and-checkpointed, 76 anomaly abort, 77 rollback budget
    # exhausted — what a babysitter script keys its relaunch decision on.
    sys.exit(run_recipe(main))
