"""Shared CLI flag system.

The reference duplicates an identical 12-flag argparse block in every recipe
(main-single.py:156-167, main-ddp.py:192-203, main-fsdp.py:206-219,
main-pipe.py:225-236); here it is one dataclass + builder imported by all
five recipes (SURVEY §5 config plan). Flag names and defaults are twinned
exactly; `--cpu_offload` is the FSDP recipe's extra flag (main-fsdp.py:219).

TPU reinterpretations (documented divergences, not silent ones):
  - `--disable_amp`: flips the compute dtype from bfloat16 to float32. There
    is no GradScaler twin — bf16 needs no loss scaling (the reference's
    scaler is a no-op for bf16 anyway, main-single.py:78).
  - `--disable_compile`: runs the train/eval steps eagerly via
    `jax.disable_jit()` — the debugging analogue of skipping torch.compile
    (main-single.py:38-39).
"""

from __future__ import annotations

import argparse
import dataclasses


@dataclasses.dataclass
class TrainFlags:
    batch_size: int = 64
    epochs: int = 5
    sequence_length: int = 256
    dim: int = 256
    head_dim: int = 32
    heads: int = 8
    num_layers: int = 8
    learning_rate: float = 1e-4
    dataset_slice: str = "100%"
    num_workers: int = 4
    disable_amp: bool = False
    disable_compile: bool = False
    # FSDP recipe only (main-fsdp.py:219):
    cpu_offload: bool = False
    # tpukit extensions (absent in the reference; see SURVEY §5 plans):
    seed: int = 0
    # Dropout rate (the reference model takes it as a constructor arg but its
    # CLIs never expose it, models/gpt.py:14,50; here it is a flag). Active
    # in train steps only, seeded per step from --seed.
    dropout: float = 0.0
    checkpoint_every: int = 0  # steps; 0 = end-of-training only (reference behavior)
    # "auto" writes the sharded format exactly when the state cannot be
    # host-gathered (multi-host FSDP/pipeline), else the consolidated
    # msgpack the reference-style save produces. Force either explicitly.
    checkpoint_format: str = "auto"  # auto | consolidated | sharded
    # Non-blocking checkpoint writes (round 7): snapshot on the training
    # thread, encode/write/publish on a background thread with a join
    # barrier at the next save/exit. Same formats, same atomic-publish
    # durability; only the loop no longer stalls on disk.
    async_checkpoint: bool = False
    # Retention (round 13): after each successful checkpoint publish, prune
    # published checkpoints older than the newest K, so long elastic runs
    # don't exhaust disk. Quarantined timelines and the newest
    # integrity-verified (`latest_good`) checkpoint are never pruned.
    # 0 = keep everything (the pre-round-13 behavior). Note K also bounds
    # how far back `--on_anomaly rollback` can reach.
    keep_checkpoints: int = 0
    # Resume path (either format) or "latest". Round 13: `--resume` is
    # ELASTIC — when the checkpoint's recorded world (nprocs, device
    # count, strategy, mesh axes; written into every save's meta sidecar)
    # differs from the current run's, the state is resharded onto the
    # current `state_sharding` specs (tpukit/reshard.py) instead of
    # failing or silently misloading, and a kind="resize" JSONL record
    # names the change. Hold global batch (batch_size x data shards)
    # constant across a resize for loss-trajectory parity.
    resume: str = ""
    # Host input pipeline depth (round 7): a background thread runs
    # prepare_batch + the strategy's host transform + global-batch H2D
    # assembly this many batches ahead, overlapping the in-flight compiled
    # step. 0 = the synchronous reference path (bit-identical losses).
    prefetch: int = 2
    # Explicit override of where JAX's persistent compilation cache lives.
    # Empty = tpukit/cache.py's rule: $JAX_COMPILATION_CACHE_DIR if set,
    # else <checkout>/.jax_cache. Repeat runs of the same program skip XLA
    # recompiles, and fit logs a kind="compile_cache" hit/miss record.
    compilation_cache_dir: str = ""
    profile_dir: str = ""  # if set, jax.profiler traces land here
    metrics_log: str = ""  # if set, JSONL step metrics land here
    # Metrics plane (round 22, tpukit/obs/metrics.py): mergeable
    # counters + log-bucket histograms derived from the fit() window
    # spans and the recovery observers — ON by default (pure observer,
    # window-boundary host code only). --metrics_dir points at a SHARED
    # directory where every process atomically publishes its snapshot
    # file each window and process 0 merges by bucket sum.
    no_metrics: bool = False
    metrics_dir: str = ""
    # Debug toolchain (SURVEY §5 race-detection plan): aborts with a traceback
    # at the first NaN/Inf produced inside any jitted computation.
    debug_nans: bool = False
    # Telemetry (tpukit/obs, round 6). --log_grad_norms computes global
    # grad/update/param L2 norms INSIDE the existing jitted train step and
    # logs them per window; off = the compiled step is untouched.
    log_grad_norms: bool = False
    # Loss-spike/NaN sentinel on the window-averaged loss: 0 disables; N > 0
    # fires when the loss exceeds the rolling mean by N deviations (or goes
    # non-finite). Action: "warn" logs and continues; "abort" writes a
    # checkpoint then raises (so the blow-up step is preserved for autopsy).
    spike_threshold: float = 0.0
    spike_action: str = "warn"  # warn | abort
    # Multi-host liveness: if set, every process writes a heartbeat file
    # (step + timestamp) to this SHARED directory each PRINT_FREQ window and
    # process 0 reports processes whose beats go stale past the timeout.
    heartbeat_dir: str = ""
    heartbeat_timeout: float = 120.0  # seconds
    # Failure observability (round 8, tpukit/obs). The flight recorder (a
    # bounded in-memory ring of recent step/window/sentinel records) is
    # ALWAYS on; these flags control what gets done with it when a run
    # goes wrong:
    #   --hang_timeout S > 0 starts the hang watchdog: a monitor thread
    #     armed around each step iteration that dumps a diagnostics bundle
    #     (all-thread stacks, recorder ring, HBM gauges, heartbeat
    #     snapshot, in-flight async-checkpoint/prefetch state, run config)
    #     to --debug_dir when an iteration overruns S seconds. The first
    #     step of each compiled function is exempt (compile time is not a
    #     hang); S bounds the steady-state step, not the compile.
    #   --debug_dir D is where bundles (and the anomaly trace) land; any
    #     sentinel firing (spike/NaN/straggler/divergence) also dumps a
    #     bundle there. Defaults to "debug" when a feature needing it is
    #     on; render bundles with tools/flightview.py.
    hang_timeout: float = 0.0  # seconds; 0 disables the watchdog
    debug_dir: str = ""
    # Trace-on-anomaly: K > 0 arms a jax.profiler capture of the K steps
    # following the FIRST anomaly of the run (spike/NaN/straggler/
    # divergence/hang-recovery), so the expensive trace is collected
    # exactly when it matters. Traces land under --debug_dir/anomaly_trace.
    # Ignored when --profile_dir already traces the whole run.
    trace_on_anomaly: int = 0
    # Cross-replica divergence detection: every N steps compute an in-jit
    # XOR checksum of params + opt state (a separate jitted program — the
    # train step's HLO is byte-identical on/off, the --log_grad_norms
    # discipline), publish it through the heartbeat file, and have
    # process 0 compare across processes; a mismatch at the same step
    # logs kind="divergence" and dumps a bundle. 0 disables.
    divergence_check_freq: int = 0
    # Recovery (round 9, docs/DESIGN.md "recovery"). --on_anomaly rollback
    # turns a sentinel/divergence firing from checkpoint-then-abort into an
    # in-process rollback: restore the last integrity-verified checkpoint
    # OLDER than the detection window, keep the input stream moving forward
    # (the offending batch window is never replayed), and continue — up to
    # --max_rollbacks times, then escalate to the round-8 bundle-dump-and-
    # abort path (exit code 77). "none" keeps the round-8 behavior.
    on_anomaly: str = "none"  # none | rollback
    max_rollbacks: int = 3
    # Transient host-I/O retry budget (tpukit/retry.py): checkpoint
    # reads/writes and dataset fetches retry up to N times with jittered
    # exponential backoff before failing loud. Every retry leaves a
    # kind="retry" JSONL record. 0 disables retrying.
    io_retries: int = 3
    # Deterministic fault injection (tpukit/chaos.py), e.g.
    # "nan_loss@120,sigterm@300,ckpt_io_fail@2,hang@450:2.5" — see the
    # chaos-spec grammar in docs/DESIGN.md. Empty = no harness installed;
    # the compiled train step is byte-identical either way (all injection
    # is host-side).
    chaos_spec: str = ""
    # Rematerialization policy: checkpoint each decoder layer (backward
    # recomputes the layer forward; less HBM traffic and memory — needed for
    # the larger ladder configs at long sequence).
    remat: bool = False
    # Run the layer stack as one lax.scan body instead of unrolled blocks
    # (slower on v5e at the reference depth, but keeps compile time flat for
    # very deep models).
    scan_layers: bool = False
    # Pipeline recipes: micro-batch count. 0 = 4x the stage count (shrinks
    # the GPipe bubble to ~16%); the reference ties it to the stage count
    # (chunks=num_stages, main-pipe.py:83) — pass it explicitly for that.
    microbatches: int = 0
    # main-ring.py only: sequence-parallel attention schedule — "ring"
    # (zigzag-balanced ppermute hops) or "ulysses" (all_to_all head
    # re-partitioning; needs heads % seq_shards == 0).
    cp_attention: str = "ring"
    # pipeline recipes only: "gpipe" (autodiff schedule, vocab-sharded
    # embeddings/head) or "1f1b" (explicit per-stage vjps — activation
    # memory bounded by the stage count instead of the micro count).
    pipeline_schedule: str = "gpipe"
    # pipeline recipes only (round 22, ROADMAP #5): interleaved virtual
    # stages for the 1f1b schedule — device d owns V non-contiguous layer
    # chunks (d, d+S, d+2S, ...) and the tick table interleaves their
    # forward/backward micro-steps, shrinking the warm-up/cool-down bubble
    # toward (S-1)/(M*V) at equal micro count. 1 = the existing schedules,
    # byte-identical HLO; needs --schedule 1f1b and num_layers >= V*S.
    virtual_stages: int = 1
    # main-moe.py AND (round 22) the pipeline recipes: number of routed
    # experts replacing each layer's FFN (0 = the dense reference model)
    # and how many experts each token routes to (1 = Switch, 2 =
    # GShard/Mixtral-style top-2). Under the pipeline recipes the expert
    # FFN rides INSIDE a stage chunk and only the meshless
    # --moe_dispatch pallas dataflow is legal (no a2a axis on a stage
    # mesh); xla/a2a are rejected by name at validate_config.
    num_experts: int = 0
    moe_top_k: int = 1
    # main-moe.py only: expert dispatch dataflow (round 10/11). "a2a"
    # (default) hand-places the token exchange as a shard_map
    # lax.all_to_all pair over the `expert` mesh axis — forward AND
    # backward — instead of leaving the dispatch einsums to GSPMD, whose
    # backward falls into involuntary replicate-repartition
    # (MULTICHIP_r05.json). "pallas" keeps that exchange but computes the
    # expert FFN with the fused grouped-expert GEMM (tpukit/ops/
    # moe_gemm.py; on one chip it is the dropless sorted segment GEMM —
    # the moe_e8 throughput path). "xla" restores the round-5
    # einsum-and-GSPMD behavior for comparison.
    moe_dispatch: str = "a2a"
    # Collective payload dtype (round 12, tpukit/ops/quant_comm.py —
    # EQuARX-style block-scaled quantized collectives). "f32" (default)
    # keeps the exact pre-round-12 collectives; "bf16"/"int8" compress the
    # wire payload of the strategies with hand-wired quantized collectives:
    # the DDP gradient all-reduce (two-shot: int8 reduce-scatter -> f32
    # accumulate -> int8 all-gather), the FSDP gradient reduce-scatter
    # (param all-gathers stay full precision — grads first), and the
    # ExpertParallel a2a dispatch payload. Optimizer math and master
    # params stay f32; correctness is gated by a loss-trajectory tolerance
    # (tests/test_quant_comm.py), not bit parity. Strategies without wired
    # collectives reject non-f32 values at startup.
    comm_dtype: str = "f32"
    # Stochastic rounding for the int8 quantizer (unbiased per element;
    # default off = round-to-nearest-even).
    quant_stochastic: bool = False
    # Overlap-scheduled gradient collectives (round 18, ROADMAP #5):
    # 0 (default) = the serial schedule, byte-identical HLO. N >= 1 =
    # DDP/FSDP partition the grad tree into N ~equal-byte buckets in
    # layer-reversed order and launch each bucket's collective as soon as
    # its grads are ready, overlapping wire with the remaining backward;
    # under ExpertParallel (per-layer a2a, already bucket-granular) any
    # N declares the hlolint `overlap` gate. Composes with --comm_dtype
    # (int8 wire cut + overlap win stack). Strategies without a
    # hand-placed grad wire reject the flag at startup.
    grad_buckets: int = 0


# The canonical 12 flags of every reference recipe (main-single.py:156-167).
_CORE_FLAGS = [
    ("batch_size", int),
    ("epochs", int),
    ("sequence_length", int),
    ("dim", int),
    ("head_dim", int),
    ("heads", int),
    ("num_layers", int),
    ("learning_rate", float),
    ("dataset_slice", str),
    ("num_workers", int),
]


def build_parser(
    cpu_offload: bool = False,
    cp_attention: bool = False,
    pipeline_schedule: bool = False,
    num_experts: bool = False,
    default_experts: int = 8,
) -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser()
    defaults = TrainFlags()
    for name, typ in _CORE_FLAGS:
        parser.add_argument(f"--{name}", type=typ, default=getattr(defaults, name))
    parser.add_argument("--disable_amp", action="store_true")
    parser.add_argument("--disable_compile", action="store_true")
    if cpu_offload:
        parser.add_argument("--cpu_offload", action="store_true")
    if cp_attention:
        parser.add_argument(
            "--cp_attention", choices=("ring", "ulysses"), default="ring"
        )
    if pipeline_schedule:
        parser.add_argument(
            "--schedule", dest="pipeline_schedule",
            choices=("gpipe", "1f1b"), default="gpipe",
        )
        parser.add_argument(
            "--virtual_stages", type=int, default=defaults.virtual_stages
        )
    if num_experts:
        # main-moe.py keeps its 8-expert default; the pipeline recipes opt
        # in with default_experts=0 so `main-pipe.py` stays the dense
        # reference unless --num_experts is passed explicitly
        parser.add_argument("--num_experts", type=int, default=default_experts)
        parser.add_argument("--moe_top_k", type=int, default=1)
        parser.add_argument(
            "--moe_dispatch", choices=("a2a", "xla", "pallas"), default="a2a"
        )
    parser.add_argument("--seed", type=int, default=defaults.seed)
    parser.add_argument("--dropout", type=float, default=defaults.dropout)
    parser.add_argument("--checkpoint_every", type=int, default=defaults.checkpoint_every)
    parser.add_argument(
        "--checkpoint_format",
        choices=("auto", "consolidated", "sharded"),
        default=defaults.checkpoint_format,
    )
    parser.add_argument("--async_checkpoint", action="store_true")
    parser.add_argument(
        "--keep_checkpoints", type=int, default=defaults.keep_checkpoints
    )
    parser.add_argument("--resume", type=str, default=defaults.resume)
    parser.add_argument("--prefetch", type=int, default=defaults.prefetch)
    parser.add_argument(
        "--compilation_cache_dir", type=str,
        default=defaults.compilation_cache_dir,
    )
    parser.add_argument("--profile_dir", type=str, default=defaults.profile_dir)
    parser.add_argument("--metrics_log", type=str, default=defaults.metrics_log)
    parser.add_argument("--no_metrics", action="store_true",
                        default=defaults.no_metrics)
    parser.add_argument("--metrics_dir", type=str, default=defaults.metrics_dir)
    parser.add_argument("--debug_nans", action="store_true")
    parser.add_argument("--log_grad_norms", action="store_true")
    parser.add_argument(
        "--spike_threshold", type=float, default=defaults.spike_threshold
    )
    parser.add_argument(
        "--spike_action", choices=("warn", "abort"), default=defaults.spike_action
    )
    parser.add_argument("--heartbeat_dir", type=str, default=defaults.heartbeat_dir)
    parser.add_argument(
        "--heartbeat_timeout", type=float, default=defaults.heartbeat_timeout
    )
    parser.add_argument("--hang_timeout", type=float, default=defaults.hang_timeout)
    parser.add_argument("--debug_dir", type=str, default=defaults.debug_dir)
    parser.add_argument(
        "--trace_on_anomaly", type=int, default=defaults.trace_on_anomaly
    )
    parser.add_argument(
        "--divergence_check_freq", type=int,
        default=defaults.divergence_check_freq,
    )
    parser.add_argument(
        "--on_anomaly", choices=("none", "rollback"), default=defaults.on_anomaly
    )
    parser.add_argument(
        "--max_rollbacks", type=int, default=defaults.max_rollbacks
    )
    parser.add_argument("--io_retries", type=int, default=defaults.io_retries)
    parser.add_argument("--chaos_spec", type=str, default=defaults.chaos_spec)
    parser.add_argument(
        "--comm_dtype", choices=("f32", "bf16", "int8"),
        default=defaults.comm_dtype,
    )
    parser.add_argument("--quant_stochastic", action="store_true")
    parser.add_argument(
        "--grad_buckets", type=int, default=defaults.grad_buckets
    )
    parser.add_argument("--remat", action="store_true")
    parser.add_argument("--scan_layers", action="store_true")
    parser.add_argument("--microbatches", type=int, default=defaults.microbatches)
    return parser


def parse_flags(
    argv=None,
    cpu_offload: bool = False,
    cp_attention: bool = False,
    pipeline_schedule: bool = False,
    num_experts: bool = False,
    default_experts: int = 8,
) -> TrainFlags:
    ns = build_parser(
        cpu_offload=cpu_offload,
        cp_attention=cp_attention,
        pipeline_schedule=pipeline_schedule,
        num_experts=num_experts,
        default_experts=default_experts,
    ).parse_args(argv)
    kw = vars(ns)
    kw.setdefault("cpu_offload", False)
    kw.setdefault("cp_attention", "ring")
    kw.setdefault("pipeline_schedule", "gpipe")
    kw.setdefault("virtual_stages", 1)
    kw.setdefault("num_experts", 0)
    kw.setdefault("moe_top_k", 1)
    kw.setdefault("moe_dispatch", "a2a")
    return TrainFlags(**kw)


def add_serve_flags(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """The serving-engine shape flags (main-serve.py, recipe 9), one
    spelling shared by the recipe and any harness that builds a
    `ServeConfig` from a CLI. Round 15 adds the paged-KV group: pages +
    block tables replace the per-slot ring when --page_size > 0, with
    shared-prefix reuse, chunked prefill, and int8 page payloads riding
    on top (tpukit/serve/paged.py; validation lives on ServeConfig and
    the engine so misconfigurations fail with named errors, not XLA
    shape errors)."""
    parser.add_argument("--slots", type=int, default=8)
    parser.add_argument("--buckets", type=str, default="16,32,64",
                        help="comma-separated prompt-length buckets — the "
                        "declared compile budget of the serve path")
    parser.add_argument("--max_new_tokens", type=int, default=20)
    parser.add_argument("--temperature", type=float, default=0.0)
    parser.add_argument("--top_k", type=int, default=0)
    parser.add_argument("--window_steps", type=int, default=32)
    parser.add_argument("--decode_quantum", type=int, default=4,
                        help="decode steps per runtime dispatch (and per "
                        "host sync); with --fused_decode the quantum runs "
                        "as ONE on-device while_loop — raise it toward "
                        "--window_steps to amortize dispatch overhead")
    parser.add_argument("--page_size", type=int, default=0,
                        help="paged KV cache: token positions per page "
                        "(must divide every bucket); 0 = the per-slot ring")
    parser.add_argument("--num_pages", type=int, default=0,
                        help="page-pool size; 0 = ring-equivalent HBM "
                        "(slots x pages-per-slot + the null page)")
    parser.add_argument("--kv_dtype", choices=("f32", "bf16", "int8"),
                        default="f32",
                        help="page payload storage; int8 block-quantizes "
                        "page rows (quant_comm's 256-element blocks) for "
                        "~4x pages per HBM byte, gated by a token-level "
                        "tolerance test — requires --page_size")
    parser.add_argument("--prefill_chunk", type=int, default=0,
                        help="chunked-prefill tokens per dispatch (page "
                        "multiple dividing every bucket); 0 = one page")
    parser.add_argument("--fused_decode", action="store_true",
                        help="on-device scheduler window (round 21): "
                        "each quantum runs as one "
                        "on-device while_loop with early exit "
                        "(decode.decode_loop_window) — token streams "
                        "identical, host dispatch amortized across the "
                        "quantum. Requires --page_size")
    # Speculative decoding (round 17, tpukit/serve/spec.py) — the output
    # distribution is EXACT either way: greedy token-identical to vanilla
    # decode, sampled corrected by rejection sampling.
    parser.add_argument("--draft", choices=("", "ngram", "model"),
                        default="",
                        help="speculative decoding proposer: 'ngram' = "
                        "self-speculation (on-device prompt-lookup, no "
                        "second model), 'model' = a small tpukit GPT "
                        "draft (--draft_checkpoint + --draft_* shape "
                        "flags); '' = vanilla decode. Requires the ring "
                        "cache (page_size 0)")
    parser.add_argument("--spec_k", type=int, default=4,
                        help="draft tokens proposed per slot per quantum "
                        "(the verify window is spec_k + 1 wide)")
    parser.add_argument("--ngram_max", type=int, default=3,
                        help="longest n-gram the self-speculation "
                        "proposer matches (falls back through shorter "
                        "suffixes down to 1)")
    # Request-scoped tracing (round 20, tpukit/obs/trace.py): ON by
    # default — the ring is bounded, an emit is one dict and a deque
    # append, and token streams are bit-identical either way
    # (tests/test_trace.py).
    parser.add_argument("--no_trace", action="store_true",
                        help="disable request-scoped span tracing "
                        "(kind=\"trace_event\"/\"trace\" JSONL rows, "
                        "per-phase latency percentiles, traceview export)")
    parser.add_argument("--trace_capacity", type=int, default=8192,
                        help="span events retained per replica ring "
                        "(oldest evicted; evictions break the trace-"
                        "completeness invariant on long runs — grow this "
                        "before gating with --min_trace_complete)")
    # Metrics plane (round 22, tpukit/obs/metrics.py): ON by default —
    # counters/gauges/log-bucket histograms DERIVED from completions,
    # trace trees and quantum walls at window boundaries (the decode hot
    # path is untouched), token streams bit-identical either way
    # (tests/test_metrics.py).
    parser.add_argument("--no_metrics", action="store_true",
                        help="disable the metrics plane (mergeable "
                        "latency histograms, kind=\"metrics\"/\"slo\" "
                        "JSONL rows, snapshot files, tools/top.py feed)")
    parser.add_argument("--slo", type=str, default="",
                        help="declared service objectives, e.g. "
                        "\"ttft<=250ms@p99;tpot<=40ms@p95;e2e<=2s@p99\" "
                        "— parsed at startup (typos fail fast); each "
                        "window emits per-target compliance + error-"
                        "budget burn as kind=\"slo\" rows, gated by "
                        "report.py --min_slo_compliance")
    parser.add_argument("--metrics_dir", type=str, default="",
                        help="shared directory for atomic per-process/"
                        "per-replica metric snapshot files "
                        "(metrics-pNNNNN.json, heartbeat-file "
                        "discipline); process 0 publishes the bucket-"
                        "summed merge + OpenMetrics textfile beside them")
    return parser


def add_fleet_flags(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """Fleet-router flags (round 19, tpukit/serve/fleet.py) — one spelling
    shared by main-serve.py and any harness that builds a `FleetConfig`
    from a CLI. `--replicas 0` (the default) keeps the single-engine
    round-14/15 path byte-untouched; >= 1 routes the stream through a
    FleetRouter over that many `ServeEngine` replicas, each on its own
    device subset (`--devices_per_replica`). Validation lives on
    FleetConfig, so misconfigurations fail with named errors at startup."""
    parser.add_argument("--replicas", type=int, default=0,
                        help="fleet mode: route the stream over this many "
                        "engine replicas (0 = the single-engine path)")
    parser.add_argument("--devices_per_replica", type=int, default=0,
                        help="devices per replica subset (each replica "
                        "grids its subset via pick_serve_grid); 0 = "
                        "meshless replicas on the default device")
    parser.add_argument("--min_replicas", type=int, default=1,
                        help="autoscale floor (scale-down never goes below)")
    parser.add_argument("--max_replicas", type=int, default=0,
                        help="autoscale ceiling; 0 = --replicas (no "
                        "scale-up headroom)")
    parser.add_argument("--scale_up_occupancy", type=float, default=0.0,
                        help="mean fleet slot occupancy above which a "
                        "window triggers a scale-up (0 disables)")
    parser.add_argument("--scale_down_occupancy", type=float, default=0.0,
                        help="mean fleet slot occupancy below which an "
                        "idle-queue window drains one replica (0 disables)")
    parser.add_argument("--fleet_window_steps", type=int, default=16,
                        help="fleet window cadence in dispatch rounds "
                        "(drives kind=\"fleet\" records AND the autoscale "
                        "check)")
    parser.add_argument("--disagg_prefill", action="store_true",
                        help="disaggregated prefill: a dedicated worker "
                        "runs chunked prefill and hands finished prefixes "
                        "to decode replicas as pages (requires --page_size)")
    parser.add_argument("--prefill_slots", type=int, default=0,
                        help="prefill worker lanes (0 = --slots)")
    parser.add_argument("--prefill_pages", type=int, default=0,
                        help="prefill worker pool pages (0 = the "
                        "--num_pages default)")
    parser.add_argument("--fleet_kill", type=str, default="",
                        help="deterministic serving chaos (one grammar "
                        "with --chaos_spec): replica_kill@R[:idx], "
                        "replica_sigkill@R[:idx] (real SIGKILL under "
                        "--fleet_procs), slow_replica@R:ms (heartbeat "
                        "stall), stuck_request@N (lane never finishes — "
                        "pair with --deadline_ms), ledger_io_fail@k:c "
                        "(IOError on ledger I/O occurrence k, c times)")
    parser.add_argument("--fleet_dir", type=str, default="",
                        help="durable fleet state directory: the request "
                        "ledger (write-ahead leases, exactly-once "
                        "completion records, stream replay on restart) "
                        "plus replica heartbeat files live here")
    parser.add_argument("--replica_timeout", type=float, default=0.0,
                        help="heartbeat liveness: declare a replica dead "
                        "when its beat file is older than this many "
                        "seconds — leases revoke, in-flight requests "
                        "requeue on survivors (0 disables; requires "
                        "--fleet_dir)")
    parser.add_argument("--request_retries", type=int, default=3,
                        help="per-request re-assignment budget after "
                        "replica deaths; exhaustion is a terminal NAMED "
                        "failure (reason=retry_budget), never a silent "
                        "kill/requeue loop")
    parser.add_argument("--max_queue_depth", type=int, default=0,
                        help="queue-depth backpressure: shed arrived "
                        "requests beyond this depth, lowest priority "
                        "first, as named request_rejected events "
                        "(0 = unbounded queue)")
    parser.add_argument("--deadline_ms", type=float, default=0.0,
                        help="per-request completion deadline applied to "
                        "the synthetic stream: a lane still decoding past "
                        "arrival+deadline is EVICTED with its partial "
                        "tokens (reason=\"deadline\", kind=deadline_miss "
                        "record; 0 = no deadlines)")
    parser.add_argument("--fleet_procs", action="store_true",
                        help="process fleet: run each replica as a real "
                        "worker PROCESS driven through the ledger "
                        "(requires --fleet_dir); replica_sigkill chaos "
                        "delivers a real SIGKILL and liveness comes from "
                        "process exit + heartbeat age")
    parser.add_argument("--fleet_worker", type=int, default=-1,
                        help="INTERNAL: run as ledger worker replica N "
                        "(set by the --fleet_procs supervisor when "
                        "re-execing itself; not for direct use)")
    return parser
