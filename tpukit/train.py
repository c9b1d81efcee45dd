"""Shared trainer: train state, jitted steps, and the epoch loop.

The reference duplicates its whole train/eval/generate/checkpoint loop in
every recipe (SURVEY §2.7; e.g. main-single.py:80-151 vs main-ddp.py:102-185
are near-identical). Here the loop lives once and the *strategy* is the only
thing a recipe supplies — the same pedagogical diff the cookbook wanted,
without the duplication.

Loop surface twins the reference exactly:
  - running train loss printed through tqdm every PRINT_FREQ=8 steps
    (main-single.py:19,104-108), process-0-gated in distributed recipes
    (tqdm(..., disable=rank != 0), main-ddp.py:106,137);
  - per-epoch validation loss + masked accuracy in the bar
    (main-single.py:110-138);
  - three fixed greedy generations per epoch: "The big brown cat ",
    "One day, ", "She said " (main-single.py:140-144), process-0 only;
  - end-of-training checkpoint (main-single.py:146-151).

TPU-native differences (deliberate, documented):
  - One jitted `train_step` holds forward+loss+backward+AdamW update; the
    state is donated, so parameters update in place in HBM.
  - The running-loss accumulator stays on device; the host syncs once per
    PRINT_FREQ window instead of the reference's per-step `loss.item()`
    (main-single.py:103, a D2H sync every step).
  - bf16 is the compute dtype (no GradScaler twin: bf16 needs no loss
    scaling; the reference's scaler is inert for bf16 anyway,
    main-single.py:78). `--disable_amp` flips compute to fp32. Eval runs
    in bf16 *unconditionally*, twinning the reference quirk of an
    always-enabled eval autocast (main-single.py:119).
  - `--disable_compile` maps to `jax.disable_jit()` (debug mode), the
    analogue of skipping torch.compile (main-single.py:38-39).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from tqdm import tqdm

from tpukit import chaos as chaos_lib
from tpukit import checkpoint as ckpt_lib
from tpukit import reshard as reshard_lib
from tpukit import retry as retry_lib
from tpukit.batching import IGNORE_INDEX, prepare_batch
from tpukit.cache import enable_compilation_cache
from tpukit.data import get_dataset, get_tokenizer, transform_dataset
from tpukit.flags import TrainFlags
from tpukit.loader import DataLoader
from tpukit.prefetch import HostPrefetcher
from tpukit.mesh import initialize_runtime, is_process_zero
from tpukit.recovery import (
    AnomalyAbort,
    Preempted,
    PreemptCoordinator,
    PreemptionGuard,
    RecoveryEngine,
    RollbackBudgetExhausted,
    RollbackCoordinator,
)
from tpukit.model import gpt
from tpukit.obs import (
    AnomalyTracer,
    FlightRecorder,
    HangWatchdog,
    Heartbeat,
    MetricRegistry,
    MFUMeter,
    SpanTimeline,
    SpikeSentinel,
    StepLogger,
    capture_compiler_stderr,
    compiled_stats,
    format_breakdown,
    format_checksum,
    global_norms,
    live_memory_stats,
    make_state_checksum,
    merge_snapshot_dir,
    profiler_trace,
    publish_snapshot,
    write_merged,
)
from tpukit.sampling import generate_batch
from tpukit.shardings import Strategy

PRINT_FREQ = 8  # twin of main-single.py:19
GENERATION_PROMPTS = ["The big brown cat ", "One day, ", "She said "]  # main-single.py:142-144


class TrainState(struct.PyTreeNode):
    params: Any
    opt_state: Any
    step: jax.Array


def create_train_state(rng, cfg: gpt.GPTConfig, optimizer, strategy=None) -> TrainState:
    if not isinstance(cfg, gpt.GPTConfig):
        from tpukit.model import ServedOnlyError

        raise ServedOnlyError(
            f"{type(cfg).__name__}: this block family is served only "
            f"(main-serve.py --model latent): it has no loss, no backward "
            f"pass and no training kernels (ROADMAP R0-R3)"
        )
    params = gpt.init_params(rng, cfg)
    if strategy is not None:
        # layout hook (e.g. Pipeline pads stacked layers to a stage multiple
        # with identity layers when num_layers doesn't divide the stages)
        params = strategy.prepare_params(params, cfg)
    return TrainState(params=params, opt_state=optimizer.init(params), step=jnp.int32(0))


def make_optimizer(learning_rate: float) -> optax.GradientTransformation:
    """Twin of `torch.optim.AdamW(params, lr=...)` (main-single.py:42): torch
    AdamW defaults are betas=(0.9, 0.999), eps=1e-8, weight_decay=1e-2."""
    return optax.adamw(learning_rate, b1=0.9, b2=0.999, eps=1e-8, weight_decay=1e-2)


def make_step_fns(
    cfg: gpt.GPTConfig, optimizer, strategy: Strategy, state_shapes,
    seed: int = 0, log_grad_norms: bool = False,
):
    """Build jitted train/eval steps with the strategy's shardings applied.

    GSPMD reads the in/out shardings and inserts the collectives: grad psum
    for DP, per-tensor all-gather/reduce-scatter for FSDP, nothing for
    single-device. The pipeline strategy's schedule is inside its loss_fn.

    Dropout (VERDICT r2 #6): when cfg.dropout > 0 the train step folds the
    training step counter into a seed-derived key and threads it to the
    strategy's loss — active in training, never in eval (the reference's
    train()/eval() mode split, models/gpt.py:31,65). With dropout off no rng
    is traced at all, so the compiled step is unchanged.

    `log_grad_norms` (round-6 telemetry, --log_grad_norms): the train step
    ADDITIONALLY returns `{grad,update,param}_norm` f32 scalars, computed
    inside the same jitted program (the grads/updates are already live — no
    second compilation, no extra pass). Off (default): the traced graph is
    exactly the flag-free one, so the compiled HLO is byte-identical.
    """
    eval_cfg = cfg.replace(compute_dtype=jnp.bfloat16)  # eval autocast always on
    # The per-step key feeds dropout AND (round 12) the stochastic-rounding
    # noise of the DataParallel quantized grad psum — the one SR site a key
    # can be threaded into (FSDP's and EP's SR noise lives inside custom-vjp
    # backwards, which derive step-varying keys from the cotangent data
    # instead: quant_comm._fallback_key). With dropout 0 the rate-0 dropout
    # is an identity, so the SR-only case changes nothing but the rounding.
    needs_rng = cfg.dropout > 0 or (
        cfg.quant_stochastic and cfg.comm_dtype == "int8"
    )
    dropout_key = jax.random.PRNGKey(seed ^ 0x5EED) if needs_rng else None

    def train_step(state: TrainState, batch, targets):
        state = strategy.to_compute(state)
        rng = (
            jax.random.fold_in(dropout_key, state.step)
            if dropout_key is not None
            else None
        )

        # autodiff over loss_fn by default; Pipeline1F1B overrides with its
        # explicit per-stage-vjp schedule (see Strategy.value_and_grad)
        with jax.named_scope("loss"):
            loss, grads = strategy.value_and_grad(
                state.params, cfg, batch, targets, rng=rng
            )
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            params=params, opt_state=opt_state, step=state.step + 1
        )
        if log_grad_norms:
            return new_state, loss, global_norms(grads, updates, params)
        return new_state, loss

    def eval_step(state: TrainState, batch, targets):
        state = strategy.to_compute(state)
        with jax.named_scope("loss"):
            loss, accuracy = strategy.loss_fn(
                state.params, eval_cfg, batch, targets, with_accuracy=True
            )
        return loss, accuracy

    state_sh = strategy.state_sharding(state_shapes)
    state_sharding = TrainState(
        params=state_sh.params, opt_state=state_sh.opt_state, step=strategy.replicated()
    )
    batch_sh = strategy.batch_sharding()
    repl = strategy.replicated()

    train_out_sh = (state_sharding, repl)
    if log_grad_norms:
        norm_sh = {k: repl for k in ("grad_norm", "update_norm", "param_norm")}
        train_out_sh = (state_sharding, repl, norm_sh)
    train_step = jax.jit(
        train_step,
        in_shardings=(state_sharding, batch_sh, batch_sh),
        out_shardings=train_out_sh,
        donate_argnums=(0,),
    )
    eval_step = jax.jit(
        eval_step,
        in_shardings=(state_sharding, batch_sh, batch_sh),
        out_shardings=(repl, repl),
    )
    return train_step, eval_step, state_sharding


def make_global_batch(batch_sharding, model_batch, targets, place: bool = False):
    """Assemble per-process host arrays into global device arrays.

    Single-process: identity (jit places numpy at the sharding). Multi-host
    (the v4-32 ladder configs: one process per host, SURVEY §2.5): each
    process holds only its DistributedSampler shard of the batch —
    `jax.make_array_from_process_local_data` builds the global sharded
    array a cross-host jit can consume. This replaces the reference's
    per-rank DataLoader+DistributedSampler feeding (main-ddp.py:83-100);
    feeding the full global batch from every process would be rejected by
    a jit whose shardings span non-addressable devices.

    `place=True` (the prefetch path) makes the single-process case an
    explicit `jax.device_put` at the batch sharding instead of leaving the
    H2D copy to the jit boundary — so the transfer itself happens on the
    prefetch thread, ahead of the step that consumes it. Values are
    bit-identical either way (the batch is integer/bool data placed at the
    same sharding the jit would have used).
    """
    if jax.process_count() == 1:
        if not place:
            return model_batch, targets

        def conv(x):
            return jax.device_put(x, batch_sharding)

        return jax.tree.map(conv, model_batch), conv(targets)

    spec = batch_sharding.spec
    if len(spec) > 0 and spec[0] is not None:
        # batch rows are sharded across processes: each process supplied
        # only its DistributedSampler shard
        def conv(x):
            return jax.make_array_from_process_local_data(batch_sharding, x)
    else:
        # rows are process-replicated (pure pipeline / CP seq sharding):
        # every process loaded the identical full global batch; carve each
        # host's addressable shards out of it
        def conv(x):
            return jax.make_array_from_callback(
                x.shape, batch_sharding, lambda idx, x=x: x[idx]
            )

    return jax.tree.map(conv, model_batch), conv(targets)


@jax.jit
def _valid_count(targets):
    """Global valid-token count of a (possibly cross-host sharded) targets
    array. jit makes the sum a collective under GSPMD, so every process sees
    the same number — a host-side count would only cover the local shard."""
    return jnp.sum(targets != IGNORE_INDEX)


@functools.lru_cache(maxsize=None)
def _replicator(mesh):
    """One jitted all-gather-to-replicated program per mesh — rebuilding the
    lambda per call would retrace (and recompile) every epoch."""
    from jax.sharding import NamedSharding

    repl = NamedSharding(mesh, jax.sharding.PartitionSpec())
    return jax.jit(lambda p: p, out_shardings=repl)


def replicated_params(strategy: Strategy, state: TrainState):
    """Parameters addressable on every host for the decode loop — running it
    on process 0 with params still sharded across hosts is the reference's
    latent multi-host hang (rank-0-only FSDP generate, main-ddp.py:170-174,
    SURVEY §3.5). This is a collective — EVERY process must call it.

    Small models get a fully-replicated copy (one compiled all-gather, then
    the 20-step decode runs gather-free). Past TPUKIT_REPLICATE_PARAMS_MB
    (default 1 GiB — ADVICE r3: FSDP configs that shard out of memory
    necessity would OOM on a transient full copy) the params keep their
    sharded layout — routed through `strategy.to_compute` so offloaded
    (pinned_host) state still moves into device memory — and the decode jit
    lets GSPMD gather per-op: one layer's parameters live at a time instead
    of all of them.
    """
    limit = int(os.environ.get("TPUKIT_REPLICATE_PARAMS_MB", "1024")) * 2**20
    total = sum(
        l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(state.params)
    )
    if total > limit:
        # Move ONLY the params subtree into device memory: to_compute maps
        # leaf-wise, and running it on the whole TrainState would transiently
        # pull both Adam moments (~3x params) into HBM for a decode that
        # never reads them (ADVICE r4).
        return strategy.to_compute(state.params)
    return _replicator(strategy.mesh)(state.params)


def generate_samples(
    strategy: Strategy,
    state: TrainState,
    cfg: gpt.GPTConfig,
    tokenizer,
    prompts=GENERATION_PROMPTS,
    max_new_tokens: int = 20,
    temperature: float = 0.0,
    top_k: int = 0,
    seed: int = 0,
) -> list[str]:
    """SPMD-safe qualitative eval: replicate params, then decode each
    prompt (greedy by default; `temperature`/`top_k`/`seed` sample — round
    14, through the serving engine's batched KV-cached decode). Every
    process must call this (the replication is collective); each returns
    the same texts, and the caller prints on process 0 only — the
    reference's rank-0 gating (main-ddp.py:170-174) moved from "only
    rank 0 computes" (a deadlock for sharded state) to "all compute, rank 0
    prints"."""
    params = replicated_params(strategy, state)
    # Strategies that train on a re-laid-out param tree (the interleaved
    # pipeline stores the layer stack chunk-permuted) restore the natural
    # layer order for the plain sequential decode; identity for the rest.
    params = strategy.inference_params(params, cfg)
    # ONE batched jitted call (VERDICT r4 #7): one compile and one decode
    # per epoch instead of a serial compile+decode per prompt — `generate`
    # stays as the single-prompt API.
    return generate_batch(
        params, cfg, list(prompts), tokenizer, max_new_tokens=max_new_tokens,
        temperature=temperature, top_k=top_k, seed=seed,
    )


def _place_like(host_tree, sharding_tree):
    """Place a host-array pytree at the given shardings (multi-host safe —
    see mesh.place_host_array)."""
    from tpukit.mesh import place_host_array

    return jax.tree.map(place_host_array, host_tree, sharding_tree)


@contextlib.contextmanager
def _debug_nans_scope():
    prev = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", True)
    try:
        yield
    finally:
        jax.config.update("jax_debug_nans", prev)


@dataclasses.dataclass
class FitResult:
    state: TrainState
    tokenizer: Any
    config: gpt.GPTConfig
    checkpoint_path: Any
    metrics: dict


def fit(
    flags: TrainFlags,
    strategy: Strategy,
    num_epochs: int | None = None,
    make_loaders: Callable | None = None,
) -> FitResult:
    """The shared training entry point every recipe calls.

    Round 9: `fit` validates the recovery flags, installs the run-scoped
    environment — SIGTERM/SIGINT preemption handlers, the chaos
    fault-injection engine (`--chaos_spec`), the transient-I/O retry
    policy + observer (`--io_retries`) — and guarantees their teardown on
    EVERY exit path (clean, abort, preemption, crash), so none of it
    leaks across fits in one process. The training loop itself lives in
    `_fit_body`.
    """
    initialize_runtime()
    if flags.prefetch < 0:
        raise ValueError(f"--prefetch must be >= 0, got {flags.prefetch}")
    if flags.hang_timeout < 0:
        raise ValueError(f"--hang_timeout must be >= 0, got {flags.hang_timeout}")
    if flags.divergence_check_freq < 0:
        raise ValueError(
            f"--divergence_check_freq must be >= 0, got "
            f"{flags.divergence_check_freq}"
        )
    if flags.on_anomaly not in ("none", "rollback"):
        raise ValueError(
            f"--on_anomaly must be none|rollback, got {flags.on_anomaly!r}"
        )
    if flags.max_rollbacks < 0:
        raise ValueError(f"--max_rollbacks must be >= 0, got {flags.max_rollbacks}")
    if flags.io_retries < 0:
        raise ValueError(f"--io_retries must be >= 0, got {flags.io_retries}")
    if flags.keep_checkpoints < 0:
        raise ValueError(
            f"--keep_checkpoints must be >= 0 (0 keeps everything), got "
            f"{flags.keep_checkpoints}"
        )
    if flags.on_anomaly == "rollback" and jax.process_count() > 1 and not flags.heartbeat_dir:
        # the rollback decision is made collective through the heartbeat
        # directory; without it a multi-process world could roll back to
        # two different steps and deadlock in mismatched collectives
        raise ValueError(
            "--on_anomaly rollback needs --heartbeat_dir on multi-process "
            "runs: the rollback decision is published through the shared "
            "heartbeat directory"
        )
    # Chaos harness (round 9): parse NOW so a typo'd fault plan fails at
    # startup, not silently never fires. Installed module-wide for the
    # run's duration (checkpoint/loader I/O sites reach it through
    # tpukit.chaos.maybe_io_fault); uninstalled on any exit.
    chaos_engine = (
        chaos_lib.ChaosEngine(
            flags.chaos_spec, seed=flags.seed,
            process_index=jax.process_index(),
            process_count=jax.process_count(),
        )
        if flags.chaos_spec
        else None
    )
    # Transient host-I/O retry policy + observer: every retried attempt
    # lands in the JSONL (kind="retry") and the flight-recorder ring.
    retry_log = retry_lib.RetryLog()
    prev_policy = retry_lib.set_default_policy(
        retry_lib.RetryPolicy(retries=flags.io_retries)
    )
    retry_lib.set_observer(retry_log)
    prev_chaos = chaos_lib.install(chaos_engine)
    guard = PreemptionGuard()
    try:
        with guard:
            return _fit_body(
                flags, strategy, num_epochs, make_loaders,
                chaos_engine, retry_log, guard,
            )
    finally:
        chaos_lib.install(prev_chaos)
        retry_lib.set_observer(None)
        retry_lib.set_default_policy(prev_policy)


def _fit_body(
    flags: TrainFlags,
    strategy: Strategy,
    num_epochs: int | None,
    make_loaders: Callable | None,
    chaos_engine,
    retry_log,
    preempt_guard: PreemptionGuard,
) -> FitResult:
    p0 = is_process_zero()
    # Persistent XLA compilation cache, placed by tpukit/cache.py's one rule
    # (--compilation_cache_dir > $JAX_COMPILATION_CACHE_DIR >
    # <checkout>/.jax_cache): repeat runs of the same program skip
    # recompiles; hits/misses are logged at the end of the run.
    cache_stats = enable_compilation_cache(flags.compilation_cache_dir)

    tokenizer = get_tokenizer()
    tokenizer.pad_token_id = 2  # every recipe pins pad to 2 (main-single.py:23)

    compute_dtype = jnp.float32 if flags.disable_amp else jnp.bfloat16
    cfg = gpt.GPTConfig(
        dim=flags.dim,
        head_dim=flags.head_dim,
        heads=flags.heads,
        num_layers=flags.num_layers,
        vocab_size=tokenizer.vocab_size,
        max_position_embeddings=flags.sequence_length,
        dropout=flags.dropout,
        compute_dtype=compute_dtype,
        remat_layers=flags.remat,
        scan_layers=flags.scan_layers,
        num_experts=flags.num_experts,
        router_top_k=flags.moe_top_k,
        virtual_stages=flags.virtual_stages,
        comm_dtype=flags.comm_dtype,
        quant_stochastic=flags.quant_stochastic,
        grad_buckets=flags.grad_buckets,
    )
    optimizer = make_optimizer(flags.learning_rate)
    strategy.validate_config(cfg)  # fail fast with a clear shape/mesh error

    # ---- data -----------------------------------------------------------
    if make_loaders is not None:
        train_loader, validation_loader = make_loaders(flags, tokenizer, strategy)
        # meter math: a rank-sharded custom loader reports per-host rows
        loader_procs = getattr(train_loader, "num_replicas", 1)
        global_batch = None  # a custom loader owns its batch geometry
    else:
        train_ds, validation_ds = get_dataset(slice_size=flags.dataset_slice)
        train_ds = transform_dataset(
            train_ds, tokenizer, max_length=flags.sequence_length, num_proc=flags.num_workers
        )
        validation_ds = transform_dataset(
            validation_ds, tokenizer, max_length=flags.sequence_length, num_proc=flags.num_workers
        )
        # Global batch = per-replica batch x data-parallel degree, the twin
        # of "per-rank DataLoader(batch_size)" under torchrun (main-ddp.py:
        # 83-100). Wrap-padding keeps every step full-shape — the twin of
        # DistributedSampler's pad-by-wrapping, applied unconditionally so
        # the jitted step compiles exactly once (a ragged final batch would
        # recompile and, under Pipeline, violate the micro-batch divisor).
        replicas = strategy.mesh.shape.get("data", 1)
        global_batch = flags.batch_size * replicas
        if global_batch % strategy.batch_divisor:
            raise ValueError(
                f"global batch {global_batch} (batch_size {flags.batch_size} x "
                f"{replicas} data shards) must be a multiple of "
                f"{strategy.batch_divisor} for the {strategy.name} strategy"
            )
        # Multi-host: when the strategy shards batch rows, each process
        # loads only its DistributedSampler shard of every global batch
        # (twin of per-rank DataLoader under torchrun, main-ddp.py:83-100);
        # make_global_batch assembles the global array. Strategies that
        # replicate rows across processes (pure pipeline / CP) need the
        # identical full batch on every host instead.
        spec = strategy.batch_spec()
        rows_sharded = len(spec) > 0 and spec[0] is not None
        procs = jax.process_count() if rows_sharded else 1
        rank = jax.process_index() if rows_sharded else 0
        if global_batch % procs:
            raise ValueError(
                f"global batch {global_batch} must divide across {procs} hosts"
            )
        per_host = global_batch // procs
        loader_procs = procs
        train_loader = DataLoader(
            train_ds, per_host, shuffle=True, seed=flags.seed, drop_last=False,
            pad_to_batch=True, num_replicas=procs, rank=rank,
        )
        # Validation pads with all-ignore rows (not wrap-duplicates), so the
        # final batch's metrics equal the exact partial-batch metrics the
        # reference's single-device eval computes (main-single.py:110-138).
        validation_loader = DataLoader(
            validation_ds, per_host, shuffle=False, pad_to_batch=True,
            pad_mode="empty", pad_fill=tokenizer.pad_token_id,
            num_replicas=procs, rank=rank,
        )

    # ---- state ----------------------------------------------------------
    init_fn = partial(create_train_state, cfg=cfg, optimizer=optimizer, strategy=strategy)
    state_shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(flags.seed))
    train_step, eval_step, state_sharding = make_step_fns(
        cfg, optimizer, strategy, state_shapes, seed=flags.seed,
        log_grad_norms=flags.log_grad_norms,
    )

    # Initialize directly into the sharded layout (no host-side giant pytree).
    state = jax.jit(init_fn, out_shardings=state_sharding)(jax.random.PRNGKey(flags.seed))

    # The world THIS run saves from / resumes into (round 13): every save's
    # meta sidecar records it, and `--resume` compares it against the
    # checkpoint's to decide plain-restore vs reshard.
    run_world = reshard_lib.current_world(strategy, global_batch=global_batch)

    # Mid-epoch continuation (round 9): a PREEMPTION save carries resume
    # metadata (epoch + batches consumed); resuming from one continues the
    # interrupted epoch at the exact batch it stopped at — the uninterrupted
    # run's state, bit-exact. Other checkpoints (periodic/final) keep the
    # established semantics: train `--epochs` more epochs from batch 0.
    # Round 13 makes the restore ELASTIC: a checkpoint whose recorded world
    # differs from this run's is resharded onto the current state_sharding
    # specs (tpukit/reshard.py) instead of failing or silently misloading.
    start_epoch, start_skip = 0, 0
    resize_event = None
    if flags.resume:
        from pathlib import Path

        resume_path = (
            ckpt_lib.latest_any() if flags.resume == "latest" else Path(flags.resume)
        )
        if resume_path is None or not resume_path.exists():
            raise FileNotFoundError(
                f"--resume {flags.resume}: no checkpoint found"
            )
        if flags.resume != "latest":
            # `latest_any` already verified its pick (hashing the whole
            # blob / every shard); only an explicit path needs the check.
            ok, detail = ckpt_lib.verify_checkpoint(resume_path)
            if not ok:
                raise ValueError(
                    f"--resume {flags.resume}: checkpoint {resume_path} "
                    f"failed integrity verification ({detail})"
                )
        meta = ckpt_lib.read_meta(resume_path)
        saved_w = reshard_lib.saved_world(resume_path)
        mismatch = reshard_lib.describe_mismatch(saved_w, run_world)
        if mismatch and meta and meta.get("resize_to") is not None:
            # resize@N:M chaos contract: the preempt-save named the world
            # it expects to come back at — a relaunch at a DIFFERENT world
            # that is not M means the resize path under test was not
            # exercised; fail loud instead of quietly passing another
            # scenario. A same-world resume (mismatch is None) stays
            # legal: that is how a control run reproduces the trajectory.
            want = int(meta["resize_to"])
            if want != run_world["device_count"]:
                raise RuntimeError(
                    f"--resume {flags.resume}: checkpoint {resume_path} was "
                    f"preempt-saved by a resize@N:{want} chaos fault "
                    f"expecting relaunch at {want} devices, but this world "
                    f"has {run_world['device_count']}"
                )
        if mismatch:
            # Stale-incarnation sweep BEFORE any new-world reader exists:
            # beat files, rollback decisions and preempt requests from the
            # old world carry step numbers, checksums and process indices
            # the resized world must never compare against (a vanished
            # rank's beat file is never overwritten — without the sweep it
            # poisons the straggler/divergence checks forever).
            swept = (
                reshard_lib.sweep_stale_world(flags.heartbeat_dir)
                if flags.heartbeat_dir and p0
                else []
            )
            state, rs_info = reshard_lib.reshard_restore(
                resume_path, state_shapes, state_sharding
            )
            resize_event = dict(
                kind="resize",
                step=int(jax.device_get(state.step)),
                checkpoint=str(resume_path),
                mismatch=mismatch,
                saved_world=saved_w,
                world=run_world,
                swept=swept,
                **rs_info,
            )
        else:
            # Both formats restore against the abstract state_shapes (never
            # a device_get of the live state — that is exactly the gather
            # that fails for cross-host-sharded state). Sharded checkpoints
            # place their shards straight into the strategy's shardings;
            # consolidated ones come back as host arrays and are placed
            # below.
            restored, was_sharded = ckpt_lib.restore_any(
                resume_path, state_shapes, state_sharding
            )
            state = (
                restored if was_sharded else _place_like(restored, state_sharding)
            )
        if meta and meta.get("preempted"):
            start_epoch = int(meta.get("epoch", 0))
            start_skip = int(meta.get("batch_in_epoch", 0))
            saved_gb = (saved_w or {}).get("global_batch")
            if start_skip and saved_gb and global_batch and saved_gb != global_batch:
                import warnings

                warnings.warn(
                    f"mid-epoch resume across a global-batch change "
                    f"({saved_gb} -> {global_batch} rows): batch_in_epoch "
                    f"counts the OLD world's batches, so the stream position "
                    f"is approximate — hold batch_size x data-shards "
                    f"constant across a resize for exact continuation",
                    stacklevel=2,
                )
        if p0:
            print(
                f"resumed from {resume_path} at step {int(jax.device_get(state.step))}"
                + (
                    f" (resharded: {mismatch})"
                    if resize_event is not None
                    else ""
                )
                + (
                    f" (preempted mid-epoch: continuing epoch {start_epoch} "
                    f"at batch {start_skip})"
                    if start_skip or meta and meta.get("preempted")
                    else ""
                )
            )
    if chaos_engine is not None and chaos_engine.skip_batches:
        # chaos `skip@N`: fast-forward the first trained epoch's stream by
        # N batches WITHOUT moving the step counter — exactly the stream
        # position a post-rollback run sits at, which is what lets a
        # control run reproduce a recovered run's trajectory bit-exactly.
        start_skip += chaos_engine.skip_batches

    batch_sh = strategy.batch_sharding()
    # Host-side batch transform (ContextParallel's zigzag permute — ADVICE
    # r4: in-jit it is a per-step cross-shard reshard collective).
    host_batch = strategy.host_batch_fn(cfg)

    def host_pipeline(raw):
        """The whole host side of one training batch — prepare, strategy
        transform, global-array assembly WITH explicit device placement.
        This is what the prefetch thread runs `--prefetch` batches ahead;
        it is the same work the synchronous path's data+h2d spans time."""
        b, t = prepare_batch(raw, tokenizer.pad_token_id)
        if host_batch is not None:
            b, t = host_batch(b, t)
        b, t = make_global_batch(batch_sh, b, t, place=True)
        return raw, b, t

    # Checkpoint writer: the async writer snapshots on this thread and
    # publishes from a background one (join barrier at the next save), so
    # periodic saves stop stalling the step loop on encode+disk I/O.
    async_saver = ckpt_lib.AsyncCheckpointer() if flags.async_checkpoint else None

    def save_checkpoint(st, meta=None):
        # Every save records the SAVING world (round 13): the meta sidecar's
        # `world` entry is what lets a relaunch detect a topology change and
        # reshard instead of failing — periodic and final saves carry it
        # too, not just preemption saves, because any checkpoint can be the
        # one an elastic relaunch resumes from.
        meta = {**(meta or {}), "world": run_world}
        if async_saver is not None:
            return async_saver.save_auto(
                st, format=flags.checkpoint_format, meta=meta
            )
        return ckpt_lib.save_auto(st, format=flags.checkpoint_format, meta=meta)

    def prune_checkpoints() -> None:
        """Retention (--keep_checkpoints K, round 13): after a successful
        publish, drop published checkpoints older than the newest K.
        Quarantined timelines and the newest integrity-verified
        (`latest_good`) candidate are never pruned (checkpoint.py). An
        in-flight async save is invisible to the scan until its atomic
        publish — the next prune catches up."""
        if flags.keep_checkpoints <= 0 or not p0:
            return
        # assume_newest_verified: this call always follows OUR OWN publish,
        # whose writer just computed the checksums — re-hashing it here
        # every save interval would double per-save disk I/O.
        removed = ckpt_lib.prune_checkpoints(
            "checkpoints", keep=flags.keep_checkpoints,
            assume_newest_verified=True,
        )
        if removed:
            logger.log(
                kind="ckpt_prune", step=host_step,
                keep=flags.keep_checkpoints, pruned=removed,
            )
            recorder.record("ckpt_prune", step=host_step, pruned=len(removed))

    seq = flags.sequence_length - 1  # model sees S-1 after the shift
    meter = MFUMeter(cfg, seq)
    logger = StepLogger(flags.metrics_log if p0 else "")
    # ---- telemetry (tpukit/obs, round 6) --------------------------------
    # every span below is also a `tpukit:<name>` event on the host thread's
    # line of a --profile_dir trace, and the `step` span a profiler step
    spans = SpanTimeline(
        annotation=jax.profiler.TraceAnnotation,
        step_annotation=functools.partial(jax.profiler.StepTraceAnnotation, "train"),
    )
    # Flight recorder (round 8): always on — a bounded ring of recent
    # step/window/sentinel records, read only when a diagnostics bundle is
    # dumped. The cost is one dict + deque append per step (<1% of any
    # real step; bench.py's obs_overhead record audits it).
    recorder = FlightRecorder()
    # Metrics plane (round 22): mergeable counters/gauges/log-bucket
    # histograms derived from telemetry the loop ALREADY computes (the
    # window spans, the MFU meter, the recovery observers) — never a new
    # sync or wall read on the hot path. Pure observer: --no_metrics must
    # not change a single token (bench.py's metrics_overhead record
    # asserts bit-identity and <1% throughput cost).
    metric_reg = None if flags.no_metrics else MetricRegistry()

    def publish_metrics(final: bool = False) -> None:
        """Atomic per-process snapshot into --metrics_dir (heartbeat-file
        discipline: every process writes its own file, process 0 merges).
        Window cadence, so tools/top.py can tail a live run."""
        if metric_reg is None or not flags.metrics_dir:
            return
        nproc = jax.process_count()
        publish_snapshot(
            flags.metrics_dir, jax.process_index(), metric_reg,
            process_count=nproc, time_s=time.time(),
        )
        if p0:
            merged, meta = merge_snapshot_dir(flags.metrics_dir, nproc)
            write_merged(flags.metrics_dir, merged, meta=meta)

    if resize_event is not None:
        # the elastic restore happened before the logger existed; surface
        # it now so the JSONL (and tools/report.py) names the topology
        # change, the reshard cost, and the stale files swept
        logger.log(**resize_event)
        recorder.record(
            "resize", step=resize_event["step"],
            mismatch=resize_event["mismatch"],
        )
        if p0:
            print(
                f"elastic resize: {resize_event['mismatch']} "
                f"({resize_event['format']} reshard, "
                f"{resize_event['bytes_read']} bytes read in "
                f"{resize_event['wall_s']:.3f}s)"
            )
    # Sentinel runs on EVERY process with identical inputs (the window loss
    # is a replicated global mean), so an "abort" decision is collective-
    # consistent — each process checkpoints and raises in lockstep instead
    # of process 0 abandoning a collective the others are blocked in.
    sentinel = (
        SpikeSentinel(flags.spike_threshold)
        if flags.spike_threshold > 0
        else None
    )
    heart = (
        Heartbeat(flags.heartbeat_dir, timeout_s=flags.heartbeat_timeout)
        if flags.heartbeat_dir
        else None
    )
    spike_events = 0
    # XLA static analysis (cost/memory/comm bytes) is captured once per
    # compiled step function, lazily at its first batch (real avals in
    # hand), and only when a metrics log is requested — with telemetry off
    # nothing here touches the step functions.
    xla_pending = {"train_step": train_step, "eval_step": eval_step}

    def capture_xla(fn_name, *call_args):
        """Log the step's kind="xla" record on its first call. Returns the
        executable the analysis compiled — the loop runs THAT from then on,
        or the step would compile a second time (obs/xla.compiled_stats) —
        or None when nothing was compiled."""
        jitted = xla_pending.pop(fn_name, None)
        # p0-gated like the logger that consumes it: the analysis
        # (as_text + HLO parse) is pure host work other processes would
        # only discard. The AOT lower/compile it triggers is process-local,
        # so skipping it off-p0 cannot desynchronize a multi-host run.
        if jitted is None or not flags.metrics_log or not p0:
            return None
        with spans.span("telemetry"):
            structs = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), call_args
            )
            hlo = {}
            # the AOT compile below is what emits GSPMD's involuntary-
            # remat warnings — captured here so the lint's remat rule
            # audits the production compile, not an empty string (a
            # cache-served compile stays silent; the CI lane runs cold
            # for exactly that reason)
            with capture_compiler_stderr() as cap:
                stats = compiled_stats(jitted, *structs, hlo_out=hlo)
        if stats:
            ops_for = getattr(strategy, "comm_ops_for", None)
            expected = (
                ops_for(cfg) if ops_for is not None
                else getattr(strategy, "comm_ops", ())
            )
            extra = {}
            # Hand-scheduled dispatch audit (round 10): strategies that
            # place their own collectives (ExpertParallel's a2a MoE
            # dispatch) predict the per-device all-to-all payload in
            # closed form; the record carries it next to the measured HLO
            # bytes so tools/report.py can flag a dispatch regression.
            audit_fn = getattr(strategy, "dispatch_comm", None)
            if audit_fn is not None:
                ids = call_args[1]["input_ids"]
                # backend-aware expectation (round 12): the formula prices
                # in XLA:CPU's bf16->f32 wire upcast, so the renderer can
                # compare bytes EXACTLY on every backend instead of
                # soft-excusing CPU eval windows
                audit = audit_fn(
                    cfg, global_batch=ids.shape[0], seq=ids.shape[1],
                    backend=jax.default_backend(),
                )
                if audit:
                    key = "train" if fn_name == "train_step" else "eval"
                    extra["a2a_expected"] = audit[key]
            # quantized grad-collective audit (round 12): DDP/FSDP predict
            # their compressed grad payload in closed form; the record
            # carries it next to the measured HLO bytes
            grad_fn = getattr(strategy, "grad_comm", None)
            if grad_fn is not None and fn_name == "train_step":
                gaudit = grad_fn(
                    cfg, state_shapes.params, backend=jax.default_backend()
                )
                if gaudit:
                    extra["quant_grad_expected"] = gaudit
                    extra["comm_dtype"] = cfg.comm_dtype
            # hlolint rule verdicts (round 16, tpukit/analysis): the same
            # engine the dryrun and tools/hlolint.py run — CommPlan diff,
            # remat/wire/donation/index-plumbing rules, overlap tally —
            # summarized onto the record so a report can flag a schedule
            # regression without recompiling anything. Best-effort like
            # the rest of telemetry: a lint crash must never take down
            # the run.
            if hlo.get("text"):
                try:
                    from tpukit.analysis import (
                        lint_module, parse_hlo,
                        summarize as lint_summarize, train_comm_plan,
                    )

                    ids = call_args[1]["input_ids"]
                    lint_plan = train_comm_plan(
                        strategy, cfg, param_shapes=state_shapes.params,
                        global_batch=ids.shape[0], seq=ids.shape[1],
                        backend=jax.default_backend(),
                        phase="train" if fn_name == "train_step" else "eval",
                    )
                    findings = lint_module(
                        parse_hlo(hlo["text"]), plan=lint_plan,
                        compiler_stderr=cap["text"],
                        backend=jax.default_backend(),
                        # train_step donates the state (donate_argnums);
                        # eval_step does not
                        expect_donated=(
                            len(jax.tree_util.tree_leaves(state_shapes))
                            if fn_name == "train_step" else None
                        ),
                    )
                    extra["hlolint"] = lint_summarize(findings)
                except Exception:
                    pass
            logger.log(
                kind="xla", fn=fn_name, strategy=strategy.name,
                backend=jax.default_backend(),
                expected_comm_ops=list(expected), **extra, **stats,
            )
        # --disable_compile means eager steps: never hand back an executable
        return None if flags.disable_compile else hlo.get("executable")

    epochs = num_epochs if num_epochs is not None else flags.epochs
    checkpoint_path = None

    # ---- recovery engine (round 9, docs/DESIGN.md "recovery") -----------
    # --on_anomaly rollback: a sentinel/divergence firing restores the
    # last integrity-verified checkpoint older than the detection window,
    # in process, and training continues with the input stream still
    # moving FORWARD (the offending batch window is never replayed).
    recovery = (
        RecoveryEngine(
            "checkpoints",
            max_rollbacks=flags.max_rollbacks,
            coordinator=RollbackCoordinator(
                flags.heartbeat_dir or None,
                process_index=jax.process_index(),
                process_count=jax.process_count(),
                timeout_s=flags.heartbeat_timeout,
            ),
        )
        if flags.on_anomaly == "rollback"
        else None
    )
    timeline = 0  # collective rollbacks executed (tags heartbeat checksums)
    skip_save_step = -1  # suppress the periodic re-save right after a restore
    # Multi-process preemption is collectivized the same way (see
    # recovery.PreemptCoordinator): the graceful checkpoint is a
    # step-keyed collective write, so every rank must save at the same
    # step even though their host loops observe the signal at different
    # wall-clocks. Without a shared heartbeat directory we fall back to
    # the uncoordinated poll and say so once.
    preempt_coord = None
    if jax.process_count() > 1:
        if flags.heartbeat_dir:
            preempt_coord = PreemptCoordinator(
                flags.heartbeat_dir,
                process_index=jax.process_index(),
                process_count=jax.process_count(),
            )
        elif jax.process_index() == 0:
            import warnings

            warnings.warn(
                "multi-process run without --heartbeat_dir: a SIGTERM/"
                "SIGINT preemption checkpoint cannot be coordinated across "
                "processes and may deadlock the step-keyed collective save "
                "if ranks observe the signal at different steps"
            )

    # The step counter is tracked on host (one D2H sync here, after a
    # possible resume, then pure host arithmetic) so periodic checkpointing
    # never forces a per-step `int(state.step)` sync inside the hot loop.
    host_step = int(state.step)
    if preempt_coord is not None:
        # Tag this incarnation's coordination records with its starting
        # step: every rank restores the same checkpoint, so the tag is
        # collective for free, and a stale decision/request that survives
        # the init cleanup (relaunch race — a fast rank can poll before a
        # slow p0's sweep) can never match a resumed run, whose start step
        # sits exactly ON the stale decision's execute_after boundary.
        preempt_coord.run_start = host_step

    # ---- failure observability (round 8): watchdog + bundles + trace-on-
    # anomaly + divergence checksums (docs/DESIGN.md "failure
    # observability"). The watchdog exists whenever bundles can be asked
    # for (--hang_timeout and/or --debug_dir); its monitor thread only
    # runs with a positive timeout.
    debug_dir = flags.debug_dir or (
        "debug"
        if flags.hang_timeout > 0
        or flags.trace_on_anomaly > 0
        or flags.divergence_check_freq > 0
        else ""
    )
    # in-flight async state the bundle snapshots; the prefetcher slot is
    # re-pointed each epoch
    pf_live: dict[str, Any] = {"pf": None}

    def _prefetch_probe():
        pf = pf_live["pf"]
        if pf is None:
            return None
        return {"depth": pf.depth, "buffered": pf.buffered}

    watchdog = (
        HangWatchdog(
            debug_dir,
            timeout_s=flags.hang_timeout,
            recorder=recorder,
            heartbeat=heart,
            probes={
                "host_step": lambda: host_step,
                "async_checkpoint_in_flight": (
                    lambda: async_saver.in_flight if async_saver else False
                ),
                "prefetcher": _prefetch_probe,
            },
            config=flags,
        )
        if debug_dir
        else None
    )
    # Trace-on-anomaly: the FIRST anomaly arms a jax.profiler capture of
    # the next K steps. Mutually exclusive with a whole-run --profile_dir
    # trace (jax supports one active capture).
    tracer = (
        AnomalyTracer(
            os.path.join(debug_dir, "anomaly_trace"), flags.trace_on_anomaly
        )
        if flags.trace_on_anomaly > 0 and debug_dir and not flags.profile_dir
        else None
    )
    # Divergence checksums ride a SEPARATE jitted program so the train
    # step's HLO is byte-identical with the flag off (tests assert it).
    checksum_fn = (
        make_state_checksum() if flags.divergence_check_freq > 0 else None
    )
    if checksum_fn is not None:
        if jax.process_count() > 1 and heart is None:
            # cross-replica comparison rides the heartbeat files; without
            # them a multi-host run would pay for checksums that nothing
            # ever compares — the exact silent failure this flag exists
            # to catch. Fail loudly instead.
            raise ValueError(
                "--divergence_check_freq needs --heartbeat_dir on multi-"
                "process runs: checksums are compared across processes "
                "through the shared heartbeat files"
            )
        # Compile the checksum program NOW, before the watchdog ever arms:
        # its one-off trace+compile at the first check step would otherwise
        # run inside an armed iteration and a long compile could dump a
        # spurious "hang" bundle (and burn the once-per-run anomaly trace).
        jax.block_until_ready(checksum_fn(state)["params"])
    last_checksum: tuple[int, str] | None = None  # (step, hex)
    # (process, checksum_step, checksum) triples already reported: beats
    # republish the same mismatch every window until the next check step,
    # and one divergence must not spam the JSONL or drain the bundle budget
    reported_divergence: set = set()
    # Check-step dispatches are ASYNC (two u32 scalars in flight, the
    # producing state released at dispatch); the D2H read happens at the
    # window boundary, which syncs anyway — so a check step costs one
    # extra jitted pass, never a mid-window pipeline stall.
    pending_checks: list[tuple[int, dict]] = []
    hangs_logged = 0

    def flush_checks() -> None:
        nonlocal last_checksum
        for st, ck in pending_checks:
            cs = format_checksum(ck)  # the deferred D2H read
            last_checksum = (st, cs)
            recorder.record("divergence_check", step=st, checksum=cs)
            logger.log(kind="divergence_check", step=st, checksum=cs)
        pending_checks.clear()

    def note_anomaly(reason: str, step: int) -> None:
        """First anomaly arms the trace; every anomaly lands in the ring."""
        recorder.record("anomaly", reason=reason, step=step)
        if tracer is not None and tracer.trigger(reason):
            logger.log(
                kind="anomaly_trace", event="armed", reason=reason, step=step
            )

    def dump_bundle(reason: str, step: int, **ctx):
        if watchdog is None:
            return None
        path = watchdog.trigger(reason, step=step, **ctx)
        if path is not None:
            logger.log(
                kind="watchdog", event="bundle", reason=reason, step=step,
                bundle=str(path),
            )
        return path

    # ---- round-9 helpers: side-event drain, preemption, rollback --------

    def drain_side_events() -> None:
        """Surface retry/chaos events collected since the last drain (they
        fire on any thread: training, async-checkpoint writer, prefetch
        worker) into the JSONL + flight recorder, on the training thread."""
        for ev in retry_log.drain():
            logger.log(kind="retry", step=host_step, **ev)
            recorder.record("retry", step=host_step, **ev)
            if metric_reg is not None:
                metric_reg.inc("train_retries")
        if chaos_engine is not None:
            for ev in chaos_engine.drain_fired():
                rec = dict(ev)
                rec.setdefault("step", host_step)
                logger.log(kind="chaos", **rec)
                recorder.record("chaos", **rec)

    null_polls = [0]  # eval/generate-phase poll throttle (see below)

    def check_preempt(consumed: int | None, epoch_idx: int) -> None:
        """Graceful preemption (SIGTERM/SIGINT → exit code 75): polled at
        iteration boundaries, where device state is coherent. Writes a
        DURABLE checkpoint carrying resume metadata — the epoch and the
        number of batches consumed (`consumed=None` means the epoch's
        training phase is complete) — so `--resume latest` continues the
        interrupted epoch at the exact batch it stopped at, bit-exact."""
        sig = preempt_guard.pending
        if preempt_coord is not None:
            # Multi-process: collectivize through the heartbeat directory.
            # Ranks publish their pending signal as a request; process 0
            # turns the first request into a decision naming a window
            # boundary ≥ one full window ahead (host loops can run up to a
            # window past the collective frontier, so anything closer could
            # already be behind a rank); every rank's deterministic
            # host-step counter passes through that boundary's poll exactly
            # once, so the step-keyed collective save matches. A decision
            # whose boundary falls past the end of training is never
            # executed — all ranks uniformly finish clean (exit 0), which
            # is strictly better than a preempt exit anyway.
            boundary = consumed is None or host_step % PRINT_FREQ == 0
            if sig is not None:
                preempt_coord.request(sig)
            elif not boundary:
                return  # cheap poll: no signal here, not a boundary step
            elif consumed is None:
                # eval/generate call this per batch with host_step frozen:
                # a per-batch decision-file read (plus p0's request glob)
                # hammers a shared filesystem for nothing. Poll at the
                # window cadence instead — the counter advances identically
                # on every rank (same batch sequence), so a matching
                # decision is still executed by all ranks at the same poll.
                # An actual local signal (sig above) is never throttled.
                null_polls[0] += 1
                if null_polls[0] % PRINT_FREQ:
                    return
            dec = preempt_coord.read()
            if dec is None and p0 and boundary:
                req = sig or preempt_coord.any_request()
                if req is not None:
                    dec = preempt_coord.publish(
                        req,
                        execute_after=(
                            (host_step // PRINT_FREQ + 2) * PRINT_FREQ
                        ),
                    )
            if dec is None:
                return
            if host_step != int(dec["execute_after"]):
                # not the decision's boundary: keep training (epoch-end
                # polls included — the in-loop poll at execute_after is hit
                # by every rank, possibly in the next epoch)
                return
            sig = dec["signal"]
        elif sig is None:
            return
        if watchdog is not None:
            watchdog.disarm()
        if consumed is None:
            ep, nb = epoch_idx + 1, 0
        else:
            ep, nb = epoch_idx, consumed
            spe = (
                len(train_loader)
                if hasattr(train_loader, "__len__")
                else None
            )
            if spe is not None and nb >= spe:
                ep, nb = ep + 1, 0  # epoch boundary: resume starts the next
        meta = {
            "step": host_step, "epoch": ep, "batch_in_epoch": nb,
            "preempted": True, "signal": sig,
        }
        if chaos_engine is not None and chaos_engine.resize_target is not None:
            # resize@N:M chaos: record the world this run expects to come
            # back at, so the relaunch can assert it resharded to M
            meta["resize_to"] = chaos_engine.resize_target
        with spans.span("checkpoint"):
            path = save_checkpoint(state, meta=meta)
            if async_saver is not None:
                # the exit is imminent: the checkpoint must be durable NOW
                async_saver.wait()
        recorder.record("preempt", step=host_step, signal=sig)
        logger.log(
            kind="preempt", step=host_step, signal=sig, epoch=ep,
            batch_in_epoch=nb, checkpoint=str(path),
        )
        if metric_reg is not None:
            metric_reg.inc("train_preempts")
        if heart is not None:
            heart.beat(host_step, timeline=timeline)
        drain_side_events()
        publish_metrics()  # last snapshot before the exit below
        if p0:
            print(f"preempted by {sig} at step {host_step}; checkpoint {path}")
        logger.close()
        raise Preempted(
            f"{sig} at step {host_step}; checkpoint {path}; relaunch with "
            f"--resume latest to continue",
            checkpoint=path, step=host_step,
        )

    def abort_with(exc_cls, message: str):
        """The round-8 bundle-dump-and-abort tail shared by --spike_action
        abort and rollback-budget exhaustion: preserve the blown-up state
        for autopsy, then fail loudly with the documented exit code."""
        nonlocal checkpoint_path
        with spans.span("checkpoint"):
            checkpoint_path = save_checkpoint(state) or checkpoint_path
            if async_saver is not None:
                # abort must leave a DURABLE autopsy
                async_saver.wait()
        drain_side_events()
        publish_metrics()  # the autopsy snapshot: counters up to the abort
        # (the raise unwinds through _cleanup, which closes this epoch's
        # prefetcher and bar)
        logger.close()
        raise exc_cls(f"{message}; state checkpointed at {checkpoint_path}")

    # Jitted identity at the strategy's shardings, used by execute_rollback
    # below. Hoisted so every rollback reuses one traced/compiled program
    # (jit's cache is keyed on function identity — a fresh lambda per call
    # would re-trace inside the quiesce window).
    _relaunder = jax.jit(lambda s: s, out_shardings=state_sharding)

    def execute_rollback(plan) -> None:
        """Restore the plan's checkpoint in process and reset every piece
        of host state that belongs to the abandoned timeline segment. The
        input stream is NOT rewound: the loader/prefetcher keeps streaming
        forward, so the batch window that fired the anomaly is never
        replayed."""
        nonlocal state, host_step, running, win_n, norms, last_checksum
        nonlocal timeline, skip_save_step
        if watchdog is not None:
            watchdog.disarm()  # restore I/O may exceed the step deadline
        if async_saver is not None:
            # An in-flight async save of an abandoned-timeline step must
            # publish BEFORE the quarantine sweep, or it would land after
            # it — resurrecting a possibly-poisoned checkpoint that a later
            # rollback/resume could restore.
            async_saver.wait()
        quarantined = recovery.quarantine(plan, process_zero=p0)
        # Quiesce the prefetch worker across the restore: its batch
        # device_puts racing the restore's state placement corrupts the
        # CPU runtime (prefetch.HostPrefetcher.quiesce). Buffered batches
        # keep serving — production pauses, the stream position holds.
        pf = pf_live["pf"]
        pf_quiet = pf.quiesce() if pf is not None else contextlib.nullcontext()
        with pf_quiet, spans.span("checkpoint"):
            restored, was_sharded = ckpt_lib.restore_any(
                plan.target_path, state_shapes, state_sharding
            )
            state = (
                restored if was_sharded else _place_like(restored, state_sharding)
            )
            # Launder the restored pytree through a jitted identity: the
            # next train_step dispatch then sees ordinary jit-output
            # arrays and takes the fast path, instead of re-placing
            # host-restored arrays inside the dispatch — host-side work
            # that would land OUTSIDE this quiesce and race the prefetch
            # worker's device_put (same corruption the quiesce exists
            # for). Compiled once, cached across rollbacks.
            state = _relaunder(state)
            jax.block_until_ready(jax.tree_util.tree_leaves(state))
        host_step = plan.target_step
        skip_save_step = host_step  # the target step's checkpoint exists
        running, win_n, norms = None, 0, None
        if sentinel is not None:
            # post-restore losses revisit an OLDER point of the curve; the
            # pre-anomaly baseline would re-fire on a healthy recovery
            sentinel.reset()
        pending_checks.clear()
        last_checksum = None
        timeline += 1  # heartbeat checksums from before the rollback are
        # now a different timeline: equal step numbers, different data
        recovery.committed(plan)
        recovery.coordinator.ack(plan.seq, plan.target_step)
        rec = plan.record()
        logger.log(kind="rollback", timeline=timeline, quarantined=quarantined, **rec)
        recorder.record("rollback", **rec)
        if metric_reg is not None:
            metric_reg.inc("train_rollbacks")
        if heart is not None:
            heart.beat(host_step, timeline=timeline)
        if p0:
            print(
                f"rollback {plan.seq}/{recovery.max_rollbacks} "
                f"({plan.reason} at step {plan.anomaly_step}): restored "
                f"{plan.target_path} at step {plan.target_step}, "
                f"{plan.steps_lost} steps lost; input stream continues "
                f"forward"
            )

    def try_rollback(reason: str, anomaly_step: int) -> bool:
        """Immediate collective rollback — for anomalies EVERY process
        observes in lockstep (the sentinel's window loss is replicated).
        Each process computes the same plan from the shared checkpoint
        directory; process 0 publishes the decision record and the others
        confirm theirs against it before restoring. False = escalate."""
        if recovery is None:
            return False
        plan = recovery.plan(reason, anomaly_step, window=PRINT_FREQ)
        if plan is None:
            return False
        if jax.process_index() == 0:
            recovery.coordinator.publish(plan)
        else:
            recovery.coordinator.confirm(plan)
        execute_rollback(plan)
        return True

    pending_deferred: dict[int, Any] = {}  # p0's not-yet-executed decisions

    def defer_rollback(reason: str, anomaly_step: int) -> bool:
        """Deferred collective rollback — for anomalies only process 0
        observes (divergence). The decision file is published one window
        AHEAD of execution so every process discovers it on the shared
        heartbeat directory and executes at the same boundary."""
        seq = recovery.count + 1
        if seq in pending_deferred or recovery.coordinator.read(seq) is not None:
            # A decision for this anomaly is already in flight (a persistent
            # divergence re-fires at every boundary until the rollback
            # executes). Re-publishing would push execute_after back each
            # window — postponing the rollback forever — and a rank that
            # already read the old record would execute at the old boundary
            # while p0 waits for the new one: split-brain.
            return True
        plan = recovery.plan(reason, anomaly_step, window=PRINT_FREQ)
        if plan is None:
            return False
        recovery.coordinator.publish(
            plan, execute_after=anomaly_step + PRINT_FREQ
        )
        pending_deferred[plan.seq] = plan
        return True

    def poll_rollback(final: bool = False) -> None:
        """Window-boundary poll (every process, multi-process worlds):
        execute a published deferred decision once its execute_after step
        is reached. `final=True` (end of the last epoch's training phase)
        executes a still-pending decision regardless of its boundary — a
        decision published during the LAST window has no later boundary,
        and dropping it would eval, save, and exit 0 on the diverged
        state. Every rank reaches the final drain at the same host_step,
        so the restore's (or abort's) collectives still match. The drain
        itself is a rendezvous: process 0 publishes a final-drain marker
        AFTER anything it will ever publish is on disk, and other ranks
        wait (bounded) for it before trusting a None read — p0 detects
        divergence inside its last boundary block (heartbeat reads +
        hashing, slow), so a faster rank's lone read could land before
        the publish and sail into eval on the diverged state."""
        if recovery is None or jax.process_count() == 1:
            return
        seq = recovery.count + 1
        plan = pending_deferred.pop(seq, None)
        if plan is not None:  # process 0's own deferred decision
            if final or host_step >= plan.anomaly_step + PRINT_FREQ:
                if final:
                    # marker before the (long) restore: other ranks can
                    # read the already-published decision and restore
                    # concurrently instead of waiting out p0's I/O
                    recovery.coordinator.publish_final_drain(host_step)
                execute_rollback(plan)
            else:
                pending_deferred[seq] = plan
            return
        if final:
            if p0:
                recovery.coordinator.publish_final_drain(host_step)
            else:
                recovery.coordinator.wait_final_drain()
        rec = recovery.coordinator.read(seq)
        if rec is None or "execute_after" not in rec:
            return  # nothing pending (immediate decisions ran via confirm)
        if not final and host_step < int(rec["execute_after"]):
            return
        if rec.get("action") == "abort":
            # collective-abort decision (publish_abort): every process —
            # including the p0 that published it — reaches abort_with here
            # at the same boundary, so the autopsy checkpoint's collective
            # completes before the run exits 77
            abort_with(
                RollbackBudgetExhausted,
                f"{rec['reason']} at step {rec['anomaly_step']}: rollback "
                f"budget exhausted ({recovery.count}/"
                f"{recovery.max_rollbacks} used) or no integrity-verified "
                f"checkpoint to restore",
            )
        from tpukit.recovery import RollbackPlan

        execute_rollback(
            RollbackPlan(
                seq=int(rec["seq"]), reason=rec["reason"],
                anomaly_step=int(rec["anomaly_step"]),
                target_step=int(rec["target_step"]),
                target_path=rec["target_path"],
                steps_lost=int(rec["steps_lost"]),
            )
        )

    if heart is not None:
        heart.beat(host_step)  # liveness file exists before the first compile

    maybe_nojit = jax.disable_jit() if flags.disable_compile else contextlib.nullcontext()
    # Debug toolchain (SURVEY §5): abort with a traceback at the first
    # NaN/Inf inside any jitted computation. Scoped to this fit() so debug
    # mode does not leak into later runs in the same process.
    maybe_nans = (
        _debug_nans_scope() if flags.debug_nans else contextlib.nullcontext()
    )
    # First call of each compiled step function pays the jit compile —
    # minutes at pod scale — so the watchdog only arms once the function
    # is warm: --hang_timeout bounds the steady-state step, not the
    # compile.
    warm = {"train": False, "eval": False}

    def _close_obs():
        # runs on ANY exit of the training block (normal, spike abort,
        # debug_nans, KeyboardInterrupt): flush a partial anomaly trace
        # and stop the monitor thread before the final checkpoint I/O
        if tracer is not None and tracer.stop():
            logger.log(kind="anomaly_trace", event="stopped", step=host_step)
        if watchdog is not None:
            watchdog.close()

    # _cleanup: any exception unwinding the loop (debug_nans aborts, device
    # OOM, KeyboardInterrupt) must release the epoch's prefetch worker —
    # close() is idempotent, so registering each epoch's prefetcher is safe.
    # A run resumed AT the end of training (preempted during the final
    # epoch's eval phase → meta epoch == epochs) never enters the epoch
    # loop, so eval_metrics must exist before it.
    eval_metrics = {}
    with contextlib.ExitStack() as _obs_guard, maybe_nojit, maybe_nans, \
            profiler_trace(flags.profile_dir), contextlib.ExitStack() as _cleanup:
        _obs_guard.callback(_close_obs)
        for epoch in range(start_epoch, epochs):
            # ---- train ---------------------------------------------------
            train_loader.set_epoch(epoch)
            # Mid-epoch continuation of a preempted run: drop the batches
            # the interrupted run already trained on, so the resumed epoch
            # consumes exactly the remainder (bit-exact with the
            # uninterrupted run; the per-epoch shuffle is seeded, so the
            # stream is reproducible).
            skip = start_skip if epoch == start_epoch else 0
            # Exact global real-row schedule (VERDICT r4 #6): pure host math
            # (wrap-pad positions don't depend on the shuffle), so the meter
            # is exact on ragged final batches without a per-step cross-host
            # reduction that would re-serialize the async dispatch pipeline.
            # Custom loaders without the method fall back to the
            # per-shard x num_replicas approximation.
            global_rows = (
                train_loader.global_real_row_counts()
                if hasattr(train_loader, "global_real_row_counts")
                else None
            )
            # total=None for reduced-interface custom loaders (make_loaders
            # contract: iterable + set_epoch; __len__ optional)
            bar = tqdm(
                total=len(train_loader) if hasattr(train_loader, "__len__") else None,
                initial=skip,
                disable=not p0,
            )
            bar.set_description(f"[training] Epoch {epoch+1}/{epochs} | loss: ?????")
            # win_n counts the losses actually accumulated this window: a
            # mid-epoch resume (or chaos skip@N) starts i mid-window, so
            # the first boundary may close over fewer than PRINT_FREQ
            # steps — dividing by the nominal width would understate the
            # logged loss and seed the spike sentinel's baseline with it.
            running, win_n = None, 0
            norms = None  # on-device window norms when --log_grad_norms
            # Input source (round 7): with --prefetch N (default 2) a
            # background thread runs the whole host pipeline N batches
            # ahead, so loader wait + prepare + H2D assembly overlap the
            # in-flight compiled step; the measured wait is the residual
            # `prefetch_stall` span. --prefetch 0 is the synchronous
            # reference path, bit-identical losses (tests/test_prefetch.py).
            # One prefetcher per epoch: set_epoch has already run, and the
            # epoch boundary flushes instead of buffering across epochs.
            pf = (
                HostPrefetcher(
                    train_loader, host_pipeline, depth=flags.prefetch,
                    skip=skip, span=spans.annotate,
                )
                if flags.prefetch > 0
                else None
            )
            pf_live["pf"] = pf  # bundle probe sees this epoch's prefetcher
            if pf is not None:
                _cleanup.callback(pf.close)
            _cleanup.callback(bar.close)
            if pf is None:
                it = iter(train_loader)
                for _ in range(skip):  # sync path's resume fast-forward
                    next(it, None)
            else:
                it = None
            i = skip - 1
            while True:
                # Preemption poll: SIGTERM/SIGINT landed since the last
                # iteration → graceful checkpoint-and-exit (code 75) at a
                # boundary where device state is coherent.
                check_preempt(i + 1, epoch)
                # The watchdog deadline covers the WHOLE iteration — input
                # wait, dispatch, window sync, periodic checkpoint — so a
                # hang in any of them trips it; re-arming each iteration
                # resets the clock.
                if watchdog is not None and warm["train"]:
                    watchdog.arm(host_step + 1)
                if tracer is not None and tracer.maybe_start():
                    logger.log(
                        kind="anomaly_trace", event="started",
                        step=host_step + 1, reason=tracer.reason,
                        dir=tracer.trace_dir,
                    )
                if pf is not None:
                    with spans.span("prefetch_stall"):
                        try:
                            raw, batch, targets = next(pf)
                        except StopIteration:
                            break
                    i += 1
                else:
                    # Explicit iterator so the loader wait is a measured
                    # span — a data-bound run shows up as a "data" slice of
                    # the window instead of silently deflating tokens/sec.
                    with spans.span("data"):
                        try:
                            raw = next(it)
                        except StopIteration:
                            break
                        i += 1
                        batch, targets = prepare_batch(raw, tokenizer.pad_token_id)
                        if host_batch is not None:
                            batch, targets = host_batch(batch, targets)
                    with spans.span("h2d"):
                        batch, targets = make_global_batch(batch_sh, batch, targets)
                bar.update(1)
                train_step = (
                    capture_xla("train_step", state_shapes, batch, targets)
                    or train_step
                )
                with spans.span("step", step_num=host_step + 1):
                    if flags.log_grad_norms:
                        state, loss, norms = train_step(state, batch, targets)
                    else:
                        state, loss = train_step(state, batch, targets)
                warm["train"] = True
                host_step += 1
                recorder.record("step", step=host_step, epoch=epoch)
                if chaos_engine is not None:
                    # deterministic fault injection at exactly this step:
                    # poisoned losses enter the window average below, a
                    # flipped bit enters the next divergence checksum, an
                    # injected signal is polled right here. A bitflip
                    # device_puts into the state on THIS thread, so it
                    # takes the same prefetcher quiesce the rollback
                    # restore does (two threads must never place at once).
                    _pf = pf_live["pf"]
                    _quiet = (
                        _pf.quiesce()
                        if _pf is not None
                        and chaos_engine.mutates_state_at(host_step)
                        else contextlib.nullcontext()
                    )
                    with _quiet:
                        state, loss, _fired = chaos_engine.on_step(
                            host_step, state, loss
                        )
                    if _fired:
                        check_preempt(i + 1, epoch)
                if tracer is not None and tracer.tracing and tracer.step():
                    logger.log(
                        kind="anomaly_trace", event="stopped", step=host_step
                    )
                if (
                    checksum_fn is not None
                    and host_step % flags.divergence_check_freq == 0
                ):
                    with spans.span("telemetry"):
                        pending_checks.append((host_step, checksum_fn(state)))
                running = loss if running is None else running + loss
                win_n += 1
                # Honest throughput (VERDICT r2 #8): count only original
                # dataset rows — wrap-padding duplicates train but are not
                # new tokens; the precomputed global schedule makes the
                # count exact on ragged multi-host batches (VERDICT r4 #6).
                real_rows = raw.get("real_rows") if isinstance(raw, dict) else None
                if global_rows is not None:
                    meter.update(int(global_rows[i]) * targets.shape[1])
                elif real_rows is None:
                    meter.update(targets.size)  # custom loader: no row info
                else:
                    meter.update(real_rows * loader_procs * targets.shape[1])
                if i > 0 and not i % PRINT_FREQ:
                    # Rollbacks executed inside this boundary block (an
                    # immediate divergence rollback above, or a deferred
                    # decision in poll_rollback) reset the sentinel and
                    # rewind host_step — `avg` then belongs to the
                    # abandoned timeline and must not seed the cleared
                    # history (a poisoned avg would even re-fire the NaN
                    # sentinel and burn a second budget slot).
                    pre_rollbacks = recovery.count if recovery is not None else 0
                    with spans.span("sync"):
                        avg = float(running) / win_n  # one D2H sync per window
                        norm_vals = (
                            {k: float(v) for k, v in norms.items()}
                            if norms is not None
                            else {}
                        )
                    win = spans.window()
                    bar.set_description(
                        f"[training] Epoch {epoch+1}/{epochs} | loss: {avg:.3f}"
                    )
                    record = dict(
                        kind="train", epoch=epoch, step=host_step, loss=avg,
                        tokens_per_sec=meter.tokens_per_sec, mfu=meter.mfu,
                        goodput=win["goodput"], spans=win["fractions"],
                        window_s=win["total_s"], **norm_vals,
                    )
                    hbm = live_memory_stats()
                    if hbm:
                        record["hbm"] = hbm
                    if pf is not None:
                        # buffer gauges: how long this thread actually
                        # blocked on input (the honest residual of the old
                        # data+h2d cost after overlap) and how full the
                        # prefetch buffer ran (0 = starved, depth = ahead)
                        pstats = pf.window_stats()
                        record["prefetch_stall_s"] = round(
                            win["seconds"].get("prefetch_stall", 0.0), 6
                        )
                        record["prefetch_occupancy"] = round(
                            pstats["occupancy"], 3
                        )
                    logger.log(**record)
                    recorder.record(
                        "window", step=host_step, epoch=epoch, loss=avg,
                        goodput=win["goodput"],
                        window_s=round(win["total_s"], 6),
                    )
                    if metric_reg is not None:
                        # Goodput-component walls: the window's per-span
                        # seconds the timeline already measured, one
                        # histogram per phase (step/data/h2d/sync/...).
                        for _ph, _secs in win["seconds"].items():
                            if _secs > 0:
                                metric_reg.observe(
                                    "train_span_s", _secs, phase=_ph
                                )
                        metric_reg.observe("train_window_s", win["total_s"])
                        metric_reg.gauge("train_goodput", win["goodput"])
                        metric_reg.gauge(
                            "train_tokens_per_sec", meter.tokens_per_sec
                        )
                        if meter.mfu:
                            metric_reg.gauge("train_mfu", meter.mfu)
                        metric_reg.inc("train_windows")
                        publish_metrics()
                    if (
                        watchdog is not None
                        and len(watchdog.hang_events) > hangs_logged
                    ):
                        # the monitor thread already dumped the bundle(s);
                        # surface the event in the JSONL from this thread
                        # and trace the recovery steps. hang_events pairs
                        # each overrun with ITS bundle (None if the dump
                        # budget was spent), so the record never points at
                        # an unrelated sentinel bundle.
                        new_events = watchdog.hang_events[hangs_logged:]
                        hangs_logged = len(watchdog.hang_events)
                        logger.log(
                            kind="watchdog", event="hang", step=host_step,
                            hangs=len(watchdog.hang_events),
                            bundles=[
                                e["bundle"] for e in new_events if e["bundle"]
                            ],
                        )
                        note_anomaly("hang", host_step)
                    running, win_n = None, 0
                    drain_side_events()
                    if pending_checks:
                        with spans.span("telemetry"):
                            flush_checks()
                    if heart is not None:
                        heart.beat(
                            host_step,
                            checksum=last_checksum[1] if last_checksum else None,
                            checksum_step=(
                                last_checksum[0] if last_checksum else None
                            ),
                            timeline=timeline,
                        )
                        if p0:
                            # step_lag = one window: SPMD lockstep keeps
                            # healthy processes equal, so a process a full
                            # window behind (e.g. restarted onto an old
                            # checkpoint) is worth naming
                            stragglers = heart.check(step_lag=PRINT_FREQ)
                            if stragglers:
                                logger.log(
                                    kind="straggler", step=host_step,
                                    stragglers=stragglers,
                                )
                                recorder.record(
                                    "straggler", step=host_step,
                                    stragglers=stragglers,
                                )
                                print(f"heartbeat: straggling processes {stragglers}")
                                note_anomaly("straggler", host_step)
                                dump_bundle(
                                    "straggler", host_step,
                                    stragglers=stragglers,
                                )
                            if checksum_fn is not None:
                                # beats republish their latest checksum
                                # every window; report each mismatching
                                # (process, step, checksum) ONCE
                                diverged = [
                                    m for m in heart.check_divergence()
                                    if (
                                        m["process"], m["checksum_step"],
                                        m["checksum"],
                                    ) not in reported_divergence
                                ]
                                if diverged:
                                    reported_divergence.update(
                                        (
                                            m["process"], m["checksum_step"],
                                            m["checksum"],
                                        )
                                        for m in diverged
                                    )
                                    logger.log(
                                        kind="divergence", step=host_step,
                                        mismatches=diverged,
                                    )
                                    recorder.record(
                                        "divergence", step=host_step,
                                        mismatches=diverged,
                                    )
                                    print(
                                        "divergence: replica checksum "
                                        f"mismatch {diverged}"
                                    )
                                    note_anomaly("divergence", host_step)
                                    dump_bundle(
                                        "divergence", host_step,
                                        mismatches=diverged,
                                    )
                                    if recovery is not None:
                                        # divergence is a p0-only
                                        # observation: single-process
                                        # rolls back right here;
                                        # multi-process publishes the
                                        # decision one window ahead and
                                        # poll_rollback executes it on
                                        # every process
                                        did = (
                                            try_rollback
                                            if jax.process_count() == 1
                                            else defer_rollback
                                        )("divergence", host_step)
                                        if not did:
                                            if jax.process_count() == 1:
                                                abort_with(
                                                    RollbackBudgetExhausted,
                                                    f"divergence at step "
                                                    f"{host_step}: rollback "
                                                    f"budget exhausted "
                                                    f"({recovery.count}/"
                                                    f"{recovery.max_rollbacks} "
                                                    f"used) or no integrity-"
                                                    f"verified checkpoint to "
                                                    f"restore",
                                                )
                                            else:
                                                # A lone-p0 abort_with would
                                                # strand the other ranks in
                                                # the autopsy checkpoint's
                                                # collective: publish the
                                                # abort one window ahead and
                                                # every process (p0 too)
                                                # executes it in
                                                # poll_rollback.
                                                recovery.coordinator.publish_abort(
                                                    recovery.count + 1,
                                                    "divergence", host_step,
                                                    execute_after=(
                                                        host_step + PRINT_FREQ
                                                    ),
                                                )
                    poll_rollback()
                    if sentinel is not None and (
                        recovery is None or recovery.count == pre_rollbacks
                    ):
                        event = sentinel.observe(avg, host_step)
                        if event is not None:
                            spike_events += 1
                            logger.log(
                                kind="spike", action=flags.spike_action,
                                **event.record(),
                            )
                            recorder.record(
                                "spike", step=event.step, event=event.kind,
                                action=flags.spike_action,
                            )
                            note_anomaly(event.kind, host_step)
                            dump_bundle(event.kind, host_step)
                            if p0:
                                print(
                                    f"loss sentinel: {event.kind} at step "
                                    f"{event.step} (loss {event.loss:.4g})"
                                )
                            if recovery is not None:
                                # Collective-consistent recovery: every
                                # process observed the same replicated
                                # window loss, so all reach this rollback
                                # in lockstep (process 0 publishes the
                                # decision record, the rest confirm).
                                # Budget exhausted (or nothing restorable)
                                # escalates to the round-8 bundle-dump-
                                # and-abort path with exit code 77.
                                if not try_rollback(event.kind, host_step):
                                    abort_with(
                                        RollbackBudgetExhausted,
                                        f"loss sentinel {event.kind} at "
                                        f"step {event.step} (loss "
                                        f"{event.loss:.6g}): rollback "
                                        f"budget exhausted "
                                        f"({recovery.count}/"
                                        f"{recovery.max_rollbacks} used) "
                                        f"or no integrity-verified "
                                        f"checkpoint to restore",
                                    )
                            elif flags.spike_action == "abort":
                                # Preserve the blown-up state for autopsy,
                                # then fail loudly (exit code 76).
                                # Collective-consistent: every process
                                # observed the same replicated loss and
                                # takes this branch together.
                                abort_with(
                                    AnomalyAbort,
                                    f"loss sentinel aborted training: "
                                    f"{event.kind} at step {event.step} "
                                    f"(loss {event.loss:.6g})",
                                )
                if (
                    flags.checkpoint_every
                    and host_step % flags.checkpoint_every == 0
                    # right after a rollback the restored step's checkpoint
                    # is exactly what is already on disk — re-saving it
                    # would only trip the sharded same-step-re-save warning
                    and host_step != skip_save_step
                ):
                    if watchdog is not None:
                        # checkpoint I/O (sync writer: encode + disk) may
                        # legitimately exceed the step deadline; the next
                        # iteration re-arms
                        watchdog.disarm()
                    # Async: only the snapshot is charged here; the encode +
                    # disk write overlaps the following steps.
                    with spans.span("checkpoint"):
                        checkpoint_path = (
                            save_checkpoint(state) or checkpoint_path
                        )
                    recorder.record("checkpoint", step=host_step)
                    prune_checkpoints()
            # Close THIS epoch's prefetcher + bar now (pop_all keeps the
            # fit-lifetime stack from accumulating dead objects across
            # epochs; the stack still covers exceptional unwinds above).
            _cleanup.pop_all().close()
            pf_live["pf"] = None
            if watchdog is not None:
                watchdog.disarm()
            if epoch == epochs - 1:
                # A deferred decision (divergence rollback or collective
                # abort) published during the run's LAST training window
                # names a boundary no training poll will ever reach. Drain
                # it here — before eval and the final save — or the run
                # would evaluate, checkpoint, and exit 0 on the diverged
                # state. (Earlier epochs need no drain: host_step keeps
                # advancing, so the next epoch's boundary polls reach it.)
                poll_rollback(final=True)

            # ---- validation ---------------------------------------------
            bar = tqdm(validation_loader, disable=not p0)
            bar.set_description(
                f"[validation] Epoch {epoch+1}/{epochs} | loss: ?????, accuracy: ?????"
            )
            total_loss, total_acc, total_weight = 0.0, 0.0, 0.0
            eval_metrics = {"loss": float("nan"), "accuracy": float("nan")}
            for i, raw in enumerate(bar):
                # the epoch's training phase is complete: a preemption here
                # checkpoints end-of-epoch state and resumes at epoch+1
                check_preempt(None, epoch)
                # eval steps hang in the same collectives train steps do;
                # same deadline, same first-call compile exemption
                if watchdog is not None and warm["eval"]:
                    watchdog.arm(host_step)
                with spans.span("eval"):
                    batch, targets = prepare_batch(raw, tokenizer.pad_token_id)
                    if host_batch is not None:
                        batch, targets = host_batch(batch, targets)
                    batch, targets = make_global_batch(batch_sh, batch, targets)
                    eval_step = (
                        capture_xla("eval_step", state_shapes, batch, targets)
                        or eval_step
                    )
                    # Token-weighted epoch aggregate (VERDICT r3 #9): each
                    # batch's mean loss/accuracy weighs by its valid-token
                    # count, so a padded final batch no longer weighs like a
                    # full one (the reference's mean-of-batch-means,
                    # main-single.py:128-137, is exact only when batches
                    # divide evenly). Counted on the GLOBAL targets (a jitted
                    # reduction over the sharded array), so every process
                    # aggregates with the same weights — a host-local count
                    # would make ranks disagree about the epoch metric
                    # (caught by tests/test_multiprocess.py).
                    weight = float(_valid_count(targets))
                    loss, acc = eval_step(state, batch, targets)
                    warm["eval"] = True
                    if weight > 0.0:
                        total_loss += float(loss) * weight
                        total_acc += float(acc) * weight
                        total_weight += weight
                    if total_weight > 0.0:
                        eval_metrics = {
                            "loss": total_loss / total_weight,
                            "accuracy": total_acc / total_weight,
                        }
                bar.set_description(
                    f"[validation] Epoch {epoch+1}/{epochs} | "
                    f"loss: {eval_metrics['loss']:.3f}, accuracy: {eval_metrics['accuracy']:.2f}"
                )
            logger.log(kind="validation", epoch=epoch, **eval_metrics)
            recorder.record("validation", epoch=epoch, **eval_metrics)
            if watchdog is not None:
                # generation + epoch-end checkpointing have their own (much
                # longer) natural durations; the next epoch's loop re-arms
                watchdog.disarm()

            # ---- qualitative eval (all processes compute — the replication
            # inside generate_samples is collective — process 0 prints) ----
            # clamp the decode budget so tiny --sequence_length debug
            # runs still fit a prompt in the position table
            gen_tokens = min(20, cfg.max_position_embeddings - 2)
            check_preempt(None, epoch)
            with spans.span("generate"):
                texts = generate_samples(
                    strategy, state, cfg, tokenizer, max_new_tokens=gen_tokens
                )
            if p0:
                print("Argmax sampling from model")
                for text in texts:
                    print(text)

            # ---- epoch wall-clock summary (span timeline): where the
            # epoch's host time went, and the goodput fraction (share spent
            # inside/waiting on the compiled steps) ------------------------
            ep = spans.epoch()
            logger.log(
                kind="epoch", epoch=epoch, goodput=ep["goodput"],
                total_s=ep["total_s"], seconds=ep["seconds"],
                fractions=ep["fractions"],
            )
            recorder.record(
                "epoch", epoch=epoch, goodput=ep["goodput"],
                total_s=round(ep["total_s"], 6),
            )
            if pending_checks:
                flush_checks()  # checks taken since the last window
            if heart is not None:
                heart.beat(
                    host_step,
                    checksum=last_checksum[1] if last_checksum else None,
                    checksum_step=last_checksum[0] if last_checksum else None,
                    timeline=timeline,
                )
            if p0:
                print(f"epoch {epoch+1} wallclock: {format_breakdown(ep)}")

    # ---- final checkpoint (twin of main-single.py:146-151; format routed
    # by save_auto so sharded multi-host state never hits the consolidated
    # gather, VERDICT r2 #1) ----------------------------------------------
    checkpoint_path = save_checkpoint(state) or checkpoint_path
    if async_saver is not None:
        # exit barrier: fit() must not return before the last write is
        # durable (the caller may read or delete the checkpoint next)
        async_saver.wait()
    prune_checkpoints()
    # Retries/chaos firings since the last window boundary — the epoch tail
    # (validation/generation loader fetches) and the final save above — must
    # reach the JSONL before the logger closes.
    drain_side_events()
    if metric_reg is not None:
        # Metrics epilogue (round 22): one kind="metrics" summary record —
        # cumulative counters + per-histogram count/sum/p50/p99 — so
        # tools/report.py --compare can diff two runs without replaying
        # every window. The final snapshot publish lands the complete run
        # in --metrics_dir for external scrapers.
        rec_m = dict(kind="metrics", source="train", **metric_reg.summary())
        logger.log(**rec_m)
        recorder.record(
            "metrics", source="train", hists=len(rec_m.get("hists", {})),
        )
        publish_metrics()
    if p0:
        cs = cache_stats.stats()
        logger.log(kind="compile_cache", **cs)
        print(
            f"compile cache {cs['dir']}: "
            f"{cs['hits']} hits, {cs['misses']} misses, "
            f"{cs['entries']} entries (+{cs['new_entries']})"
        )
    logger.close()

    metrics = {
        "eval": eval_metrics,
        "tokens_per_sec": meter.tokens_per_sec,
        "tokens_per_sec_per_chip": meter.tokens_per_sec_per_chip,
        "mfu": meter.mfu,
        # exact global count (VERDICT r4 #6) — multi-process tests assert
        # ranks agree and match the dataset's real row total
        "train_tokens": meter.total_tokens,
        "spike_events": spike_events,
    }
    if p0 and meter.tokens_per_sec:
        print(
            f"throughput: {meter.tokens_per_sec:,.0f} tok/s "
            f"({meter.tokens_per_sec_per_chip:,.0f} tok/s/chip)"
            + (f", MFU {meter.mfu*100:.1f}%" if meter.mfu else "")
        )
    return FitResult(
        state=state, tokenizer=tokenizer, config=cfg,
        checkpoint_path=checkpoint_path, metrics=metrics,
    )
