"""Parallelism strategies as mesh + sharding rules.

This is the TPU-native re-design of the reference's L1 layer (SURVEY §2.4):
where the reference wraps the model object (`DDP(model)` main-ddp.py:55,
`FSDP(model, ...)` main-fsdp.py:64-69, `Pipe(...)` main-pipe.py:79-83), here
a *strategy object* owns a `Mesh` and emits `NamedSharding`s for the train
state and the batch. `jax.jit` + GSPMD then inserts the collectives the
reference got from NCCL:

  - DataParallel: params/opt-state replicated, batch sharded on the `data`
    axis -> XLA emits a gradient all-reduce over ICI (the twin of DDP's
    bucketed NCCL all-reduce fired by autograd hooks, main-ddp.py:55,124).
  - FSDP: every tensor of params/grads/opt-state >= `min_shard_size` elements
    is sharded along its largest divisible axis -> XLA emits per-tensor
    all-gather (forward/backward) and reduce-scatter (grad) — the twin of
    FullyShardedDataParallel with `size_based_auto_wrap_policy(
    min_num_params=100)` (main-fsdp.py:60-69), where the wrap threshold
    becomes a shard-size threshold. `cpu_offload=True` pins the sharded
    params/opt-state to host memory (twin of `CPUOffload(offload_params=
    True)`, main-fsdp.py:68).
  - ContextParallel: the sequence dimension shards over a `seq` axis and
    attention runs as a ppermute ring (tpukit/ring_attention.py) inside
    shard_map — long-context capability the reference lacks entirely
    (SURVEY §5: its attention materializes S x S on one device).
  - Pipeline strategies live in tpukit/pipeline.py (they need a schedule,
    not just shardings) and subclass `Strategy`.

Every strategy also carries the default loss computation; the pipeline
overrides it with the micro-batched schedule.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpukit import mesh as mesh_lib
from tpukit.model import gpt
from tpukit.ops import quant_comm
from tpukit.ops.layers import (
    IGNORE_INDEX, cross_entropy_loss, cross_entropy_sum, masked_accuracy,
)


def _sharding_tree(mesh: Mesh, spec_fn, tree_shapes):
    """Map `spec_fn(shape) -> PartitionSpec` over a pytree of ShapeDtypeStructs
    (or arrays), returning NamedShardings."""
    return jax.tree.map(lambda leaf: NamedSharding(mesh, spec_fn(leaf.shape)), tree_shapes)



def _fused_head_disabled() -> bool:
    """TPUKIT_FUSED_HEAD=0 routes every strategy back to the unfused XLA
    head+CE (read at use time so it works however late it is set)."""
    return os.environ.get("TPUKIT_FUSED_HEAD", "1") == "0"


def _local_loss_sum(params, cfg, input_ids, position_ids, mask, tgts, rng,
                    fused: bool):
    """Per-shard (loss_sum, valid_count) over local batch rows — the
    shard_map building block of the quantized-comm strategies (the same
    local spelling ContextParallel's block uses): trunk forward on the
    local rows, then the fused head+CE kernel (no logits buffer) or the
    custom-VJP CE sum. Row-local math, so summing across shards equals the
    global loss sum bit-for-modulo-reduction-order."""
    x = gpt.apply_embeddings(params, cfg, input_ids, position_ids)
    x = gpt.apply_decoder_layers(
        params["layers"], cfg, x, mask, rng=rng, deterministic=rng is None,
    )
    if fused:
        from tpukit.ops.fused_head_ce import fused_head_ce
        from tpukit.ops.layers import layer_norm

        h = layer_norm(x, params["norm_out"]).astype(cfg.compute_dtype)
        loss_sum, count, _ = fused_head_ce(
            h.reshape(-1, h.shape[-1]),
            params["lm_head"]["kernel"],
            tgts.reshape(-1),
            cfg.vocab_size,
            with_accuracy=False,
        )
    else:
        logits = gpt.apply_head(params, cfg, x)
        loss_sum, count = cross_entropy_sum(logits, tgts)
    return loss_sum, count


def _n_elems(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _quant_rng(cfg, local_rng):
    """Stochastic-rounding key for the DP quantized grad psum (the one SR
    site outside a custom-vjp backward, so the per-step key make_step_fns
    threads can reach it): fold the step's dropout/comm key. Direct
    callers without a key fall back to round-to-nearest. The FSDP/EP SR
    sites derive their keys from the cotangent data instead
    (quant_comm._fallback_key — still step-varying, just not seed-keyed)."""
    if not cfg.quant_stochastic or local_rng is None:
        return None
    return jax.random.fold_in(local_rng, 0x5151)


def _quantized_dp_grads(strategy, params, cfg, batch, targets, rng):
    """DataParallel value_and_grad with the gradient psum hand-placed and
    compressed (--comm_dtype bf16/int8): the whole loss+backward runs
    inside shard_map over `data`, local grads are exact f32, and the ONLY
    lossy step is the wire — quant_comm.quantized_psum_tree flattens the
    grad tree into one payload and runs the EQuARX two-shot all-reduce
    (int8 reduce-scatter -> f32 accumulate -> int8 all-gather). The loss
    scalar and the global valid-token count psum in full precision.

    --grad_buckets N >= 1 (round 18) replaces the single payload with
    quant_comm.bucketed_psum_tree: N ~equal-byte buckets in layer-
    reversed order, one two-shot exchange each (f32 keeps the two-shot
    shape — the bucket collectives stay auditable and the f32 trajectory
    is bit-identical under any bucket count). Each bucket's exchange
    depends only on its own leaves' backward, so the remaining backward
    compute overlaps the wire — the hlolint `overlap` rule gates it."""
    mesh = strategy.mesh
    world = mesh.shape["data"]
    batch_spec = P("data", None)
    fused = strategy.fused_head and not _fused_head_disabled()

    def block(p, input_ids, position_ids, mask, tgts):
        local_rng = (
            jax.random.fold_in(rng, jax.lax.axis_index("data"))
            if rng is not None
            else None
        )
        gcount = jax.lax.psum(
            jnp.sum(tgts != IGNORE_INDEX).astype(jnp.float32), "data"
        )

        def local_loss(p):
            loss_sum, _ = _local_loss_sum(
                p, cfg, input_ids, position_ids, mask, tgts, local_rng, fused
            )
            return loss_sum / jnp.maximum(gcount, 1.0)

        val, grads = jax.value_and_grad(local_loss)(p)
        loss = jax.lax.psum(val, "data")
        if cfg.grad_buckets > 0:
            grads = quant_comm.bucketed_psum_tree(
                grads, "data", world, cfg.grad_buckets, cfg.comm_dtype,
                rng=_quant_rng(cfg, local_rng),
            )
        else:
            grads = quant_comm.quantized_psum_tree(
                grads, "data", world, cfg.comm_dtype,
                rng=_quant_rng(cfg, local_rng),
            )
        return loss, grads

    return shard_map(
        block,
        mesh=mesh,
        in_specs=(P(), batch_spec, batch_spec, batch_spec, batch_spec),
        out_specs=(P(), P()),
        check_vma=False,
    )(params, batch["input_ids"], batch["position_ids"], batch["mask"], targets)


def _quantized_fsdp_grads(strategy, params, cfg, batch, targets, rng):
    """FSDP value_and_grad with the gradient reduce-scatter hand-placed
    and compressed, params-at-use full precision ("grads-only first"):
    inside shard_map each sharded leaf gathers through
    quant_comm.all_gather_qgrad — a FULL-PRECISION lax.all_gather whose
    custom vjp compresses the cotangent through the quantized
    reduce-scatter, landing grads directly in the FSDP shard layout.
    Replicated (sub-threshold) leaves ride psum_grad: identity forward,
    full-precision grad psum.

    --grad_buckets N >= 1 (round 18): the sharded leaves partition into
    N ~equal-byte, layer-reversed buckets and each bucket gathers through
    ONE quant_comm.bucket_gather_qgrad — forward per-leaf full-precision
    gathers unchanged, backward ONE packed reduce-scatter a2a per BUCKET
    instead of one per leaf. The bucket vjp fires when its last
    (earliest-layer) cotangent lands, so each wire launch interleaves
    with the remaining backward. Replicated leaves stay on the f32 psum
    path regardless of bucketing (compressing or batching them buys
    noise, not bandwidth)."""
    mesh = strategy.mesh
    world = mesh.shape["data"]
    batch_spec = P("data", None)
    fused = strategy.fused_head and not _fused_head_disabled()
    leaves, treedef = jax.tree_util.tree_flatten(params)
    spec_list = [strategy.param_spec(l.shape) for l in leaves]
    spec_tree = jax.tree_util.tree_unflatten(treedef, spec_list)
    dim_list = [
        next((i for i, ax in enumerate(spec) if ax == "data"), None)
        for spec in spec_list
    ]
    buckets = []
    if cfg.grad_buckets > 0:
        sharded = {i for i, d in enumerate(dim_list) if d is not None}
        buckets = quant_comm.grad_bucket_plan(
            params, cfg.grad_buckets, include=sharded
        )

    def block(p_shards, input_ids, position_ids, mask, tgts):
        local_rng = (
            jax.random.fold_in(rng, jax.lax.axis_index("data"))
            if rng is not None
            else None
        )
        gcount = jax.lax.psum(
            jnp.sum(tgts != IGNORE_INDEX).astype(jnp.float32), "data"
        )

        def local_loss(ps):
            flat, td = jax.tree_util.tree_flatten(ps)
            full = [None] * len(flat)
            for i, (leaf, dim) in enumerate(zip(flat, dim_list)):
                if dim is None:
                    full[i] = quant_comm.psum_grad(leaf, "data")
            if buckets:
                for idxs in buckets:
                    gathered = quant_comm.bucket_gather_qgrad(
                        tuple(flat[i] for i in idxs), "data", world,
                        tuple(dim_list[i] for i in idxs), cfg.comm_dtype,
                        quant_comm.DEFAULT_BLOCK, cfg.quant_stochastic,
                    )
                    for i, g in zip(idxs, gathered):
                        full[i] = g
            else:
                for i, (leaf, dim) in enumerate(zip(flat, dim_list)):
                    if dim is not None:
                        full[i] = quant_comm.all_gather_qgrad(
                            leaf, "data", world, dim, cfg.comm_dtype,
                            quant_comm.DEFAULT_BLOCK, cfg.quant_stochastic,
                        )
            loss_sum, _ = _local_loss_sum(
                td.unflatten(full), cfg, input_ids, position_ids, mask,
                tgts, local_rng, fused,
            )
            return loss_sum / jnp.maximum(gcount, 1.0)

        val, grads = jax.value_and_grad(local_loss)(p_shards)
        return jax.lax.psum(val, "data"), grads

    return shard_map(
        block,
        mesh=mesh,
        in_specs=(spec_tree, batch_spec, batch_spec, batch_spec, batch_spec),
        out_specs=(P(), spec_tree),
        check_vma=False,
    )(params, batch["input_ids"], batch["position_ids"], batch["mask"], targets)


class Strategy:
    """Base: single-device (twin of main-single.py: plain `.to(device)`,
    main-single.py:21,33 — here, a trivial 1-device mesh)."""

    name = "single"
    # Compute the loss through the fused head+CE kernel (no [B*S, V] logits
    # buffer — ops/fused_head_ce.py). TensorParallel turns this off: its
    # vocab-sharded head wants the GSPMD matmul path. TPUKIT_FUSED_HEAD=0
    # (checked at use time, never forces the kernel ON) is the operational
    # escape hatch back to the unfused XLA path.
    fused_head = True
    # HLO collective kinds this strategy is EXPECTED to emit in its compiled
    # train step (tpukit/obs/xla.py COLLECTIVE_OPS names). Telemetry
    # (`fit()`'s kind="xla" record, tools/report.py) reports the measured
    # per-kind comm bytes from the compiled module and flags kinds outside
    # this set — a sharding regression (say, FSDP silently all-gathering
    # the whole state per step) shows up as a surprise entry, not a hunch.
    comm_ops: tuple[str, ...] = ()
    # Strategies with hand-wired quantized collectives (--comm_dtype,
    # round 12: ops/quant_comm.py) set this True. Everything else rejects
    # a non-f32 comm_dtype at validate_config — a flag that silently does
    # nothing would read as a 4x win that never happened.
    quantized_comm = False

    def __init__(self, mesh: Mesh | None = None):
        self.mesh = mesh if mesh is not None else mesh_lib.create_mesh(None)

    # -- sharding rules ----------------------------------------------------

    def param_spec(self, shape: tuple[int, ...]) -> P:
        return P()

    def batch_spec(self) -> P:
        return P()

    def state_sharding(self, state_shapes):
        """The train state's placement on this strategy's mesh. Besides
        feeding the jitted step's in/out shardings, this tree is the
        TARGET spec of an elastic restore (tpukit/reshard.py): a
        checkpoint saved under ANY strategy/world reshards onto whatever
        this returns for the current mesh — which is why the rules here
        must be pure functions of (shape, mesh), never of the saving
        world (FSDP's min_shard_size threshold and divisibility checks
        re-derive per world for free under that discipline)."""
        return _sharding_tree(self.mesh, self.param_spec, state_shapes)

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec())

    def to_compute(self, state):
        """Hook run at the top of each jitted step: move offloaded state into
        device memory. Identity unless a strategy offloads (FSDP
        cpu_offload)."""
        return state

    def prepare_params(self, params, cfg: gpt.GPTConfig):
        """Hook run once at init: adapt freshly-initialized parameters to the
        strategy's layout. Identity for every strategy except Pipeline, which
        pads the stacked layers to a stage multiple (see
        Pipeline.prepare_params)."""
        return params

    def host_batch_fn(self, cfg: gpt.GPTConfig):
        """Optional host-side per-batch transform, applied by the trainer to
        the numpy batch BEFORE device placement. None (default) for every
        strategy except ContextParallel, whose zigzag sequence permutation
        would otherwise be a cross-shard reshard collective inside every
        jitted step (ADVICE r4)."""
        return None

    def validate_config(self, cfg: gpt.GPTConfig) -> None:
        """Raise a clear error before any tracing when the model shape cannot
        map onto this strategy's mesh (divisibility constraints)."""
        self._validate_comm_dtype(cfg)

    def _validate_comm_dtype(self, cfg: gpt.GPTConfig) -> None:
        """The --comm_dtype / --grad_buckets gate every validate_config
        override must also call: a quantized comm dtype or a bucket
        schedule on a strategy without hand-wired collectives is a no-op
        masquerading as a wire win."""
        if cfg.comm_dtype != "f32" and not self.quantized_comm:
            raise ValueError(
                f"--comm_dtype {cfg.comm_dtype}: the {self.name} strategy "
                f"has no wired quantized collectives — supported on ddp "
                f"(grad all-reduce), fsdp (grad reduce-scatter) and ep "
                f"(a2a dispatch payload)"
            )
        if cfg.grad_buckets > 0 and not self.quantized_comm:
            raise ValueError(
                f"--grad_buckets {cfg.grad_buckets}: the {self.name} "
                f"strategy has no hand-placed grad wire to bucket — "
                f"supported on ddp (bucketed two-shot all-reduce), fsdp "
                f"(bucketed grad reduce-scatter) and ep (the per-layer "
                f"a2a pairs are already bucket-granular)"
            )

    def _hand_placed(self, cfg: gpt.GPTConfig) -> bool:
        """True when a quantized-comm strategy's value_and_grad must run
        its hand-placed shard_map grad block instead of leaving the
        collectives to GSPMD: a quantized wire, or any bucket schedule
        (bucketed f32 keeps exact math but hand-places the exchanges so
        they stay auditable). ONE spelling — DDP and FSDP branching on
        different predicates here would silently run different
        schedules."""
        return cfg.comm_dtype != "f32" or cfg.grad_buckets > 0

    def grad_comm(self, cfg: gpt.GPTConfig, param_shapes,
                  backend: str | None = None) -> dict | None:
        """Closed-form expected {op: {count, bytes}} of THIS strategy's
        quantized gradient collectives for one train step, or None when
        nothing is compressed. The audit number fit()'s xla record, the
        multichip dryrun and tests compare against the compiled HLO —
        hand-compressing a collective means being able to predict its
        bytes (the round-10 dispatch-audit discipline, applied to grads).
        Round 16: consumed through `analysis.plan.train_comm_plan`, which
        folds this and `dispatch_comm` into one CommPlan the rule engine
        diffs (DESIGN.md §15) — new strategies declare here, the engine
        audits everywhere."""
        return None

    def overlap_comm(self, cfg: gpt.GPTConfig, param_shapes) -> dict | None:
        """Declared overlap expectation of this strategy's train step
        (round 18, ROADMAP #5): {op: K} meaning at least K collectives of
        that HLO kind must each have independent compute the scheduler
        can hide them behind — the promoted hlolint `overlap` rule gates
        it (analysis/rules.py). None when the schedule is serial (no
        bucket wire declared). Only bucketed worlds declare: a 1-bucket
        payload after the whole backward has nothing to overlap with,
        and claiming otherwise would make the gate a lie."""
        return None

    def comm_ops_for(self, cfg: gpt.GPTConfig) -> tuple[str, ...]:
        """The expected-collective-kinds set for THIS config — `comm_ops`
        unless the config reshapes the schedule (DP/FSDP under a quantized
        comm dtype replace their GSPMD grad collective with the packed
        a2a + all-gather pair). A pure function of cfg, never a mutation:
        one strategy instance must audit an f32 run correctly after
        validating an int8 config."""
        return self.comm_ops

    def inference_params(self, params, cfg: gpt.GPTConfig):
        """Params as the plain sequential `gpt.forward` expects them.
        Identity for every strategy whose training layout IS the natural
        layout; the interleaved pipeline (cfg.virtual_stages > 1) stores
        the layer stack chunk-permuted and overrides this to restore
        natural layer order before generation/decode (train.py's
        generate_samples calls it after replication)."""
        return params

    @property
    def batch_divisor(self) -> int:
        """Every global batch fed to this strategy must be a multiple of this.
        The loader pads the final batch by wrapping to satisfy it (torch
        `Pipe` handles uneven chunks internally; here the divisor is explicit
        so every step keeps one static, compiled shape)."""
        return self.mesh.shape.get("data", 1)

    def replicated(self):
        return NamedSharding(self.mesh, P())

    def kernel_shard(self, cfg: gpt.GPTConfig):
        """`(mesh, batch_axes, head_axes)` — how this strategy's GSPMD jit
        shards batch and attention heads, for the Pallas kernels' per-shard
        calls (pallas_attention.per_shard, fused_head_ce) — or None on one
        device. `loss_fn` injects it into the config at loss time, like
        ExpertParallel's `moe_mesh`; strategies that run the model inside
        their own shard_map never do."""
        if self.mesh.size == 1:
            return None
        spec = self.batch_spec()
        return (self.mesh, spec[0] if len(spec) else None, None)

    # -- loss --------------------------------------------------------------

    def loss_fn(
        self, params, cfg: gpt.GPTConfig, batch, targets,
        with_accuracy: bool = False, rng=None, aux_out: list | None = None,
    ):
        """Default forward + masked CE (+ masked accuracy for eval).

        Under a sharded batch this single jitted function IS the distributed
        step: the mean over the global batch is the twin of DDP's gradient
        all-reduce and of the explicit eval `dist.all_reduce(..., AVG)`
        (main-ddp.py:159-160) — GSPMD inserts the psum.

        `rng` is the per-step dropout key (None = deterministic, the eval
        path). Under GSPMD the global mask is generated once and sharded
        (threefry is partitionable), so dropout is consistent across DP/FSDP
        shards — the twin of torch dropout running under DDP.

        `aux_out` (MoE configs): list receiving the summed load-balance aux
        loss; value_and_grad passes it so training optimizes
        CE + moe_aux_weight * aux while eval metrics stay pure CE.

        The head + cross-entropy run through the fused Pallas kernel
        (ops/fused_head_ce.py) unless the strategy opts out: no logits
        buffer in HBM, which is both the long-context perf win and what
        lets batch sizes the unfused logits tensor would OOM.
        """
        shard = self.kernel_shard(cfg)
        cfg = cfg.replace(kernel_shard=shard)
        if self.fused_head and not _fused_head_disabled():
            from tpukit.ops.fused_head_ce import fused_head_ce

            h = gpt.forward_hidden(
                params, cfg, batch["input_ids"], batch["position_ids"],
                batch["mask"], rng=rng, deterministic=rng is None,
                aux_out=aux_out,
            )
            loss_sum, count, correct = fused_head_ce(
                h.reshape(-1, h.shape[-1]),
                params["lm_head"]["kernel"],
                targets.reshape(-1),
                cfg.vocab_size,
                with_accuracy=with_accuracy,
                shard=shard and shard[:2],  # [B*S] tokens follow the batch
            )
            denom = jnp.maximum(count, 1.0)
            return loss_sum / denom, correct / denom * 100.0
        logits = gpt.forward(
            params, cfg, batch["input_ids"], batch["position_ids"], batch["mask"],
            rng=rng, deterministic=rng is None, aux_out=aux_out,
        )
        loss = cross_entropy_loss(logits, targets)
        accuracy = masked_accuracy(logits, targets) if with_accuracy else jnp.float32(0)
        return loss, accuracy

    def value_and_grad(self, params, cfg: gpt.GPTConfig, batch, targets, rng=None):
        """Loss and parameter gradients for one global batch — the training
        half of the strategy contract (make_step_fns calls this). Default:
        autodiff over `loss_fn`. Schedules that must build their gradient
        explicitly (Pipeline1F1B's per-stage vjps) override it.

        MoE configs train on CE + moe_aux_weight * load-balance aux (the
        Switch objective); the RETURNED loss is the pure CE, so the train
        bar and eval report the same quantity."""

        if cfg.num_experts == 0:

            def loss_of(p):
                loss, _ = self.loss_fn(p, cfg, batch, targets, rng=rng)
                return loss

            return jax.value_and_grad(loss_of)(params)

        def loss_of_moe(p):
            aux_list: list = []
            loss, _ = self.loss_fn(
                p, cfg, batch, targets, rng=rng, aux_out=aux_list
            )
            total = loss
            for aux in aux_list:
                total = total + cfg.moe_aux_weight * aux
            return total, loss

        (_, loss), grads = jax.value_and_grad(loss_of_moe, has_aux=True)(params)
        return loss, grads

    def describe(self) -> str:
        return f"{self.name} over mesh {dict(zip(self.mesh.axis_names, self.mesh.devices.shape))}"


class SingleDevice(Strategy):
    name = "single"


class DataParallel(Strategy):
    """Twin of the DDP recipe's parallelism (main-ddp.py:55): batch sharded
    over `data`, params replicated. With the default comm_dtype the gradient
    psum is emitted by XLA from the replicated-param + sharded-batch specs;
    with --comm_dtype bf16/int8 (round 12) value_and_grad hand-places it as
    the EQuARX two-shot quantized all-reduce of ops/quant_comm.py instead —
    one packed all_to_all (the reduce-scatter phase) plus one packed
    all_gather carrying ~1/4 of the f32 bytes, f32 accumulation, loss and
    token-count psums untouched."""

    name = "ddp"
    comm_ops = ("all-reduce",)  # the grad psum
    quantized_comm = True

    def __init__(self, mesh: Mesh | None = None):
        self.mesh = mesh if mesh is not None else mesh_lib.create_mesh({"data": -1})

    def batch_spec(self) -> P:
        return P("data")

    def validate_config(self, cfg: gpt.GPTConfig) -> None:
        if (cfg.comm_dtype != "f32" or cfg.grad_buckets > 0) and cfg.num_experts > 0:
            raise ValueError(
                f"--comm_dtype {cfg.comm_dtype} / --grad_buckets "
                f"{cfg.grad_buckets} under DataParallel requires a dense "
                f"model: the MoE aux-loss statistics are not psummed by "
                f"the hand-placed grad block — use ExpertParallel "
                f"(main-moe.py) for MoE comm"
            )

    def comm_ops_for(self, cfg: gpt.GPTConfig) -> tuple[str, ...]:
        if self._hand_placed(cfg):
            # the hand-placed two-shot replaces the GSPMD grad all-reduce
            # with a packed a2a + all-gather; scalar loss/count psums keep
            # "all-reduce" in the expected set
            return ("all-gather", "all-reduce", "all-to-all")
        return self.comm_ops

    def value_and_grad(self, params, cfg: gpt.GPTConfig, batch, targets, rng=None):
        if not self._hand_placed(cfg):
            return super().value_and_grad(params, cfg, batch, targets, rng=rng)
        if cfg.num_experts > 0:
            raise ValueError(
                "--comm_dtype bf16/int8 / --grad_buckets under DataParallel "
                "requires a dense model (see DataParallel.validate_config)"
            )
        return _quantized_dp_grads(self, params, cfg, batch, targets, rng)

    def grad_comm(self, cfg: gpt.GPTConfig, param_shapes,
                  backend: str | None = None) -> dict | None:
        """Expected payload of the hand-placed grad wire. Serial
        (grad_buckets 0): the whole grad tree flattens into ONE two-shot
        exchange (quant_comm.expected_all_reduce — one packed a2a + one
        packed all-gather, [world, row] each). Bucketed: one two-shot
        pair per grad_bucket_plan bucket, priced at the bucket payload
        dtype (f32 included — the bucket schedule is always hand-placed
        and therefore always predicted)."""
        if cfg.grad_buckets > 0:
            buckets = quant_comm.grad_bucket_plan(param_shapes, cfg.grad_buckets)
            leaves = jax.tree_util.tree_leaves(param_shapes)
            sizes = [
                sum(_n_elems(leaves[i].shape) for i in idxs)
                for idxs in buckets
            ]
            return quant_comm.expected_bucketed_all_reduce(
                sizes, self.mesh.shape["data"], cfg.comm_dtype,
                backend=backend,
            )
        if cfg.comm_dtype == "f32":
            return None
        n = sum(
            _n_elems(l.shape) for l in jax.tree_util.tree_leaves(param_shapes)
        )
        return quant_comm.expected_all_reduce(
            n, self.mesh.shape["data"], cfg.comm_dtype, backend=backend
        )

    def overlap_comm(self, cfg: gpt.GPTConfig, param_shapes) -> dict | None:
        """The DDP bucket schedule's overlap declaration: every bucket's
        two-shot pair (its a2a AND its all-gather) must have independent
        compute scheduled around it — with B >= 2 buckets each exchange
        depends only on its own leaves' backward, so the rest of the
        sweep is free to hide the wire."""
        if cfg.grad_buckets < 2 or param_shapes is None:
            return None
        buckets = quant_comm.grad_bucket_plan(param_shapes, cfg.grad_buckets)
        if len(buckets) < 2:
            return None
        return {"all-to-all": len(buckets), "all-gather": len(buckets)}


class FSDP(Strategy):
    """Twin of the FSDP recipe (main-fsdp.py:60-69): ZeRO-3-style sharding of
    params, grads and optimizer state over the `data` axis, via GSPMD."""

    name = "fsdp"
    # param all-gather at use, grad reduce-scatter, small-tensor all-reduce
    comm_ops = ("all-gather", "reduce-scatter", "all-reduce")
    quantized_comm = True

    # Twin of size_based_auto_wrap_policy(min_num_params=100): tensors below
    # the threshold stay replicated (main-fsdp.py:62).
    def __init__(self, mesh: Mesh | None = None, min_shard_size: int = 100, cpu_offload: bool = False):
        self.mesh = mesh if mesh is not None else mesh_lib.create_mesh({"data": -1})
        self.min_shard_size = min_shard_size
        self.cpu_offload = cpu_offload
        if cpu_offload:
            self.name = "fsdp-offload"

    def validate_config(self, cfg: gpt.GPTConfig) -> None:
        if (cfg.comm_dtype != "f32" or cfg.grad_buckets > 0) and cfg.num_experts > 0:
            raise ValueError(
                f"--comm_dtype {cfg.comm_dtype} / --grad_buckets "
                f"{cfg.grad_buckets} under FSDP requires a dense model: "
                f"the MoE aux-loss statistics are not psummed by the "
                f"hand-placed grad block — use ExpertParallel "
                f"(main-moe.py) for MoE comm"
            )

    def comm_ops_for(self, cfg: gpt.GPTConfig) -> tuple[str, ...]:
        if self._hand_placed(cfg):
            # grads-only first: the grad reduce-scatter becomes a packed
            # a2a; forward param gathers stay full-precision all-gathers
            return ("all-gather", "all-reduce", "all-to-all")
        return self.comm_ops

    def value_and_grad(self, params, cfg: gpt.GPTConfig, batch, targets, rng=None):
        """Default (f32, no buckets): GSPMD autodiff — per-tensor
        all-gather at use, grad reduce-scatter, all inserted by the
        partitioner. bf16/int8 (round 12) or --grad_buckets (round 18):
        the hand-placed shard_map block of `_quantized_fsdp_grads` —
        gather-at-use stays FULL precision, the grad reduce-scatter
        compresses (and/or buckets) through ops/quant_comm.py."""
        if not self._hand_placed(cfg):
            return super().value_and_grad(params, cfg, batch, targets, rng=rng)
        if cfg.num_experts > 0:
            raise ValueError(
                "--comm_dtype bf16/int8 / --grad_buckets under FSDP "
                "requires a dense model (see FSDP.validate_config)"
            )
        return _quantized_fsdp_grads(self, params, cfg, batch, targets, rng)

    def _sharded_indices(self, param_shapes) -> tuple[list, set]:
        """(flat leaves, indices of leaves the param_spec shards over
        `data`) — the subset the bucket plan partitions."""
        leaves = jax.tree_util.tree_leaves(param_shapes)
        sharded = {
            i for i, leaf in enumerate(leaves)
            if any(ax == "data" for ax in self.param_spec(leaf.shape))
        }
        return leaves, sharded

    def grad_comm(self, cfg: gpt.GPTConfig, param_shapes,
                  backend: str | None = None) -> dict | None:
        """Expected payload of the hand-placed FSDP grad wire. Serial:
        one packed reduce-scatter a2a per SHARDED leaf (replicated
        sub-threshold leaves psum in f32 and are not audited). Bucketed:
        one packed a2a per grad_bucket_plan bucket over the sharded
        subset, priced at the bucket dtype (f32 included). Either way the
        full-precision forward param all-gathers (one per sharded leaf,
        f32 result = the gathered tensor) ride alongside."""
        if not self._hand_placed(cfg):
            return None
        world = self.mesh.shape["data"]
        leaves, sharded = self._sharded_indices(param_shapes)
        gather = {
            "count": len(sharded),
            # f32 param gather, full tensor result
            "bytes": sum(_n_elems(leaves[i].shape) * 4 for i in sharded),
        }
        if cfg.grad_buckets > 0:
            buckets = quant_comm.grad_bucket_plan(
                param_shapes, cfg.grad_buckets, include=sharded
            )
            sizes = [
                sum(_n_elems(leaves[i].shape) for i in idxs)
                for idxs in buckets
            ]
            exp = quant_comm.expected_bucketed_reduce_scatter(
                sizes, world, cfg.comm_dtype, backend=backend
            )
            if not exp:
                return None
            return {"all-to-all": exp["all-to-all"], "all-gather": gather}
        a2a = {"count": 0, "bytes": 0}
        for i in sorted(sharded):
            exp = quant_comm.expected_reduce_scatter(
                _n_elems(leaves[i].shape), world, cfg.comm_dtype,
                backend=backend,
            )
            if exp:
                a2a["count"] += exp["all-to-all"]["count"]
                a2a["bytes"] += exp["all-to-all"]["bytes"]
        if not a2a["count"]:
            return None
        return {"all-to-all": a2a, "all-gather": gather}

    def overlap_comm(self, cfg: gpt.GPTConfig, param_shapes) -> dict | None:
        """The FSDP bucket schedule's overlap declaration: every bucket's
        backward reduce-scatter a2a must have independent compute around
        it. Forward param gathers are at-use by design (serial on the
        critical path) and are NOT declared."""
        if cfg.grad_buckets < 2 or param_shapes is None:
            return None
        _, sharded = self._sharded_indices(param_shapes)
        buckets = quant_comm.grad_bucket_plan(
            param_shapes, cfg.grad_buckets, include=sharded
        )
        if len(buckets) < 2:
            return None
        return {"all-to-all": len(buckets)}

    def param_spec(self, shape: tuple[int, ...]) -> P:
        axis_size = self.mesh.shape["data"]
        size = 1
        for d in shape:
            size *= d
        if size < self.min_shard_size:
            return P()
        # shard the largest dimension divisible by the axis size
        candidates = [(d, i) for i, d in enumerate(shape) if d % axis_size == 0]
        if not candidates:
            return P()
        _, dim = max(candidates)
        spec = [None] * len(shape)
        spec[dim] = "data"
        return P(*spec)

    def state_sharding(self, state_shapes):
        shardings = _sharding_tree(self.mesh, self.param_spec, state_shapes)
        if self.cpu_offload:
            # Twin of CPUOffload(offload_params=True) (main-fsdp.py:68):
            # sharded state lives in host memory; XLA streams it in on use.
            # Host memory spaces are a TPU feature; on other backends the
            # flag degrades to plain FSDP with a warning (the reference's
            # CPUOffload is likewise CUDA-only).
            if self._offload_supported():
                shardings = jax.tree.map(
                    lambda s: s.with_memory_kind("pinned_host"), shardings
                )
            else:
                import warnings

                warnings.warn(
                    "--cpu_offload needs a TPU backend with host memory "
                    "spaces; running plain FSDP instead",
                    stacklevel=2,
                )
        return shardings

    def _offload_supported(self) -> bool:
        from tpukit.ops.pallas_attention import on_tpu_backend

        return on_tpu_backend()

    def to_compute(self, state):
        """Stream host-pinned state into device HBM at the top of the step
        (the XLA twin of FSDP's CPUOffload H2D param streaming,
        main-fsdp.py:68). The step's out_shardings put the updated state
        back in host memory."""
        if not (self.cpu_offload and self._offload_supported()):
            return state

        def put(leaf):
            sharding = NamedSharding(
                self.mesh, self.param_spec(leaf.shape), memory_kind="device"
            )
            return jax.device_put(leaf, sharding)

        return jax.tree.map(put, state)

    def batch_spec(self) -> P:
        return P("data")


class ContextParallel(Strategy):
    """Sequence/context parallelism via ring attention.

    The batch's *sequence* dimension shards over a `seq` mesh axis (optionally
    combined with a `data` axis for batch sharding). The whole forward runs
    inside shard_map: embeddings / norms / MLPs / head are token-local, and
    attention is the exact-causal ppermute ring of tpukit/ring_attention.py.
    Params are replicated; their gradient psum over the mesh falls out of the
    shard_map transpose. This axis has no reference counterpart — the
    cookbook caps sequence at 256 on one device (SURVEY §5) — and is the
    scale-out path for the long-context capability.
    """

    name = "cp"

    def __init__(
        self, mesh: Mesh | None = None, attention: str = "ring",
        host_permute: bool = False,
    ):
        """`attention` picks the sequence-parallel schedule:
        "ring" (default) — K/V ppermute hops, zigzag-balanced, works for
        any head count; "ulysses" — two all_to_alls re-partition to
        head-sharding and run full-sequence flash attention locally
        (needs heads % seq_shards == 0). See tpukit/ring_attention.py.

        `host_permute=True` declares that the CALLER applies the zigzag
        permutation host-side (via the fn `host_batch_fn` returns, as
        fit() does) and loss_fn must NOT re-permute in-jit — in-jit the
        same gather on the seq-sharded batch is a cross-shard reshard
        collective every step (ADVICE r4). With it set, every loss_fn
        call must receive host-permuted batches whenever zigzag is
        active."""
        self.mesh = mesh if mesh is not None else mesh_lib.create_mesh({"seq": -1})
        self.host_permute = host_permute
        if "seq" not in self.mesh.axis_names:
            raise ValueError("ContextParallel needs a 'seq' mesh axis")
        if attention not in ("ring", "ulysses"):
            raise ValueError(f"attention must be 'ring' or 'ulysses', got {attention!r}")
        self.attention = attention
        if attention == "ulysses":
            self.name = "cp-ulysses"
            # head re-partition round trips; grad psum over the mesh
            self.comm_ops = ("all-to-all", "all-reduce")
        else:
            # K/V ring hops; grad psum over the mesh
            self.comm_ops = ("collective-permute", "all-reduce")
        self.seq_size = self.mesh.shape["seq"]
        self.data_size = self.mesh.shape.get("data", 1)

    def batch_spec(self) -> P:
        data = "data" if "data" in self.mesh.axis_names else None
        return P(data, "seq")

    def validate_config(self, cfg: gpt.GPTConfig) -> None:
        self._validate_comm_dtype(cfg)
        if cfg.num_experts > 0:
            raise ValueError(
                "ContextParallel does not support MoE configs (the routed "
                "dispatch is token-global, the CP loss is seq-sharded) — "
                "use ExpertParallel (main-moe.py) for num_experts > 0"
            )
        # The model consumes sequence_length - 1 tokens after the LM shift
        # (prepare_batch, tpukit/batching.py).
        seq = cfg.max_position_embeddings - 1
        if seq % self.seq_size:
            raise ValueError(
                f"--sequence_length {cfg.max_position_embeddings}: the model "
                f"sequence {seq} must divide over {self.seq_size} sequence "
                f"shards; pick sequence_length = k*{self.seq_size} + 1"
            )
        if self.attention == "ulysses" and cfg.heads % self.seq_size:
            raise ValueError(
                f"ulysses attention re-partitions heads over the seq axis: "
                f"--heads {cfg.heads} must divide by {self.seq_size} "
                f"sequence shards (or use attention='ring')"
            )

    def _use_zigzag(self, seq_len: int) -> bool:
        """Zigzag layout (causal load balance — tpukit/ring_attention.py):
        permute the sequence so each shard holds one early + one late
        chunk; every per-token computation (embeddings, MLPs, CE sums) is
        permutation-invariant, so only the ring schedule needs to know.
        Falls back to the contiguous ring when 2*P doesn't divide S.
        The ulysses schedule keeps the contiguous layout (its local
        attention sees the full gathered sequence)."""
        return (
            self.attention == "ring"
            and seq_len % (2 * self.seq_size) == 0
            and self.seq_size > 1
        )

    def host_batch_fn(self, cfg: gpt.GPTConfig):
        """The zigzag permutation as a HOST-side numpy transform, applied
        before device placement (ADVICE r4: in-jit, the same gather on the
        globally seq-sharded batch makes GSPMD insert a cross-shard reshard
        of four token-sized arrays every train/eval step). Only returned
        when the strategy was constructed with `host_permute=True` — the
        explicit contract that loss_fn will receive pre-permuted batches."""
        seq_len = cfg.max_position_embeddings - 1  # model seq after the shift
        if not (self.host_permute and self._use_zigzag(seq_len)):
            return None
        from tpukit.ring_attention import zigzag_order

        order = zigzag_order(seq_len, self.seq_size)

        def permute(model_batch, targets):
            return (
                {key: val[:, order] for key, val in model_batch.items()},
                targets[:, order],
            )

        return permute

    def loss_fn(
        self, params, cfg: gpt.GPTConfig, batch, targets,
        with_accuracy: bool = False, rng=None, aux_out: list | None = None,
    ):
        # `aux_out` matches the base signature so direct
        # `strategy.value_and_grad` calls on an MoE config reach the curated
        # error below instead of an opaque TypeError (ADVICE r5 #1);
        # validate_config raises the same message for the fit() entry point.
        if cfg.num_experts > 0:
            raise ValueError(
                "ContextParallel does not support MoE configs (the routed "
                "dispatch is token-global, the CP loss is seq-sharded) — "
                "use ExpertParallel (main-moe.py) for num_experts > 0"
            )
        seq_len = batch["input_ids"].shape[1]
        if seq_len % self.seq_size:
            raise ValueError(
                f"sequence length {seq_len} must divide over {self.seq_size} "
                f"sequence shards (pick a dividing --sequence_length)"
            )
        use_zigzag = self._use_zigzag(seq_len)
        if use_zigzag and not self.host_permute:
            from tpukit.ring_attention import zigzag_order

            order = zigzag_order(seq_len, self.seq_size)
            batch = {key: val[:, order] for key, val in batch.items()}
            targets = targets[:, order]
        local_cfg = cfg.replace(
            attention_impl="ring" if self.attention == "ring" else "ulysses",
            ring_axis="seq",
            ring_layout="zigzag" if use_zigzag else "contiguous",
        )
        batch_spec = self.batch_spec()
        axes = tuple(self.mesh.axis_names)
        def local_loss(params, input_ids, position_ids, mask, tgts):
            if rng is None:
                local_rng = None
            else:
                # independent dropout mask per mesh position: fold the
                # shard's linearized mesh index into the step key
                lin = jnp.int32(0)
                for ax in axes:
                    lin = lin * self.mesh.shape[ax] + jax.lax.axis_index(ax)
                local_rng = jax.random.fold_in(rng, lin)
            x = gpt.apply_embeddings(params, local_cfg, input_ids, position_ids)
            x = gpt.apply_decoder_layers(
                params["layers"], local_cfg, x, mask,
                rng=local_rng, deterministic=local_rng is None,
            )
            if self.fused_head and not _fused_head_disabled():
                # Each shard's tokens through the fused head+CE kernel
                # (composes under shard_map Manual like the flash kernel):
                # no [B, S_local, V] logits tensor even per shard — CP is
                # the long-context strategy, where that buffer hurts most.
                from tpukit.ops.fused_head_ce import fused_head_ce
                from tpukit.ops.layers import layer_norm

                h = layer_norm(x, params["norm_out"]).astype(
                    local_cfg.compute_dtype
                )
                loss_sum, count, correct = fused_head_ce(
                    h.reshape(-1, h.shape[-1]),
                    params["lm_head"]["kernel"],
                    tgts.reshape(-1),
                    cfg.vocab_size,
                    with_accuracy=with_accuracy,
                )
            else:
                # custom-VJP sum: no f32 [B, S, V] tensor in either
                # direction (tpukit/ops/layers.py cross_entropy_sum)
                logits = gpt.apply_head(params, local_cfg, x)
                loss_sum, count = cross_entropy_sum(logits, tgts)
                if with_accuracy:
                    valid = tgts != -100
                    correct = jnp.sum(
                        jnp.where(valid, jnp.argmax(logits, axis=-1) == tgts, False)
                    ).astype(jnp.float32)
                else:
                    correct = jnp.float32(0)
            return (
                jax.lax.psum(loss_sum, axes),
                jax.lax.psum(count, axes),
                jax.lax.psum(correct, axes),
            )

        loss_sum, count, correct = shard_map(
            local_loss,
            mesh=self.mesh,
            in_specs=(P(), batch_spec, batch_spec, batch_spec, batch_spec),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )(params, batch["input_ids"], batch["position_ids"], batch["mask"], targets)

        denom = jnp.maximum(count, 1.0)
        return loss_sum / denom, correct / denom * 100.0


class TensorParallel(Strategy):
    """Megatron-style tensor parallelism, expressed purely as GSPMD shardings
    (SURVEY §2.4 lists TP as absent from the reference; on TPU it is a
    natural extension — no new code path, just different PartitionSpecs).

    Per-layer rule over a `model` mesh axis (optionally x `data` for batch
    sharding): q/k/v kernels and the ffn up-projection shard their *output*
    (head / hidden) dimension — column parallel; the attention out-projection
    and ffn down-projection shard their *input* dimension — row parallel, so
    XLA inserts exactly one all-reduce after attention and one after the MLP,
    the classic Megatron pattern. The lm_head shards its vocab dimension and
    the token embedding its vocab rows. Dimensions that do not divide the
    axis stay replicated. Optimizer state mirrors the parameter shardings.
    """

    name = "tp"
    fused_head = False  # the vocab-sharded head wants the GSPMD matmul path
    comm_ops = ("all-reduce",)  # post-attention + post-MLP Megatron pair

    def __init__(self, mesh: Mesh | None = None):
        self.mesh = mesh if mesh is not None else mesh_lib.create_mesh({"model": -1})
        if "model" not in self.mesh.axis_names:
            raise ValueError("TensorParallel needs a 'model' mesh axis")
        self.model_size = self.mesh.shape["model"]

    def batch_spec(self) -> P:
        return P("data") if "data" in self.mesh.axis_names else P()

    def kernel_shard(self, cfg: gpt.GPTConfig):
        shard = super().kernel_shard(cfg)
        if shard is None or cfg.heads % self.model_size:
            return shard  # undividable heads replicate, like their kernels
        # the column-parallel q/k/v leave attention heads sharded over `model`
        return (*shard[:2], "model")

    def loss_fn(
        self, params, cfg: gpt.GPTConfig, batch, targets,
        with_accuracy: bool = False, rng=None, aux_out: list | None = None,
    ):
        # Same aux_out contract as the base class so MoE configs fail with
        # the curated error from any entry point (ADVICE r5 #1).
        if cfg.num_experts > 0:
            raise ValueError(
                "TensorParallel does not support MoE configs (the Megatron "
                "column/row rules assume dense FFN kernels) — use "
                "ExpertParallel (main-moe.py) for num_experts > 0"
            )
        # The fused qkv matmul would concatenate kernels along their sharded
        # (column) axis, forcing a weight re-layout every step — keep the
        # three Megatron column-parallel matmuls instead.
        return super().loss_fn(
            params, cfg.replace(fuse_qkv=False), batch, targets, with_accuracy, rng
        )

    def _spec_for(self, names: tuple[str, ...], shape: tuple[int, ...]) -> P:
        def shard(dim: int) -> P:
            if shape[dim] % self.model_size:
                return P()  # undividable -> replicate
            spec = [None] * len(shape)
            spec[dim] = "model"
            return P(*spec)

        path = "/".join(names)
        if "attn" in names and names[-1] == "kernel":
            if any(k in names for k in ("q", "k", "v")):
                return shard(len(shape) - 1)  # column parallel
            if "out" in names:
                return shard(len(shape) - 2)  # row parallel
        if "attn" in names and names[-1] == "bias" and any(
            k in names for k in ("q", "k", "v")
        ):
            return shard(len(shape) - 1)
        if "ffn" in names:
            if "up" in names:
                return shard(len(shape) - 1)  # column (kernel & bias)
            if "down" in names and names[-1] == "kernel":
                return shard(len(shape) - 2)  # row
        if "lm_head" in names and names[-1] == "kernel":
            return shard(len(shape) - 1)
        if "token" in names:
            return shard(0)  # vocab rows
        del path
        return P()

    def state_sharding(self, state_shapes):
        def spec(path, leaf):
            names = tuple(
                k.key for k in path if isinstance(k, jax.tree_util.DictKey)
            )
            return NamedSharding(self.mesh, self._spec_for(names, leaf.shape))

        return jax.tree_util.tree_map_with_path(spec, state_shapes)

    def validate_config(self, cfg: gpt.GPTConfig) -> None:
        self._validate_comm_dtype(cfg)
        if cfg.num_experts > 0:
            raise ValueError(
                "TensorParallel does not support MoE configs (the Megatron "
                "column/row rules assume dense FFN kernels) — use "
                "ExpertParallel (main-moe.py) for num_experts > 0"
            )


class ExpertParallel(Strategy):
    """Expert parallelism for MoE configs (beyond-reference: the cookbook
    has neither MoE nor EP — SURVEY §2.4 marks the row "not required").

    Layout on a `(data, expert)` mesh: batch rows shard over BOTH axes,
    each expert-bank leaf (`ffn/experts/*`, leading axes `[layers,
    num_experts, ...]`) shards its EXPERT axis over `expert`, and — round
    10 — the dense trunk (embeddings, attention, norms, router, lm_head)
    plus its Adam moments shards FSDP-style over the whole `(data,
    expert)` world (same `min_shard_size` threshold as the FSDP strategy;
    tensors below it stay replicated; dim choice and the once-per-step
    trunk gather in `to_compute` are documented at `_spec_for` /
    `to_compute` — routing is discrete, so the trunk forward must stay
    bit-exact). Round-5 EP replicated the whole trunk on every device,
    which made trunk memory — 3x trunk params with Adam — the EP scaling
    ceiling.

    The token exchange depends on `dispatch`:

      - "a2a" (default): ExpertParallel injects `moe_dispatch="a2a"` +
        this mesh into the config at loss time, and the MoE FFN runs the
        explicit shard_map dataflow of tpukit/ops/moe_dispatch.py — local
        rows pack into `[E, B_local, C, D]` capacity buffers and move
        through a hand-placed `lax.all_to_all` pair over `expert`, forward
        AND backward (the formulation is its own transpose). This is the
        token all_to_all GPU MoE frameworks hand-write with NCCL, actually
        placed by hand.

      - "pallas": the "a2a" exchange with the local expert FFN computed by
        the fused grouped-expert GEMM of tpukit/ops/moe_gemm.py instead of
        the batched capacity einsums — the collectives (and the byte
        audit) are byte-for-byte the a2a path's; only the on-device FFN
        spelling changes. Meshless callers of moe_dispatch="pallas" get
        the dropless sorted dataflow instead; under EP the exchange's
        static per-peer payloads make capacity buffers structural.

      - "xla": the round-5 behavior — global dispatch/combine einsums with
        partitioning left to GSPMD. The FORWARD partitions into
        all_to_all-shaped collectives, but the BACKWARD of the dispatch
        einsum (`jvp(bsec,bsd->ebcd)/transpose`) does not: the round-5
        multichip dryrun log (MULTICHIP_r05.json) is full of
        `[SPMD] Involuntary full rematerialization` warnings there — GSPMD
        resolves the `(data, expert)` resharding by REPLICATING the tensor
        and re-partitioning it, exactly the traffic EP exists to avoid.
        Kept as the comparison/fallback spelling; the a2a path's step is
        asserted warning-free and all_to_all-only in tests and the dryrun.

    Gradient flow falls out of the specs either way: expert grads reduce
    over `data`, trunk grads reduce-scatter over `data` (FSDP) and psum
    over `expert`. Optimizer state mirrors the parameter placement, so a
    device holds only its experts' and its trunk shard's Adam moments.
    """

    name = "ep"
    # the a2a/pallas dispatch payload quantizes (--comm_dtype int8: packed
    # block-scaled buffers through the same all_to_all schedule); trunk
    # FSDP comm stays full precision — dispatch payload first
    quantized_comm = True

    def __init__(
        self, mesh: Mesh | None = None, dispatch: str = "a2a",
        min_shard_size: int = 100,
    ):
        self.mesh = mesh if mesh is not None else mesh_lib.create_mesh({"expert": -1})
        if "expert" not in self.mesh.axis_names:
            raise ValueError("ExpertParallel needs an 'expert' mesh axis")
        if dispatch not in ("xla", "a2a", "pallas"):
            raise ValueError(
                f"dispatch must be 'xla', 'a2a' or 'pallas', got {dispatch!r}"
            )
        self.dispatch = dispatch
        self.min_shard_size = min_shard_size
        self.expert_size = self.mesh.shape["expert"]
        self.data_size = self.mesh.shape.get("data", 1)
        # expected HLO collectives (obs/xla telemetry): token dispatch/
        # combine round trips when experts actually span devices; trunk
        # FSDP all-gather/reduce-scatter when the data axis is real; grad
        # psum for whatever stays replicated.
        ops = {"all-reduce"}
        if self.expert_size > 1:
            ops.add("all-to-all")
        if self.data_size * self.expert_size > 1:
            # trunk FSDP: gather at use, scatter the grads; GSPMD also
            # moves small trunk reshards with collective-permutes
            ops.update({"all-gather", "reduce-scatter", "collective-permute"})
        self.comm_ops = tuple(sorted(ops))

    def batch_spec(self) -> P:
        axes = tuple(a for a in ("data", "expert") if a in self.mesh.axis_names)
        return P(axes)

    @property
    def batch_divisor(self) -> int:
        return self.data_size * self.expert_size

    def validate_config(self, cfg: gpt.GPTConfig) -> None:
        if cfg.num_experts <= 0:
            raise ValueError(
                "ExpertParallel requires an MoE config: pass --num_experts N "
                "(N > 0); dense models belong on the other strategies"
            )
        if cfg.num_experts % self.expert_size:
            raise ValueError(
                f"--num_experts {cfg.num_experts} must divide over the "
                f"{self.expert_size}-way expert mesh axis"
            )
        if cfg.comm_dtype != "f32" and self.dispatch == "xla":
            raise ValueError(
                f"--comm_dtype {cfg.comm_dtype} under ExpertParallel needs "
                f"the hand-placed exchange: use --moe_dispatch a2a or "
                f"pallas (the xla dispatch leaves its collectives to GSPMD, "
                f"which cannot carry the packed int8 payload)"
            )
        if cfg.grad_buckets > 0 and self.dispatch == "xla":
            raise ValueError(
                f"--grad_buckets {cfg.grad_buckets} under ExpertParallel "
                f"needs the hand-placed exchange: use --moe_dispatch a2a "
                f"or pallas (the xla dispatch leaves its collectives to "
                f"GSPMD — there is no hand-placed schedule to declare "
                f"overlap for)"
            )

    def to_compute(self, tree):
        """Gather the sharded dense trunk ONCE at the top of each jitted
        step (GSPMD all-gather from the sharding constraint), leaving the
        expert bank and the whole optimizer state sharded.

        This is the deliberate EPxFSDP numerics choice: if trunk weights
        stay sharded through the forward, GSPMD computes their matmuls as
        partial sums + all-reduce, and those reduction-order ulps flip
        discrete top-k ROUTING decisions — a dense model absorbs ulps, a
        router amplifies them into different experts (measured: ~3.5e-3
        first-step loss drift on the parity fixture). Gathering up front
        makes the trunk forward the bit-exact DDP computation, so EP
        parity holds at the dense tolerance, while the at-rest state — the
        memory ceiling round 5 hit: params + BOTH Adam moments, 3x trunk
        bytes replicated on every device — shrinks by the mesh size. The
        moments never gather; only trunk params pay one transient
        replicated copy per step, the standard ZeRO-3 gather-at-use
        trade."""
        if self.data_size * self.expert_size <= 1:
            return tree
        repl = NamedSharding(self.mesh, P())
        is_state = hasattr(tree, "params")
        params = tree.params if is_state else tree

        def gather(path, leaf):
            names = tuple(
                k.key for k in path if isinstance(k, jax.tree_util.DictKey)
            )
            if "experts" in names:
                return leaf
            return jax.lax.with_sharding_constraint(leaf, repl)

        params = jax.tree_util.tree_map_with_path(gather, params)
        return tree.replace(params=params) if is_state else params

    def _dispatch_cfg(self, cfg: gpt.GPTConfig) -> gpt.GPTConfig:
        """Config the loss actually runs with: the a2a/pallas dispatch impl
        + this mesh injected for MoE configs. Loss-time only — checkpoints,
        decode and the plain model surface never carry a mesh in their
        config."""
        if cfg.num_experts <= 0 or self.dispatch == "xla":
            return cfg
        return cfg.replace(moe_dispatch=self.dispatch, moe_mesh=self.mesh)

    def loss_fn(
        self, params, cfg: gpt.GPTConfig, batch, targets,
        with_accuracy: bool = False, rng=None, aux_out: list | None = None,
    ):
        return super().loss_fn(
            params, self._dispatch_cfg(cfg), batch, targets,
            with_accuracy=with_accuracy, rng=rng, aux_out=aux_out,
        )

    def dispatch_comm(self, cfg: gpt.GPTConfig, global_batch: int,
                      seq: int, backend: str | None = None) -> dict | None:
        """Expected per-device all-to-all payload for one step of the a2a
        or pallas dispatch (tpukit/ops/moe_dispatch.expected_a2a — the
        pallas dispatch rides the identical exchange, so the same closed
        form audits both) — the audit number fit()'s xla record and
        bench.py's moe_ep_comm probe compare against the compiled HLO.
        None for the xla dispatch (GSPMD's choices are measured, not
        predicted) and for dense configs. `backend` makes the byte
        expectation dtype-aware (XLA:CPU upcasts bf16 payloads to f32 on
        the wire) so the audit is exact on every backend; None keeps the
        nominal accelerator sizes."""
        if self.dispatch == "xla" or cfg.num_experts <= 0:
            return None
        from tpukit.ops.moe_dispatch import expected_a2a

        return expected_a2a(
            cfg, self.data_size, self.expert_size, global_batch, seq,
            backend=backend,
        )

    def overlap_comm(self, cfg: gpt.GPTConfig, param_shapes) -> dict | None:
        """EP's grad wire is already bucket-granular: the a2a exchange is
        hand-placed PER LAYER (dispatch + combine, forward and backward —
        4L a2as per train step), so --grad_buckets under EP changes no
        dataflow; any value >= 1 DECLARES the overlap audit instead. The
        declaration covers the 2L backward hops: each backward a2a has
        the other layers' weight-grad accumulation independent of it (the
        dW branches neither feed nor consume another layer's exchange),
        which is the compute the scheduler hides the wire behind. The
        forward chain is honestly serial (layer i+1's tokens need layer
        i's combine) and is not declared."""
        if cfg.grad_buckets < 1 or self.expert_size <= 1:
            return None
        if cfg.num_experts <= 0 or self.dispatch == "xla":
            return None
        return {"all-to-all": 2 * cfg.num_layers}

    def _spec_for(self, names: tuple[str, ...], shape: tuple[int, ...]) -> P:
        if "experts" in names:
            # stacked layout [num_layers, num_experts, ...]: expert axis 1
            spec = [None] * len(shape)
            spec[1] = "expert"
            return P(*spec)
        # Dense trunk: FSDP-style over the WHOLE (data x expert) world, with
        # the FSDP strategy's min-size threshold (norms/biases stay
        # replicated). Two deliberate differences from the dense FSDP rule,
        # both learned the hard way on the parity fixture:
        #   - never shard a kernel's -2 dim: that is the forward CONTRACTION
        #     dim of every trunk matmul, and GSPMD computes a
        #     contraction-sharded matmul as partial sums + all-reduce whose
        #     reduction-order ulps flip discrete top-k ROUTING decisions (a
        #     dense model absorbs ulps; a router amplifies them into
        #     different experts);
        #   - embedding TABLES shard their row (vocab/position) dim — rows
        #     are gathered by id, never contracted, and a feature-sharded
        #     table makes the take() backward's scatter-add reshard through
        #     an extra GSPMD all-to-all that would pollute the hand-placed
        #     dispatch traffic the comm audit counts.
        # Sharding the full world (not just `data`) both maximizes the
        # memory win and avoids the partial-mesh `last_tile_dim_replicate`
        # shardings that the round-5 log showed GSPMD resharding by
        # involuntary full rematerialization.
        world = self.data_size * self.expert_size
        if world <= 1:
            return P()
        size = 1
        for d in shape:
            size *= d
        if size < self.min_shard_size:
            return P()
        axes = tuple(a for a in ("data", "expert") if a in self.mesh.axis_names)
        if "embeddings" in names:
            # rows or nothing: an undividable table (e.g. a position table
            # at a +1 sequence length) stays replicated rather than
            # feature-sharded — the feature-sharded fallback would buy a
            # few KB and cost a scatter-add all-to-all in the take()
            # backward, polluting the hand-placed dispatch audit
            dim = 0 if shape[0] % world == 0 else None
        else:
            candidates = [
                i for i, d in enumerate(shape)
                if d % world == 0
                and not (len(shape) >= 2 and i == len(shape) - 2)
            ]
            dim = candidates[-1] if candidates else None
        if dim is None:
            return P()
        spec = [None] * len(shape)
        spec[dim] = axes
        return P(*spec)

    def state_sharding(self, state_shapes):
        def spec(path, leaf):
            names = tuple(
                k.key for k in path if isinstance(k, jax.tree_util.DictKey)
            )
            return NamedSharding(self.mesh, self._spec_for(names, leaf.shape))

        return jax.tree_util.tree_map_with_path(spec, state_shapes)
