"""Pipeline parallelism: a GPipe schedule over a `stage` mesh axis.

TPU-native re-design of the reference's pipeline recipe (main-pipe.py, which
uses the deprecated torch `Pipe` over an `nn.Sequential` of per-GPU stages
with TensorPipe RPC, main-pipe.py:21-28,75-83). Here there is no RPC layer
and no wrapper modules: the decoder's stacked layer parameters are sharded
along their leading `num_layers` axis over the `stage` mesh axis, and a
`shard_map` runs the classic GPipe micro-batch schedule with
`jax.lax.ppermute` (XLA collective-permute over ICI) moving activations
stage-to-stage. Autodiff through `ppermute`/`scan` gives the pipelined
backward for free — the capability torch `Pipe` implements by hand.

Faithful structure (intent of main-pipe.py:52-83, which has syntax errors —
SURVEY §2.9 #3-5):
  - embeddings are applied on stage 0 and the norm+lm_head on the last stage
    (stage layout of main-pipe.py:53-55,67-68,75-77);
  - the padding mask (and here, the targets) are threaded through the
    pipeline alongside the activations — the twin of the `(x, mask)` tuple
    threading every reference stage performs (main-pipe.py:35-37,43-50);
  - the number of micro-batches defaults to the number of stages
    (`chunks=num_stages`, main-pipe.py:83,93).

Uneven layer counts (intent of main-pipe.py:63-68, VERDICT r2 #5): any
`num_layers >= 1` trains on any stage count. The stacked layer parameters
are padded to `ceil(L/S)*S` with all-zero identity layers (zero projections
make `x + attn(...) + ffn(...) == x` exactly), appended at the end so real
layers keep their order; the schedule gates padded slots off with a `where`
on the residual stream, so padded parameters receive zero gradient and the
loss matches the unpadded single-device model exactly. Padding happens at
init via `prepare_params` (wired through `create_train_state`); checkpoints
of an uneven config therefore carry the padded layer axis and restore into
layouts with the same padded count.

Memory placement (VERDICT r2 #3): the token embedding table and the lm_head
kernel shard their VOCAB dimension over the `stage` axis (and their Adam
state follows, via `state_sharding`), so no device holds a full table — the
reference's stage layout (embeddings on the first GPU, head on the last,
main-pipe.py:53-55,75-77) achieved as sharding rather than placement.
Compute stays role-specific: stage 0 ingests through a distributed lookup
(each stage contributes its vocab slice, one exact psum), the last stage's
activations feed a Megatron-style vocab-parallel head + CE
(`ops/layers.py vocab_parallel_ce`) in which every stage owns V/S logit
columns and no full-vocab tensor ever materializes. Falls back to
replicated embeddings/head when the padded vocab does not divide the stage
count (the default 128-multiple padding divides any power-of-two count).

Loss is computed as a (sum, count) pair and `psum`-broadcast, so the
returned loss equals the non-pipelined global mean exactly (twin of
main-pipe.py:162-165).

The same shard_map serves the 2-D pipeline x data hybrid (`main-pipe-ddp.py`,
a stub in the reference — SURVEY §2.4): with a `(data, stage)` mesh the
micro-batch dimension is sharded over `data` and layer params are replicated
across it; GSPMD adds the data-axis gradient psum. That recipe is exactly
"the pipeline strategy with a second mesh axis".
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from tpukit import mesh as mesh_lib
from tpukit.model import gpt
from tpukit.ops.layers import (
    cross_entropy_sum,
    layer_norm,
    linear,
    psum_bcast,
    vocab_parallel_ce,
)
from tpukit.pipeline_schedule import cached_schedule
from tpukit.shardings import Strategy


def _vocab_slice_ce(norm_p, lm_kernel, y, targets, offset, v_local, cfg):
    """Vocab-parallel head: layer_norm -> this stage's `v_local` logit
    columns -> pad-column -1e9 mask -> collective CE over `stage`. The ONE
    definition both pipeline schedules differentiate (GPipe via autodiff,
    1F1B via an explicit jax.vjp); returns ((loss_sum, count), local_logits)
    — the logits so the eval path can compute the global argmax accuracy."""
    h = layer_norm(y, norm_p).astype(cfg.compute_dtype)
    local_logits = linear(h, {"kernel": lm_kernel}, cfg.compute_dtype)
    col = offset + jax.lax.broadcasted_iota(jnp.int32, (v_local,), 0)
    local_logits = jnp.where(
        col < cfg.vocab_size, local_logits,
        jnp.asarray(-1e9, local_logits.dtype),
    )
    return vocab_parallel_ce(local_logits, targets, offset, "stage"), local_logits


def _is_layers_path(path) -> bool:
    return any(
        isinstance(k, jax.tree_util.DictKey) and k.key == "layers" for k in path
    )


def _path_names(path) -> tuple:
    return tuple(k.key for k in path if isinstance(k, jax.tree_util.DictKey))


class Pipeline(Strategy):
    """GPipe pipeline strategy. Use mesh axes `("stage",)` or
    `("data", "stage")` for the DDP hybrid."""

    name = "pipe"
    # activation/cotangent hops between stages; the final loss/grad psums
    # (GSPMD may also emit all-reduce for the data-hybrid grad sum)
    comm_ops = ("collective-permute", "all-reduce")
    # Interleaved virtual stages (cfg.virtual_stages > 1) need a schedule
    # whose tick machine understands non-contiguous chunk ownership; the
    # autodiffed GPipe scan runs one contiguous block per stage only.
    supports_interleave = False

    def __init__(
        self,
        mesh: Mesh | None = None,
        num_microbatches: int | str | None = None,
        moe_dispatch: str | None = None,
    ):
        self.mesh = mesh if mesh is not None else mesh_lib.create_mesh({"stage": -1})
        if "stage" not in self.mesh.axis_names:
            raise ValueError("Pipeline strategy needs a 'stage' mesh axis")
        self.num_stages = self.mesh.shape["stage"]
        # None -> chunks = num_stages, the reference twin (main-pipe.py:83,93).
        # "4x"-style multipliers scale with the stage count: the GPipe bubble
        # is (S-1)/(M+S-1), so M = 4S cuts it from ~43% to ~16% at S=4 —
        # the recipes default to 4x (documented divergence; --microbatches
        # restores any count including the reference's).
        if isinstance(num_microbatches, str):
            if not num_microbatches.endswith("x"):
                raise ValueError(
                    f"num_microbatches: int, None, or '<k>x', got {num_microbatches!r}"
                )
            self.num_microbatches = int(num_microbatches[:-1]) * self.num_stages
        else:
            self.num_microbatches = num_microbatches or self.num_stages
        if self.num_microbatches < 1:
            raise ValueError(
                f"num_microbatches must be positive, got {self.num_microbatches} "
                f"(from {num_microbatches!r})"
            )
        self.data_size = self.mesh.shape.get("data", 1)
        # Expert dispatch override injected into cfg at loss time (the
        # ExpertParallel pattern): None defers to cfg.moe_dispatch. Only
        # the meshless "pallas" dataflow composes with the pipeline —
        # _check_moe enforces that at every entry point.
        self.moe_dispatch = moe_dispatch

    # -- shardings ---------------------------------------------------------

    @property
    def batch_divisor(self) -> int:
        # loss_fn splits the global batch into num_microbatches, each sharded
        # over the data axis.
        return self.num_microbatches * self.data_size

    def padded_layers(self, num_layers: int, virtual_stages: int = 1) -> int:
        """Stacked-layer count after padding to a chunk-grid multiple:
        `ceil(L / (S*V)) * S * V`, so every one of the S*V chunks holds the
        same per-chunk layer count (V=1 recovers the old stage multiple)."""
        blocks = self.num_stages * virtual_stages
        return -(-num_layers // blocks) * blocks

    def _check_moe(self, cfg: gpt.GPTConfig) -> None:
        """The curated MoE gate — raised from validate_config (the fit()
        entry point) AND from loss_fn/value_and_grad, so direct strategy
        calls fail just as loudly (ADVICE r5 #1). Round 22: the meshless
        dropless "pallas" dispatch is collective-free, so it composes with
        the pipeline's shard_map (each stage's chunk runs its MoE FFNs on
        whatever micro-batch it holds); the buffer dispatches stay rejected
        BY NAME — "xla"/"a2a" shard tokens over an 'expert' mesh axis the
        pipeline meshes do not carry."""
        if cfg.num_experts == 0:
            return
        dispatch = self.moe_dispatch or cfg.moe_dispatch
        if dispatch != "pallas":
            raise ValueError(
                f"the pipeline schedules support MoE only through the "
                f"meshless dropless dispatch — pass --moe_dispatch pallas "
                f"(got moe_dispatch={dispatch!r}: 'xla'/'a2a' need an "
                f"'expert' mesh axis the pipeline mesh does not carry) — "
                f"or use ExpertParallel (main-moe.py), optionally with a "
                f"data axis"
            )

    def _moe_cfg(self, cfg: gpt.GPTConfig) -> gpt.GPTConfig:
        """Inject the strategy's dispatch into the config at loss time (the
        ExpertParallel pattern, shardings.py _dispatch_cfg) — the pallas
        dataflow is meshless, so moe_mesh stays None."""
        if cfg.num_experts == 0:
            return cfg
        return cfg.replace(
            moe_dispatch=self.moe_dispatch or cfg.moe_dispatch, moe_mesh=None
        )

    def _check_interleave(self, cfg: gpt.GPTConfig) -> None:
        """Validation matrix for cfg.virtual_stages (round 22)."""
        v = cfg.virtual_stages
        if v == 1:
            return
        if not self.supports_interleave:
            raise ValueError(
                f"virtual_stages={v} needs the 1f1b schedule "
                f"(--pipeline_schedule 1f1b / Pipeline1F1B) — the GPipe "
                f"schedule runs one contiguous layer block per stage and "
                f"cannot interleave chunks"
            )
        if v * self.num_stages > cfg.num_layers:
            raise ValueError(
                f"virtual_stages={v} x {self.num_stages} stages = "
                f"{v * self.num_stages} chunks exceeds num_layers="
                f"{cfg.num_layers} — every chunk needs at least one real "
                f"layer, so the maximum virtual_stages here is "
                f"{cfg.num_layers // self.num_stages}"
            )

    def validate_config(self, cfg: gpt.GPTConfig) -> None:
        self._validate_comm_dtype(cfg)
        if cfg.num_layers < 1:
            raise ValueError(f"num_layers must be >= 1, got {cfg.num_layers}")
        self._check_moe(cfg)
        self._check_interleave(cfg)

    def _vocab_spec(self, names: tuple, shape: tuple) -> P | None:
        """Single source of truth for vocab-over-stage placement. Both
        `state_sharding` and the schedule's shard_map in_specs call this —
        they MUST agree, or the in_specs would mismatch the actual array
        layout at the shard_map boundary. Returns None for leaves that stay
        replicated (including the fallback when the padded vocab does not
        divide the stage count)."""
        if "token" in names and len(shape) == 2 and shape[0] % self.num_stages == 0:
            return P("stage", None)
        if (
            "lm_head" in names
            and names
            and names[-1] == "kernel"
            and shape[-1] % self.num_stages == 0
        ):
            return P(None, "stage")
        return None

    def prepare_params(self, params, cfg: gpt.GPTConfig):
        """Pad the stacked layers to `ceil(L/S)*S` with identity layers.

        Padding layers are all-zero: zero attn-out and ffn-down projections
        make the residual block an exact identity, so a plain `gpt.forward`
        over the padded stack (the generation path) equals the L-layer
        model bit-for-bit; inside the pipeline schedule the padded slots are
        additionally gated off so their parameters get zero gradient (and
        AdamW's decay of an exactly-zero parameter is zero — they stay
        identity forever). This is the twin of the reference's uneven stage
        arithmetic (main-pipe.py:52-68): L=10 on 4 stages runs 3/3/3/1 real
        layers per stage.

        Interleaved layouts (cfg.virtual_stages = V > 1, round 22): the
        padded stack is additionally PERMUTED so that the plain
        `P("stage")` sharding hands device d its V non-contiguous chunks
        d, d+S, ..., d+(V-1)S as one local slab — stacked row
        (d*V + c)*p + j holds natural layer (c*S + d)*p + j (p layers per
        chunk). V=1 is the identity permutation, so the path below only
        fires for V > 1 and dense checkpoints keep their natural order.
        `inference_params` is the inverse (the generation path runs the
        sequential `gpt.forward`, which needs natural order)."""
        v = cfg.virtual_stages
        padded = self.padded_layers(cfg.num_layers, v)
        pad = padded - cfg.num_layers
        if pad == 0 and v == 1:
            return params

        layers = params["layers"]
        if pad:

            def pad_leaf(leaf):
                return jnp.concatenate(
                    [leaf, jnp.zeros((pad, *leaf.shape[1:]), leaf.dtype)], axis=0
                )

            layers = jax.tree.map(pad_leaf, layers)
        if v > 1:
            perm = jnp.asarray(self._chunk_perm(padded, v))
            layers = jax.tree.map(lambda leaf: leaf[perm], layers)
        return {**params, "layers": layers}

    def _chunk_perm(self, padded: int, virtual_stages: int) -> list:
        """Row order of the interleaved stacked-layer layout: stacked row
        (d*V + c)*p + j <- natural layer (c*S + d)*p + j. Identity at
        V=1."""
        per_chunk = padded // (self.num_stages * virtual_stages)
        perm = []
        for d in range(self.num_stages):
            for c in range(virtual_stages):
                g = c * self.num_stages + d
                perm.extend(range(g * per_chunk, (g + 1) * per_chunk))
        return perm

    def inference_params(self, params, cfg: gpt.GPTConfig):
        """Undo the interleaved chunk permutation so the plain sequential
        `gpt.forward` (generation/eval outside the schedule) applies layers
        in natural order. Identity-padded layers are order-safe, but the
        V > 1 permutation is not — generate_samples routes every strategy's
        replicated params through this hook (tpukit/train.py)."""
        v = cfg.virtual_stages
        if v == 1:
            return params
        stack = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
        perm = self._chunk_perm(stack, v)
        inv = [0] * len(perm)
        for i, k in enumerate(perm):
            inv[k] = i
        inv = jnp.asarray(inv)
        return {
            **params,
            "layers": jax.tree.map(lambda leaf: leaf[inv], params["layers"]),
        }

    def state_sharding(self, state_shapes):
        """Layer params shard over `stage`; the token embedding and lm_head
        (and their Adam state, which shares these paths) shard their vocab
        dimension over `stage` too (VERDICT r2 #3) — the reference's
        stage-placement of embeddings/head (main-pipe.py:53-55,75-77) as
        *memory layout*, not just compute gating. The tiny position table
        and norms stay replicated. Vocab sharding needs the padded vocab to
        divide the stage count (the default 128-multiple padding divides
        every power-of-two stage count); otherwise those leaves fall back
        to replicated — the same condition loss_fn uses."""
        from jax.sharding import NamedSharding

        def spec(path, leaf):
            if _is_layers_path(path):
                if leaf.shape[0] % self.num_stages:
                    raise ValueError(
                        f"stacked layer axis {leaf.shape[0]} must be a "
                        f"multiple of {self.num_stages} stages — initialize "
                        f"through create_train_state(..., strategy=pipeline) "
                        f"(or pipeline.prepare_params) so uneven layer "
                        f"counts are identity-padded"
                    )
                return NamedSharding(self.mesh, P("stage"))
            vocab = self._vocab_spec(_path_names(path), leaf.shape)
            return NamedSharding(self.mesh, vocab if vocab is not None else P())

        return jax.tree_util.tree_map_with_path(spec, state_shapes)

    def batch_spec(self) -> P:
        return P("data") if "data" in self.mesh.axis_names else P()

    # -- the schedule ------------------------------------------------------

    def loss_fn(
        self, params, cfg: gpt.GPTConfig, batch, targets,
        with_accuracy: bool = False, rng=None, aux_out: list | None = None,
    ):
        # Direct `strategy.loss_fn`/`value_and_grad` calls on an illegal
        # MoE or interleave config hit the curated errors below, not an
        # opaque shape mismatch (ADVICE r5 #1).
        self._check_moe(cfg)
        self._check_interleave(cfg)
        cfg = self._moe_cfg(cfg)
        # MoE aux channel (round 22): collect the per-(stage, tick) summed
        # load-balance aux in the scan carry, gated to valid micros, and
        # append its (micro, data-shard) mean — the Switch per-micro-batch
        # objective. Python-gated so dense traces are untouched.
        moe_aux = cfg.num_experts > 0 and aux_out is not None
        num_stages, num_micro = self.num_stages, self.num_microbatches
        padded = self.padded_layers(cfg.num_layers)
        per_stage = padded // num_stages
        stack = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
        if stack != padded:
            raise ValueError(
                f"stacked layer axis is {stack} but num_layers="
                f"{cfg.num_layers} on {num_stages} stages needs {padded} "
                f"(identity-padded) — initialize through "
                f"create_train_state(..., strategy=pipeline) or pass params "
                f"through pipeline.prepare_params"
            )
        global_batch = batch["input_ids"].shape[0]
        if global_batch % self.batch_divisor:
            raise ValueError(
                f"batch {global_batch} must divide into {num_micro} microbatches "
                f"x {self.data_size} data shards"
            )
        micro = global_batch // num_micro
        seq = batch["input_ids"].shape[1]

        def split(x):
            return x.reshape(num_micro, micro, *x.shape[1:])

        inputs = split(batch["input_ids"])
        positions = split(batch["position_ids"])
        masks = split(batch["mask"])
        tgts = split(targets)

        # Specs: layer params split over stage; the token table and lm_head
        # kernel split their vocab dim over stage (memory placement,
        # VERDICT r2 #3) when it divides; position/norms replicated;
        # micro-batch rows split over data (if present).
        data = "data" if "data" in self.mesh.axis_names else None
        batch_spec = P(None, data)
        layers = params["layers"]
        rest = {k: v for k, v in params.items() if k != "layers"}

        v_pad = cfg.padded_vocab_size
        # Derived from the same predicate state_sharding uses, so the
        # in_specs below always match the arrays' actual placement.
        shard_vocab = (
            self._vocab_spec(
                ("embeddings", "token"), rest["embeddings"]["token"].shape
            )
            is not None
        )
        v_local = v_pad // num_stages if shard_vocab else v_pad

        def rest_spec(path, leaf):
            vocab = self._vocab_spec(_path_names(path), leaf.shape)
            return vocab if vocab is not None else P()

        rest_specs = jax.tree_util.tree_map_with_path(rest_spec, rest)

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(P("stage"), rest_specs, batch_spec, batch_spec, batch_spec, batch_spec),
            out_specs=(P(),) * (4 if moe_aux else 3),
            check_vma=False,
        )
        def schedule(local_layers, rest_params, inputs, positions, masks, tgts):
            stage = jax.lax.axis_index("stage")
            last = num_stages - 1
            mb_local = inputs.shape[1]

            x0 = jnp.zeros((mb_local, seq, cfg.dim), cfg.compute_dtype)
            # The three accumulators are carried (and returned) as shape
            # (1,), not scalars: older jax (0.4.x) shard_map partial-eval
            # mishandles rank-0 autodiff residuals that forward to other
            # residual slots (structural _SpecError in the transpose; fixed
            # upstream). Rank-1 costs nothing and sidesteps the bug on the
            # pinned-jax deployment image.
            carry0 = (
                x0,
                jnp.zeros((mb_local, seq), jnp.bool_),  # threaded pad mask
                jnp.zeros((mb_local, seq), jnp.int32),  # threaded targets
                jnp.zeros((1,), jnp.float32),  # loss sum
                jnp.zeros((1,), jnp.float32),  # valid-token count
                jnp.zeros((1,), jnp.float32),  # correct count
            )

            def step(carry, t):
                if moe_aux:
                    x, mask_c, tgt_c, loss_sum, count, correct, aux_sum = carry
                else:
                    x, mask_c, tgt_c, loss_sum, count, correct = carry
                idx = jnp.clip(t, 0, num_micro - 1)

                # Stage 0 ingests a fresh micro-batch through the embeddings
                # (embeddings live on the first stage, main-pipe.py:53,67,75).
                if shard_vocab:
                    # Vocab-sharded table: every stage contributes its slice
                    # of the lookup (each token id hits exactly one slice, so
                    # the psum is an exact select) and stage 0 ingests the
                    # result. psum_bcast: the cotangent arrives only on
                    # stage 0's path, so the transpose must psum it back to
                    # every stage's table slice.
                    tok_tab = rest_params["embeddings"]["token"]
                    pos_tab = rest_params["embeddings"]["position"]
                    rel = inputs[idx] - stage * v_local
                    ok = (rel >= 0) & (rel < v_local)
                    part = jnp.where(
                        ok[..., None],
                        jnp.take(tok_tab, jnp.where(ok, rel, 0), axis=0),
                        0.0,
                    )
                    emb = psum_bcast(part, "stage") + jnp.take(
                        pos_tab, positions[idx], axis=0
                    )
                    emb = emb.astype(cfg.compute_dtype)
                    is0 = stage == 0
                    x_in = jnp.where(is0, emb, x)
                    mask_in = jnp.where(is0, masks[idx], mask_c)
                    tgt_in = jnp.where(is0, tgts[idx], tgt_c)
                else:

                    def ingest(_):
                        emb = gpt.apply_embeddings(rest_params, cfg, inputs[idx], positions[idx])
                        return emb, masks[idx], tgts[idx]

                    def passthrough(_):
                        return x, mask_c, tgt_c

                    x_in, mask_in, tgt_in = jax.lax.cond(
                        stage == 0, ingest, passthrough, None
                    )

                if rng is None:
                    step_rng = None
                else:
                    # independent dropout per (stage, schedule step, and data
                    # shard if present): fold a linearized index into the key
                    lin = stage * (num_micro + num_stages) + t
                    if data is not None:
                        lin = lin * self.data_size + jax.lax.axis_index(data)
                    step_rng = jax.random.fold_in(rng, lin)
                # Uneven layers: slots past the real layer count are
                # identity-padded AND gated off so they take zero gradient
                # (real layers fill the stack front-to-back, so the last
                # stage holds any inactive slots).
                if padded == cfg.num_layers:
                    active = None
                else:
                    active = (
                        stage * per_stage + jnp.arange(per_stage)
                    ) < cfg.num_layers
                if moe_aux:
                    # The aux from fill/drain ticks is garbage (the stage
                    # trunk runs on zeros there) — gate it to the ticks
                    # where this stage holds a real micro: stage s sees
                    # micro t - s, valid while 0 <= t - s < M. The CE path
                    # needs no such gate (garbage work never flows into an
                    # emitted loss), but aux is accumulated directly.
                    al: list = []
                    y = gpt.apply_decoder_layers(
                        local_layers, cfg, x_in, mask_in,
                        rng=step_rng, deterministic=step_rng is None,
                        active=active, aux_out=al,
                    )
                    stage_valid = (t >= stage) & (t - stage < num_micro)
                    aux_t = jnp.where(stage_valid, al[0], 0.0)
                else:
                    y = gpt.apply_decoder_layers(
                        local_layers, cfg, x_in, mask_in,
                        rng=step_rng, deterministic=step_rng is None,
                        active=active,
                    )

                # Head + loss on micro-batch m = t - (S-1) (norm+lm_head on
                # the last stage, main-pipe.py:55,68,77; loss on the last
                # stage's output, main-pipe.py:162-165).
                if shard_vocab:
                    # Vocab-parallel head: broadcast the last stage's
                    # activations/targets, each stage computes its vocab
                    # slice of the logits and the collective CE. Every stage
                    # accumulates the SAME totals; the final psum over the
                    # stage axis scales numerator and denominator alike, so
                    # the loss/accuracy ratios are exact.
                    #
                    # The whole block — including the activation psum_bcast —
                    # is gated behind `emit` (VERDICT r3 #7): during the S-1
                    # warm-up steps no micro-batch has reached the last stage
                    # yet, so broadcasting + head compute there is pure
                    # waste (and its backward too). `emit` depends only on t,
                    # so every device takes the same cond branch and the
                    # collectives inside stay globally matched.
                    def head_loss(_):
                        y_last = psum_bcast(
                            jnp.where(stage == last, y, jnp.zeros_like(y)),
                            "stage",
                        )
                        tgt_last = jax.lax.psum(
                            jnp.where(stage == last, tgt_in, 0), "stage"
                        )
                        offset = stage * v_local
                        # no f32 [micro, S, V] anywhere: each stage holds V/S
                        # columns, CE backward is local (vocab_parallel_ce)
                        (l_sum, cnt), local_logits = _vocab_slice_ce(
                            rest_params["norm_out"],
                            rest_params["lm_head"]["kernel"],
                            y_last, tgt_last, offset, v_local, cfg,
                        )
                        if with_accuracy:
                            lf = local_logits.astype(jnp.float32)
                            lmax = jnp.max(lf, axis=-1)
                            larg = jnp.argmax(lf, axis=-1) + offset
                            gmax = jax.lax.pmax(lmax, "stage")
                            # global argmax, first-index tie-break like argmax
                            preds = jax.lax.pmin(
                                jnp.where(lmax >= gmax, larg, v_pad), "stage"
                            )
                            valid = tgt_last != -100
                            corr = jnp.sum(
                                jnp.where(valid, preds == tgt_last, False)
                            ).astype(jnp.float32)
                        else:
                            corr = jnp.float32(0)
                        return l_sum, cnt, corr

                    def no_loss(_):
                        return jnp.float32(0), jnp.float32(0), jnp.float32(0)

                    emit = t >= num_stages - 1  # uniform across stages
                    l_sum, cnt, corr = jax.lax.cond(emit, head_loss, no_loss, None)
                else:

                    def head_loss(_):
                        logits = gpt.apply_head(rest_params, cfg, y)
                        # custom-VJP sum: no f32 [micro, S, V] tensor in
                        # either direction (ops/layers.py cross_entropy_sum)
                        l_sum, cnt = cross_entropy_sum(logits, tgt_in)
                        if with_accuracy:
                            valid = tgt_in != -100
                            preds = jnp.argmax(logits, axis=-1)
                            corr = jnp.sum(
                                jnp.where(valid, preds == tgt_in, False)
                            ).astype(jnp.float32)
                        else:
                            corr = jnp.float32(0)
                        return l_sum, cnt, corr

                    def no_loss(_):
                        return jnp.float32(0), jnp.float32(0), jnp.float32(0)

                    emit = jnp.logical_and(stage == last, t >= num_stages - 1)
                    l_sum, cnt, corr = jax.lax.cond(emit, head_loss, no_loss, None)

                # Ship activations (and the threaded mask/targets — the twin
                # of the reference's (x, mask) tuple threading) to the next
                # stage over ICI.
                perm = [(i, i + 1) for i in range(num_stages - 1)]
                x_next = jax.lax.ppermute(y, "stage", perm)
                mask_next = jax.lax.ppermute(mask_in, "stage", perm)
                tgt_next = jax.lax.ppermute(tgt_in, "stage", perm)

                out = (
                    x_next, mask_next, tgt_next,
                    loss_sum + l_sum, count + cnt, correct + corr,
                )
                if moe_aux:
                    out = out + (aux_sum + aux_t,)
                return out, None

            if moe_aux:
                carry0 = carry0 + (jnp.zeros((1,), jnp.float32),)
            total_steps = num_micro + num_stages - 1
            final, _ = jax.lax.scan(step, carry0, jnp.arange(total_steps))
            loss_sum, count, correct = final[3:6]
            aux_sum = final[6] if moe_aux else None

            # Vocab-sharded path: every stage accumulated identical totals
            # from the collective CE, so this psum multiplies numerator and
            # denominator by num_stages alike — the loss/accuracy ratios are
            # exact, and vocab_parallel_ce's backward psums its incoming
            # cotangent over `stage` to undo the same inflation.
            axes = tuple(self.mesh.axis_names)
            loss_sum = jax.lax.psum(loss_sum, axes)
            count = jax.lax.psum(count, axes)
            correct = jax.lax.psum(correct, axes)
            if moe_aux:
                # psum over stage sums the per-chunk aux (each stage's
                # layers are distinct), over data the per-shard stats.
                return loss_sum, count, correct, jax.lax.psum(aux_sum, axes)
            return loss_sum, count, correct  # each shape (1,), see carry0

        outs = tuple(
            x[0] for x in schedule(layers, rest, inputs, positions, masks, tgts)
        )
        loss_sum, count, correct = outs[:3]
        if moe_aux:
            # The per-micro objective: mean over micro-batches and data
            # shards of each micro's summed layer aux (base value_and_grad
            # adds cfg.moe_aux_weight * this to the differentiated total).
            aux_out.append(outs[3] / (num_micro * self.data_size))
        denom = jnp.maximum(count, 1.0)
        loss = loss_sum / denom
        accuracy = correct / denom * 100.0
        return loss, accuracy


class Pipeline1F1B(Pipeline):
    """1F1B pipeline schedule: activation memory bounded by the STAGE count.

    The GPipe parent differentiates its whole schedule with autodiff, so
    residuals for every scheduled step stay live until the backward — temp
    memory grows linearly with the micro-batch count (measured in
    docs/DESIGN.md). Here the training gradient is built EXPLICITLY inside
    the tick loop: each tick, every stage runs one primal forward (sending
    its activation on) and one remat-style `jax.vjp` backward for the
    oldest outstanding micro-batch (recomputing the stage trunk from the
    saved stage INPUT, then transposing with the cotangent that arrived
    from the next stage). The scan itself is never differentiated, so each
    tick's internals are freed by XLA as it retires; the only persistent
    activation state is a depth-2S ring buffer of stage inputs —
    independent of the micro-batch count.

    Scheduling is correct-by-dataflow: validity flags travel with the
    forward activations and backward cotangents, invalid work is computed
    but masked to zero (a vjp is linear in its cotangent, so a zero
    cotangent contributes exactly zero gradient), and per-stage counters
    pace the in-order micro-batch streams. The last stage triggers its own
    backward the same tick as its forward — the 1F1B interleave. Ticks:
    num_micro + 2*num_stages - 2 (the bubble is the standard 1F1B one;
    the win is memory, not bubble).

    Embeddings and lm_head shard their VOCAB dimension over `stage`
    exactly like the parent (VERDICT r4 #4): the per-stage vjp covers only
    the trunk (collective-free, so stages may replay *different* micros
    the same tick), while the two vocab-collective computations run at
    TICK level where their micro index is a uniform function of the tick —
    stage 0 ingests micro `t`, the last stage's head+CE serves micro
    `t-(S-1)` — so every stage participates in the same psum for the same
    logical micro-batch and the collectives stay globally matched:

      - ingest: each stage gathers its vocab slice of the lookup, one
        psum assembles the embedding, stage 0 consumes it (the saved
        stage input is POST-ingest, so the trunk replay never re-embeds);
      - head: `jax.vjp` of (layer_norm -> local logits -> collective
        vocab_parallel_ce) at micro `t-(S-1)`, whose primal output is the
        loss contribution and whose pullback yields the lm_head/norm
        grads plus the cotangent the last stage's trunk backward consumes
        the SAME tick (the 1F1B self-trigger);
      - the embedding-table transpose: the cotangent of stage 0's trunk
        input IS d(embedding) for the micro stage 0 is retiring — also a
        uniform function of the tick, `t-(2S-2)` — so one psum broadcasts
        it and every stage scatter-adds its own vocab slice.

    With the replicated fallback (padded vocab not divisible by the stage
    count), ingest / head / table-transpose are instead `lax.cond`-gated
    to the stages that need them (no collectives inside, so the
    non-uniform predicate is safe) — stages no longer compute-and-discard
    the embedding gather every tick (VERDICT r4 #5).

    Eval reuses the parent's forward-only schedule (loss_fn). Dropout
    keys derive from (stage, micro) — not the tick — so the backward's
    recompute sees exactly the forward's mask.
    """

    name = "pipe-1f1b"
    supports_interleave = True

    def value_and_grad(self, params, cfg: gpt.GPTConfig, batch, targets, rng=None):
        """(loss, grads) for one global batch — the hook make_step_fns uses
        instead of jax.value_and_grad (tpukit/train.py).

        Dispatch (round 22): the dense V=1 case runs the ORIGINAL flat tick
        scan below, untouched — its compiled HLO is byte-identical to
        before interleaving existed. virtual_stages > 1 and/or MoE configs
        run the unrolled interleaved machine (which handles V=1 too; MoE
        needs its aux cotangent channel, so V=1 MoE also routes there
        rather than growing the scan)."""
        self._check_moe(cfg)  # fail loudly from any entry point (ADVICE r5 #1)
        self._check_interleave(cfg)
        if cfg.virtual_stages == 1 and cfg.num_experts == 0:
            return self._flat_value_and_grad(params, cfg, batch, targets, rng)
        return self._interleaved_value_and_grad(params, cfg, batch, targets, rng)

    def loss_fn(
        self, params, cfg: gpt.GPTConfig, batch, targets,
        with_accuracy: bool = False, rng=None, aux_out: list | None = None,
    ):
        """Eval: V=1 reuses the parent's forward-only GPipe schedule; V > 1
        params live in the interleaved chunk layout the GPipe scan cannot
        walk, so eval runs the forward-only interleaved tick program."""
        self._check_moe(cfg)
        self._check_interleave(cfg)
        if cfg.virtual_stages == 1:
            return super().loss_fn(
                params, cfg, batch, targets,
                with_accuracy=with_accuracy, rng=rng, aux_out=aux_out,
            )
        return self._interleaved_eval(
            params, cfg, batch, targets,
            with_accuracy=with_accuracy, rng=rng, aux_out=aux_out,
        )

    def _flat_value_and_grad(self, params, cfg: gpt.GPTConfig, batch, targets, rng=None):
        """The original flat 1F1B tick scan (every tick runs both phases;
        bubble (2S-2)/(M+2S-2)) — the `--virtual_stages 1` dense path."""
        num_stages, num_micro = self.num_stages, self.num_microbatches
        padded = self.padded_layers(cfg.num_layers)
        per_stage = padded // num_stages
        stack = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
        if stack != padded:
            raise ValueError(
                f"stacked layer axis is {stack} but num_layers="
                f"{cfg.num_layers} on {num_stages} stages needs {padded} — "
                f"initialize through create_train_state(..., strategy=...)"
            )
        global_batch = batch["input_ids"].shape[0]
        if global_batch % self.batch_divisor:
            raise ValueError(
                f"batch {global_batch} must divide into {num_micro} "
                f"microbatches x {self.data_size} data shards"
            )
        micro = global_batch // num_micro
        seq = batch["input_ids"].shape[1]

        def split(x):
            return x.reshape(num_micro, micro, *x.shape[1:])

        inputs = split(batch["input_ids"])
        positions = split(batch["position_ids"])
        masks = split(batch["mask"])
        tgts = split(targets)

        data = "data" if "data" in self.mesh.axis_names else None
        batch_spec = P(None, data)
        layers = params["layers"]
        rest = {k: v for k, v in params.items() if k != "layers"}

        v_pad = cfg.padded_vocab_size
        # Same predicate as state_sharding/loss_fn, so the in/out specs
        # below always match the arrays' actual placement.
        shard_vocab = (
            self._vocab_spec(
                ("embeddings", "token"), rest["embeddings"]["token"].shape
            )
            is not None
        )
        v_local = v_pad // num_stages if shard_vocab else v_pad

        def rest_spec(path, leaf):
            vocab = self._vocab_spec(_path_names(path), leaf.shape)
            return vocab if vocab is not None else P()

        rest_specs = jax.tree_util.tree_map_with_path(rest_spec, rest)
        # Gradients of vocab-sharded leaves stay stage-local (each stage
        # owns its slice); replicated leaves' contributions are gated to
        # one stage and psum'd. Derived from rest_specs (single source of
        # truth) — decided OUTSIDE shard_map, which needs global shapes.
        rest_sharded = jax.tree.map(
            lambda spec: spec != P(), rest_specs,
            is_leaf=lambda x: isinstance(x, P),
        )

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(P("stage"), rest_specs, batch_spec, batch_spec, batch_spec, batch_spec),
            out_specs=(P(), P(), P("stage"), rest_specs),
            check_vma=False,
        )
        def schedule(local_layers, rest_params, inputs, positions, masks, tgts):
            stage = jax.lax.axis_index("stage")
            last = num_stages - 1
            depth = 2 * num_stages  # ring depth: in-flight micros < 2S - 1
            mb_local = inputs.shape[1]
            # micro m forwards at stage s on tick m+s; the last stage
            # backwards it the same tick; the cotangent reaches stage 0 at
            # tick (m + S - 1) + (S - 1) — so the last backward retires at
            # tick M + 2S - 3, i.e. M + 2S - 2 ticks total.
            ticks = num_micro + 2 * num_stages - 2

            if padded == cfg.num_layers:
                active = None
            else:
                active = (
                    stage * per_stage + jnp.arange(per_stage)
                ) < cfg.num_layers

            def key_for(mi):
                if rng is None:
                    return None
                lin = stage * num_micro + mi
                if data is not None:
                    lin = lin * self.data_size + jax.lax.axis_index(data)
                return jax.random.fold_in(rng, lin)

            def stage_trunk(lp, x_in, mask_in, mi):
                """One stage's trunk slice for micro `mi` — collective-free,
                so its vjp can replay a DIFFERENT micro per stage."""
                k = key_for(mi)
                return gpt.apply_decoder_layers(
                    lp, cfg, x_in, mask_in,
                    rng=k, deterministic=k is None, active=active,
                )

            def sharded_ingest(mi):
                """Distributed lookup: every stage contributes its vocab
                slice, one exact psum assembles the embedding. `mi` must be
                tick-uniform (the psum is collective)."""
                rel = inputs[mi] - stage * v_local
                ok = (rel >= 0) & (rel < v_local)
                part = jnp.where(
                    ok[..., None],
                    jnp.take(
                        rest_params["embeddings"]["token"],
                        jnp.where(ok, rel, 0),
                        axis=0,
                    ),
                    0.0,
                )
                emb = jax.lax.psum(part, "stage") + jnp.take(
                    rest_params["embeddings"]["position"], positions[mi], axis=0
                )
                return emb.astype(cfg.compute_dtype)

            def zeros_rest():
                return jax.tree.map(jnp.zeros_like, rest_params)

            def add_emb_grads(grp, d_tok, d_pos):
                return {
                    **grp,
                    "embeddings": {
                        "token": grp["embeddings"]["token"] + d_tok,
                        "position": grp["embeddings"]["position"] + d_pos,
                    },
                }

            perm_f = [(i, i + 1) for i in range(num_stages - 1)]
            perm_b = [(i + 1, i) for i in range(num_stages - 1)]

            def tick(carry, t):
                (x_fwd, mask_fwd, fvalid, dy_bwd, bvalid, xbuf, maskbuf,
                 fcnt, bcnt, glp, grp, loss_sum, cnt_sum) = carry
                is0 = stage == 0
                at_last = stage == last

                # ---- forward unit: one primal trunk step of micro `fcnt`.
                # Stage 0 ingests through the embeddings; the saved stage
                # input is POST-ingest, so backward replay never re-embeds.
                okf = jnp.where(is0, fcnt < num_micro, fvalid)
                mi_f = jnp.clip(fcnt, 0, num_micro - 1)
                mask_in = jnp.where(is0, masks[mi_f], mask_fwd)
                if shard_vocab:
                    # stage 0's forward micro is `t` (its fcnt advances every
                    # tick until exhausted), a tick-uniform index — so every
                    # stage participates in the ingest psum for the same
                    # logical micro. The predicate is tick-uniform too, so
                    # the 2S-2 drain ticks skip the gather + psum entirely
                    # (collectives inside a uniform cond stay matched).
                    x_eff = jax.lax.cond(
                        t < num_micro,
                        lambda: jnp.where(is0, sharded_ingest(t), x_fwd),
                        lambda: x_fwd,
                    )
                else:
                    x_eff = jax.lax.cond(
                        is0,
                        lambda: gpt.apply_embeddings(
                            rest_params, cfg, inputs[mi_f], positions[mi_f]
                        ),
                        lambda: x_fwd,
                    )
                y = stage_trunk(local_layers, x_eff, mask_in, mi_f)
                slot = fcnt % depth
                # gate the single written slot, not a select over the whole
                # depth-2S buffer (keeps the carry update in place)
                xbuf = xbuf.at[slot].set(jnp.where(okf, x_eff, xbuf[slot]))
                maskbuf = maskbuf.at[slot].set(
                    jnp.where(okf, mask_in, maskbuf[slot])
                )
                fcnt = fcnt + okf.astype(fcnt.dtype)

                # ---- head + CE for the micro reaching the last stage this
                # tick. Its primal output is the loss contribution; its
                # pullback yields the head grads AND the trunk cotangent the
                # last stage consumes the same tick (the 1F1B self-trigger).
                okb_last = bcnt < fcnt  # last stage's backward validity
                if shard_vocab:
                    # tick-uniform micro t-(S-1): collectives inside match.
                    idx_h = t - (num_stages - 1)
                    okh = (idx_h >= 0) & (idx_h < num_micro)
                    mi_h = jnp.clip(idx_h, 0, num_micro - 1)

                    def head_block(_):
                        y_b = jax.lax.psum(
                            jnp.where(at_last, y, jnp.zeros_like(y)), "stage"
                        )
                        tgt_h = tgts[mi_h]
                        offset = stage * v_local

                        def f(norm_p, lm_k, yy):
                            (l, c), _ = _vocab_slice_ce(
                                norm_p, lm_k, yy, tgt_h, offset, v_local, cfg
                            )
                            return l, c

                        (l_s, c_s), pull_h = jax.vjp(
                            f,
                            rest_params["norm_out"],
                            rest_params["lm_head"]["kernel"],
                            y_b,
                        )
                        # vocab_parallel_ce's backward psums the incoming
                        # cotangent over `stage`; gating it to stage 0 makes
                        # that psum recover exactly 1.
                        dl = jnp.where(is0, 1.0, 0.0).astype(jnp.float32)
                        dnorm, dlm, dyb = pull_h((dl, jnp.float32(0)))
                        # f consumed the broadcast y on every stage, so the
                        # true cotangent at the last stage's y is the sum of
                        # every stage's dyb (the psum_bcast transpose).
                        dy_l = jax.lax.psum(dyb, "stage")
                        return l_s, c_s, dnorm, dlm, dy_l

                    def no_head(_):
                        return (
                            jnp.float32(0), jnp.float32(0),
                            jax.tree.map(jnp.zeros_like, rest_params["norm_out"]),
                            jnp.zeros_like(rest_params["lm_head"]["kernel"]),
                            jnp.zeros_like(y),
                        )

                    l_s, c_s, dnorm, dlm, dy_head = jax.lax.cond(
                        okh, head_block, no_head, None
                    )
                    # l_s/c_s are replicated (collective CE); accumulate on
                    # stage 0 only so the final all-axes psum counts them once
                    # per data shard.
                    loss_sum = loss_sum + jnp.where(okh & is0, l_s, 0.0)
                    cnt_sum = cnt_sum + jnp.where(okh & is0, c_s, 0.0)
                    grp = {
                        **grp,
                        "norm_out": jax.tree.map(
                            jnp.add, grp["norm_out"], dnorm
                        ),
                        "lm_head": {
                            "kernel": grp["lm_head"]["kernel"] + dlm
                        },
                    }
                else:
                    mi_b_last = jnp.clip(bcnt, 0, num_micro - 1)

                    def head_block(_):
                        def f(rp, yy):
                            logits = gpt.apply_head(rp, cfg, yy)
                            return cross_entropy_sum(logits, tgts[mi_b_last])

                        (l_s, c_s), pull_h = jax.vjp(f, rest_params, y)
                        dl = jnp.where(okb_last, 1.0, 0.0).astype(jnp.float32)
                        drp, dy_l = pull_h((dl, jnp.float32(0)))
                        return (
                            jnp.where(okb_last, l_s, 0.0),
                            jnp.where(okb_last, c_s, 0.0),
                            drp, dy_l,
                        )

                    def no_head(_):
                        return (
                            jnp.float32(0), jnp.float32(0),
                            zeros_rest(), jnp.zeros_like(y),
                        )

                    # no collectives inside -> the non-uniform predicate is
                    # safe; only the last stage pays the head compute.
                    l_s, c_s, drp_head, dy_head = jax.lax.cond(
                        at_last, head_block, no_head, None
                    )
                    loss_sum = loss_sum + l_s
                    cnt_sum = cnt_sum + c_s
                    grp = jax.tree.map(jnp.add, grp, drp_head)

                # ---- backward unit: remat vjp of the trunk for micro
                # `bcnt` (the last stage self-triggers: its cotangent is
                # dy_head from this very tick).
                okb = jnp.where(at_last, okb_last, bvalid)
                mi_b = jnp.clip(bcnt, 0, num_micro - 1)
                slot_b = bcnt % depth
                f = lambda lp, x: stage_trunk(lp, x, maskbuf[slot_b], mi_b)
                _, pull = jax.vjp(f, local_layers, xbuf[slot_b])
                dy_eff = jnp.where(
                    okb, jnp.where(at_last, dy_head, dy_bwd), 0
                ).astype(cfg.compute_dtype)
                dlp, dx = pull(dy_eff)
                glp = jax.tree.map(jnp.add, glp, dlp)
                bcnt = bcnt + okb.astype(bcnt.dtype)

                # ---- embedding-table transpose: stage 0's trunk-input
                # cotangent IS d(embedding) for the micro stage 0 retires.
                dx_gated = jnp.where(okb & is0, dx, 0).astype(jnp.float32)
                if shard_vocab:
                    # stage 0 retires micro t-(2S-2) — tick-uniform, so one
                    # psum broadcasts d(emb) and every stage scatter-adds its
                    # own vocab slice of the table gradient.
                    idx_b0 = t - (2 * num_stages - 2)
                    mi_e = jnp.clip(idx_b0, 0, num_micro - 1)
                    d_emb = jax.lax.psum(dx_gated, "stage")
                    rel = inputs[mi_e] - stage * v_local
                    ok = (rel >= 0) & (rel < v_local)
                    d_tok = (
                        jnp.zeros_like(grp["embeddings"]["token"])
                        .at[jnp.where(ok, rel, v_local)]
                        .add(
                            jnp.where(ok[..., None], d_emb, 0.0),
                            mode="drop",
                        )
                    )
                    d_pos = (
                        jnp.zeros_like(grp["embeddings"]["position"])
                        .at[positions[mi_e]]
                        .add(d_emb)
                    )
                    # position table is replicated (final psum over stage):
                    # count its contribution once.
                    grp = add_emb_grads(
                        grp, d_tok, jnp.where(is0, d_pos, 0.0)
                    )
                else:

                    def emb_bwd(_):
                        d_tok = (
                            jnp.zeros_like(grp["embeddings"]["token"])
                            .at[inputs[mi_b]]
                            .add(dx_gated)
                        )
                        d_pos = (
                            jnp.zeros_like(grp["embeddings"]["position"])
                            .at[positions[mi_b]]
                            .add(dx_gated)
                        )
                        return d_tok, d_pos

                    def no_emb(_):
                        return (
                            jnp.zeros_like(grp["embeddings"]["token"]),
                            jnp.zeros_like(grp["embeddings"]["position"]),
                        )

                    d_tok, d_pos = jax.lax.cond(is0, emb_bwd, no_emb, None)
                    grp = add_emb_grads(grp, d_tok, d_pos)

                # ---- ship: activations forward, cotangents backward ----
                x_next = jax.lax.ppermute(y, "stage", perm_f)
                mask_next = jax.lax.ppermute(mask_in, "stage", perm_f)
                fvalid_next = jax.lax.ppermute(okf, "stage", perm_f)
                dy_next = jax.lax.ppermute(dx, "stage", perm_b)
                bvalid_next = jax.lax.ppermute(okb, "stage", perm_b)
                return (
                    (x_next, mask_next, fvalid_next, dy_next, bvalid_next,
                     xbuf, maskbuf, fcnt, bcnt, glp, grp, loss_sum, cnt_sum),
                    None,
                )

            zeros_x = jnp.zeros((mb_local, seq, cfg.dim), cfg.compute_dtype)
            carry0 = (
                zeros_x,
                jnp.zeros((mb_local, seq), jnp.bool_),
                jnp.bool_(False),
                zeros_x,
                jnp.bool_(False),
                jnp.zeros((depth, mb_local, seq, cfg.dim), cfg.compute_dtype),
                jnp.zeros((depth, mb_local, seq), jnp.bool_),
                jnp.int32(0),
                jnp.int32(0),
                jax.tree.map(jnp.zeros_like, local_layers),
                jax.tree.map(jnp.zeros_like, rest_params),
                jnp.float32(0),
                jnp.float32(0),
            )
            final_carry, _ = jax.lax.scan(tick, carry0, jnp.arange(ticks))
            glp, grp, loss_sum, cnt_sum = final_carry[-4:]

            axes = tuple(self.mesh.axis_names)
            loss_sum = jax.lax.psum(loss_sum, axes)
            cnt_sum = jax.lax.psum(cnt_sum, axes)
            # layer grads are stage-local; sum row-shards over `data`.
            # Vocab-sharded leaves (token table / lm_head kernel) likewise
            # stay stage-local; replicated rest leaves were gated to a
            # single stage's contribution and psum over every axis.
            if data is not None:
                glp = jax.tree.map(lambda g: jax.lax.psum(g, data), glp)

            def reduce_rest(g, is_sharded):
                if is_sharded:
                    return jax.lax.psum(g, data) if data is not None else g
                return jax.lax.psum(g, axes)

            grp = jax.tree.map(reduce_rest, grp, rest_sharded)
            return loss_sum, cnt_sum, glp, grp

        loss_sum, count, glp, grp = schedule(
            layers, rest, inputs, positions, masks, tgts
        )
        denom = jnp.maximum(count, 1.0)
        grads = {**grp, "layers": glp}
        grads = jax.tree.map(lambda g: (g / denom).astype(g.dtype), grads)
        return loss_sum / denom, grads

    # -- interleaved virtual stages (round 22, ROADMAP #5) -----------------
    #
    # cfg.virtual_stages = V > 1: device d owns V non-contiguous chunks
    # d, d+S, ..., d+(V-1)S of the layer stack (prepare_params lays the
    # stack out so P("stage") hands each device its chunks as one slab).
    # The tick program comes from tpukit/pipeline_schedule.py — a STATIC
    # per-tick, per-device job table the machine UNROLLS (no scan): each
    # tick traces only the phases it actually runs, so pure-forward
    # warm-up and pure-backward drain ticks cost one phase, and the
    # schedule's idle-work accounting (bench.py `pipe_interleave`) prices
    # exactly what the compiled program executes. Static tables also mean
    # validity is compile-time — no ok-flags ship with the payloads, and
    # the ONLY collectives are one forward ppermute per shipping tick, one
    # backward ppermute per shipping tick, and the vocab-sharded
    # ingest/head/emb psums at their (static) ticks, so the closed-form
    # comm plan (`pipe_comm`) counts the HLO exactly.

    def _interleave_prelude(self, params, cfg: gpt.GPTConfig, batch, targets):
        """Shared shape/spec plumbing for the interleaved machines —
        mirrors the flat machine's prelude with the V-aware stack check."""
        S, M = self.num_stages, self.num_microbatches
        V = cfg.virtual_stages
        padded = self.padded_layers(cfg.num_layers, V)
        per_chunk = padded // (S * V)
        stack = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
        if stack != padded:
            raise ValueError(
                f"stacked layer axis is {stack} but num_layers="
                f"{cfg.num_layers} with virtual_stages={V} on {S} stages "
                f"needs {padded} (identity-padded, chunk-permuted) — "
                f"initialize through create_train_state(..., strategy=...)"
            )
        global_batch = batch["input_ids"].shape[0]
        if global_batch % self.batch_divisor:
            raise ValueError(
                f"batch {global_batch} must divide into {M} "
                f"microbatches x {self.data_size} data shards"
            )
        micro = global_batch // M

        def split(x):
            return x.reshape(M, micro, *x.shape[1:])

        data = "data" if "data" in self.mesh.axis_names else None
        rest = {k: v for k, v in params.items() if k != "layers"}
        v_pad = cfg.padded_vocab_size
        shard_vocab = (
            self._vocab_spec(
                ("embeddings", "token"), rest["embeddings"]["token"].shape
            )
            is not None
        )

        def rest_spec(path, leaf):
            vocab = self._vocab_spec(_path_names(path), leaf.shape)
            return vocab if vocab is not None else P()

        rest_specs = jax.tree_util.tree_map_with_path(rest_spec, rest)
        rest_sharded = jax.tree.map(
            lambda spec: spec != P(), rest_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        return dict(
            S=S, V=V, M=M, padded=padded, per_chunk=per_chunk,
            seq=batch["input_ids"].shape[1],
            inputs=split(batch["input_ids"]),
            positions=split(batch["position_ids"]),
            masks=split(batch["mask"]),
            tgts=split(targets),
            data=data, batch_spec=P(None, data),
            layers=params["layers"], rest=rest,
            rest_specs=rest_specs, rest_sharded=rest_sharded,
            shard_vocab=shard_vocab,
            v_local=v_pad // S if shard_vocab else v_pad,
            v_pad=v_pad,
        )

    def _interleaved_value_and_grad(
        self, params, cfg: gpt.GPTConfig, batch, targets, rng=None
    ):
        """The unrolled interleaved-1F1B machine: explicit-vjp training
        over the static tick table, V >= 1, dense or MoE (pallas
        dispatch). Same contract as _flat_value_and_grad."""
        cfg = self._moe_cfg(cfg)
        env = self._interleave_prelude(params, cfg, batch, targets)
        S, V, M = env["S"], env["V"], env["M"]
        per_chunk, seq = env["per_chunk"], env["seq"]
        data, shard_vocab = env["data"], env["shard_vocab"]
        v_local = env["v_local"]
        inputs_a, positions_a = env["inputs"], env["positions"]
        masks_a, tgts_a = env["masks"], env["tgts"]
        rest_specs, rest_sharded = env["rest_specs"], env["rest_sharded"]
        moe = cfg.num_experts > 0
        sched = cached_schedule(S, V, M)
        depth = sched.depth
        padded = env["padded"]

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(
                P("stage"), rest_specs,
                env["batch_spec"], env["batch_spec"],
                env["batch_spec"], env["batch_spec"],
            ),
            out_specs=(P(), P(), P("stage"), rest_specs),
            check_vma=False,
        )
        def schedule(local_layers, rest_params, inputs, positions, masks, tgts):
            stage = jax.lax.axis_index("stage")
            last = S - 1
            is0 = stage == 0
            at_last = stage == last
            mb_local = inputs.shape[1]

            # Device-local chunk stack: [V, per_chunk, ...]. Chunk c on
            # this device is global chunk c*S + stage, covering natural
            # layers [(c*S + stage)*per_chunk, +per_chunk).
            chunks = jax.tree.map(
                lambda l: l.reshape(V, per_chunk, *l.shape[1:]), local_layers
            )
            if padded == cfg.num_layers:
                active_all = None
            else:
                g_of_c = jnp.arange(V) * S + stage  # [V]
                layer_idx = (
                    g_of_c[:, None] * per_chunk + jnp.arange(per_chunk)[None, :]
                )
                active_all = layer_idx < cfg.num_layers  # [V, per_chunk]

            def key_for(c, mi):
                # keyed by the GLOBAL chunk id and micro, so the backward
                # replay of (g, m) sees exactly the forward's dropout mask
                # (and V=1 reproduces the flat machine's keys: g == stage)
                if rng is None:
                    return None
                lin = (c * S + stage) * M + mi
                if data is not None:
                    lin = lin * self.data_size + jax.lax.axis_index(data)
                return jax.random.fold_in(rng, lin)

            def chunk_call(c, x_in, mi, want_aux):
                """One chunk's trunk (collective-free). `c`/`mi` are
                traced per-device scalars from the tick table."""
                lp = jax.tree.map(
                    lambda l: jax.lax.dynamic_index_in_dim(
                        l, c, 0, keepdims=False
                    ),
                    chunks,
                )
                act = (
                    None
                    if active_all is None
                    else jax.lax.dynamic_index_in_dim(
                        active_all, c, 0, keepdims=False
                    )
                )
                k = key_for(c, mi)
                al: list = [] if want_aux else None
                y = gpt.apply_decoder_layers(
                    lp, cfg, x_in, masks[mi],
                    rng=k, deterministic=k is None, active=act, aux_out=al,
                )
                if want_aux:
                    return y, (al[0] if al else jnp.float32(0))
                return y

            def chunk_vjp(c, x_in, mi, dy, d_aux):
                """Remat backward of chunk `c` micro `mi`: recompute the
                trunk from the saved chunk input, transpose with the
                arrived cotangent (plus the aux cotangent for MoE)."""
                lp = jax.tree.map(
                    lambda l: jax.lax.dynamic_index_in_dim(
                        l, c, 0, keepdims=False
                    ),
                    chunks,
                )
                act = (
                    None
                    if active_all is None
                    else jax.lax.dynamic_index_in_dim(
                        active_all, c, 0, keepdims=False
                    )
                )
                k = key_for(c, mi)

                if moe:

                    def f(lp_, x_):
                        al: list = []
                        y = gpt.apply_decoder_layers(
                            lp_, cfg, x_, masks[mi],
                            rng=k, deterministic=k is None, active=act,
                            aux_out=al,
                        )
                        return y, al[0] if al else jnp.float32(0)

                    _, pull = jax.vjp(f, lp, x_in)
                    return pull((dy, d_aux))

                def f(lp_, x_):
                    return gpt.apply_decoder_layers(
                        lp_, cfg, x_, masks[mi],
                        rng=k, deterministic=k is None, active=act,
                    )

                _, pull = jax.vjp(f, lp, x_in)
                return pull(dy)

            def sharded_ingest(mi):
                # mi is a STATIC micro index (tick.ingest) — every device
                # participates in the psum for the same logical micro.
                rel = inputs[mi] - stage * v_local
                ok = (rel >= 0) & (rel < v_local)
                part = jnp.where(
                    ok[..., None],
                    jnp.take(
                        rest_params["embeddings"]["token"],
                        jnp.where(ok, rel, 0),
                        axis=0,
                    ),
                    0.0,
                )
                emb = jax.lax.psum(part, "stage") + jnp.take(
                    rest_params["embeddings"]["position"], positions[mi], axis=0
                )
                return emb.astype(cfg.compute_dtype)

            def zeros_rest():
                return jax.tree.map(jnp.zeros_like, rest_params)

            def add_emb_grads(g, d_tok, d_pos):
                return {
                    **g,
                    "embeddings": {
                        "token": g["embeddings"]["token"] + d_tok,
                        "position": g["embeddings"]["position"] + d_pos,
                    },
                }

            def dev_i32(entries, pos):
                return jnp.asarray(
                    [0 if e is None else e[pos] for e in entries], jnp.int32
                )[stage]

            def dev_ok(entries):
                return jnp.asarray([e is not None for e in entries])[stage]

            ring_f = [(i, (i + 1) % S) for i in range(S)]
            ring_b = [(i, (i - 1) % S) for i in range(S)]

            xbuf = jnp.zeros(
                (V, depth, mb_local, seq, cfg.dim), cfg.compute_dtype
            )
            dybuf = jnp.zeros_like(xbuf)
            glp = jax.tree.map(jnp.zeros_like, chunks)
            grp = zeros_rest()
            loss_sum = jnp.float32(0)
            cnt_sum = jnp.float32(0)
            y_wire = jnp.zeros((mb_local, seq, cfg.dim), cfg.compute_dtype)
            dx_wire = jnp.zeros_like(y_wire)

            if moe:
                # Aux cotangent: the objective is CE_sum/denom +
                # aw * sum_{g,m} aux / (M * data_size); grads accumulate
                # raw and divide by denom once at the end, so the aux seed
                # is aw * denom / (M * data_size). denom is known up
                # front: the head counts every valid target exactly once.
                cnt_local = jnp.sum(tgts != -100).astype(jnp.float32)
                cnt_global = (
                    jax.lax.psum(cnt_local, data) if data else cnt_local
                )
                alpha = (
                    cfg.moe_aux_weight
                    * jnp.maximum(cnt_global, 1.0)
                    / (M * self.data_size)
                )

            for tk in sched.ticks:
                # -- arrivals: payloads shipped at the end of the previous
                # tick land in their pre-assigned slots (static targets;
                # devices without an arrival write nothing).
                if any(e is not None for e in tk.recv_fwd):
                    c_r, s_r = dev_i32(tk.recv_fwd, 0), dev_i32(tk.recv_fwd, 1)
                    ok_r = dev_ok(tk.recv_fwd)
                    xbuf = xbuf.at[c_r, s_r].set(
                        jnp.where(ok_r, y_wire, xbuf[c_r, s_r])
                    )
                if any(e is not None for e in tk.recv_bwd):
                    c_r, s_r = dev_i32(tk.recv_bwd, 0), dev_i32(tk.recv_bwd, 1)
                    ok_r = dev_ok(tk.recv_bwd)
                    dybuf = dybuf.at[c_r, s_r].set(
                        jnp.where(ok_r, dx_wire, dybuf[c_r, s_r])
                    )

                # -- forward phase (traced only for forward-phase ticks) --
                if tk.has_fwd:
                    if tk.ingest >= 0:
                        slot0 = tk.fwd[0][2]  # device 0's job, static
                        if shard_vocab:
                            emb = sharded_ingest(tk.ingest)
                        else:
                            emb = jax.lax.cond(
                                is0,
                                lambda m=tk.ingest: gpt.apply_embeddings(
                                    rest_params, cfg, inputs[m], positions[m]
                                ),
                                lambda: jnp.zeros(
                                    (mb_local, seq, cfg.dim),
                                    cfg.compute_dtype,
                                ),
                            )
                        xbuf = xbuf.at[0, slot0].set(
                            jnp.where(is0, emb, xbuf[0, slot0])
                        )
                    fc, fm = dev_i32(tk.fwd, 0), dev_i32(tk.fwd, 1)
                    fs = dev_i32(tk.fwd, 2)
                    y = chunk_call(fc, xbuf[fc, fs], fm, want_aux=False)

                    if tk.head >= 0:
                        # the last device's job this tick IS chunk G-1 of
                        # micro tk.head; its head cotangent stashes at the
                        # (static) head_slot for the same-tick or later
                        # backward (the 1F1B self-trigger).
                        if shard_vocab:
                            y_b = jax.lax.psum(
                                jnp.where(at_last, y, jnp.zeros_like(y)),
                                "stage",
                            )
                            tgt_h = tgts[tk.head]
                            offset = stage * v_local

                            def f(norm_p, lm_k, yy):
                                (l, c), _ = _vocab_slice_ce(
                                    norm_p, lm_k, yy, tgt_h, offset,
                                    v_local, cfg,
                                )
                                return l, c

                            (l_s, c_s), pull_h = jax.vjp(
                                f,
                                rest_params["norm_out"],
                                rest_params["lm_head"]["kernel"],
                                y_b,
                            )
                            dl = jnp.where(is0, 1.0, 0.0).astype(jnp.float32)
                            dnorm, dlm, dyb = pull_h((dl, jnp.float32(0)))
                            dy_head = jax.lax.psum(dyb, "stage")
                            loss_sum = loss_sum + jnp.where(is0, l_s, 0.0)
                            cnt_sum = cnt_sum + jnp.where(is0, c_s, 0.0)
                            grp = {
                                **grp,
                                "norm_out": jax.tree.map(
                                    jnp.add, grp["norm_out"], dnorm
                                ),
                                "lm_head": {
                                    "kernel": grp["lm_head"]["kernel"] + dlm
                                },
                            }
                        else:

                            def head_block(_):
                                def f(rp, yy):
                                    logits = gpt.apply_head(rp, cfg, yy)
                                    return cross_entropy_sum(
                                        logits, tgts[tk.head]
                                    )

                                (l_s, c_s), pull_h = jax.vjp(
                                    f, rest_params, y
                                )
                                drp, dy_l = pull_h(
                                    (jnp.float32(1), jnp.float32(0))
                                )
                                return l_s, c_s, drp, dy_l

                            def no_head(_):
                                return (
                                    jnp.float32(0), jnp.float32(0),
                                    zeros_rest(), jnp.zeros_like(y),
                                )

                            l_s, c_s, drp_head, dy_head = jax.lax.cond(
                                at_last, head_block, no_head, None
                            )
                            loss_sum = loss_sum + l_s
                            cnt_sum = cnt_sum + c_s
                            grp = jax.tree.map(jnp.add, grp, drp_head)
                        dybuf = dybuf.at[V - 1, tk.head_slot].set(
                            jnp.where(
                                at_last,
                                dy_head.astype(dybuf.dtype),
                                dybuf[V - 1, tk.head_slot],
                            )
                        )

                    if tk.ship_fwd:
                        y_wire = jax.lax.ppermute(y, "stage", ring_f)

                # -- backward phase (traced only for backward-phase ticks)
                if tk.has_bwd:
                    bc, bm = dev_i32(tk.bwd, 0), dev_i32(tk.bwd, 1)
                    bs = dev_i32(tk.bwd, 2)
                    bok = dev_ok(tk.bwd)
                    dy_eff = jnp.where(bok, dybuf[bc, bs], 0).astype(
                        cfg.compute_dtype
                    )
                    if moe:
                        d_aux = jnp.where(bok, alpha, 0.0)
                        dlp, dx = chunk_vjp(bc, xbuf[bc, bs], bm, dy_eff, d_aux)
                    else:
                        dlp, dx = chunk_vjp(bc, xbuf[bc, bs], bm, dy_eff, None)
                    # a zero cotangent makes dlp exactly zero (a vjp is
                    # linear), so jobless devices scatter nothing real
                    glp = jax.tree.map(
                        lambda g, d: g.at[bc].add(d), glp, dlp
                    )

                    if tk.emb >= 0:
                        # device 0's backward this tick is (chunk 0, micro
                        # tk.emb): its input cotangent IS d(embedding).
                        dx_gated = jnp.where(bok & is0, dx, 0).astype(
                            jnp.float32
                        )
                        e = tk.emb
                        if shard_vocab:
                            d_emb = jax.lax.psum(dx_gated, "stage")
                            rel = inputs[e] - stage * v_local
                            ok = (rel >= 0) & (rel < v_local)
                            d_tok = (
                                jnp.zeros_like(grp["embeddings"]["token"])
                                .at[jnp.where(ok, rel, v_local)]
                                .add(
                                    jnp.where(ok[..., None], d_emb, 0.0),
                                    mode="drop",
                                )
                            )
                            d_pos = (
                                jnp.zeros_like(grp["embeddings"]["position"])
                                .at[positions[e]]
                                .add(d_emb)
                            )
                            grp = add_emb_grads(
                                grp, d_tok, jnp.where(is0, d_pos, 0.0)
                            )
                        else:

                            def emb_bwd(_):
                                d_tok = (
                                    jnp.zeros_like(
                                        grp["embeddings"]["token"]
                                    )
                                    .at[inputs[e]]
                                    .add(dx_gated)
                                )
                                d_pos = (
                                    jnp.zeros_like(
                                        grp["embeddings"]["position"]
                                    )
                                    .at[positions[e]]
                                    .add(dx_gated)
                                )
                                return d_tok, d_pos

                            def no_emb(_):
                                return (
                                    jnp.zeros_like(
                                        grp["embeddings"]["token"]
                                    ),
                                    jnp.zeros_like(
                                        grp["embeddings"]["position"]
                                    ),
                                )

                            d_tok, d_pos = jax.lax.cond(
                                is0, emb_bwd, no_emb, None
                            )
                            grp = add_emb_grads(grp, d_tok, d_pos)

                    if tk.ship_bwd:
                        dx_wire = jax.lax.ppermute(
                            dx.astype(cfg.compute_dtype), "stage", ring_b
                        )

            axes = tuple(self.mesh.axis_names)
            loss_sum = jax.lax.psum(loss_sum, axes)
            cnt_sum = jax.lax.psum(cnt_sum, axes)
            glp = jax.tree.map(
                lambda g: g.reshape(V * per_chunk, *g.shape[2:]), glp
            )
            if data is not None:
                glp = jax.tree.map(lambda g: jax.lax.psum(g, data), glp)

            def reduce_rest(g, is_sharded):
                if is_sharded:
                    return jax.lax.psum(g, data) if data is not None else g
                return jax.lax.psum(g, axes)

            grp = jax.tree.map(reduce_rest, grp, rest_sharded)
            return loss_sum, cnt_sum, glp, grp

        loss_sum, count, glp, grp = schedule(
            env["layers"], env["rest"], inputs_a, positions_a, masks_a, tgts_a
        )
        denom = jnp.maximum(count, 1.0)
        grads = {**grp, "layers": glp}
        grads = jax.tree.map(lambda g: (g / denom).astype(g.dtype), grads)
        return loss_sum / denom, grads

    def _interleaved_eval(
        self, params, cfg: gpt.GPTConfig, batch, targets,
        with_accuracy: bool = False, rng=None, aux_out: list | None = None,
    ):
        """Forward-only interleaved schedule (eval at V > 1): the same
        tick skeleton with include_backward=False — fwd + head units only,
        with the parent's global-argmax accuracy idioms."""
        cfg = self._moe_cfg(cfg)
        env = self._interleave_prelude(params, cfg, batch, targets)
        S, V, M = env["S"], env["V"], env["M"]
        per_chunk, seq = env["per_chunk"], env["seq"]
        data, shard_vocab = env["data"], env["shard_vocab"]
        v_local, v_pad = env["v_local"], env["v_pad"]
        moe_aux = cfg.num_experts > 0 and aux_out is not None
        sched = cached_schedule(S, V, M, include_backward=False)
        depth = sched.depth
        padded = env["padded"]

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(
                P("stage"), env["rest_specs"],
                env["batch_spec"], env["batch_spec"],
                env["batch_spec"], env["batch_spec"],
            ),
            out_specs=(P(),) * (4 if moe_aux else 3),
            check_vma=False,
        )
        def schedule(local_layers, rest_params, inputs, positions, masks, tgts):
            stage = jax.lax.axis_index("stage")
            last = S - 1
            is0 = stage == 0
            at_last = stage == last
            mb_local = inputs.shape[1]

            chunks = jax.tree.map(
                lambda l: l.reshape(V, per_chunk, *l.shape[1:]), local_layers
            )
            if padded == cfg.num_layers:
                active_all = None
            else:
                g_of_c = jnp.arange(V) * S + stage
                layer_idx = (
                    g_of_c[:, None] * per_chunk + jnp.arange(per_chunk)[None, :]
                )
                active_all = layer_idx < cfg.num_layers

            def key_for(c, mi):
                if rng is None:
                    return None
                lin = (c * S + stage) * M + mi
                if data is not None:
                    lin = lin * self.data_size + jax.lax.axis_index(data)
                return jax.random.fold_in(rng, lin)

            def chunk_call(c, x_in, mi, want_aux):
                lp = jax.tree.map(
                    lambda l: jax.lax.dynamic_index_in_dim(
                        l, c, 0, keepdims=False
                    ),
                    chunks,
                )
                act = (
                    None
                    if active_all is None
                    else jax.lax.dynamic_index_in_dim(
                        active_all, c, 0, keepdims=False
                    )
                )
                k = key_for(c, mi)
                al: list = [] if want_aux else None
                y = gpt.apply_decoder_layers(
                    lp, cfg, x_in, masks[mi],
                    rng=k, deterministic=k is None, active=act, aux_out=al,
                )
                if want_aux:
                    return y, (al[0] if al else jnp.float32(0))
                return y

            def sharded_ingest(mi):
                rel = inputs[mi] - stage * v_local
                ok = (rel >= 0) & (rel < v_local)
                part = jnp.where(
                    ok[..., None],
                    jnp.take(
                        rest_params["embeddings"]["token"],
                        jnp.where(ok, rel, 0),
                        axis=0,
                    ),
                    0.0,
                )
                emb = jax.lax.psum(part, "stage") + jnp.take(
                    rest_params["embeddings"]["position"], positions[mi], axis=0
                )
                return emb.astype(cfg.compute_dtype)

            def dev_i32(entries, pos):
                return jnp.asarray(
                    [0 if e is None else e[pos] for e in entries], jnp.int32
                )[stage]

            def dev_ok(entries):
                return jnp.asarray([e is not None for e in entries])[stage]

            ring_f = [(i, (i + 1) % S) for i in range(S)]

            xbuf = jnp.zeros(
                (V, depth, mb_local, seq, cfg.dim), cfg.compute_dtype
            )
            loss_sum = jnp.float32(0)
            cnt_sum = jnp.float32(0)
            correct = jnp.float32(0)
            aux_sum = jnp.float32(0)
            y_wire = jnp.zeros((mb_local, seq, cfg.dim), cfg.compute_dtype)

            for tk in sched.ticks:
                if any(e is not None for e in tk.recv_fwd):
                    c_r, s_r = dev_i32(tk.recv_fwd, 0), dev_i32(tk.recv_fwd, 1)
                    ok_r = dev_ok(tk.recv_fwd)
                    xbuf = xbuf.at[c_r, s_r].set(
                        jnp.where(ok_r, y_wire, xbuf[c_r, s_r])
                    )
                if not tk.has_fwd:
                    continue
                if tk.ingest >= 0:
                    slot0 = tk.fwd[0][2]
                    if shard_vocab:
                        emb = sharded_ingest(tk.ingest)
                    else:
                        emb = jax.lax.cond(
                            is0,
                            lambda m=tk.ingest: gpt.apply_embeddings(
                                rest_params, cfg, inputs[m], positions[m]
                            ),
                            lambda: jnp.zeros(
                                (mb_local, seq, cfg.dim), cfg.compute_dtype
                            ),
                        )
                    xbuf = xbuf.at[0, slot0].set(
                        jnp.where(is0, emb, xbuf[0, slot0])
                    )
                fc, fm = dev_i32(tk.fwd, 0), dev_i32(tk.fwd, 1)
                fs = dev_i32(tk.fwd, 2)
                if moe_aux:
                    fok = dev_ok(tk.fwd)
                    y, aux = chunk_call(fc, xbuf[fc, fs], fm, want_aux=True)
                    aux_sum = aux_sum + jnp.where(fok, aux, 0.0)
                else:
                    y = chunk_call(fc, xbuf[fc, fs], fm, want_aux=False)

                if tk.head >= 0:
                    tgt_h = tgts[tk.head]
                    if shard_vocab:
                        y_b = psum_bcast(
                            jnp.where(at_last, y, jnp.zeros_like(y)), "stage"
                        )
                        offset = stage * v_local
                        (l_s, c_s), local_logits = _vocab_slice_ce(
                            rest_params["norm_out"],
                            rest_params["lm_head"]["kernel"],
                            y_b, tgt_h, offset, v_local, cfg,
                        )
                        if with_accuracy:
                            lf = local_logits.astype(jnp.float32)
                            lmax = jnp.max(lf, axis=-1)
                            larg = jnp.argmax(lf, axis=-1) + offset
                            gmax = jax.lax.pmax(lmax, "stage")
                            preds = jax.lax.pmin(
                                jnp.where(lmax >= gmax, larg, v_pad), "stage"
                            )
                            valid = tgt_h != -100
                            corr = jnp.sum(
                                jnp.where(valid, preds == tgt_h, False)
                            ).astype(jnp.float32)
                        else:
                            corr = jnp.float32(0)
                        # collective CE totals are replicated — count them
                        # once per data shard (accumulate on stage 0)
                        loss_sum = loss_sum + jnp.where(is0, l_s, 0.0)
                        cnt_sum = cnt_sum + jnp.where(is0, c_s, 0.0)
                        correct = correct + jnp.where(is0, corr, 0.0)
                    else:

                        def head_loss(_):
                            logits = gpt.apply_head(rest_params, cfg, y)
                            l_s, c_s = cross_entropy_sum(logits, tgt_h)
                            if with_accuracy:
                                valid = tgt_h != -100
                                preds = jnp.argmax(logits, axis=-1)
                                corr = jnp.sum(
                                    jnp.where(valid, preds == tgt_h, False)
                                ).astype(jnp.float32)
                            else:
                                corr = jnp.float32(0)
                            return l_s, c_s, corr

                        def no_loss(_):
                            return (
                                jnp.float32(0), jnp.float32(0),
                                jnp.float32(0),
                            )

                        l_s, c_s, corr = jax.lax.cond(
                            at_last, head_loss, no_loss, None
                        )
                        loss_sum = loss_sum + l_s
                        cnt_sum = cnt_sum + c_s
                        correct = correct + corr

                if tk.ship_fwd:
                    y_wire = jax.lax.ppermute(y, "stage", ring_f)

            axes = tuple(self.mesh.axis_names)
            loss_sum = jax.lax.psum(loss_sum, axes)
            cnt_sum = jax.lax.psum(cnt_sum, axes)
            correct = jax.lax.psum(correct, axes)
            if moe_aux:
                return loss_sum, cnt_sum, correct, jax.lax.psum(aux_sum, axes)
            return loss_sum, cnt_sum, correct

        outs = schedule(
            env["layers"], env["rest"],
            env["inputs"], env["positions"], env["masks"], env["tgts"],
        )
        loss_sum, count, correct = outs[:3]
        if moe_aux:
            aux_out.append(outs[3] / (M * self.data_size))
        denom = jnp.maximum(count, 1.0)
        return loss_sum / denom, correct / denom * 100.0

    def pipe_comm(self, cfg: gpt.GPTConfig, *, global_batch: int, seq: int,
                  phase: str = "train"):
        """Closed-form schedule-collective plan for one compiled step
        (analysis/plan.py train_comm_plan discovers this hook). The flat
        V=1 dense machine carries its hops inside a scan (one HLO
        instruction regardless of tick count) — no closed form is claimed
        there. The interleaved machine is unrolled with static shipping
        ticks, so the collective-permute count in the compiled HLO is
        exactly the schedule's ship count at activation-sized payloads;
        MoE worlds additionally pin all-to-all to ZERO (the pallas
        dispatch is collective-free — the a2a-free guard hlolint checks).
        `phase="eval"` prices the forward-only schedule (no dx hops).
        """
        if cfg.virtual_stages == 1 and cfg.num_experts == 0:
            return None
        sched = cached_schedule(
            self.num_stages, cfg.virtual_stages, self.num_microbatches,
            include_backward=(phase == "train"),
        )
        mb_local = global_batch // (self.num_microbatches * self.data_size)
        payload = (
            mb_local * seq * cfg.dim * jnp.dtype(cfg.compute_dtype).itemsize
        )
        count = (
            sched.stats["ship_fwd_ticks"] + sched.stats["ship_bwd_ticks"]
        )
        ops = {
            "collective-permute": {"count": count, "bytes": count * payload}
        }
        if cfg.num_experts > 0 and self.data_size == 1:
            # the a2a-free guard: the meshless pallas dispatch adds ZERO
            # all-to-alls, so a surplus one means a buffer dispatch leaked
            # in. Only claimable on a stage-only mesh — with a data axis
            # GSPMD reshards the batch ingest via tiny s32/pred
            # all-to-alls that are not ours to pin.
            ops["all-to-all"] = {"count": 0, "bytes": 0}
        return ops
