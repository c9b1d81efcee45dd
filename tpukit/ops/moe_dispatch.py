"""Mixture-of-experts expert dispatch/combine implementations.

Three interchangeable dataflows sit behind `GPTConfig.moe_dispatch`; all
compute the SAME math (routing, per-row capacity, expert FFN, gated
combine, load-balance aux) so they are loss/grad-parity-equal and the
parity goldens in tests/test_moe.py hold across any of them:

  - "xla" (default): the original global one-hot einsum formulation.
    Dispatch is `[B,S,E,C] x [B,S,D] -> [E,B,C,D]`, combine is the
    transposed einsum. On one device (or pure DP) this is the fastest
    spelling — everything is a batched matmul. Under ExpertParallel it
    is also what GSPMD must partition, and the round-5 multichip dryrun
    showed it CANNOT: the backward of the dispatch einsum
    (`jvp(bsec,bsd->ebcd)/transpose`) makes the SPMD partitioner fall
    back to "[SPMD] Involuntary full rematerialization" — it replicates
    the tensor and re-partitions it, exactly the all-traffic pattern
    expert parallelism exists to avoid (MULTICHIP_r05.json).

  - "a2a": the explicit shard_map formulation for ExpertParallel.
    Inside the per-device block each device packs its LOCAL rows into
    `[E, C_local, D]` capacity buffers (laid out `[E, B_local, C, D]` —
    C_local = B_local*C, the per-row capacity C of the xla path so token
    dropping is identical), exchanges them with a hand-placed
    `lax.all_to_all` over the `expert` mesh axis, runs the local expert
    shard's FFN on `[E_local, ep*B_local, C, D]`, and returns results
    with the mirrored all_to_all. No custom VJP is needed: the
    formulation is symmetric — `lax.all_to_all`'s transpose is the
    inverse all_to_all and the pack/combine einsums transpose to local
    einsums — so the BACKWARD is also exactly one all_to_all pair per
    layer, never a GSPMD replicate-repartition (asserted against the
    optimized HLO in tests/test_moe.py and the multichip dryrun).

  - "pallas" (tpukit/ops/moe_gemm.py, round 11): the fused grouped-expert
    GEMM. Meshless it sorts token rows by assigned expert and runs a
    blocked segment GEMM — no `[E, B, C, D]` capacity buffer, no padding
    FLOPs, dropless unless `cfg.moe_capacity` is explicitly set. Under
    ExpertParallel it composes AFTER the a2a exchange: the same shard_map
    block as "a2a" (same collectives, same byte audit) with the local
    expert FFN routed through the kernel. The exchange block is shared
    code (`_moe_ffn_exchange`, parametrized over the local expert-FFN
    implementation), so the collective schedule — and the closed-form
    byte audit against it — cannot drift between the two.

Collectives are hand-scheduled rather than compiler-inferred — the core
lesson of the collectives literature (PAPERS.md: "The Big Send-off",
GC3). `expected_a2a` is the audit half: the closed-form per-device
all-to-all payload the compiled HLO must show, consumed by fit()'s xla
telemetry record, bench.py's `moe_ep_comm` probe and the dryrun audit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from tpukit.ops import quant_comm


def moe_capacity(cfg, seq_len: int) -> int:
    """Per-row expert capacity. Derived from the STATIC position-table size
    (width invariance: a row's dispatch is identical whatever buffer padding
    surrounds it) scaled by the routed-experts count (top-k generates k*S
    assignments per row — the GShard convention), then clamped to the call
    width: a row position can never reach seq_len, so the clamp is
    output-identical while keeping short decode buffers cheap.

    `cfg.moe_capacity > 0` overrides the factor-derived value (still
    clamped to the call width) for EVERY dispatch impl, so an explicit
    capacity produces the same drop set on "xla", "a2a" and the capacity
    mode of "pallas" — the bit-identical drop-parity contract
    tests/test_moe.py asserts."""
    if cfg.moe_capacity > 0:
        return min(cfg.moe_capacity, seq_len)
    top_k = cfg.router_top_k
    capacity = max(
        1,
        int(
            -(-cfg.max_position_embeddings * top_k * cfg.expert_capacity_factor
              // cfg.num_experts)
        ),
    )
    return min(capacity, seq_len)


def _route_topk(x, router_kernel, cfg):
    """Shared routing front half: f32 router softmax and the top-k choice.
    Row-local math — identical whether `x` is the global batch (xla/pallas
    paths) or one device's shard (a2a path). This is the ONE place the
    discrete choice is computed, so every dispatch impl routes each token
    to bit-identical experts.

    Returns (xc, top_idx, top_vals, probs, assign):
      xc       [B,S,D]  x in the compute dtype
      top_idx  [B,S,K]  int32 chosen expert ids
      top_vals [B,S,K]  f32 raw router probability of each chosen expert
      probs    [B,S,E]  f32 full softmax (aux statistics)
      assign   [B,S,E]  f32 0/1 chosen-expert mask (aux statistics + drops)
    """
    xc = x.astype(cfg.compute_dtype)
    # router math is f32 (softmax stability under bf16 compute)
    logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32), router_kernel.astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)  # [B, S, E] f32
    top_vals, top_idx = jax.lax.top_k(probs, cfg.router_top_k)  # [B, S, K]
    # per-(token, expert) assignment; the k chosen experts are distinct,
    # so the one-hot sum stays 0/1-valued
    choice_oh = jax.nn.one_hot(top_idx, cfg.num_experts, dtype=jnp.float32)
    assign = jnp.sum(choice_oh, axis=2)  # [B, S, E]
    return xc, top_idx, top_vals, probs, assign


def _slot_positions(assign):
    """[B,S,E] position of each token in its expert's per-row buffer
    (cumsum along the sequence is causal: later tokens never evict earlier
    ones); -1 where unassigned. The single spelling of the buffer-position
    rule — both the kept mask and the slot one-hot derive from it."""
    return jnp.cumsum(assign, axis=1) * assign - 1.0


def _kept_mask(assign, capacity: int):
    """[B,S,E] 0/1 mask of assignments that SURVIVE the per-row capacity
    (position >= capacity drops). The single spelling of the drop rule —
    the pallas path's capacity mode reuses it verbatim, which is what
    makes its drop set bit-identical to the xla/a2a buffers'."""
    return assign * (_slot_positions(assign) < capacity)


def _route(x, router_kernel, cfg):
    """Routing + the per-row fixed-capacity dispatch one-hot (the buffer
    formulations: "xla" and the a2a exchange).

    Returns (xc, dispatch, gate_map, probs, assign):
      xc       [B,S,D]  x in the compute dtype
      dispatch [B,S,E,C] 0/1 (compute dtype): token (b,s) -> slot c of expert e
      gate_map [B,S,E]  f32 raw router probability of each chosen expert
      probs    [B,S,E]  f32 full softmax (aux statistics)
      assign   [B,S,E]  f32 0/1 chosen-expert mask (aux statistics)
    """
    capacity = moe_capacity(cfg, x.shape[1])
    xc, top_idx, top_vals, probs, assign = _route_topk(x, router_kernel, cfg)
    choice_oh = jax.nn.one_hot(top_idx, cfg.num_experts, dtype=jnp.float32)
    gate_map = jnp.sum(top_vals[..., None] * choice_oh, axis=2)  # [B, S, E]

    kept = _kept_mask(assign, capacity)
    slot = jnp.clip(_slot_positions(assign), 0, capacity - 1).astype(jnp.int32)
    dispatch = (
        kept[..., None] * jax.nn.one_hot(slot, capacity, dtype=jnp.float32)
    ).astype(cfg.compute_dtype)  # [B, S, E, C]
    return xc, dispatch, gate_map, probs, assign


def _expert_ffn(experts, expert_in, dtype):
    """The reference FFN (up -> relu -> down -> relu, the double-relu quirk,
    models/gpt.py:33-41) as batched matmuls over an expert-major buffer
    `[E(,_local), b, C, D]`. Works on the full bank or one device's shard."""
    h = jnp.einsum(
        "ebcd,edf->ebcf", expert_in, experts["up"]["kernel"].astype(dtype)
    ) + experts["up"]["bias"].astype(dtype)[:, None, None, :]
    h = jax.nn.relu(h)
    h = jnp.einsum(
        "ebcf,efd->ebcd", h, experts["down"]["kernel"].astype(dtype)
    ) + experts["down"]["bias"].astype(dtype)[:, None, None, :]
    return jax.nn.relu(h)


def _aux_stats(probs, assign, pad_mask, cfg):
    """Switch load-balance statistics as a (numerator, denominator) pair of
    row sums, so the a2a path can psum the pair across row shards and both
    paths finish with `aux = E * num / max(den, 1)`.

    With a pad_mask and cfg.moe_aux_mask_pads (the Switch convention,
    ADVICE r5 #2): statistics over REAL tokens only, per-row normalization
    by the real-token count, all-pad rows dropped from the mean. Otherwise:
    the pre-round-8 any-position average (den = row count)."""
    top_k = cfg.router_top_k
    if pad_mask is not None and cfg.moe_aux_mask_pads:
        real = (~pad_mask).astype(jnp.float32)  # [B, S]
        count = jnp.maximum(jnp.sum(real, axis=1), 1.0)  # [B]
        frac_tokens = (
            jnp.einsum("bse,bs->be", assign, real) / count[:, None] / top_k
        )
        mean_prob = jnp.einsum("bse,bs->be", probs, real) / count[:, None]
        row_real = (jnp.sum(real, axis=1) > 0).astype(jnp.float32)  # [B]
        num = jnp.sum(jnp.sum(frac_tokens * mean_prob, axis=-1) * row_real)
        den = jnp.sum(row_real)
        return num, den
    # any-position average (cfg.moe_aux_mask_pads=False, or call sites
    # without a mask — the cached decode path), kept selectable so
    # pre-masking training curves stay reproducible
    frac_tokens = jnp.mean(assign, axis=1) / top_k  # [B, E]
    mean_prob = jnp.mean(probs, axis=1)  # [B, E]
    num = jnp.sum(jnp.sum(frac_tokens * mean_prob, axis=-1))
    den = jnp.float32(assign.shape[0])
    return num, den


def moe_ffn_xla(layer, cfg, x, pad_mask=None):
    """The einsum formulation: global one-hot dispatch/combine, partitioning
    left to GSPMD. Returns (out [B,S,D], aux scalar). The right spelling on
    one device and under pure data parallelism; see the module docstring for
    why ExpertParallel routes around it."""
    experts = layer["ffn"]["experts"]
    xc, dispatch, gate_map, probs, assign = _route(
        x, layer["ffn"]["router"]["kernel"], cfg
    )
    expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, xc)
    h = _expert_ffn(experts, expert_in, cfg.compute_dtype)
    # combine weighted by each (token, expert)'s gate — for top_k=1 this
    # is the Switch combine exactly (one expert, raw top prob)
    out = jnp.einsum(
        "ebcd,bsec->bsd", h,
        dispatch * gate_map.astype(cfg.compute_dtype)[..., None],
    )
    num, den = _aux_stats(probs, assign, pad_mask, cfg)
    aux = cfg.num_experts * num / jnp.maximum(den, 1.0)
    return out, aux


def moe_ffn_a2a(layer, cfg, x, pad_mask=None):
    """The explicit shard_map formulation for ExpertParallel (see module
    docstring). Requires `cfg.moe_mesh` (the strategy's `(data?, expert)`
    mesh — ExpertParallel.loss_fn injects it alongside moe_dispatch="a2a").

    Per-device block: route local rows -> pack `[E, B_local, C, D]` ->
    all_to_all over `expert` -> local expert shard FFN on
    `[E_local, ep*B_local, C, D]` -> mirrored all_to_all -> gated local
    combine. The aux statistics are local row sums psummed over the row
    axes, so the scalar matches the global formula. Degenerate axes
    (expert mesh size 1) skip the collective but keep the same block, so
    single-group meshes still share one code path."""
    return _moe_ffn_exchange(layer, cfg, x, pad_mask, _expert_ffn, "a2a")


def _moe_ffn_exchange(layer, cfg, x, pad_mask, expert_ffn, name):
    """The shared ExpertParallel exchange block (docstring at moe_ffn_a2a).
    `expert_ffn(experts_l, expert_in, dtype)` computes the local expert
    shard's FFN on the post-exchange `[E_local, ep*B_local, C, D]` buffer:
    the batched einsums for "a2a", the grouped segment GEMM of
    tpukit/ops/moe_gemm.py for "pallas". Everything around it — pack,
    collectives, combine, aux — is ONE copy of code, so the byte audit
    (`expected_a2a`) holds for both by construction."""
    mesh = cfg.moe_mesh
    if mesh is None:
        raise ValueError(
            f"moe_dispatch={name!r} under ExpertParallel needs cfg.moe_mesh "
            f"(a mesh with an 'expert' axis) — ExpertParallel injects it; "
            f"set moe_dispatch='xla' for meshless buffer execution"
        )
    if "expert" not in mesh.axis_names:
        raise ValueError(
            f"moe_dispatch={name!r} needs an 'expert' axis in cfg.moe_mesh, "
            f"got axes {mesh.axis_names}"
        )
    ep = mesh.shape["expert"]
    if cfg.num_experts % ep:
        raise ValueError(
            f"num_experts {cfg.num_experts} must divide over the {ep}-way "
            f"expert mesh axis for {name} dispatch"
        )
    # rows shard over every available mesh axis — ExpertParallel.batch_spec
    row_axes = tuple(a for a in ("data", "expert") if a in mesh.axis_names)
    x_spec = P(row_axes, None, None)
    mask_spec = P(row_axes, None)
    has_mask = pad_mask is not None
    mask_arr = pad_mask if has_mask else jnp.zeros(x.shape[:2], bool)

    def block(x_l, mask_l, router_kernel, experts_l):
        xc, dispatch, gate_map, probs, assign = _route(x_l, router_kernel, cfg)
        # pack local rows into per-expert capacity buffers [E, B_local, C, D]
        expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, xc)
        if ep > 1:
            # exchange: send the expert-block destined for peer j, receive
            # every peer's block for OUR experts -> [E_local, ep*B_local, C, D].
            # cfg.comm_dtype selects the wire payload (quant_comm round 12):
            # "f32" emits the exact pre-round-12 lax.all_to_all; "int8"
            # moves block-scaled payloads (scale sidecar packed into the
            # same op, custom vjp keeps the backward a mirrored exchange —
            # op schedule unchanged). Routing happened BEFORE the exchange
            # on exact local values, so quantization perturbs expert
            # activations, never the discrete routing decisions.
            expert_in = quant_comm.exchange_all_to_all(
                expert_in, "expert", ep, "dispatch", dtype=cfg.comm_dtype,
                stochastic=cfg.quant_stochastic,
            )
        h = expert_ffn(experts_l, expert_in, cfg.compute_dtype)
        if ep > 1:
            # mirrored return trip -> [E, B_local, C, D] back on the source
            h = quant_comm.exchange_all_to_all(
                h, "expert", ep, "combine", dtype=cfg.comm_dtype,
                stochastic=cfg.quant_stochastic,
            )
        out = jnp.einsum(
            "ebcd,bsec->bsd", h,
            dispatch * gate_map.astype(cfg.compute_dtype)[..., None],
        )
        num, den = _aux_stats(probs, assign, mask_l if has_mask else None, cfg)
        num = jax.lax.psum(num, row_axes)
        den = jax.lax.psum(den, row_axes)
        aux = cfg.num_experts * num / jnp.maximum(den, 1.0)
        return out, aux

    out, aux = shard_map(
        block,
        mesh=mesh,
        in_specs=(x_spec, mask_spec, P(), P("expert")),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, mask_arr, layer["ffn"]["router"]["kernel"], layer["ffn"]["experts"])
    return out, aux


def expected_a2a(cfg, data_size: int, expert_size: int, global_batch: int,
                 seq: int, backend: str | None = None) -> dict | None:
    """Closed-form per-device all-to-all payload of the a2a dispatch — what
    the optimized HLO of one step must show (the audit side of
    hand-scheduling the collective). Round 16: reaches the hlolint rule
    engine through `ExpertParallel.dispatch_comm` →
    `analysis.plan.train_comm_plan` (DESIGN.md §15); the `wire` marker
    below doubles as the wire-upcast rule's declared payload dtype.

    Per layer each device moves its `[E, B_local, C, D]` buffer out and the
    results back: 2 all_to_alls forward, and — because the formulation is
    its own transpose — exactly 2 more in the backward (6 with
    cfg.remat_layers: the checkpointed forward re-runs). Counts are HLO *op
    instances*: the scanned layer stack (cfg.scan_layers) emits each op
    once in the scan body regardless of depth, so `layers_visible` is 1
    there. A 1-way expert axis moves nothing (the block skips the
    collective). Returns {"buffer_bytes", "train": {count, bytes, wire},
    "eval": {...}} — eval uses bf16 (the always-on eval autocast) and is
    forward-only.

    Payload dtype (round 12): with cfg.comm_dtype "int8" every exchange op
    moves the PACKED block-scaled buffer (int8 values + bitcast f32 scale
    sidecar, quant_comm.packed_bytes — op counts unchanged); "bf16" casts
    the buffer; "f32" is the raw compute-dtype buffer. `backend` resolves
    the dtype each payload actually travels at: XLA:CPU's float
    normalization upcasts bf16 buffers to f32 on the wire (the round-10
    eval-audit divergence, now priced into the formula instead of excused
    by the renderer), while int8 payloads audit exactly everywhere. Pass
    backend=None for nominal accelerator sizes (the pre-round-12
    behavior)."""
    if cfg.num_experts <= 0:
        return None
    zero = {"count": 0, "bytes": 0}
    if expert_size <= 1:
        return {"buffer_bytes": 0, "train": dict(zero), "eval": dict(zero)}
    capacity = moe_capacity(cfg, seq)
    rows = data_size * expert_size
    if global_batch % rows:
        return None  # undividable batch never reaches the a2a path
    b_local = global_batch // rows
    n_buf = cfg.num_experts * b_local * capacity * cfg.dim  # buffer elems
    layers_visible = 1 if cfg.scan_layers else cfg.num_layers
    train_ops = 6 if cfg.remat_layers else 4
    comm = getattr(cfg, "comm_dtype", "f32")

    def op_bytes(compute_dtype):
        """Result bytes of ONE exchange op, comm/backend-aware."""
        if comm == "int8":
            # ep packed rows, each covering the destination group's elems
            return expert_size * quant_comm.packed_bytes(n_buf // expert_size)
        if comm == "bf16":
            return n_buf * quant_comm.wire_itemsize("bf16", backend)
        name = jnp.dtype(compute_dtype).name
        if name == "bfloat16":
            return n_buf * quant_comm.wire_itemsize("bf16", backend)
        return n_buf * jnp.dtype(compute_dtype).itemsize

    def wire_name(compute_dtype):
        if comm == "int8":
            return "s8-packed"
        if comm == "bf16" or jnp.dtype(compute_dtype).name == "bfloat16":
            return "f32" if backend == "cpu" else "bf16"
        return jnp.dtype(compute_dtype).name

    def entry(compute_dtype, ops_per_layer):
        count = ops_per_layer * layers_visible
        rec = {"count": count, "bytes": count * op_bytes(compute_dtype)}
        if backend is not None:
            # marker: this expectation already prices in the backend's
            # wire dtype — renderers must compare EXACTLY, no CPU excuse
            rec["wire"] = wire_name(compute_dtype)
        return rec

    return {
        "buffer_bytes": n_buf * jnp.dtype(cfg.compute_dtype).itemsize,
        "train": entry(cfg.compute_dtype, train_ops),
        "eval": entry(jnp.bfloat16, 2),
    }


# --------------------------------------------------------------------------
# One chip's share of an expert layer (the latent family, tpukit/model/
# latent.py): the router keeps its published width and scores every expert;
# this chip holds a contiguous range of them and computes their part of the
# result for the rows routed to them. No exchange, no capacity, nothing
# dropped, and nothing stands in for the experts that live elsewhere.
# --------------------------------------------------------------------------


@jax.named_scope("router")
def sigmoid_topk_route(x, router_kernel, select_bias, top_k: int, scale: float = 1.0, groups: int = 1,
                       topk_groups: int = 1):
    """Sigmoid scores over ALL experts, the `top_k` of largest `score + bias`
    (the bias steers the choice only, `noaux_tc`; `None`: the scores alone
    choose), gates = the chosen scores normalised over the chosen
    (`norm_topk_prob`), whoever holds them, times `scale`.
    x `[T, D]`; returns `(idx [T, k] int32, gates [T, k] float32)`. Scores are
    float32 at full matmul precision: a bf16 pass reorders near-ties at the
    k-th place.

    `groups` > 1: node-limited routing (DeepSeek-V3's `Gate`): the experts in
    `groups` equal groups, a group's score the sum of its two largest biased
    scores (its largest without a bias), and the `top_k` chosen among the
    experts of the best `topk_groups` groups only."""
    logits = jnp.matmul(x.astype(jnp.float32), router_kernel.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    choice = scores if select_bias is None else scores + select_bias.astype(jnp.float32)
    if groups > 1:
        choice = _limit_to_groups(choice, groups, topk_groups, biased=select_bias is not None)
    _, idx = jax.lax.top_k(choice, top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), gates if scale == 1.0 else scale * gates


@jax.named_scope("group_route")
def _limit_to_groups(choice, groups: int, topk_groups: int, biased: bool):
    """`choice [T, E]` with every expert outside the best `topk_groups` of
    `groups` equal groups set to -inf; a group's score is the sum of its two
    largest entries (its largest where `biased` is False)."""
    t, e = choice.shape
    by_group = choice.reshape(t, groups, e // groups)
    top = jax.lax.top_k(by_group, 2 if biased else 1)[0]
    _, best = jax.lax.top_k(jnp.sum(top, axis=-1), topk_groups)  # [T, topk_groups]
    kept = jnp.any(best[:, :, None] == jnp.arange(groups)[None, None, :], axis=1)  # [T, groups]
    return jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(t, e)


@jax.named_scope("experts")
def held_experts_ffn(x, idx, gates, experts, expert_lo: int, compute_dtype, row_mask=None, activation=None,
                     every_row: bool = False):
    """`sum over chosen AND held experts of gate x E(x)` for `x [T, D]`, with
    `E(x) = (act(x Wg) * x Wu) Wd` and `experts = {gate, up [E, D, F], down
    [E, F, D]}` the held range `[expert_lo, expert_lo + E)`. `act` is SiLU, or
    `activation(z [R, F] float32, expert [R] int32)` over the sorted rows,
    `expert[r]` the held expert row `r` was given to (`E` for a row of none):
    an activation that reduces over the expert's width and has weights of its
    expert's own runs between the grouped matmuls. Dropless: the
    (row, choice) pairs are sorted by held expert (pairs of absent experts
    last) and the three products run as grouped matmuls (`lax.ragged_dot`)
    over exactly the rows each expert was given; the static row count is the
    worst case `T x k`, the computed one is what was routed here. Rows with
    `row_mask` False (a frozen decode lane) are given to no expert.

    `every_row`: every held expert computes EVERY row and the gates keep what
    was routed (`activation`'s `expert` is then `[E, 1]` against `z [E, T,
    F]`). For the handful of rows of a decode tick the cost is the read of
    the experts' weights either way; the grouped matmul skips an expert that
    got no row, so its time follows how the rows fell, and this form's does
    not. The same sum, every term rounded as the grouped form rounds it.

    Returns `(y [T, D] float32, rows [E] int32)`, `rows[e]` the rows expert
    `expert_lo + e` computed (was routed, under `every_row`)."""
    t, d = x.shape
    k = idx.shape[1]
    e = experts["gate"].shape[0]
    local = idx - expert_lo
    held = (local >= 0) & (local < e)
    if row_mask is not None:
        held = held & row_mask[:, None]
    if every_row:
        xc = x.astype(compute_dtype)
        each = lambda spec, a, w, out: jnp.einsum(spec, a, w.astype(compute_dtype), preferred_element_type=out)  # noqa: E731
        z = each("td,edf->etf", xc, experts["gate"], jnp.float32)
        act = jax.nn.silu(z) if activation is None else activation(z, jnp.arange(e, dtype=jnp.int32)[:, None])
        act = (act * each("td,edf->etf", xc, experts["up"], jnp.float32)).astype(compute_dtype)
        out = each("etf,efd->etd", act, experts["down"], compute_dtype)
        chose = held[:, :, None] & (local[:, :, None] == jnp.arange(e))  # [T, k, E]: row t's choice j is held expert e
        weight = jnp.sum(jnp.where(chose, gates[:, :, None], 0.0), axis=1)  # [T, E]: its gate, 0 where it chose none
        y = jnp.sum((out * weight.T[:, :, None]).astype(compute_dtype).astype(jnp.float32), axis=0)
        return y, jnp.sum(chose, axis=(0, 1)).astype(jnp.int32)
    key = jnp.where(held, local, e).reshape(-1)  # [T*k]; `e` sorts the rest last
    order = jnp.argsort(key, stable=True)
    rows = jnp.zeros((e + 1,), jnp.int32).at[key].add(1)[:e]
    xs = x.astype(compute_dtype)[order // k]  # [T*k, D], sorted by held expert
    dot = lambda a, w, out: jax.lax.ragged_dot(  # noqa: E731
        a, w.astype(compute_dtype), rows, preferred_element_type=out)
    z = dot(xs, experts["gate"], jnp.float32)
    act = ((jax.nn.silu(z) if activation is None else activation(z, key[order]))
           * dot(xs, experts["up"], jnp.float32)).astype(compute_dtype)
    out = dot(act, experts["down"], compute_dtype)  # accumulated in float32, rounded once on the way out
    # rows past the routed ones belong to no group: whatever the kernel left there is dropped
    routed = (jnp.arange(t * k) < jnp.sum(rows))[:, None]
    out = jnp.where(routed, out * gates.reshape(-1)[order][:, None], 0.0).astype(compute_dtype)
    back = jnp.zeros((t * k,), jnp.int32).at[order].set(jnp.arange(t * k, dtype=jnp.int32))
    return out[back].reshape(t, k, d).astype(jnp.float32).sum(axis=1), rows
