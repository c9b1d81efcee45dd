"""Bucketed, overlap-scheduled gradient collectives (`--grad_buckets`,
round 18 — ROADMAP #5).

Four proof obligations, mirroring the quant_comm bucket scheduler's
contract:

  1. the partition itself: layer-reversed (backward-completion) order,
     ~equal bytes, every leaf exactly once, the FSDP include-filter
     (replicated sub-threshold leaves never enter a bucket);
  2. f32 BIT parity: bucketing is a pure repartition of independent
     fixed-order reductions, so the loss trajectory at grad_buckets=4 is
     bit-identical to the serial one-bucket schedule (DDP and FSDP) —
     and the serial hand-placed schedule itself tracks the GSPMD f32
     path within the dense tolerance;
  3. int8+overlap within the round-12 loss-trajectory tolerance of f32
     (the wire cut and the overlap win stack without new numerics);
  4. the HLO audit: per-BUCKET closed-form bytes exact, op counts exact
     (B a2as + B gathers for DDP, B backward a2as for FSDP with forward
     param gathers unchanged), zero involuntary-remat warnings, and the
     promoted hlolint `overlap` gate clean — every declared bucket wire
     independently schedulable.

Plus the validation matrix: strategies without a hand-placed grad wire
reject --grad_buckets at startup.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpukit.mesh import create_mesh
from tpukit.model import GPTConfig, init_params
from tpukit.obs.xla import capture_compiler_stderr, collective_bytes
from tpukit.ops import quant_comm as qc
from tpukit.shardings import DataParallel, ExpertParallel, FSDP
from tpukit.train import create_train_state, make_optimizer, make_step_fns

BATCH = 16
SEQ = 32
STEPS = 6
FINAL_LOSS_TOL = 2e-2  # the round-12 quantized-trajectory gate
DENSE_TOL = 5e-4  # hand-placed f32 block vs GSPMD (reduction order only)


def _base_cfg(**kw):
    return GPTConfig(
        dim=32,
        head_dim=8,
        heads=4,
        num_layers=2,
        vocab_size=211,
        max_position_embeddings=SEQ,
        compute_dtype=jnp.float32,
        **kw,
    )


def _batch():
    rng = np.random.RandomState(11)
    ids = rng.randint(3, 211, size=(BATCH, SEQ)).astype(np.int32)
    model_batch = {
        "input_ids": ids,
        "position_ids": np.ascontiguousarray(
            np.broadcast_to(np.arange(SEQ, dtype=np.int32), ids.shape)
        ),
        "mask": np.zeros((BATCH, SEQ), dtype=bool),
    }
    return model_batch, np.roll(ids, -1, axis=1).astype(np.int32)


def _make_world(kind: str, comm_dtype: str, buckets: int):
    cfg = _base_cfg(comm_dtype=comm_dtype, grad_buckets=buckets)
    if kind == "ddp":
        return DataParallel(create_mesh({"data": 8})), cfg
    return FSDP(create_mesh({"data": 8})), cfg


# One compiled world per (strategy, comm_dtype, buckets), shared by the
# parity gates AND the HLO audits — the 8-device compiles dominate.
_WORLDS: dict = {}


def _world(kind: str, comm_dtype: str, buckets: int) -> dict:
    key = (kind, comm_dtype, buckets)
    if key in _WORLDS:
        return _WORLDS[key]
    strategy, cfg = _make_world(kind, comm_dtype, buckets)
    strategy.validate_config(cfg)
    model_batch, targets = _batch()
    opt = make_optimizer(1e-3)
    state = create_train_state(jax.random.PRNGKey(0), cfg, opt, strategy)
    shapes = jax.eval_shape(lambda: state)
    struct = lambda x: jax.ShapeDtypeStruct(np.asarray(x).shape, np.asarray(x).dtype)  # noqa: E731
    with capture_compiler_stderr() as cap:
        train_step, _, sharding = make_step_fns(cfg, opt, strategy, shapes)
        compiled = train_step.lower(
            shapes, jax.tree.map(struct, model_batch), struct(targets)
        ).compile()
    state = jax.device_put(state, sharding)
    losses = []
    for _ in range(STEPS):
        state, loss = compiled(state, model_batch, targets)
        losses.append(float(loss))
    del state
    _WORLDS[key] = {
        "strategy": strategy,
        "cfg": cfg,
        "shapes": shapes,
        "losses": losses,
        "coll": collective_bytes(compiled.as_text()),
        "text": compiled.as_text(),
        "warns": cap["involuntary_remat"],
    }
    return _WORLDS[key]


# -- 1. the partition -------------------------------------------------------


def _param_tree():
    return init_params(jax.random.PRNGKey(0), _base_cfg())


def test_bucket_plan_layer_reversed_order():
    """Buckets are contiguous runs of backward-completion order: head and
    norm_out leaves land in the FIRST bucket, embeddings in the LAST (the
    real tree's layer leaves are STACKED along a leading num_layers axis,
    so within `layers` the completion granularity is the leaf — see
    DESIGN.md §17); on a list-structured tree a deeper (higher-index)
    layer's leaves always precede a shallower layer's."""
    params = _param_tree()
    paths = jax.tree_util.tree_flatten_with_path(params)[0]

    def top_name(i):
        return next(
            k.key for k in paths[i][0]
            if isinstance(k, jax.tree_util.DictKey)
        )

    buckets = qc.grad_bucket_plan(params, 3)
    assert len(buckets) == 3
    first = {top_name(i) for i in buckets[0]}
    assert "lm_head" in first or "norm_out" in first
    assert "embeddings" in {top_name(i) for i in buckets[-1]}

    # the SequenceKey (per-layer list) spelling: reversed layer order
    listed = {
        "embeddings": np.zeros((8, 4), np.float32),
        "layers": [
            {"w": np.zeros((4, 4), np.float32)} for _ in range(3)
        ],
        "lm_head": np.zeros((4, 8), np.float32),
    }
    lpaths = jax.tree_util.tree_flatten_with_path(listed)[0]
    order = [i for b in qc.grad_bucket_plan(listed, 100) for i in b]
    layer_seq = [
        next(k.idx for k in lpaths[i][0]
             if isinstance(k, jax.tree_util.SequenceKey))
        for i in order
        if any(getattr(k, "key", None) == "layers" for k in lpaths[i][0])
    ]
    assert layer_seq == sorted(layer_seq, reverse=True)
    assert any(getattr(k, "key", None) == "lm_head"
               for k in lpaths[order[0]][0])
    assert any(getattr(k, "key", None) == "embeddings"
               for k in lpaths[order[-1]][0])


def test_bucket_plan_equal_bytes_and_exhaustive():
    """Every leaf appears exactly once; bucket byte totals are balanced
    (no bucket above 2x the ideal share once its largest leaf fits)."""
    params = _param_tree()
    leaves = jax.tree_util.tree_leaves(params)
    sizes = [leaf.size for leaf in leaves]
    for n_buckets in (1, 2, 4, 100):
        buckets = qc.grad_bucket_plan(params, n_buckets)
        flat = [i for b in buckets for i in b]
        assert sorted(flat) == list(range(len(leaves)))
        assert len(buckets) == min(n_buckets, len(leaves))
        assert all(b for b in buckets)  # never an empty bucket
        if n_buckets in (2, 4):
            total = sum(sizes)
            biggest_leaf = max(sizes)
            for b in buckets:
                share = sum(sizes[i] for i in b)
                assert share <= max(2 * total / n_buckets, biggest_leaf + 1)


def test_bucket_plan_include_filter():
    """The FSDP restriction: only the included (sharded) indices are
    partitioned — replicated sub-threshold leaves stay outside every
    bucket (they ride the f32 psum path)."""
    params = _param_tree()
    leaves = jax.tree_util.tree_leaves(params)
    include = {i for i, leaf in enumerate(leaves) if leaf.size >= 100}
    assert include and len(include) < len(leaves)
    buckets = qc.grad_bucket_plan(params, 4, include=include)
    flat = [i for b in buckets for i in b]
    assert sorted(flat) == sorted(include)
    assert qc.grad_bucket_plan(params, 4, include=set()) == []
    with pytest.raises(ValueError, match="n_buckets"):
        qc.grad_bucket_plan(params, 0)


def test_bucket_all_reduce_partition_invariant():
    """The two-shot f32 bucket reduction is a fixed-device-order
    elementwise sum: splitting one payload into two buckets yields
    BIT-identical results (the parity bar's mechanism, unit-scale)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    mesh = create_mesh({"data": 8})
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(8, 1000).astype(np.float32))

    def blk(v):
        whole = qc.bucket_all_reduce(v, "data", 8, "f32")
        left = qc.bucket_all_reduce(v[:, :300], "data", 8, "f32")
        right = qc.bucket_all_reduce(v[:, 300:], "data", 8, "f32")
        exact = jax.lax.psum(v, "data")
        return whole, jnp.concatenate([left, right], axis=1), exact

    whole, split, exact = shard_map(
        blk, mesh=mesh, in_specs=(P("data", None),),
        out_specs=(P(), P(), P()), check_vma=False,
    )(x)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(split))
    # f32 two-shot vs psum: same values within reduction-order ulps
    np.testing.assert_allclose(
        np.asarray(whole), np.asarray(exact), rtol=1e-6, atol=1e-5
    )


# -- 2/3. trajectory parity gates -------------------------------------------


@pytest.mark.parametrize("kind", ["ddp", "fsdp"])
def test_f32_bucketed_bit_parity(kind):
    """THE f32 contract: grad_buckets=4 vs the serial one-bucket schedule
    is BIT-identical — bucketing only repartitions independent fixed-
    order reductions. (grad_buckets=1 IS the serial schedule expressed in
    the bucket machinery: one payload, one two-shot pair.)"""
    serial = _world(kind, "f32", 1)
    bucketed = _world(kind, "f32", 4)
    assert bucketed["losses"] == serial["losses"], (
        bucketed["losses"], serial["losses"],
    )


@pytest.mark.parametrize("kind", ["ddp", "fsdp"])
def test_f32_bucketed_tracks_gspmd(kind):
    """The hand-placed f32 bucket block vs the default GSPMD path
    (grad_buckets=0): same math, different reduction structure — dense
    tolerance, not bit parity (local-mean-then-psum vs global mean)."""
    gspmd = _world(kind, "f32", 0)
    bucketed = _world(kind, "f32", 4)
    drift = max(
        abs(a - b) for a, b in zip(bucketed["losses"], gspmd["losses"])
    )
    assert drift <= DENSE_TOL, (bucketed["losses"], gspmd["losses"])


@pytest.mark.parametrize("kind", ["ddp", "fsdp"])
def test_int8_bucketed_trajectory_gate(kind):
    """int8 + overlap stays inside the round-12 tolerance gate vs f32:
    the bucket schedule adds reordering, never new quantization error
    classes (per-bucket block boundaries shift, the error bound per
    block does not)."""
    ref = _world(kind, "f32", 1)
    quant = _world(kind, "int8", 4)
    assert all(np.isfinite(quant["losses"]))
    assert abs(quant["losses"][-1] - ref["losses"][-1]) < FINAL_LOSS_TOL, (
        quant["losses"], ref["losses"],
    )
    assert quant["losses"][-1] < quant["losses"][0]  # still trains


# -- 4. HLO audits ----------------------------------------------------------


@pytest.mark.parametrize("kind,comm", [
    ("ddp", "f32"), ("ddp", "int8"), ("fsdp", "int8"),
])
def test_bucketed_hlo_audit(kind, comm):
    """The compiled bucketed step moves EXACTLY the per-bucket closed
    form: B a2as + B gathers for DDP (B a2as + unchanged per-leaf f32
    param gathers for FSDP), zero involuntary-remat warnings, and the
    promoted overlap gate clean with every declared wire hidden."""
    from tpukit.analysis import (
        lint_module, parse_hlo, summarize, train_comm_plan,
    )

    w = _world(kind, comm, 4)
    assert w["warns"] == 0
    expected = w["strategy"].grad_comm(
        w["cfg"], w["shapes"].params, backend=jax.default_backend()
    )
    assert expected["all-to-all"]["count"] == 4
    if kind == "ddp":
        assert expected["all-gather"]["count"] == 4
    for op, rec in expected.items():
        got = w["coll"].get(op)
        assert got == rec, (op, got, rec)
    plan = train_comm_plan(
        w["strategy"], w["cfg"], param_shapes=w["shapes"].params,
        global_batch=BATCH, seq=SEQ, backend=jax.default_backend(),
    )
    assert plan.overlap is not None
    findings = lint_module(parse_hlo(w["text"]), plan=plan,
                           backend=jax.default_backend())
    assert [f for f in findings if f.severity == "error"] == []
    s = summarize(findings)
    gate = s["overlap_gate"]
    assert gate["ok"] and gate["overlappable"] >= gate["declared"]


def test_fsdp_replicated_leaves_stay_f32_psum():
    """Sub-threshold replicated leaves never enter a bucket: the bucket
    plan covers exactly the sharded subset, and their grads ride the
    full-precision psum: the compiled step's all-reduces, however many
    ops the compiler combines them into, carry those leaves at 4 bytes an
    element and nothing narrower than f32."""
    w = _world("fsdp", "int8", 4)
    strategy, shapes = w["strategy"], w["shapes"]
    leaves = jax.tree_util.tree_leaves(shapes.params)
    sharded = {
        i for i, leaf in enumerate(leaves)
        if any(ax == "data" for ax in strategy.param_spec(leaf.shape))
    }
    buckets = qc.grad_bucket_plan(shapes.params, 4, include=sharded)
    assert sorted(i for b in buckets for i in b) == sorted(sharded)
    replicated = [leaf for i, leaf in enumerate(leaves) if i not in sharded]
    assert replicated
    assert w["coll"]["all-reduce"]["bytes"] >= sum(4 * leaf.size for leaf in replicated)
    results = [
        line.split(" all-reduce")[0] for line in w["text"].splitlines()
        if re.search(r" all-reduce(-start)?\(", line)
    ]
    assert results and not any(re.search(r"\b(s8|u8|bf16|f16)\[", r) for r in results)


def test_serial_default_unchanged():
    """grad_buckets=0 (the default) leaves the serial schedules exactly
    as round 17 shipped them: int8 = ONE flattened two-shot pair."""
    w = _world("ddp", "int8", 0)
    assert w["coll"]["all-to-all"]["count"] == 1
    assert w["coll"]["all-gather"]["count"] == 1
    expected = w["strategy"].grad_comm(
        w["cfg"], w["shapes"].params, backend=jax.default_backend()
    )
    for op, rec in expected.items():
        assert w["coll"].get(op) == rec, op
    # and no overlap declaration exists to gate
    assert w["strategy"].overlap_comm(w["cfg"], w["shapes"].params) is None


# -- validation matrix + flags ----------------------------------------------


def test_grad_buckets_validation_matrix():
    """--grad_buckets is rejected everywhere there is no hand-placed grad
    wire to bucket: negative at config construction; single/CP/TP/
    pipeline strategies; MoE under DDP/FSDP (no aux psum in the manual
    block); EP's xla dispatch. The wired combinations validate."""
    from tpukit.pipeline import Pipeline
    from tpukit.shardings import ContextParallel, SingleDevice, TensorParallel

    with pytest.raises(ValueError, match="grad_buckets"):
        GPTConfig(grad_buckets=-1)
    cfg = _base_cfg(grad_buckets=4)
    for strategy in (
        SingleDevice(),
        ContextParallel(create_mesh({"seq": 8})),
        TensorParallel(create_mesh({"model": 4})),
        Pipeline(create_mesh({"stage": 4})),
    ):
        with pytest.raises(ValueError, match="grad_buckets"):
            strategy.validate_config(cfg)
    moe_buckets = _base_cfg(grad_buckets=4, num_experts=4)
    with pytest.raises(ValueError, match="ExpertParallel"):
        DataParallel(create_mesh({"data": 8})).validate_config(moe_buckets)
    with pytest.raises(ValueError, match="ExpertParallel"):
        FSDP(create_mesh({"data": 8})).validate_config(moe_buckets)
    with pytest.raises(ValueError, match="grad_buckets"):
        ExpertParallel(
            create_mesh({"data": 2, "expert": 4}), dispatch="xla"
        ).validate_config(moe_buckets)
    # the wired combinations pass, f32 and int8 alike
    DataParallel(create_mesh({"data": 8})).validate_config(cfg)
    FSDP(create_mesh({"data": 8})).validate_config(
        _base_cfg(grad_buckets=4, comm_dtype="int8")
    )
    ExpertParallel(create_mesh({"data": 2, "expert": 4})).validate_config(
        moe_buckets
    )


def test_ep_overlap_declaration():
    """EP + grad_buckets declares the per-layer overlap audit (2L
    backward a2a hops) without changing the dataflow; without buckets
    (or on a 1-way expert axis) nothing is declared."""
    ep = ExpertParallel(create_mesh({"data": 2, "expert": 4}))
    cfg = _base_cfg(num_experts=4, grad_buckets=4)
    assert ep.overlap_comm(cfg, None) == {"all-to-all": 2 * cfg.num_layers}
    assert ep.overlap_comm(_base_cfg(num_experts=4), None) is None


def test_fit_xla_verdict_carries_overlap_gate(tmp_path):
    """The promoted gate rides fit()'s kind="xla" verdict: a --grad_buckets
    int8 DDP run's train_step record carries hlolint.overlap_gate with
    every declared bucket wire hidden (and stays clean) — the production
    enforcement surface next to the dryrun and the CI lane."""
    import json
    import os

    from tpukit.flags import TrainFlags
    from tpukit.train import fit

    log = tmp_path / "run.jsonl"
    flags = TrainFlags(
        batch_size=2, epochs=1, sequence_length=33, dim=32, head_dim=8,
        heads=4, num_layers=2, learning_rate=1e-3, dataset_slice="32",
        num_workers=0, disable_amp=True, seed=0, metrics_log=str(log),
        comm_dtype="int8", grad_buckets=4,
    )
    cwd = os.getcwd()
    os.chdir(tmp_path)  # checkpoints/ lands in tmp
    try:
        fit(flags, DataParallel(create_mesh({"data": 8})))
    finally:
        os.chdir(cwd)
    records = [json.loads(line) for line in log.read_text().splitlines()]
    train_rec = next(
        r for r in records if r["kind"] == "xla" and r["fn"] == "train_step"
    )
    verdict = train_rec["hlolint"]
    assert verdict["clean"] is True, verdict
    gate = verdict["overlap_gate"]
    assert gate["ok"] is True
    assert gate["overlappable"] >= gate["declared"] == 8  # 4 a2a + 4 ag
    # the eval step has no grad wire: no overlap gate to declare
    eval_rec = next(
        r for r in records if r["kind"] == "xla" and r["fn"] == "eval_step"
    )
    assert "overlap_gate" not in (eval_rec.get("hlolint") or {})


def test_grad_buckets_flag_plumbing():
    """--grad_buckets parses on every recipe, defaults to the unchanged
    serial path, and reaches GPTConfig through TrainFlags."""
    from tpukit.flags import TrainFlags, parse_flags

    assert TrainFlags().grad_buckets == 0
    assert parse_flags([]).grad_buckets == 0
    flags = parse_flags(["--grad_buckets", "4", "--comm_dtype", "int8"])
    assert flags.grad_buckets == 4 and flags.comm_dtype == "int8"
    flags = parse_flags(["--grad_buckets", "2"], num_experts=True)
    assert flags.grad_buckets == 2
