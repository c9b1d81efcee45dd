"""Block-scaled int8 quantized collectives (`--comm_dtype`, round 12).

Four proof obligations, mirroring tpukit/ops/quant_comm.py's contract:

  1. the quantizer itself: per-block round-trip error bound, exact zeros,
     stochastic-rounding behavior, pack/unpack inverses;
  2. the wrappers at f32: bit-exact passthrough vs the raw lax collectives
     (compression must be opt-in, never a silent numerics change);
  3. the loss-trajectory tolerance gate per strategy (ddp / fsdp / ep on
     the 8-virtual-device mesh): bit parity is impossible by construction,
     so a bounded quantized-vs-f32 loss delta IS the correctness contract;
  4. the HLO byte audit: the compiled programs move EXACTLY the closed-form
     payload+sidecar bytes (`grad_comm` / `dispatch_comm`), at unchanged op
     schedules (zero involuntary-remat warnings), and the int8 wire cost is
     <= 30% of the f32 baseline for the DDP grad all-reduce and the EP a2a
     dispatch — the acceptance bar.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from tpukit.mesh import create_mesh
from tpukit.model import GPTConfig
from tpukit.obs.xla import (
    capture_compiler_stderr,
    collective_bytes,
    wire_bytes,
)
from tpukit.ops import quant_comm as qc
from tpukit.shardings import DataParallel, ExpertParallel, FSDP
from tpukit.train import create_train_state, make_optimizer, make_step_fns

BATCH = 16
SEQ = 32
STEPS = 6  # trajectory-gate horizon (cheap: compiled once, stepped N times)

# Tolerance gates (the correctness contract): int8 grad/dispatch payloads
# perturb each update by ~0.4% relative per block; over the 6-step fixture
# horizon the trajectories measured within ~1e-4 of f32 — the gates leave
# an order of magnitude of headroom without ever allowing a divergent run.
FIRST_STEP_TOL = 1e-3  # step 1's loss predates any quantized update
FINAL_LOSS_TOL = 2e-2


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


def _make_world(kind: str, comm_dtype: str):
    if kind == "ddp":
        return DataParallel(create_mesh({"data": 8})), _base_cfg(
            comm_dtype=comm_dtype
        )
    if kind == "fsdp":
        return FSDP(create_mesh({"data": 8})), _base_cfg(comm_dtype=comm_dtype)
    return (
        ExpertParallel(create_mesh({"data": 2, "expert": 4}), dispatch="a2a"),
        _base_cfg(comm_dtype=comm_dtype, num_experts=4),
    )


# One compiled world per (strategy, comm_dtype), shared by the trajectory
# gates AND the HLO audits — each extra compile on the 8-device mesh costs
# real tier-1 seconds.
_WORLDS: dict = {}


def _world(kind: str, comm_dtype: str) -> dict:
    key = (kind, comm_dtype)
    if key in _WORLDS:
        return _WORLDS[key]
    strategy, cfg = _make_world(kind, comm_dtype)
    strategy.validate_config(cfg)
    model_batch, targets = _batch()
    opt = make_optimizer(1e-3)
    state = create_train_state(jax.random.PRNGKey(0), cfg, opt, strategy)
    shapes = jax.eval_shape(lambda: state)
    struct = lambda x: jax.ShapeDtypeStruct(np.asarray(x).shape, np.asarray(x).dtype)  # noqa: E731
    b_structs = jax.tree.map(struct, model_batch)
    with capture_compiler_stderr() as cap:
        train_step, eval_step, sharding = make_step_fns(cfg, opt, strategy, shapes)
        compiled = train_step.lower(shapes, b_structs, struct(targets)).compile()
        ecompiled = eval_step.lower(shapes, b_structs, struct(targets)).compile()
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
        "ecoll": collective_bytes(ecompiled.as_text()),
        "warns": cap["involuntary_remat"],
    }
    return _WORLDS[key]


# -- 1. the quantizer ------------------------------------------------------


@pytest.mark.parametrize("block", [64, 256])
def test_roundtrip_error_bound(block):
    """Per-block max-abs scaling bounds the round-trip error by half a
    quantization step — scale/2 = max|block| / 254 — element-wise, for any
    block size; zero blocks round-trip exactly."""
    rng = np.random.RandomState(0)
    x = jnp.asarray((rng.randn(5 * block + 17) * rng.uniform(0.01, 10)).astype(np.float32))
    q, scales = qc.quantize_blockwise(x, block=block)
    assert q.dtype == jnp.int8 and scales.dtype == jnp.float32
    back = qc.dequantize_blockwise(q, scales, x.shape, block=block)
    n_pad = -(-x.size // block) * block
    padded = np.pad(np.asarray(x), (0, n_pad - x.size)).reshape(-1, block)
    bound = np.repeat(np.abs(padded).max(axis=1) / 253.9, block)[: x.size]
    assert (np.abs(np.asarray(back - x)) <= bound).all()

    zeros = jnp.zeros((2 * block,), jnp.float32)
    qz, sz = qc.quantize_blockwise(zeros, block=block)
    np.testing.assert_array_equal(np.asarray(qz), 0)
    np.testing.assert_array_equal(
        np.asarray(qc.dequantize_blockwise(qz, sz, zeros.shape, block=block)), 0.0
    )


def test_pack_unpack_inverse():
    """pack_quantized's wire row is exactly packed_bytes() long and
    unpack_dequantized inverts it — including the bitcast f32 scale
    sidecar — for ragged (non-block-multiple) row widths."""
    rng = np.random.RandomState(3)
    parts = jnp.asarray(rng.randn(4, 700).astype(np.float32))
    packed = qc.pack_quantized(parts)
    assert packed.dtype == jnp.int8
    assert packed.shape == (4, qc.packed_bytes(700))
    back = qc.unpack_dequantized(packed, 700)
    assert back.shape == parts.shape
    bound = np.abs(np.asarray(parts)).max() / 120  # loose: per-row blocks
    assert np.abs(np.asarray(back - parts)).max() <= bound


def test_stochastic_rounding_unbiased():
    """Stochastic rounding lands on one of the two adjacent quantization
    levels and is unbiased: the mean over many keys converges to the true
    value (round-to-nearest's systematic bias does not)."""
    x = jnp.full((1, 256), 0.3217, jnp.float32)
    q, s = qc.quantize_blocks(x)  # deterministic
    det = qc.dequantize_blocks(q, s)
    acc = np.zeros((1, 256), np.float64)
    draws = 200
    for i in range(draws):
        qi, si = qc.quantize_blocks(x, rng=jax.random.PRNGKey(i))
        back = np.asarray(qc.dequantize_blocks(qi, si))
        step = float(s[0, 0])
        assert (np.abs(back - np.asarray(x)) < step + 1e-7).all()
        acc += back
    mean_err = abs(acc.mean() / draws - 0.3217)
    det_err = abs(float(det.mean()) - 0.3217)
    assert mean_err < det_err or mean_err < 1e-4


# -- 2. wrapper-vs-lax parity at f32 ---------------------------------------


def test_wrappers_f32_passthrough_parity():
    """dtype="f32" is a bit-exact passthrough to the raw lax collective for
    every wrapper — compression is opt-in, never a silent numerics change."""
    mesh = create_mesh({"data": 8})
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(8, 16, 4).astype(np.float32))
    e = jnp.asarray(rng.randn(8 * 8, 4, 2, 6).astype(np.float32))

    def blk(v, buf):
        ar = qc.quantized_all_reduce(v, "data", 8, "f32")
        ar_ref = jax.lax.psum(v, "data")
        rs = qc.quantized_reduce_scatter(v, "data", 8, dim=1, dtype="f32")
        rs_ref = jax.lax.psum_scatter(v, "data", scatter_dimension=1, tiled=True)
        ag = qc.quantized_all_gather(v, "data", 8, dim=0, dtype="f32")
        ag_ref = jax.lax.all_gather(v, "data", axis=0, tiled=True)
        d = qc.exchange_all_to_all(buf, "data", 8, "dispatch", dtype="f32")
        d_ref = jax.lax.all_to_all(buf, "data", split_axis=0, concat_axis=1, tiled=True)
        gq = qc.all_gather_qgrad(v, "data", 8, 0, "f32", qc.DEFAULT_BLOCK, False)
        return ar, ar_ref, rs, rs_ref, ag, ag_ref, d, d_ref, gq

    sp = P("data", None, None)
    sp4 = P("data", None, None, None)
    out = shard_map(
        blk, mesh=mesh,
        in_specs=(sp, sp4),
        # ar/ag results are replicated (each device holds the full array);
        # rs keeps dim-1 sharded; the exchange keeps dim-0 sharded
        out_specs=(P(), P(), P(None, "data", None), P(None, "data", None),
                   P(), P(), sp4, sp4, P()),
        check_vma=False,
    )(x, e)
    ar, ar_ref, rs, rs_ref, ag, ag_ref, d, d_ref, gq = out
    np.testing.assert_array_equal(np.asarray(ar), np.asarray(ar_ref))
    np.testing.assert_array_equal(np.asarray(rs), np.asarray(rs_ref))
    np.testing.assert_array_equal(np.asarray(ag), np.asarray(ag_ref))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d_ref))
    np.testing.assert_array_equal(np.asarray(gq), np.asarray(ag_ref))


def test_quantized_collectives_error_bounded():
    """int8/bf16 all-reduce, reduce-scatter and all-gather land within a
    small relative error of the exact lax collective (f32 accumulation,
    only the wire is compressed), and the all_gather_qgrad backward equals
    the quantized reduce-scatter of the cotangent — the FSDP grad wire."""
    mesh = create_mesh({"data": 8})
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(8, 320, 2).astype(np.float32))

    def blk(v):
        exact = jax.lax.psum(v, "data")
        i8 = qc.quantized_all_reduce(v, "data", 8, "int8")
        b16 = qc.quantized_all_reduce(v, "data", 8, "bf16")
        rs_ref = jax.lax.psum_scatter(v, "data", scatter_dimension=1, tiled=True)
        rs_i8 = qc.quantized_reduce_scatter(v, "data", 8, dim=1, dtype="int8")
        ag_ref = jax.lax.all_gather(v, "data", axis=0, tiled=True)
        ag_i8 = qc.quantized_all_gather(v, "data", 8, dim=0, dtype="int8")
        ag_b16 = qc.quantized_all_gather(v, "data", 8, dim=0, dtype="bf16")
        return exact, i8, b16, rs_ref, rs_i8, ag_ref, ag_i8, ag_b16

    sp = P("data", None, None)
    rsp = P(None, "data", None)
    out = shard_map(
        blk, mesh=mesh, in_specs=(sp,),
        out_specs=(P(), P(), P(), rsp, rsp, P(), P(), P()),
        check_vma=False,
    )(x)
    exact, i8, b16, rs_ref, rs_i8, ag_ref, ag_i8, ag_b16 = out
    scale = float(jnp.max(jnp.abs(exact)))
    assert float(jnp.max(jnp.abs(i8 - exact))) / scale < 0.03
    assert float(jnp.max(jnp.abs(b16 - exact))) / scale < 0.03
    rs_scale = float(jnp.max(jnp.abs(rs_ref)))
    assert float(jnp.max(jnp.abs(rs_i8 - rs_ref))) / rs_scale < 0.03
    ag_scale = float(jnp.max(jnp.abs(ag_ref)))
    assert float(jnp.max(jnp.abs(ag_i8 - ag_ref))) / ag_scale < 0.02
    assert float(jnp.max(jnp.abs(ag_b16 - ag_ref))) / ag_scale < 0.01

    # backward of the full-precision gather is the quantized reduce-scatter
    shard = jnp.asarray(rng.randn(8, 2, 16).astype(np.float32))

    def gather_loss(v, cot):
        def inner(s, c):
            full = qc.all_gather_qgrad(s, "data", 8, 0, "int8", qc.DEFAULT_BLOCK, False)
            return jnp.sum(full * c)
        return shard_map(
            inner, mesh=mesh,
            in_specs=(P("data", None, None), P(None, None, None)),
            out_specs=P(), check_vma=False,
        )(v, cot)

    cot = jnp.asarray(rng.randn(8, 2, 16).astype(np.float32))
    g = jax.grad(gather_loss)(shard, cot)
    # exact reference: globally the loss is sum(gather(shard) * cot) =
    # sum(shard * cot), so d/d shard = cot — delivered physically through
    # the quantized reduce-scatter of the per-device cotangents
    ref = cot
    assert g.shape == shard.shape
    rel = float(jnp.max(jnp.abs(g - ref))) / float(jnp.max(jnp.abs(ref)))
    assert rel < 0.02


# -- 3. loss-trajectory tolerance gates ------------------------------------


@pytest.mark.parametrize("kind", ["ddp", "fsdp", "ep"])
def test_loss_trajectory_gate(kind):
    """THE correctness contract: --comm_dtype int8 must track the f32 loss
    trajectory within tolerance on every wired strategy. Step 1 predates
    any quantized update (the forward is full precision — for EP the
    payload quantizes AFTER routing, perturbing activations but never the
    discrete routing), so its gate is tight; the final-step gate bounds the
    accumulated drift of STEPS quantized gradient applications."""
    ref = _world(kind, "f32")
    quant = _world(kind, "int8")
    assert all(np.isfinite(quant["losses"]))
    first_tol = FIRST_STEP_TOL if kind != "ep" else 1e-2  # int8 activations
    assert abs(quant["losses"][0] - ref["losses"][0]) < first_tol, (
        quant["losses"][0], ref["losses"][0],
    )
    assert abs(quant["losses"][-1] - ref["losses"][-1]) < FINAL_LOSS_TOL, (
        quant["losses"], ref["losses"],
    )
    # the trajectory is monotone-ish on this fixture: training still works
    assert quant["losses"][-1] < quant["losses"][0]


@pytest.mark.parametrize("kind", ["ddp"])
def test_loss_trajectory_gate_bf16(kind):
    """The bf16 rung of the same gate (cheaper payload cut, tighter
    numerics): one strategy suffices — the wrappers share one code path."""
    ref = _world(kind, "f32")
    quant = _world(kind, "bf16")
    assert abs(quant["losses"][-1] - ref["losses"][-1]) < FINAL_LOSS_TOL


# -- 4. HLO byte audits -----------------------------------------------------


def test_ddp_int8_hlo_audit():
    """The compiled DDP int8 step moves EXACTLY the closed-form two-shot
    payload (one packed a2a + one packed all-gather), emits zero
    involuntary-remat warnings, and its grad wire costs <= 30% of the f32
    baseline's all-reduce (ring model, payload+scales counted) — the
    acceptance bar."""
    w = _world("ddp", "int8")
    assert w["warns"] == 0
    expected = w["strategy"].grad_comm(
        w["cfg"], w["shapes"].params, backend=jax.default_backend()
    )
    for op, rec in expected.items():
        got = w["coll"].get(op)
        assert got == rec, (op, got, rec)
    # <= 30% of f32 wire: quantized ops vs the baseline grad all-reduce
    base = _world("ddp", "f32")
    quant_wire = wire_bytes(
        {op: w["coll"][op] for op in expected}, 8
    )
    base_wire = wire_bytes(base["coll"], 8)
    assert base_wire > 0
    ratio = quant_wire / base_wire
    assert ratio <= 0.30, ratio


def test_fsdp_int8_hlo_audit():
    """FSDP int8: one packed grad-reduce-scatter a2a per sharded leaf at
    exact closed-form bytes, forward param all-gathers full-precision at
    exact bytes (grads-only first), zero remat warnings."""
    w = _world("fsdp", "int8")
    assert w["warns"] == 0
    expected = w["strategy"].grad_comm(
        w["cfg"], w["shapes"].params, backend=jax.default_backend()
    )
    assert expected["all-to-all"]["count"] > 1  # per-leaf wires, really many
    for op, rec in expected.items():
        got = w["coll"].get(op)
        assert got == rec, (op, got, rec)


def test_ep_int8_hlo_audit():
    """EP int8: the a2a op SCHEDULE is unchanged (same 4L train / 2L eval
    counts as f32) while every op moves the packed block-scaled buffer at
    exact closed-form bytes — train AND eval, <= 30% of the f32 payload."""
    w = _world("ep", "int8")
    base = _world("ep", "f32")
    assert w["warns"] == 0
    cfg = w["cfg"]
    expect = w["strategy"].dispatch_comm(
        cfg, global_batch=BATCH, seq=SEQ, backend=jax.default_backend()
    )
    a2a = w["coll"].get("all-to-all")
    base_a2a = base["coll"].get("all-to-all")
    assert a2a["count"] == base_a2a["count"] == expect["train"]["count"]
    assert a2a["bytes"] == expect["train"]["bytes"]
    assert a2a["bytes"] <= 0.30 * base_a2a["bytes"]
    ea2a = w["ecoll"].get("all-to-all")
    assert ea2a["count"] == expect["eval"]["count"]
    assert ea2a["bytes"] == expect["eval"]["bytes"]


def test_eval_bytes_audit_exact_on_cpu():
    """Satellite hardening (PR 5 flagged this 'softly'): the EVAL-step
    expected-bytes formula is dtype-aware — backend="cpu" prices the bf16
    eval autocast's f32 upcast into the expectation, so the f32-comm EP
    eval window audits EXACTLY on CPU too (bytes, not just op counts)."""
    w = _world("ep", "f32")
    expect = w["strategy"].dispatch_comm(
        w["cfg"], global_batch=BATCH, seq=SEQ, backend=jax.default_backend()
    )
    ea2a = w["ecoll"].get("all-to-all")
    assert ea2a["count"] == expect["eval"]["count"]
    assert ea2a["bytes"] == expect["eval"]["bytes"]
    assert expect["eval"].get("wire") is not None  # dtype-aware marker
    # the nominal (backend-less) expectation differs on CPU — the exact
    # match above is the hardening, not an accident of equal numbers
    nominal = w["strategy"].dispatch_comm(w["cfg"], global_batch=BATCH, seq=SEQ)
    if jax.default_backend() == "cpu":
        assert nominal["eval"]["bytes"] != expect["eval"]["bytes"]


# -- flag validation --------------------------------------------------------


def test_comm_dtype_validation():
    """--comm_dtype int8 is rejected everywhere it is not actually wired:
    bogus values at config construction, strategies without quantized
    collectives, MoE under DP/FSDP (no aux psum in the manual block), and
    the GSPMD xla dispatch under EP."""
    from tpukit.pipeline import Pipeline
    from tpukit.shardings import ContextParallel, SingleDevice, TensorParallel

    with pytest.raises(ValueError, match="comm_dtype"):
        GPTConfig(comm_dtype="int4")
    cfg = _base_cfg(comm_dtype="int8")
    for strategy in (
        SingleDevice(),
        ContextParallel(create_mesh({"seq": 8})),
        TensorParallel(create_mesh({"model": 4})),
        Pipeline(create_mesh({"stage": 4})),
    ):
        with pytest.raises(ValueError, match="comm_dtype"):
            strategy.validate_config(cfg)
    moe_int8 = _base_cfg(comm_dtype="int8", num_experts=4)
    with pytest.raises(ValueError, match="ExpertParallel"):
        DataParallel(create_mesh({"data": 8})).validate_config(moe_int8)
    with pytest.raises(ValueError, match="ExpertParallel"):
        FSDP(create_mesh({"data": 8})).validate_config(moe_int8)
    with pytest.raises(ValueError, match="moe_dispatch"):
        ExpertParallel(
            create_mesh({"data": 2, "expert": 4}), dispatch="xla"
        ).validate_config(moe_int8)
    # the wired combinations pass
    DataParallel(create_mesh({"data": 8})).validate_config(cfg)
    FSDP(create_mesh({"data": 8})).validate_config(cfg)
    ExpertParallel(create_mesh({"data": 2, "expert": 4})).validate_config(moe_int8)

    # comm_ops_for is a pure function of cfg — validating/auditing an int8
    # config must never widen the instance's f32 expected-op set (the
    # surprise-collective audit depends on it staying tight)
    dp = DataParallel(create_mesh({"data": 8}))
    dp.validate_config(cfg)
    assert "all-to-all" in dp.comm_ops_for(cfg)
    assert dp.comm_ops == ("all-reduce",)
    assert dp.comm_ops_for(_base_cfg()) == ("all-reduce",)


def test_comm_dtype_flag_plumbing():
    """--comm_dtype/--quant_stochastic parse on every recipe, default to
    the unchanged path, and reach GPTConfig through TrainFlags."""
    from tpukit.flags import TrainFlags, parse_flags

    assert TrainFlags().comm_dtype == "f32"
    assert TrainFlags().quant_stochastic is False
    flags = parse_flags([])
    assert flags.comm_dtype == "f32" and flags.quant_stochastic is False
    flags = parse_flags(["--comm_dtype", "int8", "--quant_stochastic"])
    assert flags.comm_dtype == "int8" and flags.quant_stochastic is True
    flags = parse_flags(["--comm_dtype", "bf16"], num_experts=True)
    assert flags.comm_dtype == "bf16"
    with pytest.raises(SystemExit):
        parse_flags(["--comm_dtype", "int4"])
