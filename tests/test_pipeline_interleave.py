"""Interleaved-1F1B virtual stages (round 25, `--virtual_stages V`).

Four layers under test, one table of truth (tpukit/pipeline_schedule.py):

1. the schedule AUTHORITY itself — every (chunk, micro) job exactly once,
   dependency-ordered, ship counts consistent, bubble strictly shrinking
   on the gate grid;
2. the tick MACHINE (Pipeline1F1B._interleaved_value_and_grad) — loss,
   eval loss and parameter updates match the single-device reference at
   V∈{2,4}, on ragged micro counts, uneven layer counts and a 2-D
   data x stage mesh; V=1 dense lowers BYTE-IDENTICAL to the original
   flat scan (the do-no-harm bar);
3. the pipeline x MoE composition — the meshless dropless "pallas"
   dispatch inside stage chunks reproduces the per-micro Switch
   objective's loss AND grads exactly, top-1 and top-2, 1F1B and GPipe,
   while "xla"/"a2a" stay rejected by name;
4. the plumbing — flags, comm plan (pipe_comm feeding train_comm_plan)
   and the param layout round-trip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpukit.mesh import create_mesh
from tpukit.model import GPTConfig, gpt
from tpukit.model.gpt import init_params
from tpukit.ops.layers import cross_entropy_sum
from tpukit.pipeline import Pipeline, Pipeline1F1B
from tpukit.pipeline_schedule import (
    bubble_table,
    build_schedule,
    cached_schedule,
    flat_1f1b_bubble,
)
from tpukit.shardings import SingleDevice
from tpukit.train import create_train_state, make_optimizer, make_step_fns

SEQ = 32


# ---------------------------------------------------------------- helpers


def make_batch(cfg, batch_size, seed=7):
    rng = np.random.RandomState(seed)
    ids = rng.randint(3, cfg.vocab_size, size=(batch_size, SEQ)).astype(np.int32)
    mask = np.zeros((batch_size, SEQ), dtype=bool)
    for row in range(0, batch_size, 3):
        pad_from = rng.randint(SEQ // 2, SEQ)
        mask[row, pad_from:] = True
    targets = np.roll(ids, -1, axis=1).astype(np.int32)
    targets[mask] = -100
    return {
        "input_ids": ids,
        "position_ids": np.ascontiguousarray(
            np.broadcast_to(np.arange(SEQ, dtype=np.int32), ids.shape)
        ),
        "mask": mask,
    }, targets


def one_step(strategy, cfg, model_batch, targets):
    opt = make_optimizer(1e-3)
    state = create_train_state(jax.random.PRNGKey(0), cfg, opt, strategy)
    shapes = jax.eval_shape(lambda: state)
    train_step, eval_step, _ = make_step_fns(cfg, opt, strategy, shapes)
    new_state, loss = train_step(state, model_batch, targets)
    eval_loss, eval_acc = eval_step(new_state, model_batch, targets)
    return new_state.params, float(loss), float(eval_loss), float(eval_acc)


def assert_interleave_matches_single(cfg, v, micro, batch_size,
                                     stages=2, data=1):
    """One optimizer step on the interleaved machine == single device:
    same loss (1e-5), same updated params (after undoing the chunk
    permutation and slicing off identity padding)."""
    mb, tg = make_batch(cfg, batch_size)
    ref_params, ref_loss, ref_eval, ref_acc = one_step(
        SingleDevice(), cfg, mb, tg
    )
    c2 = cfg.replace(virtual_stages=v)
    axes = {"stage": stages} if data == 1 else {"data": data, "stage": stages}
    strat = Pipeline1F1B(create_mesh(axes), num_microbatches=micro)
    params, loss, eval_loss, eval_acc = one_step(strat, c2, mb, tg)
    params = strat.inference_params(jax.device_get(params), c2)
    params = {
        **params,
        "layers": jax.tree.map(lambda l: l[: cfg.num_layers], params["layers"]),
    }
    assert abs(loss - ref_loss) < 1e-5, (v, micro, loss, ref_loss)
    assert abs(eval_loss - ref_eval) < 1e-2
    assert abs(eval_acc - ref_acc) < 1.0
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4),
        params, jax.device_get(ref_params),
    )


@pytest.fixture(scope="module")
def cfg4():
    return GPTConfig(
        dim=32, head_dim=8, heads=4, num_layers=4, vocab_size=211,
        max_position_embeddings=SEQ, compute_dtype=jnp.float32,
    )


# ------------------------------------------- 1. the schedule authority


@pytest.mark.parametrize(
    "s,v,m",
    [(2, 2, 4), (2, 4, 8), (4, 2, 4), (4, 4, 16), (2, 2, 3), (4, 3, 5)],
)
def test_schedule_complete_and_ordered(s, v, m):
    """Every (global chunk, micro) runs forward exactly once and backward
    exactly once, in dependency order, and the ship-tick stats match the
    per-tick flags (they are the comm plan's collective-permute count)."""
    sched = build_schedule(s, v, m)
    g_total = s * v
    f_tick, b_tick = {}, {}
    for t, tk in enumerate(sched.ticks):
        for d in range(s):
            if tk.fwd[d] is not None:
                c, mi, _slot = tk.fwd[d]
                g = c * s + d
                assert (g, mi) not in f_tick, "forward ran twice"
                f_tick[(g, mi)] = t
            if tk.bwd[d] is not None:
                c, mi, _slot = tk.bwd[d]
                g = c * s + d
                assert (g, mi) not in b_tick, "backward ran twice"
                b_tick[(g, mi)] = t
    assert len(f_tick) == g_total * m
    assert len(b_tick) == g_total * m
    for (g, mi), t in f_tick.items():
        if g > 0:
            assert f_tick[(g - 1, mi)] < t, "forward ran before its input"
        # the last chunk's backward is self-triggered the same tick (the
        # head+CE vjp); every other backward waits for the cotangent hop
        bt = b_tick[(g, mi)]
        assert bt >= t
        if g < g_total - 1:
            assert b_tick[(g + 1, mi)] < bt
    assert sched.stats["ship_fwd_ticks"] == sum(
        1 for tk in sched.ticks if tk.ship_fwd
    )
    assert sched.stats["ship_bwd_ticks"] == sum(
        1 for tk in sched.ticks if tk.ship_bwd
    )
    assert sched.stats["ticks"] == len(sched.ticks)


def test_schedule_forward_only():
    """include_backward=False is the eval program: complete forwards, no
    backward jobs, no backward shipping, NaN bubble (not priced)."""
    sched = build_schedule(4, 2, 8, include_backward=False)
    assert all(all(j is None for j in tk.bwd) for tk in sched.ticks)
    assert sched.stats["ship_bwd_ticks"] == 0
    n_fwd = sum(
        1 for tk in sched.ticks for j in tk.fwd if j is not None
    )
    assert n_fwd == 4 * 2 * 8


def test_flat_bubble_closed_form():
    assert flat_1f1b_bubble(4, 8) == pytest.approx((2 * 4 - 2) / (8 + 2 * 4 - 2))
    assert flat_1f1b_bubble(2, 4) == pytest.approx(2 / 6)


def test_bubble_strictly_decreases_on_gate_grid():
    """The gate grid (S=4, M in {4,8,16}, V 1->2->4): interleaving must
    strictly cut the idle-work fraction at every micro count."""
    for m in (4, 8, 16):
        flat = flat_1f1b_bubble(4, m)
        b2 = build_schedule(4, 2, m).stats["bubble_frac"]
        b4 = build_schedule(4, 4, m).stats["bubble_frac"]
        assert flat > b2 > b4, (m, flat, b2, b4)
        # and the headline cut is large: >= 50% relative at M=4..16
        assert 1.0 - b4 / flat >= 0.5


def test_bubble_table_shape():
    rows = bubble_table(4)
    assert len(rows) == 9  # 3 micros x 3 virtuals
    for row in rows:
        assert 0.0 < row["bubble_frac"] < 1.0
        if row["virtual_stages"] > 1:
            assert row["depth"] >= 1


# ------------------------------------------------- 2. the tick machine


def test_v1_dense_hlo_byte_identical(cfg4):
    """`--virtual_stages 1` on a dense config must cost NOTHING: the
    public value_and_grad lowers to byte-for-byte the same HLO as the
    original flat tick scan it dispatches to."""
    strat = Pipeline1F1B(create_mesh({"stage": 2}), num_microbatches=4)
    params = strat.prepare_params(init_params(jax.random.PRNGKey(0), cfg4), cfg4)
    mb, tg = make_batch(cfg4, 8)

    def lower(fn):
        return jax.jit(
            lambda p: fn(p, cfg4, mb, tg)
        ).lower(params).as_text()

    assert lower(strat.value_and_grad) == lower(strat._flat_value_and_grad)


def test_interleave_v2_ragged_micro(cfg4):
    # M=3 does not divide S*V — the warm-up/cool-down is ragged
    assert_interleave_matches_single(cfg4, v=2, micro=3, batch_size=12)


@pytest.mark.slow
def test_interleave_v4(cfg4):
    assert_interleave_matches_single(
        cfg4.replace(num_layers=8), v=4, micro=4, batch_size=16
    )


@pytest.mark.slow
def test_interleave_uneven_layers(cfg4):
    # L=5 on 2 stages x V=2 -> padded to 8, three identity chunks
    assert_interleave_matches_single(
        cfg4.replace(num_layers=5), v=2, micro=4, batch_size=16
    )


def test_interleave_data_stage_mesh(cfg4):
    # 2-D data x stage: each micro splits over the data axis too
    assert_interleave_matches_single(
        cfg4, v=2, micro=4, batch_size=16, stages=2, data=2
    )


def test_param_layout_round_trip(cfg4):
    """prepare_params permutes the stacked layers into interleaved chunk
    order (device-major); inference_params is its exact inverse."""
    cfg = cfg4.replace(num_layers=8, virtual_stages=4)
    strat = Pipeline1F1B(create_mesh({"stage": 2}), num_microbatches=4)
    params = init_params(jax.random.PRNGKey(1), cfg)
    packed = strat.prepare_params(params, cfg)
    restored = strat.inference_params(jax.device_get(packed), cfg)
    jax.tree.map(
        np.testing.assert_array_equal,
        jax.device_get(params), restored,
    )


# --------------------------------------------- 3. pipeline x MoE parity


def moe_reference_value_and_grad(params, cfg, batch, targets, num_micro):
    """Single-device reference of the pipeline's per-micro MoE objective:
    CE over the full batch + aux_weight * sum_m aux_m / M. Exact parity
    holds because the stage-only mesh keeps one dispatch group per micro
    (the Switch balance loss is nonlinear in dispatch grouping)."""
    c = cfg.replace(moe_dispatch="pallas", virtual_stages=1)
    batch_size = batch["input_ids"].shape[0]
    micro = batch_size // num_micro

    def total(p):
        ce_sum = jnp.float32(0)
        cnt = jnp.float32(0)
        aux_tot = jnp.float32(0)
        for m in range(num_micro):
            sl = slice(m * micro, (m + 1) * micro)
            al = []
            logits = gpt.forward(
                p, c, batch["input_ids"][sl], batch["position_ids"][sl],
                batch["mask"][sl], aux_out=al,
            )
            ls, cn = cross_entropy_sum(logits, targets[sl])
            ce_sum += ls
            cnt += cn
            aux_tot += al[0]
        ce = ce_sum / jnp.maximum(cnt, 1.0)
        return ce + c.moe_aux_weight * aux_tot / num_micro, ce

    (_, ce), grads = jax.value_and_grad(total, has_aux=True)(params)
    return ce, grads


# Tier-1 keeps ONE MoE composition gate (1f1b V=2, the headline case);
# the full matrix is slow-tiered and runs in the pipeline-interleave CI
# lane, whose parity step includes the slow tier (compile-heavy worlds —
# the 870s tier-1 budget is the binding constraint, see ci.yml).
@pytest.mark.parametrize(
    "schedule,v,top_k",
    [
        pytest.param("1f1b", 1, 1, marks=pytest.mark.slow),
        ("1f1b", 2, 1),
        pytest.param("1f1b", 2, 2, marks=pytest.mark.slow),
        pytest.param("gpipe", 1, 1, marks=pytest.mark.slow),
    ],
    ids=["1f1b-v1", "1f1b-v2", "1f1b-v2-top2", "gpipe"],
)
def test_moe_pipeline_parity(cfg4, schedule, v, top_k):
    """MoE inside stage chunks (--num_experts N --moe_dispatch pallas):
    loss and every grad leaf match the per-micro reference exactly."""
    cfg = cfg4.replace(num_experts=4, router_top_k=top_k, virtual_stages=v)
    mb, tg = make_batch(cfg, 8)
    cls = Pipeline1F1B if schedule == "1f1b" else Pipeline
    strat = cls(
        create_mesh({"stage": 2}), num_microbatches=4, moe_dispatch="pallas"
    )
    params = init_params(jax.random.PRNGKey(3), cfg)
    ref_loss, ref_grads = moe_reference_value_and_grad(params, cfg, mb, tg, 4)
    packed = strat.prepare_params(params, cfg)
    loss, grads = jax.jit(lambda p: strat.value_and_grad(p, cfg, mb, tg))(packed)
    grads = strat.inference_params(jax.device_get(grads), cfg)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4),
        grads, jax.device_get(ref_grads),
    )


# ------------------------------------------------ validation matrix


def test_rejects_too_many_virtual_stages(cfg4):
    strat = Pipeline1F1B(create_mesh({"stage": 2}), num_microbatches=4)
    with pytest.raises(ValueError, match="maximum virtual_stages here is 2"):
        strat.validate_config(cfg4.replace(virtual_stages=4))


def test_gpipe_rejects_interleave(cfg4):
    strat = Pipeline(create_mesh({"stage": 2}), num_microbatches=4)
    with pytest.raises(ValueError, match="1f1b"):
        strat.validate_config(cfg4.replace(virtual_stages=2))


@pytest.mark.parametrize("dispatch", ["xla", "a2a"])
def test_rejects_buffer_moe_dispatch(cfg4, dispatch):
    strat = Pipeline1F1B(
        create_mesh({"stage": 2}), num_microbatches=4, moe_dispatch=dispatch
    )
    cfg = cfg4.replace(num_experts=4, virtual_stages=2)
    with pytest.raises(ValueError, match="pallas") as exc:
        strat.validate_config(cfg)
    assert "ExpertParallel" in str(exc.value)
    # and the strategy-call entry points fail just as loudly
    with pytest.raises(ValueError, match="pallas"):
        strat.value_and_grad(None, cfg, None, None)


# --------------------------------------------------- 4. the plumbing


def test_flag_plumbing():
    from tpukit.flags import parse_flags

    flags = parse_flags(
        ["--schedule", "1f1b", "--virtual_stages", "2",
         "--num_experts", "8", "--moe_dispatch", "pallas"],
        pipeline_schedule=True, num_experts=True, default_experts=0,
    )
    assert flags.pipeline_schedule == "1f1b"
    assert flags.virtual_stages == 2
    assert flags.num_experts == 8
    assert flags.moe_dispatch == "pallas"
    defaults = parse_flags(
        [], pipeline_schedule=True, num_experts=True, default_experts=0
    )
    # the pipeline recipes stay the dense flat reference by default
    assert defaults.virtual_stages == 1
    assert defaults.num_experts == 0


def test_pipe_comm_plan(cfg4):
    """pipe_comm: None for the flat dense scan (its hops live inside the
    scan body); for V>1 the exact collective-permute count/bytes of the
    unrolled program, folded into train_comm_plan; MoE on a stage-only
    mesh additionally pins all-to-all to ZERO (pallas is collective-free)."""
    from tpukit.analysis.plan import train_comm_plan

    strat = Pipeline1F1B(create_mesh({"stage": 2}), num_microbatches=4)
    assert strat.pipe_comm(cfg4, global_batch=8, seq=SEQ) is None
    assert train_comm_plan(strat, cfg4, global_batch=8, seq=SEQ) is None

    c2 = cfg4.replace(virtual_stages=2)
    sched = cached_schedule(2, 2, 4)
    n_ship = sched.stats["ship_fwd_ticks"] + sched.stats["ship_bwd_ticks"]
    payload = (8 // 4) * SEQ * c2.dim * 4  # micro x seq x dim x f32
    ops = strat.pipe_comm(c2, global_batch=8, seq=SEQ)
    assert ops["collective-permute"] == {
        "count": n_ship, "bytes": n_ship * payload
    }
    plan = train_comm_plan(strat, c2, global_batch=8, seq=SEQ)
    assert plan.ops["collective-permute"]["count"] == n_ship
    # eval plan prices the forward-only program (fewer shipping ticks)
    ev = cached_schedule(2, 2, 4, include_backward=False)
    eplan = train_comm_plan(strat, c2, global_batch=8, seq=SEQ, phase="eval")
    assert eplan.ops["collective-permute"]["count"] == ev.stats["ship_fwd_ticks"]

    moe = Pipeline1F1B(
        create_mesh({"stage": 2}), num_microbatches=4, moe_dispatch="pallas"
    )
    mops = moe.pipe_comm(
        c2.replace(num_experts=4), global_batch=8, seq=SEQ
    )
    assert mops["all-to-all"] == {"count": 0, "bytes": 0}
    # with a data axis GSPMD reshards the batch ingest through tiny
    # all-to-alls that are not ours to pin — the guard must not appear
    moe2 = Pipeline1F1B(
        create_mesh({"data": 2, "stage": 2}), num_microbatches=4,
        moe_dispatch="pallas",
    )
    assert "all-to-all" not in moe2.pipe_comm(
        c2.replace(num_experts=4), global_batch=8, seq=SEQ
    )
