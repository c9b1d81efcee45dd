"""Tests for the round-6 telemetry subsystem (tpukit/obs) + its satellites.

Covers the four pillars on the virtual CPU mesh: span-timeline accounting
(seconds sum to wall clock, goodput in (0, 1]), XLA static analysis of a
compiled DP train step (FLOPs, memory, all-reduce comm bytes from the
HLO), in-jit grad norms vs an eager reference, the loss-spike/NaN sentinel,
heartbeat liveness files, and the end-to-end `fit()` JSONL contract that
`tools/report.py` renders. Satellite regressions ride along: the analytic
loader schedule vs brute-force enumeration, the fail-loud sampling cache
check, and `time_windows(warmup=0)`.
"""

import json
import time

import jax
import numpy as np
import optax
import pytest

from tpukit.obs import (
    Heartbeat,
    SpanTimeline,
    SpikeSentinel,
    collective_bytes,
    compiled_stats,
    format_breakdown,
)


# ---------------------------------------------------------------------------
# span timeline
# ---------------------------------------------------------------------------


def test_span_timeline_sums_to_wall_clock():
    tl = SpanTimeline()
    with tl.span("step"):
        time.sleep(0.02)
    with tl.span("data"):
        time.sleep(0.01)
    with tl.span("sync"):
        time.sleep(0.01)
    time.sleep(0.005)  # unattributed -> "other"
    win = tl.window()
    assert win["total_s"] >= 0.045
    assert abs(sum(win["seconds"].values()) - win["total_s"]) < 1e-6
    assert abs(sum(win["fractions"].values()) - 1.0) < 1e-6
    assert 0.0 < win["goodput"] <= 1.0
    # goodput is exactly the step+sync share
    assert win["goodput"] == pytest.approx(
        win["fractions"]["step"] + win["fractions"]["sync"]
    )
    assert win["seconds"]["other"] >= 0.004
    # window() resets: an immediate second window is ~empty
    win2 = tl.window()
    assert win2["seconds"].get("step", 0.0) == 0.0


def test_nested_spans_attribute_to_outer_only():
    tl = SpanTimeline()
    with tl.span("eval"):
        with tl.span("telemetry"):  # e.g. capture_xla inside the eval phase
            time.sleep(0.01)
    win = tl.window()
    assert "telemetry" not in win["seconds"]
    assert win["seconds"]["eval"] >= 0.009


def test_epoch_breakdown_spans_windows():
    tl = SpanTimeline()
    with tl.span("step"):
        time.sleep(0.01)
    tl.window()
    with tl.span("step"):
        time.sleep(0.01)
    ep = tl.epoch()  # covers both windows
    assert ep["seconds"]["step"] >= 0.018
    assert abs(sum(ep["seconds"].values()) - ep["total_s"]) < 1e-6
    assert "goodput" in format_breakdown(ep)


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: records enter/exit order."""

    log: list = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs = name, kwargs

    def __enter__(self):
        self.log.append(("enter", self.name, self.kwargs))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.kwargs))


def test_span_enters_the_annotation_it_was_given_and_sums_are_unchanged():
    """One `with`: phase sums (outermost only), the handed-over annotation
    under `tpukit:<name>` for outer AND nested spans, and the span's own two
    readings of the run clock."""
    import functools

    _FakeAnnotation.log = []
    plain = SpanTimeline()
    tl = SpanTimeline(annotation=_FakeAnnotation,
                      step_annotation=functools.partial(_FakeAnnotation, "train"))
    t_run0 = time.perf_counter()
    tl.set_epoch(t_run0)
    for timeline in (plain, tl):
        with timeline.span("eval") as outer:
            with timeline.span("telemetry") as inner:
                time.sleep(0.01)
        with timeline.span("step", step_num=7):
            time.sleep(0.005)
    names = [(kind, name) for kind, name, _ in _FakeAnnotation.log]
    assert names == [
        ("enter", "tpukit:eval"), ("enter", "tpukit:telemetry"),
        ("exit", "tpukit:telemetry"), ("exit", "tpukit:eval"),
        ("enter", "tpukit:step"), ("enter", "train"),
        ("exit", "train"), ("exit", "tpukit:step"),
    ]
    assert _FakeAnnotation.log[5][2] == {"step_num": 7}
    # the readings are on the run clock, nested inside the outer span's
    assert 0.0 <= outer.t0 <= inner.t0 <= inner.t1 <= outer.t1 <= time.perf_counter() - t_run0
    assert inner.t1 - inner.t0 >= 0.009
    # the sums are what a timeline without an annotation keeps
    win, ref = tl.window(), plain.window()
    assert set(win["seconds"]) == set(ref["seconds"]) == {"eval", "step", "other"}
    assert win["seconds"]["eval"] == pytest.approx(outer.t1 - outer.t0, abs=1e-9)
    assert abs(sum(win["seconds"].values()) - win["total_s"]) < 1e-6


def test_annotate_names_another_threads_work_outside_the_sums():
    _FakeAnnotation.log = []
    tl = SpanTimeline(annotation=_FakeAnnotation)
    with tl.annotate("prefetch.produce"):
        time.sleep(0.002)
    assert [n for _, n, _ in _FakeAnnotation.log] == ["tpukit:prefetch.produce"] * 2
    assert set(tl.window()["seconds"]) == {"other"}
    with SpanTimeline().annotate("x"):  # no annotation handed over: a no-op
        pass


def test_lap_returns_the_walls_since_the_last_lap():
    tl = SpanTimeline()
    with tl.span("admit"):
        time.sleep(0.002)
    with tl.span("decode"):
        pass
    first = tl.lap()
    assert set(first) == {"admit", "decode"} and first["admit"] >= 0.002
    assert tl.lap() == {}
    with tl.span("admit"):
        pass
    tl.epoch()  # a new run starts every account afresh
    assert tl.lap() == {}
    assert "admit" not in tl.window()["seconds"]


def _busy(seconds):
    """Spin until this thread has run `seconds` on a CPU."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("body", ["sleep", "busy"])
def test_a_span_reads_the_threads_cpu_beside_its_wall(body):
    """A span that sleeps 50 ms is a wait: its CPU is below a tenth of its
    wall. A span that spins is work: its CPU is at least four fifths of its
    wall (in one of three tries: a loaded machine can take the CPU away from
    a spinning thread too). The lap's CPU has the walls' keys, and `other`
    holds the CPU between the two lap points outside every span."""
    shares = []
    for _ in range(3):
        tl = SpanTimeline(cpu=True)
        with tl.span("phase") as sp:
            (time.sleep if body == "sleep" else _busy)(0.05)
        _busy(0.01)  # outside every span: `other`
        walls, cpu = tl.lap(), tl.lap_cpu()
        wall = sp.t1 - sp.t0
        assert walls["phase"] == pytest.approx(wall) and cpu["phase"] == sp.cpu
        assert set(cpu) == {"phase", "other"} and 0.0 <= cpu["phase"] <= wall
        assert cpu["other"] >= 0.01
        assert tl.lap() == {} and set(tl.lap_cpu()) == {"other"}  # a new lap starts empty
        shares.append(cpu["phase"] / wall)
    if body == "sleep":
        assert max(shares) < 0.1
    else:
        assert max(shares) >= 0.8 and min(shares) > 0.0


def test_nested_spans_charge_their_cpu_to_the_outer_one_only():
    tl = SpanTimeline(cpu=True)
    with tl.span("sync") as outer:
        with tl.span("sync_wait") as inner:
            _busy(0.02)
    tl.lap()
    cpu = tl.lap_cpu()
    assert "sync_wait" not in cpu and cpu["sync"] == outer.cpu >= inner.cpu >= 0.02
    assert outer.cpu <= outer.t1 - outer.t0 and inner.cpu <= inner.t1 - inner.t0


def test_a_timeline_without_cpu_reads_no_thread_clock(monkeypatch):
    def refuse():
        raise AssertionError("thread_time read on a timeline without cpu=True")

    monkeypatch.setattr(time, "thread_time", refuse)
    tl = SpanTimeline()
    with tl.span("admit") as sp:
        with tl.span("inner"):
            pass
    assert set(tl.lap()) == {"admit"} and tl.lap_cpu() == {} and sp.cpu == 0.0
    tl.epoch()


def test_prefetcher_worker_runs_process_inside_the_span_it_was_given():
    from tpukit.prefetch import HostPrefetcher

    _FakeAnnotation.log = []
    tl = SpanTimeline(annotation=_FakeAnnotation)
    seen = []

    def process(x):
        seen.append(len(_FakeAnnotation.log))  # 1, 3, 5: entered, not yet left
        return x * 2

    pf = HostPrefetcher(range(3), process, depth=2, span=tl.annotate)
    assert list(pf) == [0, 2, 4]
    assert [n for _, n, _ in _FakeAnnotation.log] == ["tpukit:prefetch.produce"] * 6
    assert seen == [1, 3, 5]
    assert list(HostPrefetcher(range(3), lambda x: x, depth=1)) == [0, 1, 2]  # no span given


# ---------------------------------------------------------------------------
# XLA static analysis
# ---------------------------------------------------------------------------


def test_collective_bytes_parses_hlo():
    hlo = """
  %ar = f32[8,128]{1,0} all-reduce(f32[8,128]{1,0} %x), replica_groups={}
  %t = (f32[16]{0}, bf16[4,4]{1,0}) all-reduce(%a, %b), channel_id=1
  %ag = bf16[64,32]{1,0} all-gather(bf16[8,32]{1,0} %y), dimensions={0}
  %cp = f32[2,2]{1,0} collective-permute-start(f32[2,2]{1,0} %z)
  %cpd = f32[2,2]{1,0} collective-permute-done(f32[2,2]{1,0} %cp)
  %rs = f32[8]{0} reduce-scatter(f32[64]{0} %w), dimensions={0}
"""
    got = collective_bytes(hlo)
    assert got["all-reduce"]["count"] == 2
    assert got["all-reduce"]["bytes"] == 8 * 128 * 4 + 16 * 4 + 16 * 2
    assert got["all-gather"] == {"count": 1, "bytes": 64 * 32 * 2}
    # async pairs count once (the -start; -done carries no new payload)
    assert got["collective-permute"] == {"count": 1, "bytes": 16}
    assert got["reduce-scatter"] == {"count": 1, "bytes": 32}
    assert collective_bytes("%a = f32[2] add(%b, %c)") == {}


def test_collective_bytes_counts_async_result_half_only():
    """TPU-optimized HLO emits async pairs whose -start result tuple
    carries (operands..., results..., ctx scalars...): only the results
    half is moved volume — summing the whole tuple would double it."""
    hlo = """
  %ag = (bf16[4,64]{1,0}, bf16[8,64]{1,0}) all-gather-start(bf16[4,64]{1,0} %x)
  %agd = bf16[8,64]{1,0} all-gather-done((bf16[4,64]{1,0}, bf16[8,64]{1,0}) %ag)
  %cp = (f32[8,128]{1,0}, f32[8,128]{1,0}, u32[], u32[]) collective-permute-start(f32[8,128]{1,0} %y)
  %ar = (f32[16]{0}, bf16[4]{0}) all-reduce-start(%a, %b)
"""
    got = collective_bytes(hlo)
    assert got["all-gather"] == {"count": 1, "bytes": 8 * 64 * 2}  # post-gather
    assert got["collective-permute"] == {"count": 1, "bytes": 8 * 128 * 4}
    # all-reduce-start's tuple holds ONLY results (XLA's combiner fuses
    # buffers into one variadic all-reduce) — never halved
    assert got["all-reduce"] == {"count": 1, "bytes": 16 * 4 + 4 * 2}


def _batch_structs(batch_size, seq):
    batch = {
        "input_ids": jax.ShapeDtypeStruct((batch_size, seq), np.int32),
        "position_ids": jax.ShapeDtypeStruct((batch_size, seq), np.int32),
        "mask": jax.ShapeDtypeStruct((batch_size, seq), np.bool_),
    }
    return batch, jax.ShapeDtypeStruct((batch_size, seq), np.int32)


def test_compiled_stats_on_cpu_mesh(tiny_config):
    """Acceptance: cost/memory analysis + comm bytes captured on the CPU
    mesh — the DP grad psum must surface as all-reduce traffic."""
    from tpukit.shardings import DataParallel
    from tpukit.train import create_train_state, make_optimizer, make_step_fns

    opt = make_optimizer(1e-3)
    strat = DataParallel()
    state_shapes = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), tiny_config, opt)
    )
    step, _, _ = make_step_fns(tiny_config, opt, strat, state_shapes)
    batch, targets = _batch_structs(8, 16)
    stats = compiled_stats(step, state_shapes, batch, targets)
    assert stats is not None
    assert stats["flops"] is not None and stats["flops"] > 0
    assert stats["bytes_accessed"] is not None and stats["bytes_accessed"] > 0
    coll = stats["collectives"]
    assert coll and "all-reduce" in coll
    assert coll["all-reduce"]["count"] >= 1
    assert coll["all-reduce"]["bytes"] > 0
    # XLA:CPU supports memory_analysis (tools/pipeline_memory.py relies on
    # it); peak estimate must cover at least the argument (state) bytes
    mem = stats["memory"]
    assert mem is not None
    assert mem["temp_size_in_bytes"] >= 0
    assert mem["peak_bytes_estimate"] > 0


def test_train_step_names_every_training_scope(tiny_config, fresh_compiles):
    """The compiled text of a tiny train step carries the model's named
    scopes in `op_name`, and `instruction_scopes` maps instructions to them
    through the autodiff wrappers."""
    from tpukit.obs import SCOPES, instruction_scopes
    from tpukit.shardings import SingleDevice
    from tpukit.train import create_train_state, make_optimizer, make_step_fns

    opt = make_optimizer(1e-3)
    shapes = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), tiny_config, opt)
    )
    step, evaluate, _ = make_step_fns(tiny_config, opt, SingleDevice(), shapes)
    batch, targets = _batch_structs(4, 16)
    text = step.lower(shapes, batch, targets).compile().as_text()
    scopes = instruction_scopes(text)
    paths = set(scopes.values())
    leaves = {p.split("/")[-1] for p in paths}
    assert {"embed", "attn", "ffn", "ln", "loss", "optimizer"} <= leaves, leaves
    assert all(set(p.split("/")) <= set(SCOPES) for p in paths)
    # the backward of attention is still attention's: jvp(...) and
    # transpose(jvp(...)) are read through
    assert "transpose(jvp(attn))" in text and "loss/attn" in paths
    # the optimizer's update is outside the loss
    assert "optimizer" in paths and not any(p.startswith("loss/optimizer") for p in paths)
    # the eval step's loss is under the same name; a cond branch traced apart
    # repeats the stack from its root, which is read as one path
    eval_paths = set(instruction_scopes(
        evaluate.lower(shapes, batch, targets).compile().as_text()).values())
    assert {"loss", "loss/attn", "loss/ffn"} <= eval_paths and "loss/loss" not in eval_paths
    # the routed FFN is `moe`, never `ffn`
    moe_cfg = tiny_config.replace(num_experts=2)
    moe_shapes = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), moe_cfg, opt)
    )
    moe_step, _, _ = make_step_fns(moe_cfg, opt, SingleDevice(), moe_shapes)
    moe_paths = set(instruction_scopes(
        moe_step.lower(moe_shapes, batch, targets).compile().as_text()).values())
    assert "loss/moe" in moe_paths and "loss/ffn" not in moe_paths


def test_instruction_scopes_maps_a_known_fusion():
    from tpukit.obs import instruction_scopes

    text = """\
HloModule jit_train_step
%fused_computation.3 (p: bf16[8,64]) -> bf16[8,64] {
  %p = bf16[8,64]{1,0} parameter(0)
  ROOT %max.1 = bf16[8,64]{1,0} maximum(%p, %p), metadata={op_name="jit(train_step)/loss/jvp(ffn)/jit(relu)/max" stack_frame_id=9}
}
ENTRY %main.1 (a: bf16[8,64]) -> bf16[8,64] {
  %a = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="state.params['layers']"}
  %fusion.3 = bf16[8,64]{1,0:T(8,128)(2,1)} fusion(%a), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(train_step)/loss/jvp(ffn)/jit(relu)/max" stack_frame_id=9}
  %flash_bwd.1 = (f32[24,1,1024,64]{3,2,1,0}, bf16[24,1024,64]{2,1,0}) custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/loss/transpose(jvp(attn))/flash_bwd/pallas_call" stack_frame_id=4}
  %jvp_head_ce_bwd_.1 = f32[8,8]{1,0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/loss/transpose(loss)/jvp(head_ce_bwd)/pallas_call"}
  %bitcast.7 = bf16[8,64]{1,0} bitcast(%fusion.3), metadata={op_name="decode/attn/squeeze;decode/attn/attend/transpose"}
  %copy.2 = bf16[8,64]{1,0} copy(%bitcast.7), metadata={op_name="jit(decode_step)/while/body/closed_call/decode/attn/kv_gather/gather"}
  ROOT %add.9 = bf16[8,64]{1,0} add(%copy.2, %copy.2)
}
"""
    assert instruction_scopes(text) == {
        "max.1": "loss/ffn",
        "fusion.3": "loss/ffn",
        "flash_bwd.1": "loss/attn",  # the kernel's own name is kernel_calls' business
        "jvp_head_ce_bwd_.1": "loss",  # transpose(loss) restates the scope it was taken under
        "bitcast.7": "decode/attn",  # merged op_names: the first one
        "copy.2": "decode/attn/kv_gather",
    }
    assert instruction_scopes(text, scopes=("attn",))["copy.2"] == "attn"


def test_compiled_stats_is_none_on_lowering_failure():
    assert compiled_stats(jax.jit(lambda x: x)) is None  # missing avals


# ---------------------------------------------------------------------------
# grad-norm sentinels (in-jit half)
# ---------------------------------------------------------------------------


def _train_batch(rng, cfg, batch_size=8, seq=16):
    ids = rng.randint(3, cfg.vocab_size, size=(batch_size, seq)).astype(np.int32)
    batch = {
        "input_ids": ids,
        "position_ids": np.broadcast_to(
            np.arange(seq, dtype=np.int32), ids.shape
        ).copy(),
        "mask": np.zeros_like(ids, dtype=bool),
    }
    return batch, np.roll(ids, -1, axis=1).astype(np.int32)


def test_grad_norms_match_eager_reference(tiny_config, rng):
    from tpukit.shardings import SingleDevice
    from tpukit.train import create_train_state, make_optimizer, make_step_fns

    cfg = tiny_config
    opt = make_optimizer(1e-3)
    strat = SingleDevice()
    state = create_train_state(jax.random.PRNGKey(0), cfg, opt)
    shapes = jax.eval_shape(lambda: state)
    step, _, _ = make_step_fns(cfg, opt, strat, shapes, log_grad_norms=True)
    batch, targets = _train_batch(rng, cfg)

    # reference grads on the PRE-step params (copied before donation)
    params_before = jax.tree.map(np.asarray, state.params)
    ref_grads = jax.jit(
        jax.grad(lambda p: strat.loss_fn(p, cfg, batch, targets)[0])
    )(params_before)
    ref_norm = float(optax.global_norm(ref_grads))

    new_state, loss, norms = step(state, batch, targets)
    assert set(norms) == {"grad_norm", "update_norm", "param_norm"}
    assert float(norms["grad_norm"]) == pytest.approx(ref_norm, rel=1e-4)
    # param_norm is the POST-update parameter norm
    assert float(norms["param_norm"]) == pytest.approx(
        float(optax.global_norm(new_state.params)), rel=1e-5
    )
    assert float(norms["update_norm"]) > 0.0
    assert np.isfinite(float(loss))


def test_train_step_unchanged_without_norm_flag(tiny_config):
    """Flag off -> the step's output arity (and traced graph) is exactly the
    pre-telemetry one; flag on only APPENDS the norms dict."""
    from tpukit.shardings import SingleDevice
    from tpukit.train import create_train_state, make_optimizer, make_step_fns

    opt = make_optimizer(1e-3)
    shapes = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), tiny_config, opt)
    )
    batch, targets = _batch_structs(4, 16)
    step_off, _, _ = make_step_fns(tiny_config, opt, SingleDevice(), shapes)
    step_on, _, _ = make_step_fns(
        tiny_config, opt, SingleDevice(), shapes, log_grad_norms=True
    )
    out_off = jax.eval_shape(step_off, shapes, batch, targets)
    out_on = jax.eval_shape(step_on, shapes, batch, targets)
    assert len(out_off) == 2
    assert len(out_on) == 3 and set(out_on[2]) == {
        "grad_norm", "update_norm", "param_norm",
    }


# ---------------------------------------------------------------------------
# loss-spike sentinel (host half)
# ---------------------------------------------------------------------------


def test_spike_sentinel_fires_on_injected_spike():
    s = SpikeSentinel(threshold=3.0, min_history=4)
    for i in range(8):  # steady-ish baseline
        assert s.observe(2.0 + 0.01 * (i % 2), step=i) is None
    ev = s.observe(5.0, step=8)
    assert ev is not None and ev.kind == "spike" and ev.step == 8
    assert ev.loss == 5.0 and 1.9 < ev.mean < 2.1
    # the spike was not absorbed into the baseline: a sustained divergence
    # keeps firing
    assert s.observe(5.0, step=9) is not None
    rec = ev.record()
    assert rec["event"] == "spike" and "kind" not in rec


def test_spike_sentinel_fires_on_nan_and_inf():
    s = SpikeSentinel(threshold=3.0)
    assert s.observe(float("nan"), step=1).kind == "nan"
    assert s.observe(float("inf"), step=2).kind == "nan"


def test_spike_sentinel_quiet_on_descent_and_noise():
    s = SpikeSentinel(threshold=3.0)
    rng = np.random.RandomState(0)
    loss = 6.0
    for i in range(64):  # normal training: decreasing + noise
        loss = loss * 0.99 + rng.randn() * 0.01
        assert s.observe(loss, step=i) is None


def test_spike_sentinel_rejects_bad_threshold():
    with pytest.raises(ValueError, match="threshold"):
        SpikeSentinel(threshold=0.0)


# ---------------------------------------------------------------------------
# heartbeats
# ---------------------------------------------------------------------------


def test_heartbeat_write_check_and_stragglers(tmp_path):
    h0 = Heartbeat(tmp_path, process_index=0, process_count=3, timeout_s=60)
    h1 = Heartbeat(tmp_path, process_index=1, process_count=3, timeout_s=60)
    h0.beat(10)
    h1.beat(8)
    beats = h0.read_all()
    assert set(beats) == {0, 1}
    assert beats[0]["step"] == 10 and beats[1]["step"] == 8

    # process 2 never wrote
    stragglers = h0.check()
    assert [(s["process"], s["reason"]) for s in stragglers] == [(2, "missing")]

    # everything is stale an hour later
    stale = h0.check(now=time.time() + 3600)
    assert {s["process"] for s in stale} == {0, 1, 2}
    assert {s["reason"] for s in stale} == {"stale", "missing"}

    # step lag: process 2 alive but far behind
    h2 = Heartbeat(tmp_path, process_index=2, process_count=3, timeout_s=60)
    h2.beat(1)
    lag = h0.check(step_lag=5)
    assert [(s["process"], s["reason"]) for s in lag] == [(2, "lagging")]
    assert lag[0]["behind"] == 9

    # torn/foreign files are skipped, never raised on
    (tmp_path / "heartbeat-p00099.json").write_text("{not json")
    assert set(h0.read_all()) == {0, 1, 2}


def test_heartbeat_timeout_scales_with_beat_cadence(tmp_path):
    """Beats land once per PRINT_FREQ window; when a big-model window is
    longer than the fixed timeout, the checker must scale its staleness
    threshold from the observed cadence instead of flagging every healthy
    peer on every check."""
    h = Heartbeat(tmp_path, process_index=0, process_count=1, timeout_s=10)
    t0 = 1_000_000.0
    h.beat(1, now=t0)
    h.beat(2, now=t0 + 100)  # observed window cadence 100s >> timeout 10s
    # 150s-old beat is healthy under the 3x-cadence threshold (300s)...
    assert h.check(now=t0 + 250) == []
    # ...but past it the stale report still fires
    stale = h.check(now=t0 + 100 + 301)
    assert [s["reason"] for s in stale] == ["stale"]


# ---------------------------------------------------------------------------
# loader satellite: analytic global schedule == brute-force enumeration
# ---------------------------------------------------------------------------


def _make_dataset(n, seq=8):
    from tpukit.data import ArrayDataset

    ids = np.arange(n * seq, dtype=np.int32).reshape(n, seq) % 97 + 3
    return ArrayDataset(ids, np.ones_like(ids))


@pytest.mark.parametrize("pad_mode", ["wrap", "empty"])
@pytest.mark.parametrize("drop_last", [False, True])
@pytest.mark.parametrize(
    "n,reps,bs",
    [(253, 2, 32), (64, 1, 16), (64, 2, 8), (100, 3, 8), (7, 4, 4), (33, 8, 2), (5, 2, 8)],
)
def test_global_real_row_counts_matches_enumeration(n, reps, bs, drop_last, pad_mode):
    from tpukit.loader import DataLoader

    ds = _make_dataset(n)
    loaders = [
        DataLoader(
            ds, bs, shuffle=True, seed=7, num_replicas=reps, rank=r,
            drop_last=drop_last, pad_to_batch=True, pad_mode=pad_mode,
        )
        for r in range(reps)
    ]
    for epoch in (0, 3):  # schedule must be shuffle-epoch-invariant
        for ld in loaders:
            ld.set_epoch(epoch)
        analytic = loaders[0].global_real_row_counts()
        # brute force: enumerate every rank's real mask per batch
        brute = None
        for ld in loaders:
            _, real = ld._indices()
            stop = (len(real) // bs) * bs if drop_last else len(real)
            per = np.array(
                [real[s : s + bs].sum() for s in range(0, stop, bs)], np.int64
            )
            brute = per if brute is None else brute + per
        np.testing.assert_array_equal(analytic, brute)
        if not drop_last:
            assert int(analytic.sum()) == n  # every original row exactly once


def test_global_real_row_counts_respects_subclass_schedule():
    """ADVICE r5 #3: a subclass overriding `_indices` must not silently get
    the base-class closed form — the method falls back to enumerating the
    subclass's actual schedule."""
    from tpukit.loader import DataLoader

    class HalfLoader(DataLoader):
        # keeps only the first half of the dataset (custom schedule)
        def _indices(self):
            idx, real = super()._indices()
            keep = len(self.dataset) // (2 * self.num_replicas)
            return idx[:keep], real[:keep]

    ds = _make_dataset(64)
    loaders = [
        HalfLoader(ds, 8, shuffle=True, seed=3, num_replicas=2, rank=r)
        for r in range(2)
    ]
    analytic = loaders[0].global_real_row_counts()
    brute = None
    for ld in loaders:
        _, real = ld._indices()
        per = np.array(
            [real[s : s + 8].sum() for s in range(0, len(real), 8)], np.int64
        )
        brute = per if brute is None else brute + per
    np.testing.assert_array_equal(analytic, brute)
    assert int(analytic.sum()) == 32  # half of 64, not the base schedule's 64


def test_global_real_row_counts_agrees_with_iterated_real_rows():
    """The schedule must match what the loaders actually YIELD (the
    real_rows field the meter consumes)."""
    from tpukit.loader import DataLoader

    ds = _make_dataset(253)
    loaders = [
        DataLoader(
            ds, 32, shuffle=True, seed=1, num_replicas=2, rank=r,
            pad_to_batch=True,
        )
        for r in range(2)
    ]
    for ld in loaders:
        ld.set_epoch(2)
    analytic = loaders[0].global_real_row_counts()
    yielded = [
        np.array([b["real_rows"] for b in ld], dtype=np.int64) for ld in loaders
    ]
    np.testing.assert_array_equal(analytic, yielded[0] + yielded[1])


# ---------------------------------------------------------------------------
# remaining satellites
# ---------------------------------------------------------------------------


def test_generate_use_cache_with_temperature_samples(tiny_config, tiny_params):
    """Round 11 (ROADMAP #1 first rung): the cached decode loop implements
    temperature sampling — the explicit use_cache=True + temperature>0
    combination that raised through round 10 (VERDICT r5 #5) now decodes,
    reproducibly under a fixed seed. Token-level cached-vs-uncached
    same-seed equivalence lives in tests/test_sampling.py."""
    from tpukit.data import get_tokenizer
    from tpukit.sampling import generate

    tok = get_tokenizer()
    a = generate(
        tiny_params, tiny_config, "The big brown cat ", tok,
        max_new_tokens=6, use_cache=True, temperature=0.7, seed=3,
    )
    b = generate(
        tiny_params, tiny_config, "The big brown cat ", tok,
        max_new_tokens=6, use_cache=True, temperature=0.7, seed=3,
    )
    assert isinstance(a, str) and a == b


def test_generate_auto_cache_with_temperature_uses_cached_loop(
    tiny_config, tiny_params, monkeypatch
):
    """The long-buffer heuristic no longer downgrades sampling runs: with
    use_cache auto-resolved (caller passed None) and a >=512-token buffer,
    temperature>0 routes to the CACHED loop with the temperature intact
    (through round 10 it silently fell back to the O(S^2) re-forward loop
    because the cached loop was greedy-only)."""
    import tpukit.sampling as sampling
    from tpukit.data import get_tokenizer

    seen = {}

    def fake_loop(params, cfg, buf, prompt_len, max_new, eos,
                  temperature=0.0, top_k=0, rng=None):
        seen["temperature"] = temperature
        seen["has_rng"] = rng is not None
        return buf, np.int32(int(prompt_len))

    monkeypatch.setattr(sampling, "_decode_loop_cached", fake_loop)
    cfg = tiny_config.replace(max_position_embeddings=1024)
    tok = get_tokenizer()
    out = sampling.generate(
        tiny_params, cfg, "The big brown cat ", tok,
        max_new_tokens=600, temperature=0.7,
    )
    assert seen["temperature"] == 0.7 and seen["has_rng"]
    assert isinstance(out, str)


def test_moe_config_fails_loudly_from_direct_value_and_grad(tiny_config):
    """ADVICE r5 #1: the curated MoE ValueError (not a TypeError about
    aux_out) from direct strategy.value_and_grad calls."""
    from tpukit.mesh import create_mesh
    from tpukit.pipeline import Pipeline, Pipeline1F1B
    from tpukit.shardings import ContextParallel, TensorParallel

    cfg = tiny_config.replace(num_experts=4)
    dummy = {"input_ids": None}
    for strat, match in [
        (ContextParallel(create_mesh({"seq": 2})), "ExpertParallel"),
        (TensorParallel(create_mesh({"model": 2})), "ExpertParallel"),
        (Pipeline(create_mesh({"stage": 2})), "ExpertParallel"),
        (Pipeline1F1B(create_mesh({"stage": 2})), "ExpertParallel"),
    ]:
        with pytest.raises(ValueError, match=match):
            strat.value_and_grad({}, cfg, dummy, None)


# ---------------------------------------------------------------------------
# fit() end to end: the JSONL contract tools/report.py renders
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def telemetry_run(tmp_path_factory):
    import os

    from tpukit.flags import TrainFlags
    from tpukit.shardings import SingleDevice
    from tpukit.train import fit

    tmp = tmp_path_factory.mktemp("obs")
    log = tmp / "run.jsonl"
    hb = tmp / "hb"
    flags = TrainFlags(
        batch_size=8, epochs=1, sequence_length=33, dim=32, head_dim=8,
        heads=4, num_layers=2, learning_rate=1e-3, dataset_slice="80",
        num_workers=0, disable_amp=True, seed=0,
        metrics_log=str(log), log_grad_norms=True, spike_threshold=8.0,
        heartbeat_dir=str(hb),
    )
    cwd = os.getcwd()
    os.chdir(tmp)  # checkpoints/ lands in tmp
    try:
        result = fit(flags, SingleDevice())
    finally:
        os.chdir(cwd)
    records = [json.loads(line) for line in log.read_text().splitlines()]
    return flags, result, records, log, hb


def test_fit_emits_goodput_windows(telemetry_run):
    _, _, records, _, _ = telemetry_run
    train = [r for r in records if r["kind"] == "train"]
    assert train, "no window record (dataset too small for PRINT_FREQ?)"
    for r in train:
        assert 0.0 < r["goodput"] <= 1.0
        assert abs(sum(r["spans"].values()) - 1.0) < 1e-6
        assert r["window_s"] > 0
        for key in ("grad_norm", "update_norm", "param_norm"):
            assert r[key] > 0.0
        assert np.isfinite(r["loss"])


def test_fit_emits_xla_analysis_once_per_compile(telemetry_run):
    _, _, records, _, _ = telemetry_run
    xla = [r for r in records if r["kind"] == "xla"]
    fns = {r["fn"] for r in xla}
    assert {"train_step", "eval_step"} <= fns
    assert len(xla) == len(fns)  # once per compile, not per step/window
    train_rec = next(r for r in xla if r["fn"] == "train_step")
    assert train_rec["flops"] > 0
    assert train_rec["bytes_accessed"] > 0
    assert train_rec["memory"]["peak_bytes_estimate"] > 0
    assert train_rec["strategy"] == "single"
    assert train_rec["collectives"] == {}  # single device: no comm


def test_fit_xla_records_carry_hlolint_verdict(telemetry_run):
    """Round 16: every xla record carries the rule-engine summary
    (tpukit/analysis) — on the single-device world the verdict is clean
    (donated state aliases, no collectives, no async pairs)."""
    _, _, records, _, _ = telemetry_run
    xla = [r for r in records if r["kind"] == "xla"]
    for r in xla:
        verdict = r.get("hlolint")
        assert verdict is not None, r["fn"]
        assert verdict["clean"] is True, (r["fn"], verdict)
        assert verdict["errors"] == 0


def test_fit_emits_epoch_and_validation_records(telemetry_run):
    _, _, records, _, _ = telemetry_run
    ep = next(r for r in records if r["kind"] == "epoch")
    assert abs(sum(ep["fractions"].values()) - 1.0) < 1e-6
    assert 0.0 < ep["goodput"] <= 1.0
    assert ep["seconds"]["eval"] > 0 and ep["seconds"]["generate"] > 0
    val = next(r for r in records if r["kind"] == "validation")
    assert np.isfinite(val["loss"])


def test_fit_writes_heartbeat_and_counts_no_spikes(telemetry_run):
    _, result, _, _, hb = telemetry_run
    files = list(hb.glob("heartbeat-p*.json"))
    assert len(files) == 1  # one per process
    beat = json.loads(files[0].read_text())
    assert beat["process"] == 0
    assert beat["step"] == int(result.state.step)
    assert result.metrics["spike_events"] == 0


def test_report_renders_run(telemetry_run):
    from tools.report import load, summarize

    _, _, _, log, _ = telemetry_run
    text = summarize(load(str(log)))
    assert "goodput" in text
    assert "xla static analysis: train_step" in text
    assert "val loss" in text


def test_report_flags_unexpected_collectives():
    """A strategy that DECLARES no collectives (comm_ops = ()) must have
    every measured collective flagged; a foreign log without the key
    cannot flag anything."""
    from tools.report import summarize

    base = {
        "kind": "xla", "fn": "train_step", "strategy": "single",
        "flops": 1.0, "bytes_accessed": 1.0, "memory": None, "time": 0,
        "collectives": {"all-gather": {"count": 1, "bytes": 1024}},
    }
    declared_empty = summarize([dict(base, expected_comm_ops=[])])
    assert "UNEXPECTED" in declared_empty
    declared_match = summarize([dict(base, expected_comm_ops=["all-gather"])])
    assert "UNEXPECTED" not in declared_match
    undeclared = summarize([base])
    assert "UNEXPECTED" not in undeclared


# ---------------------------------------------------------------------------
# round-7 satellites: line-buffered StepLogger, compile-cache accounting,
# report.py prefetch rendering + --min_goodput gate
# ---------------------------------------------------------------------------


def test_steplogger_line_visible_without_close(tmp_path):
    """Line-buffered single-write records: every logged line is durable on
    disk immediately (no close/flush needed), so a killed run's log is
    readable up to its last complete record."""
    from tpukit.obs import StepLogger

    path = tmp_path / "log.jsonl"
    logger = StepLogger(str(path))
    logger.log(kind="train", step=1, loss=2.5)
    logger.log(kind="train", step=2, loss=2.25)
    lines = path.read_text().splitlines()  # BEFORE close
    assert [json.loads(l)["step"] for l in lines] == [1, 2]
    logger.close()
    logger.close()  # idempotent
    StepLogger("").log(kind="noop")  # empty path stays a no-op


def test_compile_cache_misses_then_hits(tmp_path):
    """enable_compilation_cache mid-process: first compile misses and
    writes an entry; an identical fresh jit then HITS — counted through
    jax's own monitoring events."""
    from tpukit.cache import enable_compilation_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        stats = enable_compilation_cache(
            str(tmp_path / "cc"), min_compile_time_secs=0.0
        )
        jax.jit(lambda x: x @ x + 5)(np.ones((32, 32), np.float32)).block_until_ready()
        s1 = stats.stats()
        assert s1["requests"] >= 1 and s1["misses"] >= 1
        assert s1["new_entries"] >= 1  # the executable landed on disk

        stats2 = enable_compilation_cache(
            str(tmp_path / "cc"), min_compile_time_secs=0.0
        )
        jax.jit(lambda x: x @ x + 5)(np.ones((32, 32), np.float32)).block_until_ready()
        s2 = stats2.stats()
        assert s2["hits"] >= 1 and s2["misses"] == 0
    finally:
        # hand the suite back its conftest-configured cache
        if prev_dir:
            enable_compilation_cache(prev_dir, min_compile_time_secs=prev_min)
        else:
            jax.config.update("jax_compilation_cache_dir", prev_dir)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", prev_min
            )


def test_report_renders_prefetch_and_compile_cache():
    from tools.report import summarize

    recs = [
        {
            "kind": "train", "step": 8, "loss": 2.0, "goodput": 0.9,
            "tokens_per_sec": 1000.0, "window_s": 2.0,
            "spans": {"prefetch_stall": 0.05, "step": 0.2, "sync": 0.7,
                      "other": 0.05},
            "prefetch_stall_s": 0.1, "prefetch_occupancy": 1.8, "time": 0,
        },
        {
            "kind": "compile_cache", "dir": "/x/cache", "entries": 5,
            "new_entries": 2, "requests": 5, "hits": 3, "misses": 2,
            "time": 1,
        },
    ]
    text = summarize(recs)
    assert "prefetch: stall 5.0% of window wall-clock" in text
    assert "occupancy mean 1.80" in text
    assert "compile cache" in text and "hits 3" in text and "misses 2" in text


def test_report_min_goodput_gate(tmp_path):
    from tools.report import check_min_goodput
    from tools.report import main as report_main

    recs = [
        {"kind": "train", "step": 8, "loss": 2.0, "goodput": 0.9, "time": 0},
        {"kind": "train", "step": 16, "loss": 1.9, "goodput": 0.7, "time": 1},
    ]
    ok, msg = check_min_goodput(recs, 0.75)  # mean 0.8
    assert ok and "OK" in msg
    ok, msg = check_min_goodput(recs, 0.85)
    assert not ok and "FAIL" in msg
    assert not check_min_goodput([{"kind": "epoch"}], 0.5)[0]  # no windows

    log = tmp_path / "r.jsonl"
    log.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    assert report_main([str(log), "--min_goodput", "0.75"]) == 0
    assert report_main([str(log), "--min_goodput", "0.85"]) == 2
    assert report_main([str(log)]) == 0  # gate off by default


# ---------------------------------------------------------------------------
# multi-host heartbeats, for real (reuses the 2-process world harness)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_heartbeat_files_in_two_process_world(tmp_path):
    from test_multiprocess import _launch_world

    hb = tmp_path / "hb"
    _launch_world(
        "main-ddp.py", tmp_path,
        extra=["--heartbeat_dir", str(hb), "--heartbeat_timeout", "300"],
    )
    files = sorted(p.name for p in hb.glob("heartbeat-p*.json"))
    assert files == ["heartbeat-p00000.json", "heartbeat-p00001.json"]
    recs = [json.loads((hb / f).read_text()) for f in files]
    assert {r["process"] for r in recs} == {0, 1}
    assert all(r["step"] > 0 for r in recs)  # the epoch-end beat
