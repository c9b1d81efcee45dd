"""A CPU rehearsal of the jamba2-3b cell (modes/serve_hybrid.py) on a tiny
preset: a temporary copy of the benchmark to which a tiny configuration (the
published keys at toy widths, the layer pattern kept: attention at `i % 3 ==
1` of five layers), a tiny traffic mix and a cell are ADDED. One traced and
one untraced `run_cell`: `correct` true (the set-up's logits, the timed path's
tokens), every counter reader the real cell lists reads a number, the window
carries the family's two gauges; and the mode's `judge` refuses the reference
computed a precision below the program's. On a program without the hybrid
family the mode exits at once and says so."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import run

ROOT = Path(__file__).resolve().parents[2]
REAL, TINY = "jamba2-3b.serve-reason", "tiny-jamba.sat"
SEED = 2**31 + 36


def tiny_config(config: dict) -> dict:
    tiny = dict(config, name="tiny-jamba")
    tiny.update(
        hidden_size=64, vocab_size=97, intermediate_size=96, num_attention_heads=4, mamba_d_state=4,
        mamba_dt_rank=8, num_hidden_layers=5, attn_layer_offset=1, attn_layer_period=3,
        max_position_embeddings=4096,
        program=dict(config["program"], param_dtype="float32", compute_dtype="float32"),
        tolerance=dict(config["tolerance"], logit_rms_rel=1e-4),
    )
    return tiny


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_jamba")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "benchmark", root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    b = root / "benchmark"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    config = json.loads((b / "configs" / "jamba2-3b.json").read_text())
    (b / "configs" / "tiny-jamba.json").write_text(json.dumps(tiny_config(config)))
    traffic = json.loads((b / "traffic" / "think-saturate.json").read_text())
    traffic.update(
        requests={"base": 400, "per_second": 0, "block": 8}, eos_id=96,
        prompt_len={"distribution": "lognormal", "median": 20, "sigma": 0.5, "min": 6, "max": 40},
        output_len={"distribution": "lognormal", "median": 10, "sigma": 0.5, "min": 3, "max": 20},
        engine={"slots": 4, "page_size": 4, "kv_dtype": "f32", "prefill_chunk": 8, "max_len": 64,
                "buckets": [40], "decode_quantum": 2},
        ramp={"completions": 4}, setup_check={"prompt_tokens": 40, "decode_steps": 12, "lanes": 2},
        check_requests=2, check_new_tokens=8, compile_workers=2, trace_seconds=1)
    (b / "traffic" / "tiny-jamba-sat.json").write_text(json.dumps(traffic))
    entry = next(c for c in bench["configs"] if c["name"] == "jamba2-3b")
    bench["configs"].append(dict(entry, name="tiny-jamba", file="benchmark/configs/tiny-jamba.json"))
    bench["workloads"].append({"name": TINY, "config": "tiny-jamba", "traffic": "tiny-jamba-sat", "chips": 1,
                               "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(TINY)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def capsys_module():
    """The info lines a run printed (`run_cell` prints them; a module-scoped
    fixture cannot use `capsys`)."""
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        yield buf.getvalue


@pytest.fixture(scope="module")
def traced(tiny_root, capsys_module):
    out = run.run_cell(TINY, SEED, 1.0, True, root=tiny_root, require_tpu=False)
    return out, [json.loads(line) for line in capsys_module().splitlines() if line.startswith('{"info"')]


# the CPU run has no device plane: readers of the device trace find nothing to read there
DEVICE_TRACE = {"decode_tick_device_ms.tput", "device_idle_pct.tput", "prefill_device_ms_per_ktoken.tput",
                "hbm_peak_gb.tput"}


@pytest.mark.parametrize("part", ["correct", "counter_readers", "info_lines"])
def test_the_traced_rehearsal(traced, tiny_root, part):
    out, info = traced
    by = {line["info"]: line for line in info}
    if part == "correct":
        assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    elif part == "counter_readers":
        bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
        listed = {m["name"] for m in bench["per_layer"] if TINY in m.get("workloads", [])}
        assert listed == {"decode_tick_device_ms.tput", "prefill_device_ms_per_ktoken.tput",
                          "kv_bytes_per_ctx_token.tput", "device_idle_pct.tput", "hbm_peak_gb.tput",
                          "host_ms_per_quantum.tput", "dispatch_ms_per_quantum.tput", "slot_occupancy_pct.tput"}
        for name in sorted(listed - DEVICE_TRACE):
            assert name in out["metrics"], name
        # the state of every live lane is in the bytes a context token: far above the pages' own 2 x 2 x 16 x 4
        assert out["metrics"]["kv_bytes_per_ctx_token.tput"]["value"] > 2 * 2 * 16 * 4
    else:
        gauges = by["window"]["counters_at_close"]
        assert set(gauges) == {"state_bytes", "ssm_state_absmax"}
        assert gauges["state_bytes"] % by["engine"]["slot_state_bytes"] == 0 and 0 < gauges["ssm_state_absmax"] < 1e3
        assert by["window"]["queue_never_empty"] and by["window"]["compiled_in_window"] == 0
        assert by["setup_check"]["ok"] and by["setup_check"]["selection_overlap"] == []
        assert by["reference_check"]["ok"] and 0 < by["reference_check"]["tokens_checked"] <= 2 * 8
        before, after = by["setup"]["cache_when_compiled"], by["setup"]["cache_when_fetched"]
        assert before["requests"] >= 1 + 3 + 2  # the quantum, admit sizes 1, 2, 4, the check's two, side by side


def test_untraced_run_reports_the_cells_end_to_end_metrics(tiny_root):
    out = run.run_cell(TINY, SEED + 1, 1.0, False, root=tiny_root, require_tpu=False)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}


def test_the_judge_passes_the_programs_precision_and_refuses_the_one_below(tiny_root):
    """The control `tools/jamba_tolerance.py` runs on the chip (bf16 passes,
    fp8 is refused), here one step up: the tiny program is float32, so the
    reference rounded to bf16 is the precision below, and the same `judge`
    with the tiny configuration's limits has to refuse it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import common
    from tpukit.model import hybrid

    config = json.loads((tiny_root / "benchmark" / "configs" / "tiny-jamba.json").read_text())
    mode = common.load_by_name("modes", "serve_latent", tiny_root)
    ref = common.load_by_name("reference", config["reference"], tiny_root)
    cfg = hybrid.config_from_hf(config, compute_dtype="float32", param_dtype="float32")
    params = hybrid.init_params(jax.random.PRNGKey(3), cfg)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab_size, size=52).astype(np.int32))
    exact = np.asarray(ref.logits(params, ids, hf=config))
    low = np.asarray(ref.logits(params, ids, hf=config, round_to=jnp.bfloat16))
    kw = dict(prompt_tokens=40, topk=0, tolerance=config["tolerance"])
    assert mode.judge(exact, [], exact, [], **kw)[0]
    ok, report = mode.judge(low, [], exact, [], **kw)
    assert not ok and report["decode_logit_rms_rel"] > config["tolerance"]["logit_rms_rel"]


def test_on_a_program_without_the_family_the_mode_exits_at_once(tiny_root, monkeypatch):
    import sys

    import tpukit.model

    # as on the parent's tree: `from tpukit.model import hybrid` finds no such module
    monkeypatch.delattr(tpukit.model, "hybrid")
    monkeypatch.setitem(sys.modules, "tpukit.model.hybrid", None)
    with pytest.raises(SystemExit, match="no hybrid family"):
        run.run_cell(TINY, SEED, 1.0, False, root=tiny_root, require_tpu=False)


def test_the_reference_names_every_program_its_forward_dispatches(tiny_root, monkeypatch):
    """`lowered_programs` (what the mode compiles in threads while the sampled
    completions are served) against the calls `logits` makes: the same
    modules, text for text, so the forward finds each one in the cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import common
    from tpukit.model import hybrid

    config = json.loads((tiny_root / "benchmark" / "configs" / "tiny-jamba.json").read_text())
    ref = common.load_by_name("reference", config["reference"], tiny_root)
    cfg = hybrid.config_from_hf(config, compute_dtype="float32", param_dtype="float32")
    params = hybrid.init_params(jax.random.PRNGKey(5), cfg)
    called = set()

    def recording(fn):
        def call(*args, **kw):
            called.add(fn.lower(*args, **kw).as_text())
            return fn(*args, **kw)
        return call

    for name in ("mamba_layer", "attention_layer", "head"):
        monkeypatch.setattr(ref, name, recording(getattr(ref, name)))
    ids = jnp.asarray(np.random.default_rng(5).integers(0, cfg.vocab_size, size=48).astype(np.int32))
    ref.logits(params, ids, hf=config)
    monkeypatch.undo()
    ahead = [low.as_text() for low in ref.lowered_programs(jax.eval_shape(lambda: params), 48, hf=config)]
    assert set(ahead) == called and len(ahead) == len(called) == 3
