"""A CPU rehearsal of the motif-3-beta cell (modes/serve_latent_cold.py) on a
tiny preset: a temporary copy of the benchmark to which a tiny configuration
(the published keys at toy widths: 2 KV groups of 4 + 1 heads, 4 streams,
window 9, 16 experts of which 2 are held), a tiny traffic mix and a cell are
ADDED. One traced `run_cell`: `correct` true (the set-up's logits, the timed
path's tokens), every counter reader the real cell lists reads a number, the
quanta carry the two health gauges; and the mode's `judge` refuses the
reference computed a precision below the program's."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import run

ROOT = Path(__file__).resolve().parents[2]
REAL, TINY = "motif-3-beta.serve-reason", "tiny-motif.sat"
SEED = 2**31 + 33


def tiny_config(config: dict) -> dict:
    tiny = dict(config, name="tiny-motif")
    tiny.update(
        hidden_size=64, vocab_size=97, intermediate_size=128, moe_intermediate_size=32,
        num_attention_heads=10, num_key_value_heads=2, num_noise_heads=2, head_dim=24, qk_rope_head_dim=8,
        v_head_dim=16, q_lora_rank=32, kv_lora_rank=24, sliding_window=9,
        num_experts=2, experts_top_k=4, max_position_embeddings=4096,
        published=dict(config["published"], num_experts=16),
        program=dict(config["program"], param_dtype="float32", compute_dtype="float32"),
        tolerance=dict(config["tolerance"], logit_rms_rel=1e-4),
    )
    return tiny


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_motif")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "benchmark", root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    b = root / "benchmark"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    config = json.loads((b / "configs" / "motif-3-beta.json").read_text())
    (b / "configs" / "tiny-motif.json").write_text(json.dumps(tiny_config(config)))
    traffic = json.loads((b / "traffic" / "reason-saturate.json").read_text())
    traffic.update(
        requests={"base": 400, "per_second": 0, "block": 8}, eos_id=96,
        prompt_len={"distribution": "lognormal", "median": 20, "sigma": 0.5, "min": 6, "max": 40},
        output_len={"distribution": "lognormal", "median": 10, "sigma": 0.5, "min": 3, "max": 20},
        engine={"slots": 4, "page_size": 4, "kv_dtype": "f32", "prefill_chunk": 8, "max_len": 64,
                "buckets": [40], "decode_quantum": 2},
        ramp={"completions": 4}, setup_check={"prompt_tokens": 40, "decode_steps": 12, "lanes": 2},
        check_requests=2, check_max_tokens=56, check_max_new_tokens=14, compile_workers=2, trace_seconds=1)
    (b / "traffic" / "tiny-motif-sat.json").write_text(json.dumps(traffic))
    entry = next(c for c in bench["configs"] if c["name"] == "motif-3-beta")
    bench["configs"].append(dict(entry, name="tiny-motif", file="benchmark/configs/tiny-motif.json"))
    bench["workloads"].append({"name": TINY, "config": "tiny-motif", "traffic": "tiny-motif-sat", "chips": 1,
                               "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(TINY)
    # the cell's share of the whole step: the reader is here, its BENCHMARK.json entry waits for a `benchmark` PR
    # (test_bench_paged_attend_roofline.py pins the list's last place, so no other PR can append a per-layer metric)
    bench["per_layer"].append({"name": "mfu_active_motif3_pct.tput", "unit": "%", "better": "higher",
                               "source": "program_counter", "layer": "model", "moves": "serve_out_tokens_per_s",
                               "workloads": [TINY]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def traced(tiny_root, capsys_module):
    out = run.run_cell(TINY, SEED, 1.0, True, root=tiny_root, require_tpu=False)
    return out, [json.loads(line) for line in capsys_module().splitlines() if line.startswith('{"info"')]


@pytest.fixture(scope="module")
def capsys_module():
    """The info lines a run printed: `run_cell` prints them, a module-scoped
    fixture cannot use `capsys`, so standard output is swapped by hand."""
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        yield buf.getvalue


def test_the_rehearsal_is_correct_and_complete(traced):
    out, _ = traced
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0


# the CPU run has no device plane: readers of the device trace find nothing to read there
DEVICE_TRACE = {"decode_tick_device_ms.tput", "device_idle_pct.tput", "prefill_device_ms_per_ktoken.tput",
                "hbm_peak_gb.tput"}


def test_every_counter_reader_of_the_real_cell_reads_a_number(traced, tiny_root):
    out, _ = traced
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in bench["per_layer"] if TINY in m.get("workloads", [])}
    assert {"mfu_active_motif3_pct.tput", "prefill_device_ms_per_ktoken.tput", "kv_bytes_per_ctx_token.tput",
            "expert_load_max_over_mean.tput", "decode_tick_device_ms.tput"} <= listed
    assert "mfu_active_pct.tput" not in listed  # that reader counts another configuration's FLOPs
    for name in sorted(listed - DEVICE_TRACE - {"mfu_active_motif3_pct.tput"}):  # the MFU needs a chip's peak
        assert name in out["metrics"], name
    assert out["metrics"]["expert_load_max_over_mean.tput"]["value"] >= 1.0
    assert out["metrics"]["kv_bytes_per_ctx_token.tput"]["value"] > 0


def test_the_info_lines_say_what_the_run_did(traced):
    _, info = traced
    by = {line["info"]: line for line in info}
    before, after = by["setup"]["cache_when_compiled"], by["setup"]["cache_when_fetched"]
    assert before["requests"] >= 1 + 3 + 2  # the quantum, admit sizes 1, 2, 4, the check's two, side by side
    # the calls that followed found those six compiled: what they still compiled is small fry
    assert after["compile_s"] - before["compile_s"] < 0.25 * before["compile_s"]
    gauges = by["window"]["counters_at_close"]
    assert 0 <= gauges["mhc_row_err_max"] < 1e-3 and 0 < gauges["diff_lambda_mean"] < 1
    assert by["window"]["queue_never_empty"] and by["window"]["compiled_in_window"] == 0
    assert by["setup_check"]["ok"] and by["setup_check"]["selection_overlap"] == []
    assert by["reference_check"]["ok"] and by["reference_check"]["tokens_checked"] > 0
    assert all(n <= 56 for n in by["reference_check"]["contexts"])


def test_untraced_run_reports_the_cells_end_to_end_metrics(tiny_root):
    out = run.run_cell(TINY, SEED + 1, 1.0, False, root=tiny_root, require_tpu=False)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}


def test_the_mfu_reader_reads_this_configuration_and_no_other(tiny_root):
    from benchmark import common

    reader = common.load_by_name("layer_metrics", "mfu_active_motif3_pct.tput", tiny_root)
    config = json.loads((tiny_root / "benchmark" / "configs" / "tiny-motif.json").read_text())
    rec = {"quanta": [{"delivered": 8, "ctx_tokens": 120, "decoding": 4}], "config": config, "window_s": 2.0,
           "peaks": {"flops_bf16": 1e9}, "prefill_chunk": 8, "prefills": [{"tokens": 8, "chunk": 1}]}
    from benchmark import flops_motif3

    want = (8 * flops_motif3.forward_flops_per_output_token(config, 30.0)
            + 8 * flops_motif3.forward_flops_per_prompt_token(config, 12.0)) / 2.0 / 1e9 * 100
    assert reader.read(rec) == pytest.approx(want)
    other = json.loads((tiny_root / "benchmark" / "configs" / "dots3-note-prev.json").read_text())
    assert reader.read(dict(rec, config=other)) is None and reader.read(dict(rec, quanta=[])) is None


def test_the_judge_passes_the_programs_precision_and_refuses_the_one_below(tiny_root):
    """The control `tools/motif3_tolerance.py` runs on the chip (bf16 passes,
    fp8 is refused), here one step up: the tiny program is float32, so the
    reference rounded to bf16 is the precision below, and the same `judge`
    with the tiny configuration's limits has to refuse it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import common
    from tpukit.model import latent

    config = json.loads((tiny_root / "benchmark" / "configs" / "tiny-motif.json").read_text())
    mode = common.load_by_name("modes", "serve_latent", tiny_root)
    ref = common.load_by_name("reference", config["reference"], tiny_root)
    cfg = latent.config_from_hf(config, compute_dtype="float32", param_dtype="float32")
    params = latent.init_params(jax.random.PRNGKey(3), cfg)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab_size, size=52).astype(np.int32))
    exact = np.asarray(ref.logits(params, ids, hf=config))
    low = np.asarray(ref.logits(params, ids, hf=config, round_to=jnp.bfloat16))
    kw = dict(prompt_tokens=40, topk=cfg.index_topk, tolerance=config["tolerance"])
    ok, report = mode.judge(exact, [], exact, [], **kw)
    assert ok and report["prefill_logit_rms_rel"] == 0.0 and report["selection_overlap"] == []
    ok, report = mode.judge(low, [], exact, [], **kw)
    assert not ok and report["decode_logit_rms_rel"] > config["tolerance"]["logit_rms_rel"]


def test_the_reference_names_every_program_its_forward_dispatches(tiny_root, monkeypatch):
    """`lowered_programs` (what the mode compiles in threads while the sampled
    completions are served) against the calls `logits` makes: the same
    modules, text for text, so the forward finds each one in the cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import common
    from tpukit.model import latent

    config = json.loads((tiny_root / "benchmark" / "configs" / "tiny-motif.json").read_text())
    ref = common.load_by_name("reference", config["reference"], tiny_root)
    cfg = latent.config_from_hf(config, compute_dtype="float32", param_dtype="float32")
    params = latent.init_params(jax.random.PRNGKey(5), cfg)
    called = set()

    def recording(fn):
        def call(*args, **kw):
            called.add(fn.lower(*args, **kw).as_text())
            return fn(*args, **kw)
        return call

    for name in ("stream_maps", "write_back", "attention", "_rms_jit", "gated_ffn", "route", "head"):
        monkeypatch.setattr(ref, name, recording(getattr(ref, name)))
    ids = jnp.asarray(np.random.default_rng(5).integers(0, cfg.vocab_size, size=48).astype(np.int32))
    ref.logits(params, ids, hf=config)
    monkeypatch.undo()
    shapes = jax.eval_shape(lambda: params)
    ahead = [low.as_text() for low in ref.lowered_programs(shapes, 48, hf=config)]
    assert set(ahead) == called and len(ahead) == len(called) == 8
