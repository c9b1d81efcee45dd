"""A CPU rehearsal of the latent family's serving mode (modes/serve_latent.py)
on a tiny preset: a temporary copy of the benchmark to which a tiny
configuration (the published keys at toy widths, five layers of the same
kinds, a top-k and a window smaller than the contexts, 16 experts of which 2
are held), a tiny traffic mix and a cell are ADDED. One traced `run_cell`:
`correct` true (the set-up's logits and selections, the timed path's tokens),
every per-layer metric the real cell lists reads a number; and the mode's
`judge` refuses the reference computed a precision below the program's."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import run

ROOT = Path(__file__).resolve().parents[2]
REAL, TINY = "dots3-note-prev.serve-longdoc", "tiny-latent.sat"
SEED = 2**31 + 27


def tiny_config(config: dict) -> dict:
    tiny = dict(config, name="tiny-latent")
    tiny.update(
        hidden_size=64, vocab_size=97, intermediate_size=128, moe_intermediate_size=32,
        num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        q_lora_rank=32, kv_lora_rank=24, index_n_heads=4, index_head_dim=16, index_topk=16,
        swa_num_attention_heads=2, swa_num_key_value_heads=2, swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
        swa_v_head_dim=16, swa_q_lora_rank=32, swa_kv_lora_rank=32, sliding_window_size=9,
        n_routed_experts=2, num_experts_per_tok=4, max_position_embeddings=4096,
        published=dict(config["published"], n_routed_experts=16),
        program=dict(config["program"], param_dtype="float32", compute_dtype="float32"),
        tolerance=dict(config["tolerance"], logit_rms_rel=1e-4, selection_overlap_min=0.99),
    )
    return tiny


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_latent")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "benchmark", root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    b = root / "benchmark"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    config = json.loads((b / "configs" / "dots3-note-prev.json").read_text())
    (b / "configs" / "tiny-latent.json").write_text(json.dumps(tiny_config(config)))
    traffic = json.loads((b / "traffic" / "longdoc-saturate.json").read_text())
    traffic.update(
        requests={"base": 400, "per_second": 0, "block": 8}, eos_id=96,
        prompt_len={"distribution": "lognormal", "median": 24, "sigma": 0.5, "min": 6, "max": 48},
        output_len={"distribution": "lognormal", "median": 6, "sigma": 0.5, "min": 2, "max": 12},
        engine={"slots": 4, "page_size": 4, "kv_dtype": "f32", "prefill_chunk": 8, "max_len": 64,
                "buckets": [48], "decode_quantum": 2},
        ramp={"completions": 4}, setup_check={"prompt_tokens": 40, "decode_steps": 12, "lanes": 2},
        check_requests=2, trace_seconds=1)
    (b / "traffic" / "tiny-latent-sat.json").write_text(json.dumps(traffic))
    entry = next(c for c in bench["configs"] if c["name"] == "dots3-note-prev")
    bench["configs"].append(dict(entry, name="tiny-latent", file="benchmark/configs/tiny-latent.json"))
    bench["workloads"].append({"name": TINY, "config": "tiny-latent", "traffic": "tiny-latent-sat", "chips": 1,
                               "why": "rehearsal"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(TINY)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def traced(tiny_root):
    return run.run_cell(TINY, SEED, 1.0, True, root=tiny_root, require_tpu=False)


def test_the_rehearsal_is_correct_and_complete(traced):
    assert traced["correct"] is True
    assert traced["failed"] == 0 and traced["attempted"] > 0


# the CPU run has no device plane: readers of the device trace find nothing to read there
DEVICE_TRACE = {"decode_tick_device_ms.tput", "device_idle_pct.tput", "prefill_device_ms_per_ktoken.tput",
                "hbm_peak_gb.tput"}


def test_every_counter_reader_of_the_real_cell_reads_a_number(traced, tiny_root):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in bench["per_layer"] if TINY in m.get("workloads", [])}
    assert {"mfu_active_pct.tput", "prefill_device_ms_per_ktoken.tput", "kv_bytes_per_ctx_token.tput",
            "expert_load_max_over_mean.tput"} <= listed
    for name in sorted(listed - DEVICE_TRACE - {"mfu_active_pct.tput"}):  # the MFU needs a chip's peak
        assert name in traced["metrics"], name
    assert traced["metrics"]["expert_load_max_over_mean.tput"]["value"] >= 1.0
    assert traced["metrics"]["kv_bytes_per_ctx_token.tput"]["value"] > 0


def test_untraced_run_reports_the_cells_end_to_end_metrics(tiny_root):
    out = run.run_cell(TINY, SEED + 1, 1.0, False, root=tiny_root, require_tpu=False)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"serve_out_tokens_per_s", "setup_s"}


def test_the_judge_passes_the_programs_precision_and_refuses_the_one_below(tiny_root):
    """The control `tools/dots3_tolerance.py` runs on the chip (bf16 passes,
    fp8 is refused), here one step up: the tiny program is float32, so the
    reference rounded to bf16 is the precision below, and the same `judge`
    with the tiny configuration's limits has to refuse it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import common
    from tpukit.model import latent

    config = json.loads((tiny_root / "benchmark" / "configs" / "tiny-latent.json").read_text())
    mode = common.load_by_name("modes", "serve_latent", tiny_root)
    ref = common.load_by_name("reference", config["reference"], tiny_root)
    cfg = latent.config_from_hf(config, compute_dtype="float32", param_dtype="float32")
    params = latent.init_params(jax.random.PRNGKey(3), cfg)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab_size, size=52).astype(np.int32))
    exact_sel, low_sel = [], []
    exact = np.asarray(ref.logits(params, ids, hf=config, selected=exact_sel))
    low = np.asarray(ref.logits(params, ids, hf=config, round_to=jnp.bfloat16, selected=low_sel))
    kw = dict(prompt_tokens=40, topk=cfg.index_topk, tolerance=config["tolerance"])
    ok, report = mode.judge(exact, exact_sel, exact, exact_sel, **kw)
    assert ok and report["prefill_logit_rms_rel"] == 0.0 and report["selection_overlap"] == [1.0, 1.0]
    ok, report = mode.judge(low, low_sel, exact, exact_sel, **kw)
    assert not ok and report["decode_logit_rms_rel"] > config["tolerance"]["logit_rms_rel"]
