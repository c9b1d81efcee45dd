"""benchmark/flops_deepseek_v3.py against the configuration file's `held`
table and what `init_params` builds, by hand at a few contexts, and
`mfu_active_deepseek_v3_pct.tput` on a worked record."""

from pathlib import Path

import jax
import numpy as np
import pytest

from benchmark import common, flops_deepseek_v3

ROOT = Path(__file__).resolve().parents[2]
MATMUL_FREE = ("norm", "select_bias")  # leaves that are no matmul's weight
NAME = "mfu_active_deepseek_v3_pct.tput"


@pytest.fixture(scope="module")
def config():
    return common.load_json(ROOT / "benchmark" / "configs" / "deepseek-v3.json")


TABLE = {"q_a": 11.01, "q_b": 37.75, "kv_a": 4.13, "kv_b": 16.78, "o": 117.44}


def test_attention_parameters_are_the_files_table(config):
    got = flops_deepseek_v3.attention_params(config)
    for name, millions in TABLE.items():
        assert round(got[name] / 1e6, 2) == millions, name
    assert round(sum(got.values()) / 1e6, 1) == 187.1


def test_experts_router_dense_and_vocabulary_parts(config):
    held = flops_deepseek_v3.held_matmul_params(config)
    assert round(flops_deepseek_v3.expert_params(config) / 1e6, 2) == 44.04
    assert held["router"] == 4 * 7168 * 256 and round(held["dense_ffn"] / 1e6, 1) == 396.4
    assert round((held["embedding"] + held["head"]) / 1e6, 1) == 231.7
    assert held["routed_experts"] == 4 * 16 * flops_deepseek_v3.expert_params(config)
    assert held["shared_experts"] == 4 * flops_deepseek_v3.expert_params(config)


def test_held_parameters_are_what_init_params_builds_and_what_the_file_states(config):
    from tpukit.model import latent

    cfg = latent.config_from_hf(config)
    shapes = jax.eval_shape(lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    matmul = sum(int(np.prod(x.shape)) for path, x in leaves
                 if not any(tag in jax.tree_util.keystr(path) for tag in MATMUL_FREE))
    assert matmul == sum(flops_deepseek_v3.held_matmul_params(config).values())
    assert sum(int(np.prod(x.shape)) for _, x in leaves) == config["held"]["parameters"]


def test_a_token_crosses_half_a_routed_expert_a_layer(config):
    crossed = flops_deepseek_v3.crossed_matmul_params(config)
    assert "embedding" not in crossed
    assert crossed["routed_experts"] == 4 * 0.5 * flops_deepseek_v3.expert_params(config)  # 8 x 16 / 256 land here
    rest = 5 * 187_105_280 + 396_361_728 + 4 * (7168 * 256 + 44_040_192 + 22_020_096)
    assert sum(crossed.values()) - crossed["head"] == rest


@pytest.mark.parametrize("ctx", [1, 128, 8192, 20_480])
def test_context_flops_by_hand(config, ctx):
    a_key = 2 * 128 * ((512 + 64) + 512)  # every layer, every key, all 128 heads
    assert flops_deepseek_v3.context_flops(config, ctx) == 5 * a_key * ctx
    total = flops_deepseek_v3.forward_flops_per_output_token(config, ctx)
    assert total == 2 * sum(flops_deepseek_v3.crossed_matmul_params(config).values()) + 5 * a_key * ctx


@pytest.mark.parametrize("ctx", [64, 15_000])
def test_a_prompt_token_is_an_output_token_less_the_head_and_the_last_layers_ffn(config, ctx):
    last_ffn = 7168 * 256 + flops_deepseek_v3.expert_params(config) * 1.5  # router, shared, half a routed
    crossed = flops_deepseek_v3.crossed_matmul_params(config)
    want = 2 * (sum(crossed.values()) - crossed["head"] - last_ffn) + flops_deepseek_v3.context_flops(config, ctx)
    assert flops_deepseek_v3.forward_flops_per_prompt_token(config, ctx) == pytest.approx(want, rel=1e-12)
    assert want < flops_deepseek_v3.forward_flops_per_output_token(config, ctx)


def _record(cfg, **kw):
    rec = {"quanta": [{"delivered": 256, "decoding": 64, "ctx_tokens": 64 * 15_000, "steps": 4},
                      {"delivered": 250, "decoding": 62, "ctx_tokens": 62 * 15_000, "steps": 4}],
           "prefills": [{"chunk": 160, "tokens": 128}, {"chunk": 161, "tokens": 27}, {"chunk": 0, "tokens": 0}],
           "prefill_chunk": 128, "window_s": 2.0, "peaks": {"flops_bf16": 197e12}, "config": cfg}
    rec.update(kw)
    return rec


def test_the_reader_on_a_worked_record(config):
    reader = common.load_by_name("layer_metrics", NAME, ROOT)
    out = 506 * flops_deepseek_v3.forward_flops_per_output_token(config, 15_000)
    prompt = (128 * flops_deepseek_v3.forward_flops_per_prompt_token(config, 160 * 128 + 64)
              + 27 * flops_deepseek_v3.forward_flops_per_prompt_token(config, 161 * 128 + 13.5))
    assert reader.read(_record(config)) == pytest.approx(100 * (out + prompt) / 2.0 / 197e12, rel=1e-12)


@pytest.mark.parametrize("missing", ["quanta", "peaks", "window_s", "config", "model_type"])
def test_a_record_without_what_it_reads_is_nothing(config, missing):
    reader = common.load_by_name("layer_metrics", NAME, ROOT)
    if missing == "model_type":  # another configuration: dots3's keys read nothing here
        rec = _record(common.load_json(ROOT / "benchmark" / "configs" / "dots3-note-prev.json"))
    else:
        rec = _record(config, **{missing: None})
    assert reader.read(rec) is None
