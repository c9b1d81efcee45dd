"""benchmark/flops_dots3.py against ISSUE 27's parameter table, against what
`init_params` builds for the configuration file, and by hand at one context."""

from pathlib import Path

import jax
import numpy as np
import pytest

from benchmark import common, flops_dots3

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def config():
    return common.load_json(ROOT / "benchmark" / "configs" / "dots3-note-prev.json")


FULL_TABLE = {"q_a": 5.2, "q_b": 25.2, "kv_a": 2.9, "kv_b": 16.8, "o": 83.9, "gate": 0.7}
WINDOW_TABLE = {"q_a": 5.2, "q_b": 16.8, "kv_a": 5.6, "kv_b": 21.0, "o": 41.9, "gate": 0.3}


@pytest.mark.parametrize("kind,table,total", [("full_attention", FULL_TABLE, 134.7), ("sliding_attention", WINDOW_TABLE, 90.8)])
def test_attention_parameters_are_the_issues_table(config, kind, table, total):
    got = flops_dots3.attention_params(config, kind)
    for name, millions in table.items():
        assert round(got[name] / 1e6, 1) == millions, name
    assert round(sum(got[k] for k in table) / 1e6, 1) == total
    if kind == "full_attention":
        assert round(sum(got[k] for k in ("idx_q", "idx_k", "idx_w")) / 1e6, 1) == 9.4  # the indexer


def test_expert_router_dense_and_vocabulary_parts(config):
    held = flops_dots3.held_matmul_params(config)
    assert round(flops_dots3.expert_params(config) / 1e6, 1) == 23.6
    assert round(held["router"] / 4 / 1e6, 1) == 1.3 and round(held["dense_ffn"] / 1e6, 1) == 212.3
    assert round((held["embedding"] + held["head"]) / 1e6, 1) == 194.6
    assert held["routed_experts"] == 4 * 32 * flops_dots3.expert_params(config)


def test_held_parameters_are_what_init_params_builds(config):
    from tpukit.model import latent

    cfg = latent.config_from_hf(config)
    shapes = jax.eval_shape(lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    # matmul parameters: every leaf but the norm weights and the router's selection bias
    matmul = sum(int(np.prod(x.shape)) for path, x in leaves
                 if not any(tag in jax.tree_util.keystr(path) for tag in ("norm", "select_bias")))
    assert matmul == sum(flops_dots3.held_matmul_params(config).values()) == 4_087_087_104
    assert sum(int(np.prod(x.shape)) for _, x in leaves) == config["held"]["parameters"] == 4_087_154_176


def test_a_token_crosses_967m_and_the_heads_97m(config):
    crossed = flops_dots3.crossed_matmul_params(config)
    assert round(crossed["head"] / 1e6) == 97
    assert round((sum(crossed.values()) - crossed["head"]) / 1e6) == 967
    assert crossed["routed_experts"] == 4 * flops_dots3.expert_params(config)  # one routed expert in eight lands here


@pytest.mark.parametrize("ctx", [100, 513, 2048, 8000])
def test_context_flops_by_hand(config, ctx):
    full = 2 * 64 * 128 * ctx + 2 * 128 * ((512 + 64) + 512) * min(ctx, 2048)
    window = 2 * 64 * ((1024 + 64) + 1024) * min(ctx, 513)
    assert flops_dots3.context_flops(config, ctx) == 2 * full + 3 * window
    total = flops_dots3.forward_flops_per_output_token(config, ctx)
    assert total == 2 * sum(flops_dots3.crossed_matmul_params(config).values()) + 2 * full + 3 * window


@pytest.mark.parametrize("ctx", [64, 4000])
def test_a_prompt_token_is_an_output_token_less_the_head_and_the_last_layers_ffn(config, ctx):
    """967M crossed without the head; the last layer's FFN is its router, its
    shared expert and the one routed expert in eight that lands here."""
    last_ffn = 5120 * 256 + 2 * flops_dots3.expert_params(config)
    crossed = flops_dots3.crossed_matmul_params(config)
    want = 2 * (sum(crossed.values()) - crossed["head"] - last_ffn) + flops_dots3.context_flops(config, ctx)
    assert flops_dots3.forward_flops_per_prompt_token(config, ctx) == want
    assert want < flops_dots3.forward_flops_per_output_token(config, ctx)


def test_the_mfu_reader_credits_prompt_and_output_tokens(config):
    reader = common.load_by_name("layer_metrics", "mfu_active_pct.tput", ROOT)
    quanta = [{"delivered": 40, "decoding": 10, "ctx_tokens": 10 * 5000}]
    prefills = [{"chunk": 3, "tokens": 128}, {"chunk": 10, "tokens": 40}]
    rec = {"quanta": quanta, "prefills": prefills, "prefill_chunk": 128, "config": config, "window_s": 2.0,
           "peaks": {"flops_bf16": 1e12}}
    want = (40 * flops_dots3.forward_flops_per_output_token(config, 5000)
            + 128 * flops_dots3.forward_flops_per_prompt_token(config, 3 * 128 + 64)
            + 40 * flops_dots3.forward_flops_per_prompt_token(config, 10 * 128 + 20)) / 2.0 / 1e12 * 100
    assert reader.read(rec) == pytest.approx(want)
    assert reader.read(dict(rec, peaks=None)) is None and reader.read({"quanta": quanta}) is None
