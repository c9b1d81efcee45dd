"""benchmark/flops_motif3.py against ISSUE 33's parameter table, against what
`init_params` builds for the configuration file and the file's own `held`
table, and by hand at one context."""

from pathlib import Path

import jax
import numpy as np
import pytest

from benchmark import common, flops_motif3

ROOT = Path(__file__).resolve().parents[2]
MATMUL_FREE = ("norm", "poly_", "alpha", "['b']")  # leaves that are no matmul's weight


@pytest.fixture(scope="module")
def config():
    return common.load_json(ROOT / "benchmark" / "configs" / "motif-3-beta.json")


TABLE = {"q_a": 4.19, "q_b": 15.73, "kv_a": 2.36, "kv_b": 2.10, "lam": 0.26, "gate": 33.55, "o": 33.55}


def test_attention_parameters_are_the_issues_table(config):
    got = flops_motif3.attention_params(config)
    for name, millions in TABLE.items():
        assert round(got[name] / 1e6, 2) == millions, name
    assert round(sum(got.values()) / 1e6, 2) == 91.75


def test_streams_experts_router_dense_and_vocabulary_parts(config):
    held = flops_motif3.held_matmul_params(config)
    assert round(flops_motif3.stream_params(config) / 1e6, 2) == 0.79  # two sublayers: phi 16,384 x 24 each
    assert round(flops_motif3.expert_params(config) / 1e6, 2) == 15.73
    assert round(held["router"] / 4 / 1e6, 2) == 1.57 and round(held["dense_ffn"] / 1e6, 1) == 151.0
    assert round((held["embedding"] + held["head"]) / 1e6, 1) == 225.4
    assert held["routed_experts"] == 4 * 48 * flops_motif3.expert_params(config)
    assert round(48 * flops_motif3.expert_params(config) / 1e6, 1) == 755.0
    assert flops_motif3.held_layers(config) == [0, 4, 5, 6, 7]
    assert [flops_motif3.layer_is_full(config, i) for i in (0, 4, 5, 6, 7)] == [False, False, False, False, True]


def test_held_parameters_are_what_init_params_builds_and_what_the_file_states(config):
    from tpukit.model import latent

    cfg = latent.config_from_hf(config)
    shapes = jax.eval_shape(lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    matmul = sum(int(np.prod(x.shape)) for path, x in leaves
                 if not any(tag in jax.tree_util.keystr(path) for tag in MATMUL_FREE))
    assert matmul == sum(flops_motif3.held_matmul_params(config).values()) == 3_928_227_840
    total = sum(int(np.prod(x.shape)) for _, x in leaves)
    assert total == config["held"]["parameters"] == 3_928_281_634  # ISSUE 33: about 3.93B
    size = sum(int(np.prod(x.shape)) * x.dtype.itemsize for _, x in leaves)
    assert size == config["held"]["bytes_bf16"] == 7_877_118_088  # 7.88 GB: ISSUE 33 reckoned 7.86 with all of it bf16
    # the table's sum: the dense layer, four expert layers, the vocabulary
    per_layer = lambda layer: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(layer))  # noqa: E731
    assert round(per_layer(shapes["layers"][0]) / 1e6, 1) == 243.5
    assert {round(per_layer(shapes["layers"][i]) / 1e6, 1) for i in (1, 2, 3, 4)} == {864.8}


def test_every_published_number_is_in_the_file_under_its_key(config):
    """The catalog row's `config`, key by key: only the three `reduced` keys differ."""
    import json

    row = next(r for r in map(json.loads, open("/opt/skills/guides/model-configs/architectures.jsonl"))
               if r["name"] == "Motif-3-Beta") if Path("/opt/skills/guides/model-configs/architectures.jsonl").exists() else None
    if row is None:
        pytest.skip("the catalog is not on this machine")
    differ = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differ == sorted(config["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert config["source"] == row["source_url"]
    assert {k: config["published"][k] for k in differ} == {k: row["config"][k] for k in differ}


def test_a_token_crosses_743m_and_the_heads_113m(config):
    crossed = flops_motif3.crossed_matmul_params(config)
    assert round(crossed["head"] / 1e6) == 113
    assert crossed["routed_experts"] == 4 * flops_motif3.expert_params(config)  # one routed expert in eight lands here
    rest = 5 * (91_750_400 + 786_432) + 150_994_944 + 4 * (4096 * 384 + 2 * 15_728_640)
    assert sum(crossed.values()) - crossed["head"] == rest and round(rest / 1e6) == 746


@pytest.mark.parametrize("ctx", [100, 128, 2048, 9000])
def test_context_flops_by_hand(config, ctx):
    a_key = 2 * 80 * ((512 + 64) + 512)
    assert flops_motif3.context_flops(config, ctx) == a_key * ctx + 4 * a_key * min(ctx, 128)
    total = flops_motif3.forward_flops_per_output_token(config, ctx)
    assert total == 2 * sum(flops_motif3.crossed_matmul_params(config).values()) + a_key * (ctx + 4 * min(ctx, 128))


@pytest.mark.parametrize("ctx", [64, 4000])
def test_a_prompt_token_is_an_output_token_less_the_head_and_the_last_layers_ffn(config, ctx):
    last_ffn = 4096 * 384 + 2 * flops_motif3.expert_params(config)
    crossed = flops_motif3.crossed_matmul_params(config)
    want = 2 * (sum(crossed.values()) - crossed["head"] - last_ffn) + flops_motif3.context_flops(config, ctx)
    assert flops_motif3.forward_flops_per_prompt_token(config, ctx) == want
    assert want < flops_motif3.forward_flops_per_output_token(config, ctx)
