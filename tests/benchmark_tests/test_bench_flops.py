"""The copied yardstick against the program's own arithmetic: equal today;
from now on the copy is what the benchmark measures with."""

from pathlib import Path

import pytest

from benchmark import common, flops

CONFIGS = Path(__file__).resolve().parents[2] / "benchmark" / "configs"


@pytest.mark.parametrize("name,params_m,gflop", [("gpt2-medium", 406, 2.42), ("gpt2-xl", 1638, 10.3)])
def test_flops_copy_matches_the_program(name, params_m, gflop):
    from tpukit.obs.meter import matmul_param_count, train_flops_per_token

    cfg = common.gpt_config(common.load_json(CONFIGS / f"{name}.json"))
    for seq in (511, 1023):
        assert flops.cfg_train_flops_per_token(cfg, seq) == train_flops_per_token(cfg, seq)
    assert flops.matmul_param_count(cfg.dim, cfg.heads, cfg.head_dim, cfg.num_layers,
                                    cfg.padded_vocab_size) == matmul_param_count(cfg)
    assert flops.cfg_train_flops_per_token(cfg, 1023) / 1e9 == pytest.approx(gflop, rel=0.01)


@pytest.mark.parametrize("name,params_m", [("gpt2-medium", 406), ("gpt2-xl", 1638)])
def test_configuration_builds_the_published_widths(name, params_m):
    import jax

    from tpukit.model.gpt import init_params, param_count

    config = common.load_json(CONFIGS / f"{name}.json")
    cfg = common.gpt_config(config)
    assert (cfg.dim, cfg.heads, cfg.num_layers) == (config["n_embd"], config["n_head"], config["n_layer"])
    assert cfg.head_dim == 64 and cfg.max_position_embeddings == 1024 and cfg.vocab_size == 50257
    assert cfg.padded_vocab_size == 50304
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert param_count(shapes) / 1e6 == pytest.approx(params_m, rel=0.01)


@pytest.mark.parametrize("name", ["gpt2-medium", "gpt2-xl"])
def test_configuration_file_states_its_cut_and_departures(name):
    config = common.load_json(CONFIGS / f"{name}.json")
    assert config["source"].startswith("https://huggingface.co/openai-community/") and len(config["source"]) <= 200
    assert config["reduced"] == []
    for key in ("ffn", "qkv_bias", "lm_head", "vocab_padding", "dtypes"):
        assert key in config["assumed"]
    assert config["deployment"] and config["tolerance"]["why"]


def test_kernel_arithmetic_of_flash_and_head():
    from types import SimpleNamespace

    flash = common.load_by_name("kernels", "flash", CONFIGS.parents[1])
    head = common.load_by_name("kernels", "head_ce", CONFIGS.parents[1])
    cfg = SimpleNamespace(heads=16, head_dim=64, dim=1024, padded_vocab_size=50304)
    rec = {"cfg": cfg, "rows_per_chip": 8, "seq": 1023}
    w = flash.work(rec)
    bh = 8 * 16
    assert w["flash_fwd"][0] == 2.0 * 1023 * 1023 * 64 * bh          # causal half of 4*S^2*D
    assert w["flash_bwd"][0] == 2 * w["flash_fwd"][0]                  # recompute not credited
    assert w["flash_dq"][0] + w["flash_dkv"][0] == w["flash_bwd"][0]
    assert w["flash_fwd"][1] == 4 * bh * 1023 * 64 * 2 + bh * 1023 * 4
    h = head.work(rec)
    assert h["head_ce_fwd"][0] == 2.0 * 8 * 1023 * 1024 * 50304
    assert h["head_ce_bwd"][0] == 2 * h["head_ce_fwd"][0]
