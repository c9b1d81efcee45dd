"""benchmark/traffic/think-saturate.json: what the file states about its
lengths against what `traffic_gen` draws from its `size_seed`, and ISSUE 36's
parameters of the cell."""

import re
from pathlib import Path

import numpy as np
import pytest

from benchmark import common, traffic_gen

ROOT = Path(__file__).resolve().parents[2]
VOCAB = 65536
num = lambda s: float(s.replace(",", ""))  # noqa: E731


@pytest.fixture(scope="module")
def traffic():
    return common.load_json(ROOT / "benchmark" / "traffic" / "think-saturate.json")


@pytest.fixture(scope="module")
def requests(traffic):
    return traffic_gen.serve_requests(traffic, VOCAB, 2**31 + 7, 30.0)


def test_the_cells_parameters_are_the_issues(traffic):
    assert traffic["mode"] == "serve_hybrid" and traffic["arrivals"] == {"kind": "all_at_once"}
    assert traffic["requests"] == {"base": 512, "per_second": 16, "block": 4} and traffic["eos_id"] == -1
    assert traffic["prompt_len"] == {"distribution": "lognormal", "median": 2048, "sigma": 0.8, "min": 512, "max": 8192}
    assert traffic["output_len"] == {"distribution": "lognormal", "median": 3072, "sigma": 0.6, "min": 1024,
                                     "max": 8192}
    eng = traffic["engine"]
    assert (eng["slots"], eng["page_size"], eng["kv_dtype"], eng["decode_quantum"], eng["max_len"]) == (
        128, 16, "bf16", 4, 16384)
    assert eng["prefill_chunk"] in (128, 256) and eng["buckets"] == [traffic["prompt_len"]["max"]]
    assert traffic["ramp"] == {"completions": 32}
    check = traffic["setup_check"]
    assert check["prompt_tokens"] >= 8192 and check["prompt_tokens"] % eng["prefill_chunk"] == 0
    assert check["decode_steps"] == 64 and traffic["check_requests"] == 2
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] <= eng["max_len"]
    config = common.load_json(ROOT / "benchmark" / "configs" / "jamba2-3b.json")
    assert config["vocab_size"] == VOCAB and traffic["ids"] == {"distribution": "uniform"}


def test_the_blocks_sizes_and_means_are_the_ones_the_file_states(traffic, requests):
    block = traffic["requests"]["block"]
    prompts = np.array([len(r["ids"]) for r in requests[:block]])
    answers = np.array([r["max_new_tokens"] for r in requests[:block]])
    said = re.search(r"block of 4 comes closest to them.*: prompts ((?:[\d,]+ / ){3}[\d,]+) with answers "
                     r"((?:[\d,]+ / ){3}[\d,]+), means ([\d,.]+) and ([\d,.]+), longest request ([\d,]+) tokens",
                     traffic["lengths_source"])
    assert block == 4 and said
    stated = zip(map(num, said.group(1).split(" / ")), map(num, said.group(2).split(" / ")))
    assert sorted(zip(prompts.tolist(), answers.tolist())) == sorted(stated)
    assert prompts.mean() == pytest.approx(num(said.group(3)), abs=0.01)
    assert answers.mean() == pytest.approx(num(said.group(4)), abs=0.01)
    longest = int((prompts + answers).max())
    assert longest == int(num(said.group(5))) < traffic["setup_check"]["prompt_tokens"]
    # the sampled completions' checked tokens fit the reference's one padded length
    check = traffic["setup_check"]
    assert int(prompts.max()) + traffic["check_new_tokens"] <= check["prompt_tokens"] + check["decode_steps"]


def test_size_seed_is_the_one_the_stated_rule_picks(traffic):
    """The seed in 2700-2799 whose block comes closest to the clipped
    distributions' means: chosen by a rule, not by a run."""
    said = re.search(r"the distributions' means are ([\d,.]+) and ([\d,.]+) tokens", traffic["lengths_source"])
    want_p, want_o = num(said.group(1)), num(said.group(2))
    block = traffic["requests"]["block"]

    def miss(seed):
        rng = np.random.default_rng(seed)
        p = traffic_gen._lengths(traffic["prompt_len"], block, rng)
        o = traffic_gen._lengths(traffic["output_len"], block, rng)
        return abs(p.mean() / want_p - 1) + abs(o.mean() / want_o - 1)

    assert min(range(2700, 2800), key=miss) == traffic["size_seed"]


@pytest.mark.parametrize("which,group", [("prompt_len", 1), ("output_len", 2)])
def test_the_clipped_distributions_means_are_the_ones_the_file_states(traffic, which, group):
    said = re.search(r"the distributions' means are ([\d,.]+) and ([\d,.]+) tokens", traffic["lengths_source"])
    mean = traffic_gen._lengths(traffic[which], 400_000, np.random.default_rng(group)).mean()
    assert mean == pytest.approx(num(said.group(group)), rel=0.01)


def test_every_block_holds_the_same_sizes_and_all_arrive_at_once(traffic, requests):
    block = traffic["requests"]["block"]
    sizes = lambda rs: sorted((len(r["ids"]), r["max_new_tokens"]) for r in rs)  # noqa: E731
    assert len(requests) == 512 + 16 * 30
    for start in range(block, len(requests) // block * block, block):
        assert sizes(requests[start:start + block]) == sizes(requests[:block])
    ids = np.concatenate([np.asarray(r["ids"]) for r in requests[:32]])
    assert ids.min() >= 0 and ids.max() < VOCAB and all(r["arrival_s"] == 0.0 for r in requests)
