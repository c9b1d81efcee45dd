"""benchmark/traffic/reason-saturate.json: what the file states about its
lengths against what `traffic_gen` draws from its `size_seed`, and ISSUE 33's
parameters of the cell."""

import re
from pathlib import Path

import numpy as np
import pytest

from benchmark import common, traffic_gen

ROOT = Path(__file__).resolve().parents[2]
VOCAB = 27520
num = lambda s: float(s.replace(",", ""))  # noqa: E731


@pytest.fixture(scope="module")
def traffic():
    return common.load_json(ROOT / "benchmark" / "traffic" / "reason-saturate.json")


@pytest.fixture(scope="module")
def requests(traffic):
    return traffic_gen.serve_requests(traffic, VOCAB, 2**31 + 5, 30.0)


def test_the_cells_parameters_are_the_issues(traffic):
    assert traffic["arrivals"] == {"kind": "all_at_once"} and traffic["eos_id"] == -1
    # ISSUE 33's remedy for a spread over 1.2%: 4, not 8 (read at 8 on the final program: 4.75%, PERF.md section 6)
    assert traffic["requests"] == {"base": 256, "per_second": 8, "block": 4}
    assert traffic["prompt_len"] == {"distribution": "lognormal", "median": 3072, "sigma": 0.8, "min": 512, "max": 12288}
    assert traffic["output_len"] == {"distribution": "lognormal", "median": 1536, "sigma": 0.6, "min": 256, "max": 4096}
    eng = traffic["engine"]
    assert (eng["page_size"], eng["kv_dtype"], eng["decode_quantum"], eng["buckets"], eng["max_len"]) == (
        16, "bf16", 4, [12288], 16384)
    assert eng["slots"] in (32, 64, 128) and eng["prefill_chunk"] in (128, 256)
    assert traffic["ramp"] == {"completions": 16} and traffic["trace_seconds"] == 5
    # ISSUE 33 named a 4,096-token prompt; the review of PR 33 asked for one as long as the longest request served
    assert traffic["setup_check"] == {"prompt_tokens": 7104, "decode_steps": 64, "lanes": 8}
    assert traffic["check_requests"] == 2 and traffic["check_max_tokens"] == 8192
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] <= eng["max_len"]
    config = common.load_json(ROOT / "benchmark" / "configs" / "motif-3-beta.json")
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
    assert int((prompts + answers).max()) == int(num(said.group(5))) <= traffic["engine"]["max_len"]
    # the bounds on the sampled completions (the second a bound on seconds: a tick a generated token) leave the
    # shortest answer's size, and the set-up check's sequence is as long as the longest request, in whole key blocks
    assert [int(a) for p, a in zip(prompts, answers)
            if p + a <= traffic["check_max_tokens"] and a <= traffic["check_max_new_tokens"]] == [int(answers.min())]
    check = traffic["setup_check"]
    longest = int((prompts + answers).max())
    assert -(-longest // 512) * 512 == check["prompt_tokens"] + check["decode_steps"]


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


def test_the_clipped_distributions_means_are_the_ones_the_file_states(traffic):
    rng = np.random.default_rng(1)
    said = re.search(r"the distributions' means are ([\d,.]+) and ([\d,.]+) tokens", traffic["lengths_source"])
    for spec, group in ((traffic["prompt_len"], 1), (traffic["output_len"], 2)):
        mean = traffic_gen._lengths(spec, 400_000, rng).mean()
        assert mean == pytest.approx(num(said.group(group)), rel=0.01)


def test_every_block_holds_the_same_sizes_and_every_seed_the_same_set(traffic, requests):
    block = traffic["requests"]["block"]
    sizes = lambda rs: sorted((len(r["ids"]), r["max_new_tokens"]) for r in rs)  # noqa: E731
    assert len(requests) == 256 + 8 * 30
    for start in range(block, len(requests) // block * block, block):
        assert sizes(requests[start:start + block]) == sizes(requests[:block])
    other = traffic_gen.serve_requests(traffic, VOCAB, 77, 30.0)
    assert sizes(other[:block]) == sizes(requests[:block])
    assert [r["ids"] for r in other[:block]] != [r["ids"] for r in requests[:block]]
    order = lambda rs: [(len(r["ids"]), r["max_new_tokens"]) for r in rs]  # noqa: E731
    assert order(other) != order(requests)


def test_ids_lie_in_the_slice_and_all_arrive_at_once(traffic, requests):
    ids = np.concatenate([np.asarray(r["ids"]) for r in requests[:32]])
    assert ids.min() >= 0 and ids.max() < VOCAB
    assert all(r["arrival_s"] == 0.0 for r in requests)
