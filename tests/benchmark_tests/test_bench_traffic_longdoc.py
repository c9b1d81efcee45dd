"""benchmark/traffic/longdoc-saturate.json: what the file states about its
lengths against what `traffic_gen` draws from its `size_seed`."""

import math
import re
from pathlib import Path

import numpy as np
import pytest

from benchmark import common, traffic_gen

ROOT = Path(__file__).resolve().parents[2]
VOCAB = 19008


@pytest.fixture(scope="module")
def traffic():
    return common.load_json(ROOT / "benchmark" / "traffic" / "longdoc-saturate.json")


@pytest.fixture(scope="module")
def requests(traffic):
    return traffic_gen.serve_requests(traffic, VOCAB, 2**31 + 5, 30.0)


def test_medians_are_the_published_means_under_the_stated_shape(traffic):
    assert traffic["prompt_len"]["median"] == pytest.approx(7590 * math.exp(-0.8**2 / 2), abs=0.1)
    assert traffic["output_len"]["median"] == pytest.approx(182 * math.exp(-1.0**2 / 2), abs=0.1)
    assert (traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]) == (1024, 15360)
    assert (traffic["output_len"]["min"], traffic["output_len"]["max"]) == (8, 1024)
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] <= traffic["engine"]["max_len"]


def test_the_blocks_sizes_and_means_are_the_ones_the_file_states(traffic, requests):
    block = traffic["requests"]["block"]
    prompts = np.array([len(r["ids"]) for r in requests[:block]])
    answers = np.array([r["max_new_tokens"] for r in requests[:block]])
    stated = traffic["lengths_source"]
    said = re.search(r"block of 4 comes closest to them.*: prompts ([\d,]+) / ([\d,]+) / ([\d,]+) / ([\d,]+) with answers "
                     r"(\d+) / (\d+) / (\d+) / (\d+), means ([\d,.]+) and ([\d,.]+), (\d+) of 4 prompts past 2,048, "
                     r"longest request ([\d,]+) tokens", stated)
    num = lambda s: float(s.replace(",", ""))  # noqa: E731
    assert block == 4 and said
    drawn = sorted(zip(prompts.tolist(), answers.tolist()))
    assert drawn == sorted(zip((num(said.group(i)) for i in range(1, 5)), (num(said.group(i)) for i in range(5, 9))))
    assert prompts.mean() == pytest.approx(num(said.group(9)), abs=0.01)
    assert answers.mean() == pytest.approx(num(said.group(10)), abs=0.01)
    assert int((prompts > 2048).sum()) == int(said.group(11))
    assert int((prompts + answers).max()) == int(num(said.group(12))) <= traffic["engine"]["max_len"]


def test_size_seed_is_the_one_the_stated_rule_picks(traffic):
    """The seed in 2700-2799 whose block comes closest to the clipped
    distributions' means: chosen by a rule, not by a run."""
    said = re.search(r"the distributions' means are ([\d,.]+) and ([\d,.]+) tokens", traffic["lengths_source"])
    want_p, want_o = (float(said.group(i).replace(",", "")) for i in (1, 2))
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
        assert mean == pytest.approx(float(said.group(group).replace(",", "")), rel=0.01)


def test_every_block_holds_the_same_sizes_and_every_seed_the_same_set(traffic, requests):
    block = traffic["requests"]["block"]
    sizes = lambda rs: sorted((len(r["ids"]), r["max_new_tokens"]) for r in rs)  # noqa: E731
    assert len(requests) == traffic["requests"]["base"] + traffic["requests"]["per_second"] * 30
    whole = len(requests) // block * block
    for start in range(block, whole, block):
        assert sizes(requests[start:start + block]) == sizes(requests[:block])
    other = traffic_gen.serve_requests(traffic, VOCAB, 77, 30.0)
    assert sizes(other[:block]) == sizes(requests[:block])
    assert [r["ids"] for r in other[:block]] != [r["ids"] for r in requests[:block]]


def test_ids_lie_in_the_slice_and_never_draw_eos(traffic, requests):
    ids = np.concatenate([np.asarray(r["ids"]) for r in requests[:32]])
    assert ids.min() >= 0 and ids.max() < VOCAB and traffic["eos_id"] not in set(ids.tolist())
    assert all(r["arrival_s"] == 0.0 for r in requests)


def test_the_window_opens_after_slots_completions_and_the_seed_orders_the_blocks(traffic, requests):
    """ISSUE 27's parameters: a ramp of `slots` completions, and no order but
    the generator's, which shuffles every block by `--seed`."""
    assert traffic["ramp"] == {"completions": traffic["engine"]["slots"]}
    assert set(traffic["requests"]) == {"base", "per_second", "block"}
    block = traffic["requests"]["block"]
    order = lambda rs: [(len(r["ids"]), r["max_new_tokens"]) for r in rs]  # noqa: E731
    other = traffic_gen.serve_requests(traffic, VOCAB, 2**31 + 9, 30.0)
    assert order(other) != order(requests)
    blocks = {tuple(order(requests[s:s + block])) for s in range(0, len(requests), block)}
    assert len(blocks) > 4  # blocks of one run differ in order too
