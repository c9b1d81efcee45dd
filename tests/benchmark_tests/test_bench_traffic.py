"""The traffic generator: determinism per seed, clips, and that every seed
runs the same set of sizes and gaps in another order."""

from pathlib import Path

import numpy as np
import pytest

from benchmark import common, traffic_gen

TRAFFIC = Path(__file__).resolve().parents[2] / "benchmark" / "traffic"
SERVE = ["open-loop", "chat-saturate"]
# no cell is an open loop yet (PERF.md section 7 keeps five for later); the
# generator's open loop is held on the saturating mix's lengths at a fixed rate
OPEN = {"arrivals": {"kind": "poisson", "rate_per_s": 1.5}, "ramp": {"seconds": 8}, "drain_limit_s": 30}
TRAIN = ["train-1k", "train-1k-fsdp4"]
BIG_SEED = 2**31 + 12345  # the driver's seeds pass 32 signed bits


def load(name):
    if name == "open-loop":
        return dict(common.load_json(TRAFFIC / "chat-saturate.json"), **OPEN)
    return common.load_json(TRAFFIC / f"{name}.json")


@pytest.mark.parametrize("name", SERVE)
def test_serve_requests_repeat_per_seed(name):
    a = traffic_gen.serve_requests(load(name), 50257, BIG_SEED, 5.0)
    b = traffic_gen.serve_requests(load(name), 50257, BIG_SEED, 5.0)
    c = traffic_gen.serve_requests(load(name), 50257, BIG_SEED + 1, 5.0)
    assert a == b
    assert [r["ids"] for r in a] != [r["ids"] for r in c]


@pytest.mark.parametrize("name", SERVE)
def test_serve_lengths_are_clipped_and_fit_the_engine(name):
    t = load(name)
    reqs = traffic_gen.serve_requests(t, 50257, 7, 20.0)
    plen = np.array([len(r["ids"]) for r in reqs])
    olen = np.array([r["max_new_tokens"] for r in reqs])
    assert plen.min() >= t["prompt_len"]["min"] and plen.max() <= t["prompt_len"]["max"]
    assert olen.min() >= t["output_len"]["min"] and olen.max() <= t["output_len"]["max"]
    assert (plen + olen).max() <= t["engine"]["max_len"]
    assert plen.max() <= max(t["engine"]["buckets"])
    assert all(b % t["engine"]["prefill_chunk"] == 0 for b in t["engine"]["buckets"])
    assert t["eos_id"] not in {i for r in reqs for i in r["ids"]}
    assert max(max(r["ids"]) for r in reqs) < 50257


@pytest.mark.parametrize("name", SERVE)
def test_every_seed_runs_the_same_sizes_in_another_order(name):
    a = traffic_gen.serve_requests(load(name), 50257, 1, 10.0)
    b = traffic_gen.serve_requests(load(name), 50257, BIG_SEED, 10.0)
    size = lambda rs: sorted((len(r["ids"]), r["max_new_tokens"]) for r in rs)  # noqa: E731
    assert size(a) == size(b)
    assert [len(r["ids"]) for r in a] != [len(r["ids"]) for r in b]
    gaps = lambda rs: np.sort(np.diff([0.0] + [r["arrival_s"] for r in rs]))  # noqa: E731
    np.testing.assert_allclose(gaps(a), gaps(b), atol=1e-9)


def test_open_loop_arrivals_fill_ramp_and_window_exactly():
    t = load("open-loop")
    reqs = traffic_gen.serve_requests(t, 50257, 3, 12.0)
    ramp_s, rate = t["ramp"]["seconds"], t["arrivals"]["rate_per_s"]
    arrivals = [r["arrival_s"] for r in reqs]
    assert arrivals == sorted(arrivals)
    assert arrivals[-1] == pytest.approx(ramp_s + 12.0)
    ramp = [r for r in reqs if r["segment"] == "ramp"]
    window = [r for r in reqs if r["segment"] == "window"]
    assert len(ramp) == round(rate * ramp_s) and len(window) == round(rate * 12.0)
    assert ramp[-1]["arrival_s"] == pytest.approx(ramp_s) and window[0]["arrival_s"] > ramp_s


def test_every_seed_measures_the_same_requests_in_the_window():
    """The measured segment is its own fixed set: a tail over it is a tail
    over the same prompts whatever the seed."""
    t = load("open-loop")
    size = lambda rs: sorted((len(r["ids"]), r["max_new_tokens"]) for r in rs if r["segment"] == "window")  # noqa: E731
    a = traffic_gen.serve_requests(t, 50257, 1, 30.0)
    b = traffic_gen.serve_requests(t, 50257, BIG_SEED, 30.0)
    assert size(a) == size(b) and len(size(a)) == round(t["arrivals"]["rate_per_s"] * 30.0)


def test_every_block_of_the_saturating_queue_is_the_same_mix():
    t = load("chat-saturate")
    reqs = traffic_gen.serve_requests(t, 50257, 5, 30.0)
    block = t["requests"]["block"]
    size = lambda rs: sorted((len(r["ids"]), r["max_new_tokens"]) for r in rs)  # noqa: E731
    blocks = [reqs[i:i + block] for i in range(0, len(reqs) - block + 1, block)]
    assert len(blocks) >= 3 and all(size(b) == size(blocks[0]) for b in blocks)
    assert [len(r["ids"]) for r in blocks[0]] != [len(r["ids"]) for r in blocks[1]]


def test_saturating_traffic_is_all_due_at_once():
    t = load("chat-saturate")
    reqs = traffic_gen.serve_requests(t, 50257, 3, 10.0)
    assert {r["arrival_s"] for r in reqs} == {0.0}
    assert len(reqs) == t["requests"]["base"] + t["requests"]["per_second"] * 10


@pytest.mark.parametrize("name", TRAIN)
def test_train_rows_repeat_per_seed_and_avoid_the_pad_id(name):
    t = load(name)
    ids, mask = traffic_gen.train_rows(t, 50257, BIG_SEED)
    ids2, _ = traffic_gen.train_rows(t, 50257, BIG_SEED)
    ids3, _ = traffic_gen.train_rows(t, 50257, 4)
    assert ids.shape == (t["dataset_rows"], t["row_tokens"]) and ids.dtype == np.int32
    np.testing.assert_array_equal(ids, ids2)
    assert (ids != ids3).any()
    assert mask.all()  # fixed full-length rows: no padding in these mixes
    assert (ids != t["pad_id"]).all() and ids.min() >= 0 and ids.max() < 50257


def test_train_ids_are_zipf_not_uniform():
    t = load("train-1k")
    ids, _ = traffic_gen.train_rows(t, 50257, 11)
    counts = np.sort(np.bincount(ids.ravel(), minlength=50257))[::-1]
    # Zipf(1) over 50k ids: the most frequent id takes about 1/H(50256) = 8.8% of the draws
    assert 0.07 < counts[0] / ids.size < 0.11
    assert counts[0] > 50 * np.median(counts[counts > 0])


def test_ragged_rows_are_padded_and_masked():
    t = dict(load("train-1k"), lengths={"distribution": "lognormal", "median": 300, "sigma": 0.6, "min": 8, "max": 1024})
    ids, mask = traffic_gen.train_rows(t, 50257, 2)
    lens = mask.sum(axis=1)
    assert lens.min() >= 8 and lens.max() <= 1024 and len(set(lens.tolist())) > 10
    assert (ids[mask == 0] == t["pad_id"]).all() and (ids[mask == 1] != t["pad_id"]).all()


@pytest.mark.parametrize("spec", [{"distribution": "weibull"}, {"distribution": "fixed"}])
def test_unknown_or_incomplete_specs_are_errors(spec):
    with pytest.raises((ValueError, KeyError)):
        traffic_gen._lengths(spec, 4, np.random.default_rng(0))
