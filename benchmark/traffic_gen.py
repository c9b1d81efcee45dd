"""The one general traffic generator. A traffic mix is a data file under
benchmark/traffic/; everything here is driven by its parameters.

Seeds. Sizes (row lengths, prompt and output lengths, arrival gaps) are drawn
from the traffic file's own `size_seed`, so every `--seed` runs the SAME set
of sizes and gaps; `--seed` orders them and draws the token ids. Runs with
different seeds then do the same work in another order.
"""

from __future__ import annotations

import numpy as np


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    """`n` lengths from a spec: {"distribution": "fixed", "value": v} or
    {"distribution": "lognormal", "median": m, "sigma": s, "min": a, "max": b}."""
    kind = spec["distribution"]
    if kind == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if kind == "lognormal":
        raw = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
        return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {kind!r}")


def _token_ids(spec: dict, vocab: int, shape, rng, exclude: int | None = None) -> np.ndarray:
    """Token ids from {"distribution": "uniform"} or {"distribution": "zipf",
    "exponent": s}: Zipf over the whole vocabulary through a seeded
    rank-to-id permutation. `exclude` (the pad / EOS id) is never drawn."""
    ids = np.arange(vocab)
    if exclude is not None:
        ids = ids[ids != exclude]
    kind = spec["distribution"]
    if kind == "uniform":
        return ids[rng.integers(0, len(ids), size=shape)].astype(np.int32)
    if kind == "zipf":
        p = 1.0 / np.arange(1, len(ids) + 1) ** float(spec["exponent"])
        ranks = rng.choice(len(ids), size=shape, p=p / p.sum())
        return rng.permutation(ids)[ranks].astype(np.int32)
    raise ValueError(f"unknown id distribution {kind!r}")


def train_rows(traffic: dict, vocab: int, seed: int):
    """`(input_ids, attention_mask)` `[dataset_rows, row_tokens]` int32: rows
    of `lengths` real tokens, padded with `pad_id` to `row_tokens`."""
    n, width, pad = traffic["dataset_rows"], traffic["row_tokens"], traffic["pad_id"]
    lens = _lengths(traffic["lengths"], n, np.random.default_rng(traffic["size_seed"]))
    rng = np.random.default_rng(seed)
    lens = np.minimum(rng.permutation(lens), width)
    ids = _token_ids(traffic["ids"], vocab, (n, width), rng, exclude=pad)
    mask = (np.arange(width)[None, :] < lens[:, None]).astype(np.int32)
    return np.where(mask == 1, ids, pad).astype(np.int32), mask


def serve_requests(traffic: dict, vocab: int, seed: int, seconds: float) -> list[dict]:
    """Requests as dicts {rid, ids, max_new_tokens, arrival_s, segment},
    sorted by arrival. `arrivals.kind`:

    - "all_at_once": `requests.base + requests.per_second * seconds` requests,
      all due at 0 (a queue that is never empty), made of blocks of
      `requests.block`: every block holds the same sizes, shuffled by the
      seed, so whichever stretch of the queue a window consumes is the same
      mix;
    - "poisson": two segments, "ramp" (`ramp.seconds`, set-up) and "window"
      (`seconds`, measured), each with round(rate x duration) requests whose
      sizes and exponential gaps are the segment's own fixed set, the gaps
      rescaled to fill the segment exactly. The seed shuffles within a
      segment, so every seed measures the same requests in another order.
    """
    arr = traffic["arrivals"]
    sizes = np.random.default_rng(traffic["size_seed"])
    rng = np.random.default_rng(seed)
    plen, olen, gaps, segment = [], [], [], []

    def add(p, o, g, name):
        order = rng.permutation(len(p))
        plen.append(p[order]); olen.append(o[order]); gaps.append(rng.permutation(g))
        segment.extend([name] * len(p))

    if arr["kind"] == "all_at_once":
        spec = traffic["requests"]
        n, block = int(spec["base"] + spec["per_second"] * seconds), int(spec["block"])
        p, o = _lengths(traffic["prompt_len"], block, sizes), _lengths(traffic["output_len"], block, sizes)
        for start in range(0, n, block):
            k = min(block, n - start)
            add(p[:k], o[:k], np.zeros(k), "window")
    elif arr["kind"] == "poisson":
        for name, duration in (("ramp", traffic["ramp"]["seconds"]), ("window", seconds)):
            k = max(int(round(arr["rate_per_s"] * duration)), 1)
            g = sizes.exponential(1.0, size=k)
            add(_lengths(traffic["prompt_len"], k, sizes), _lengths(traffic["output_len"], k, sizes),
                g * (duration / g.sum()), name)
    else:
        raise ValueError(f"unknown arrivals kind {arr['kind']!r}")
    plen, olen = np.concatenate(plen), np.concatenate(olen)
    arrivals = np.cumsum(np.concatenate(gaps))
    flat = _token_ids(traffic["ids"], vocab, (int(plen.sum()),), rng, exclude=traffic["eos_id"])
    cuts = np.concatenate([[0], np.cumsum(plen)])
    return [
        {"rid": i, "ids": tuple(flat[cuts[i]:cuts[i + 1]].tolist()), "max_new_tokens": int(olen[i]),
         "arrival_s": float(arrivals[i]), "segment": segment[i]}
        for i in range(len(plen))
    ]
