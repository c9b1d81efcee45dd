"""Document sessions: a few long documents, each asked many short questions.
A traffic mix's file (benchmark/traffic/<name>.json) gives the parameters;
`traffic_gen.py` draws prompts with no shared prefixes, so this file draws
the shared ones, with the same seed discipline.

Seeds. The documents' lengths are the file's own (`documents.tokens`);
question and answer lengths are drawn from its `size_seed`, one block of
`requests.block` pairs, each pair tied to a document in turn, so every
`--seed` runs the SAME sizes. `--seed` draws the documents' token ids, the
questions' token ids and the order of each block.
"""

from __future__ import annotations

import numpy as np

from benchmark import traffic_gen


def documents(traffic: dict, vocab: int, seed: int) -> list[tuple]:
    """The documents' token ids, uniform over `vocab` (the EOS id left out),
    one tuple a document, from `seed`."""
    rng = np.random.default_rng([seed, 1])
    return [tuple(traffic_gen._token_ids(traffic["ids"], vocab, (n,), rng, exclude=traffic["eos_id"]).tolist())
            for n in traffic["documents"]["tokens"]]


def block_sizes(traffic: dict) -> list[tuple[int, int, int]]:
    """One block's `(document, question tokens, answer tokens)`, from the
    file's `size_seed`: the block's i-th question is on document i modulo
    the number of documents."""
    n, block = len(traffic["documents"]["tokens"]), int(traffic["requests"]["block"])
    sizes = np.random.default_rng(traffic["size_seed"])
    q = traffic_gen._lengths(traffic["question_len"], block, sizes)
    a = traffic_gen._lengths(traffic["output_len"], block, sizes)
    return [(i % n, int(q[i]), int(a[i])) for i in range(block)]


def serve_requests(traffic: dict, vocab: int, seed: int, seconds: float, docs: list[tuple]) -> list[dict]:
    """Requests as dicts {rid, doc, ids, max_new_tokens, arrival_s}: a
    document followed by a question of its own ids, all due at 0 (a queue
    that is never empty), `requests.base + requests.per_second * seconds`
    of them in blocks whose order `seed` draws."""
    spec = traffic["requests"]
    n = int(spec["base"] + spec["per_second"] * seconds)
    sizes = block_sizes(traffic)
    rng = np.random.default_rng([seed, 2])
    out = []
    while len(out) < n:
        for j in rng.permutation(len(sizes))[: n - len(out)]:
            doc, q, a = sizes[j]
            question = traffic_gen._token_ids(traffic["ids"], vocab, (q,), rng, exclude=traffic["eos_id"])
            out.append({"rid": len(out), "doc": doc, "ids": docs[doc] + tuple(question.tolist()),
                        "max_new_tokens": a, "arrival_s": 0.0})
    return out


def setup_requests(traffic: dict, vocab: int, seed: int, docs: list[tuple]) -> list[dict]:
    """One request a document, prefilled cold before the window so that the
    registry holds it: the document and `setup.question_tokens` ids of its
    own (so that every page of the document is registered: the page that
    holds a prompt's last token stays private), `setup.new_tokens` to
    generate. Their rids follow the window's."""
    rng = np.random.default_rng([seed, 3])
    q, base = traffic["setup"]["question_tokens"], 1 << 30
    return [{"rid": base + i, "doc": i, "max_new_tokens": traffic["setup"]["new_tokens"], "arrival_s": 0.0,
             "ids": doc + tuple(traffic_gen._token_ids(traffic["ids"], vocab, (q,), rng,
                                                        exclude=traffic["eos_id"]).tolist())}
            for i, doc in enumerate(docs)]
