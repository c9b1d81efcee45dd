"""Cache manager: bytes of page pool in use for every token of context behind
the decoding slots: sum of the `quantum` events' `kv_bytes` (live pages of
every page kind x that kind's page bytes, every live lane: decoding ones,
prefilling ones and each request's pages for the answer it has not written
yet) over the sum of their `ctx_tokens` (prompt + delivered of the decoding
lanes). The stored rows alone cost 2 x 1,408 + 3 x 2,176 x min(L, 544) / L
bytes a token at context L in the dots3-note-prev cell; what is above that is
pages held ahead of use and lanes still in prefill."""


def read(rec):
    quanta = [q for q in rec.get("quanta") or () if q.get("ctx_tokens") and q.get("kv_bytes") is not None]
    if not quanta:
        return None
    return sum(q["kv_bytes"] for q in quanta) / sum(q["ctx_tokens"] for q in quanta)
