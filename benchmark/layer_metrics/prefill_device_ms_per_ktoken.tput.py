"""Prefill: device time of the chunked-prefill programs a thousand prompt
tokens. The trace's module events of `prefill_chunk_paged` (one event a
dispatch, whatever the admit size) summed, over the `tokens` of the `prefill`
events the engine emitted inside the window (real prompt tokens a lane's chunk
forwarded; a chunk's padding is not a token). A dispatch that straddles the
window's edge is counted on one side only; over hundreds of dispatches that
is noise."""


def read(rec):
    red, prefills = rec.get("reduced"), rec.get("prefills")
    if red is None or not red.devices or not prefills:
        return None
    tokens = sum(p.get("tokens", 0) for p in prefills)
    events = red.module_events(lambda name: "prefill_chunk_paged" in name)
    if not tokens or not events:
        return None
    return sum(e - s for _, s, e in events) / 1e6 / (tokens / 1e3)
