"""Input pipeline: mean time the loop waited for the next placed batch from
HostPrefetcher, per step (the benchmark's own span around `next()`)."""


def read(rec):
    waits = rec.get("input_wait_s")
    return sum(waits) / len(waits) * 1e3 if waits else None
