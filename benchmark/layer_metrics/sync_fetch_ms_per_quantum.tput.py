"""Serve engine: the host's part of each decode quantum's sync after it
learned the quantum's outputs were ready. The engine queues the copies of the
cursors, the flags and the model family's counters behind the decode program
(as one `device_get` queues them), takes the cursors (`sync_wait`), then the
other copies and the conversions (`sync_fetch`); a `quantum` event's
`s_ready` is the wait's end. The loop dispatches prefill before decode, so no
program is queued behind the decode program, and `s1 - s_ready` is device
idle the host causes at every sync. The lag from the program's end to
`s_ready` (the runtime telling the host) is device idle too, which no host
span separates: read it from the device trace. One exception: the hybrid
family's `counters()` dispatches its jitted gauge program (`hybrid._gauges`,
at most 1.45 ms of device time a sync on a v5e) before the wait, and its
output is copied after the cursors, so that program's time lands here too.
Mean over the window's quanta, in ms; nothing on quanta without `s_ready` (a
program whose sync is one undivided wall)."""


def read(rec):
    quanta = [q for q in rec.get("quanta") or () if "s_ready" in q]
    return sum(q["s1"] - q["s_ready"] for q in quanta) / len(quanta) * 1e3 if quanta else None
