"""Serve engine: mean serial host time per decode quantum. A `quantum` event's
`host` holds the walls of the engine's own spans (admit, place, prefill,
decode, retire, window, idle, other) between the previous quantum's sync
returning and this one's sync starting: time no device work hides except an
in-flight prefill chunk. `idle` is the open loop's sleep and is left out. The
window's first quantum is left out too: the gap before it holds the window's
opening (the profiler's start), and the quantum before it is not in the list.
The per-phase means go to the `per_layer_notes` line."""


def read(rec):
    quanta = [q for q in (rec.get("quanta") or ())[1:] if "host" in q]
    if not quanta:
        return None
    phases = sorted({name for q in quanta for name in q["host"]} - {"idle"})
    mean_ms = {name: sum(q["host"].get(name, 0.0) for q in quanta) / len(quanta) * 1e3 for name in phases}
    rec.setdefault("notes", {})["host_ms_per_quantum"] = dict(mean_ms, quanta=len(quanta))
    return sum(mean_ms.values())
