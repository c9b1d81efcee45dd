"""Serve engine: the host's own serial work a decode quantum. A traced engine
reads the thread's CPU clock in each of its spans, and a `quantum` event's
`host_cpu` holds, phase by phase, the CPU seconds inside the `host` walls
between the previous sync's return and this one's start. What a wall holds
beyond its CPU is the host waiting (on the runtime, a lock, the scheduler),
not working. The value is the mean over the window's quanta of the sum of
`host_cpu` without `idle` (the open loop's sleep); the window's first quantum
is left out, as `host_ms_per_quantum.tput` leaves it. Per phase, `cpu` and
`wait` (wall minus CPU) go to the `per_layer_notes` line. Where the thread's
CPU clock advances in ticks (10 ms on the sandboxed host of a v5e machine),
a quantum's reading is a sample, a phase's `wait` can read below zero, and
only the window's mean says something, to about one tick over the number of
quanta. Nothing on quanta
without `host_cpu` (a program that reads no CPU clock)."""


def read(rec):
    quanta = [q for q in (rec.get("quanta") or ())[1:] if "host_cpu" in q]
    if not quanta:
        return None
    mean_ms = lambda part, name: sum(q[part].get(name, 0.0) for q in quanta) / len(quanta) * 1e3  # noqa: E731
    phases = sorted({name for q in quanta for name in q["host_cpu"]} - {"idle"})
    cpu = {name: mean_ms("host_cpu", name) for name in phases}
    rec.setdefault("notes", {})["host_cpu_ms_per_quantum"] = dict(
        {name: {"cpu": cpu[name], "wait": mean_ms("host", name) - cpu[name]} for name in phases}, quanta=len(quanta))
    return sum(cpu.values())
