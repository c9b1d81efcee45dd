"""Serve engine: mean host time to dispatch one decode quantum (t1 - t0 of
the TraceRecorder's `quantum` events inside the window)."""


def read(rec):
    quanta = rec.get("quanta")
    return sum(q["t1"] - q["t0"] for q in quanta) / len(quanta) * 1e3 if quanta else None
