"""Readers that several per-layer metrics share: one quantity, split by the
end-to-end metric it moves (a metric's file is three lines that name one)."""


def device_idle_pct(rec):
    """1 - union of device-op intervals over the traced window, mean over the
    devices."""
    red = rec.get("reduced")
    if red is None or not red.devices:
        return None
    return 100.0 * (1.0 - red.busy_s / rec["window_s"])


def hbm_peak_gb(rec):
    """memory_stats()["peak_bytes_in_use"] on the fullest chip, after the
    window: the contract's memory_peak_bytes. It leaves a program's temp out
    (PERF.md section 3); hbm_program_gb reports the compiler's own total."""
    peak = rec.get("memory_peak_bytes")
    return peak / 1e9 if peak else None


def decode_tick_device_ms(rec):
    """Device time of the jitted decode program's events over the ticks they
    ran (each event is one quantum of `decode_quantum` ticks)."""
    red = rec.get("reduced")
    if red is None or not red.devices:
        return None
    events = red.module_events(lambda name: "decode_step" in name)
    if not events:
        return None
    total_ns = sum(e - s for _, s, e in events)
    return total_ns / 1e6 / (len(events) * rec["decode_quantum"])
