"""Device: what the step executable needs on a chip by the compiler's own
account (memory_analysis(): temp + arguments + outputs - aliased). This, not
memory_stats()'s peak counter (hbm_peak_gb), which leaves the program's temp
out, is what bounds a batch."""


def read(rec):
    total = rec.get("compiler_bytes")
    return total / 1e9 if total else None
