"""A kernel's share of its roofline from the reduced trace: the least time
the chip could take for the calls' FLOPs and bytes (a kernel file's `work`,
from the shapes) over the summed device time of the kernel's events."""

from benchmark import peaks


def share(rec, kernel_file) -> tuple[float, dict] | None:
    red = rec.get("reduced")
    if red is None or not red.devices or rec.get("peaks") is None:
        return None
    least = spent = 0.0
    detail = {}
    for name, (flops, nbytes) in kernel_file.work(rec).items():
        calls = red.op_count(lambda n, name=name: n == name)
        if not calls:
            continue
        t, bound = peaks.roofline_seconds(flops, nbytes, rec["device_kind"])
        seconds = red.op_seconds(lambda n, name=name: n == name)
        least += t * calls
        spent += seconds
        detail[name] = {"calls": calls, "seconds": seconds, "least_seconds": t * calls, "bound": bound}
    if spent == 0.0:
        return None
    rec.setdefault("notes", {}).update(detail)  # printed on an earlier line: which bound applies
    return 100.0 * least / spent, detail
