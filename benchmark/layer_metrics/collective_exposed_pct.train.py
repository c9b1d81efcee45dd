"""Strategies: share of the traced window in which a collective ran on a
device and no compute ran there, mean over the devices (device trace). The
share in which a collective ran at all, hidden or not, goes on the
`per_layer_notes` line."""


def read(rec):
    red = rec.get("reduced")
    if red is None or not red.devices or rec["chips"] < 2:
        return None
    rec.setdefault("notes", {})["collective_running_pct"] = 100.0 * red.collective_s() / rec["window_s"]
    return 100.0 * red.exposed_collective_s() / rec["window_s"]
