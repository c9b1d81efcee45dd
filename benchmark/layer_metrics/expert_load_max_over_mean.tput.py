"""Experts: how far the fullest held expert is above the mean one in the
decode ticks, the grouped matmul's straggler. `expert_rows` is the rows the
held experts computed in a quantum, `expert_rows_max` the fullest expert's of
each layer and tick, summed; x the experts held over the rows = the mean over
layers and ticks of max / mean, weighted by rows. 1 is perfect balance."""


def read(rec):
    quanta = [q for q in rec.get("quanta") or () if q.get("expert_rows")]
    cfg = rec.get("cfg")
    if not quanta or not hasattr(cfg, "experts_held"):
        return None
    return sum(q["expert_rows_max"] for q in quanta) * cfg.experts_held / sum(q["expert_rows"] for q in quanta)
