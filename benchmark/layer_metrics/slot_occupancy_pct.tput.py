"""Serve engine: the share of slot-ticks that yielded a token. Each `quantum`
event counts the tokens that reached the host at its sync (`delivered`, summed
over lanes from the cursors the sync fetched) and the ticks it ran (`steps`);
a full engine delivers `steps x slots` tokens a quantum. What is missing is a
lane that finished mid-quantum, a slot still in prefill, or a slot left empty
while the head of the queue waited for pages."""


def read(rec):
    quanta = [q for q in rec.get("quanta") or () if "delivered" in q]
    slot_ticks = sum(q["steps"] for q in quanta) * rec["traffic"]["engine"]["slots"] if quanta else 0
    if not slot_ticks:
        return None
    return 100.0 * sum(q["delivered"] for q in quanta) / slot_ticks
