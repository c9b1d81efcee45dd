"""Cache manager: the share of the prompt tokens admitted over the window that
the prefix registry served from shared pages, never prefilled: the window's
`quantum` events' `prefix_tokens` over their `admitted_tokens` (each counts
since the previous quantum's sync). Nothing where the quanta carry neither (a
program from before the engine counted them) or nothing was admitted."""


def read(rec):
    quanta = [q for q in rec.get("quanta") or () if "prefix_tokens" in q and "admitted_tokens" in q]
    admitted = sum(q["admitted_tokens"] for q in quanta)
    if not admitted:
        return None
    return 100.0 * sum(q["prefix_tokens"] for q in quanta) / admitted
