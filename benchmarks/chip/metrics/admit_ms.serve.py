"""Host time in ``Engine.admit_many`` per request admitted, in ms, over
the window: prefill, the first token to the host, and the dispatch of the
per-page cache splices (which finish on the device during the next call)."""


def read(rec):
    t0, t1 = rec["window"]
    spent = sum(b - a for a, b in rec["spans"].get("admit", [])
                if t0 <= b <= t1)
    admitted = sum(1 for r in rec["requests"]
                   if r["times"] and t0 <= r["times"][0] <= t1)
    return 1000 * spent / admitted if admitted else None
