"""Host time per ``Engine.step`` call in the window, in ms: the decode
program, the tokens to the host, and the slot accounting (the call blocks
on the tokens, so this spans the device's work)."""


def read(rec):
    t0, t1 = rec["window"]
    steps = [b - a for a, b in rec["spans"].get("step", []) if t0 <= b <= t1]
    return 1000 * sum(steps) / len(steps) if steps else None
