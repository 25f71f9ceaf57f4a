"""Share of the traced window in which no operation ran on the chip, %."""


def read(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    return 100 * (1 - tr["busy_s"] / tr["window_s"])
