"""Device-busy time inside the benchmark's ``Engine.step`` spans, per step,
in ms, from the profiler trace. It includes what the previous admission's
splices left running on the device."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["span_count"].get("step"):
        return None
    return 1000 * tr["busy_in_s"]["step"] / tr["span_count"]["step"]
