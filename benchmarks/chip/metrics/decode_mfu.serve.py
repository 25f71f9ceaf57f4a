"""Model operations of the window's decode steps (each at its rows and
their cache lengths, ``flops.decode_step``) over the device-busy time
inside the step spans times the chip's peak bf16 rate, in %."""
import flops
import peaks


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["busy_in_s"].get("step"):
        return None
    t0, t1 = rec["window"]
    ops = sum(flops.decode_step(rec["config"], rows, attended)[0]
              for t, rows, attended, _ in rec["steps"] if t0 <= t <= t1)
    peak = peaks.peaks(rec["device"]["kind"])["bf16_flops"]
    return 100 * ops / (tr["busy_in_s"]["step"] * peak)
