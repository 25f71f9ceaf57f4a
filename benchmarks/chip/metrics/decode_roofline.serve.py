"""Decode step's share of its roofline, in %: for each step of the window
the least time the chip could take (the larger of its operations over the
peak rate and its bytes, weights once plus each row's keys and values at
its own length, over the peak bandwidth; ``flops.decode_step``), summed,
over the device-busy time inside the step spans."""
import flops
import peaks


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["busy_in_s"].get("step"):
        return None
    t0, t1 = rec["window"]
    pk = peaks.peaks(rec["device"]["kind"])
    least = 0.0
    for t, rows, attended, _ in rec["steps"]:
        if t0 <= t <= t1:
            ops, nbytes = flops.decode_step(rec["config"], rows, attended)
            least += max(ops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100 * least / tr["busy_in_s"]["step"]
