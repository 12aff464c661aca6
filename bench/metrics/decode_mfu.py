"""Decode steps' share of the chip's peak: the operations every decode
step in the window needs (``flops.decode_step_flops``), over the steps'
summed host-clock time and the peak bf16 rate."""
from bench import flops


def read(run):
    steps = [s for s in run.steps if s["kind"] == "decode"]
    secs = sum(s["t1"] - s["t0"] for s in steps)
    if not steps or run.peak is None or secs <= 0:
        return None
    n = run.info["dims"]
    work = sum(flops.decode_step_flops(n, s["B"], s["live"]) for s in steps)
    return 100.0 * work / secs / run.peak["bf16_flops_per_s"]
