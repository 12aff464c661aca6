"""The flash-decode kernel's share of its roofline: the least time of
every call (``flops.flash_decode_cost`` at the step's live positions, one
call per layer) over the summed device time of the Pallas calls made
inside ``kernels.ops.decode_attention`` (the split kernel, and the combine
kernel where the cell's blocks select it), over the decode steps whose
calls the trace recorded whole."""
from bench import flops, tracefile

KERNEL = "decode_attention"
#: the jitted step whose runs on the device hold the kernel's calls
PROGRAM = "decode_step"


def read(run):
    if run.trace is None or run.peak is None:
        return None
    n = run.info["dims"]
    steps = [s for s in run.steps if s["kind"] == "decode"]
    whole = tracefile.traced_steps(run.trace, steps, KERNEL, PROGRAM, n["L"])
    secs = sum(t for _, t in whole)
    if secs <= 0:
        return None
    least = sum(flops.least_seconds(*flops.flash_decode_cost(
        s["B"], s["live"], n["H"], n["KV"], n["hd"]), run.peak)
        for s, _ in whole) * n["L"]
    return 100.0 * least / secs
