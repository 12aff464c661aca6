"""The flash prefill kernel's share of its roofline: the least time of
every call (``flops.flash_prefill_cost``, one call per layer of a prefill
step) over the summed device time of the Pallas calls made inside
``kernels.ops.flash_attention``, over the prefill steps whose calls the
trace recorded whole."""
from bench import flops, tracefile

KERNEL = "flash_attention"
#: the jitted step whose runs on the device hold the kernel's calls
PROGRAM = "prefill_step"


def read(run):
    if run.trace is None or run.peak is None:
        return None
    n = run.info["dims"]
    steps = [s for s in run.steps if s["kind"] == "prefill"]
    whole = tracefile.traced_steps(run.trace, steps, KERNEL, PROGRAM, n["L"])
    secs = sum(t for _, t in whole)
    if secs <= 0:
        return None
    least = sum(flops.least_seconds(*flops.flash_prefill_cost(
        s["B"], s["S"], n["H"], n["KV"], n["hd"]), run.peak)
        for s, _ in whole) * n["L"]
    return 100.0 * least / secs
