"""The latent flash-decode kernel's share of its roofline: the least time
of every call (``flops_mla_moe.mla_decode_cost`` at the step's live
positions, one call per layer) over the summed device time of the Pallas
calls made inside ``kernels.ops.mla_decode_attention``, over the decode
steps whose calls the trace recorded whole."""
from bench import flops, flops_mla_moe, tracefile

KERNEL = "mla_decode_attention"
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
    least = sum(flops.least_seconds(*flops_mla_moe.mla_decode_cost(
        s["B"], s["live"], n["H"], n["r"], n["dr"]), run.peak)
        for s, _ in whole) * n["L"]
    return 100.0 * least / secs
