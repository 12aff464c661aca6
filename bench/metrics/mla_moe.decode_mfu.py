"""Decode steps' share of the chip's peak, for an MLA + MoE decoder: the
operations every decode step in the window needs
(``flops_mla_moe.decode_step_flops``, with the routed copies that the
program's counter found the held experts computed over the window), over
the steps' summed host-clock time and the peak bf16 rate."""
from bench import flops_mla_moe


def read(run):
    steps = [s for s in run.steps if s["kind"] == "decode"]
    secs = sum(s["t1"] - s["t0"] for s in steps)
    copies = run.info.get("copies")
    if not steps or run.peak is None or secs <= 0 or copies is None:
        return None
    n = run.info["dims"]
    work = sum(flops_mla_moe.decode_step_flops(n, s["B"], s["live"], 0)
               for s in steps)
    work += copies * flops_mla_moe.expert_flops(n)
    return 100.0 * work / secs / run.peak["bf16_flops_per_s"]
