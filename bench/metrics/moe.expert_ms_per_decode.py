"""Device milliseconds a decode step spends in its held experts' loops: the
summed device time of the loops that run inside another loop of the jitted
decode step, in the step's runs that overlap the window, over the number
of those runs. In the decode step the layer loop holds one expert loop
for each MoE layer (``models.layers.moe_held``), and nothing else nests a
loop. The program's ``moe.experts`` and ``moe.route`` scopes do not reach
the trace, whose operations carry only their HLO instruction's text, so
the loop is found by its nesting; the router's few small operations
outside it are not counted."""
import re

PROGRAM = "decode_step"
LOOP = re.compile(r"while(\.\d+)?")


def read(run):
    if run.trace is None:
        return None
    s, e = run.trace.window
    head = f"jit_{PROGRAM}("
    ns, runs = 0, 0
    for plane, rs in run.trace.runs.items():
        mine = {i for i, (a, b, name) in enumerate(rs)
                if name.startswith(head) and b > s and a < e}
        runs += len(mine)
        loops = {}
        for o in run.trace.device_ops.get(plane, ()):
            if o.run in mine and LOOP.fullmatch(o.name):
                loops.setdefault(o.run, []).append((o.start, o.end))
        for spans in loops.values():
            ns += sum(b - a for a, b in spans if any(
                A <= a and b <= B and (A, B) != (a, b) for A, B in spans))
    if not runs or ns <= 0:
        return None
    return ns / 1e6 / runs
