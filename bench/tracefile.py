"""Reading a profiler trace: device operations, host spans, busy time.

The harness runs the JAX profiler over the measured window; ``load(dir)``
reads the ``.xplane.pb`` it wrote with nothing but JAX. Device operations are
the events of each TPU plane's "XLA Ops" line, each named on the chip by its
whole HLO instruction (``%decode_attention.5 = (f32[...]) custom-call(...)``)
and read here by the instruction's name, inside the run of a program (an
event of the "XLA Modules" line, ``jit_decode_step(<hash>)``) that it ran
in. A kernel's events are given to the steps by those runs, on the device's
clock alone: the host's clock, on which the ``bench.*`` annotations around
each call into a layer lie, is a millisecond or more off the device's, so a
short step's kernels can fall outside its host span. The spans name the
host's work in each idle gap, and ``bench.window`` bounds the window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the instruction's name at the head of an operation's event name
INSTRUCTION = re.compile(r"%?([^\s%=]+)")
#: operations that run others inside them, whose time those others fill
CONTROL = re.compile(r"(while|conditional|call)(\.\d+)?")

Interval = Tuple[int, int]


@dataclass
class Op:
    name: str           # the HLO instruction's name
    start: int          # ns
    end: int            # ns
    text: str           # the event's name and every string it carries
    module: str = ""    # the program it ran in
    run: int = -1       # which run of a program, in the plane's order


@dataclass
class Trace:
    #: device plane name -> its operations in start order
    device_ops: Dict[str, List[Op]] = field(default_factory=dict)
    #: device plane name -> (start, end, program) of each program run
    runs: Dict[str, List[Tuple[int, int, str]]] = field(default_factory=dict)
    #: (name, start, end) of each ``bench.*`` host span
    host_spans: List[Tuple[str, int, int]] = field(default_factory=list)
    #: plane name -> line name -> events: what the profile held, read or not
    lines: Dict[str, Dict[str, int]] = field(default_factory=dict)

    @property
    def window(self) -> Interval:
        spans = [(s, e) for n, s, e in self.host_spans if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
        return spans[0]

    def ops(self, start: int, end: int) -> List[Op]:
        """Device operations of every chip that overlap [start, end)."""
        return [o for ops in self.device_ops.values() for o in ops
                if o.end > start and o.start < end]

    def busy_seconds(self) -> float:
        """Seconds of the window in which some operation ran, averaged over
        the chips that ran any."""
        s, e = self.window
        per_chip = [union_ns(clip([(o.start, o.end) for o in ops], s, e))
                    for ops in self.device_ops.values() if ops]
        return sum(per_chip) / len(per_chip) / 1e9 if per_chip else 0.0

    def window_seconds(self) -> float:
        s, e = self.window
        return (e - s) / 1e9

    def kernel_per_run(self, kernel: str,
                       program: str) -> List[Tuple[int, float]]:
        """(events, summed device seconds) of ``kernel`` in each run of the
        jitted function ``program`` (the module ``jit_<program>(<hash>)``)
        that overlaps the window, in the runs' order, over every chip."""
        s, e = self.window
        head = f"jit_{program}("
        per: Dict[Tuple[str, int], List] = {}
        for plane, runs in sorted(self.runs.items()):
            for i, (a, b, name) in enumerate(runs):
                if name.startswith(head) and b > s and a < e:
                    per[(plane, i)] = [0, 0]
        match = kernel_match(kernel)
        for plane, ops in self.device_ops.items():
            for o in ops:
                hit = per.get((plane, o.run))
                if hit is not None and match(o):
                    hit[0] += 1
                    hit[1] += o.end - o.start
        return [(n, t / 1e9) for n, t in per.values()]


def kernel_match(kernel: str):
    """Whether an operation is a Pallas call made inside the jitted function
    ``kernel``: XLA names such a custom call ``<kernel>.<n>`` and gives it
    the op name ``.../jit(<kernel>)/pallas_call``."""
    name = re.compile(rf"{re.escape(kernel)}(\.\d+)?")
    tag = f"jit({kernel})/pallas_call"
    return lambda o: bool(name.fullmatch(o.name)) or tag in o.text


def traced_steps(trace: Trace, steps: List[dict], kernel: str, program: str,
                 calls: int) -> List[Tuple[dict, float]]:
    """(step, device seconds of ``kernel``) for each of ``steps`` whose run
    of ``program`` holds a positive multiple of ``calls`` of the kernel's
    events, that is each step whose calls the trace recorded whole. Empty
    where the runs and the steps do not pair one to one."""
    per = trace.kernel_per_run(kernel, program)
    if len(per) != len(steps):
        return []
    return [(st, t) for st, (ev, t) in zip(steps, per)
            if ev and ev % calls == 0]


def clip(intervals: List[Interval], start: int, end: int) -> List[Interval]:
    return [(max(a, start), min(b, end)) for a, b in intervals
            if b > start and a < end]


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_ns(intervals: List[Interval]) -> int:
    return sum(b - a for a, b in merge(intervals))


def _texts(event) -> str:
    parts = [event.name]
    for _, v in event.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


def from_profile(prof) -> Trace:
    tr = Trace()
    for plane in prof.planes:
        tr.lines[plane.name] = {line.name: sum(1 for _ in line.events)
                                for line in plane.lines}
        if plane.name.startswith("/device:TPU:"):
            mods = sorted((int(e.start_ns), int(e.end_ns), e.name)
                          for line in plane.lines
                          if line.name == MODULES_LINE for e in line.events)
            starts = [m[0] for m in mods]
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    a, b = int(e.start_ns), int(e.end_ns)
                    i = bisect.bisect_right(starts, a) - 1
                    i = i if i >= 0 and mods[i][1] >= b else -1
                    ops.append(Op(INSTRUCTION.match(e.name).group(1), a, b,
                                  _texts(e), mods[i][2] if i >= 0 else "",
                                  i))
            tr.device_ops[plane.name] = sorted(ops, key=lambda o: o.start)
            tr.runs[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.host_spans.append((e.name, int(e.start_ns),
                                              int(e.end_ns)))
    return tr


def summary(tr: Trace, n: int = 12) -> str:
    """What the trace held, for a reader that found nothing in it: its
    planes and lines, the window, the host spans, and the operations that
    took the most time, with the strings each carries."""
    out = [f"{p}: {ls}" for p, ls in tr.lines.items()]
    spans: Dict[str, int] = {}
    for name, _, _ in tr.host_spans:
        spans[name] = spans.get(name, 0) + 1
    out.append(f"host spans: {spans}")
    try:
        s, e = tr.window
    except ValueError as err:
        return "\n".join(out + [str(err)])
    ops = tr.ops(s, e)
    out.append(f"window [{s}, {e}) ns holds {len(ops)} device operations")
    out.extend(f"  {name} {secs!r} s" for name, secs in top_ops(tr, n))
    texts = {o.name: o.text for o in ops}
    calls = [k for k, v in texts.items() if "pallas" in v or "custom" in v]
    out.append(f"operations that name a Pallas or custom call: {calls[:n]}")
    out.extend(f"  {k}: {texts[k][:300]}" for k in calls[:3])
    return "\n".join(out)


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` operations that took the most device seconds in the
    window, each named ``<program>/<instruction>`` and summed over its
    events, averaged over chips; loops and calls, whose time the
    operations inside them fill, are left out."""
    s, e = tr.window
    tot: Dict[str, float] = {}
    chips = max(len([1 for v in tr.device_ops.values() if v]), 1)
    for o in tr.ops(s, e):
        if CONTROL.fullmatch(o.name):
            continue
        k = f"{o.module}/{o.name}" if o.module else o.name
        tot[k] = tot.get(k, 0.0) + (min(o.end, e) - max(o.start, s)) / 1e9
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / chips] for k, v in ranked]


def idle_gaps(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` longest stretches of the window in which no operation ran
    on the first chip, each named by the innermost host span around its
    middle (``host idle`` where the benchmark was in no span)."""
    s, e = tr.window
    plane = next((k for k, v in sorted(tr.device_ops.items()) if v), None)
    if plane is None:
        return []
    busy = merge(clip([(o.start, o.end) for o in tr.device_ops[plane]],
                      s, e))
    gaps, cur = [], s
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < e:
        gaps.append((cur, e))
    spans = [(n_, a, b) for n_, a, b in tr.host_spans if n_ != WINDOW_SPAN]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) // 2
        around = [(b_ - a_, n_) for n_, a_, b_ in spans if a_ <= mid < b_]
        out.append([min(around)[1] if around else "host idle", (b - a) / 1e9])
    return out
