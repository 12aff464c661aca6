r"""The program's own spans on the device's clock.

``repro.launch.serve.DecodeServer`` opens a span around each step,
``serve.prefill`` or ``serve.decode``, carrying ``batch_id`` and ``pos``,
and inside it one span per host call: ``.inputs`` (decode only),
``.dispatch``, ``.sample`` and ``.sync``. This module reads them from a
profile beside what ``tracefile`` reads (``from_profile`` keeps them as
``Trace.program_spans``), moves them onto the device's clock, and says which
host call each idle stretch of the first chip belongs to.

The host's clock lies a millisecond or two off the device's. The offset is
bounded by causality: the k-th step span of a kind pairs with the k-th run
of that kind's jitted step (``STEPS``) on the "XLA Modules" line; the run
starts after its span opened, and it and the runs the step dispatched
after it before its ``.sync`` (a decode step's argmax) end before that
closed. The host returns from the sync within tens of microseconds of the
device, so the sync side is the tight one, and the offset is taken from it.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

runs a cell traced, as ``bench/run.py --trace 1`` does, and prints as its
last line a JSON object: the offset's feasible interval, the three metrics
of ``bench/metrics/serve.*.py`` that read these spans, and the window's
device-idle seconds by innermost program span.
"""
from __future__ import annotations

import bisect
import collections
import statistics
from typing import Dict, List, NamedTuple, Optional, Tuple

try:
    from bench import tracefile
except ImportError:                         # run as a script
    import os
    import sys
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    from bench import tracefile

PREFIX = "serve."
#: kind of step -> the jitted function whose runs pair with its spans
STEPS = {"prefill": "prefill_step", "decode": "decode_step"}
#: where the window's time lies in no program span
OUTSIDE = "outside any program span"


class Span(NamedTuple):
    name: str
    start: int          # ns, on the host's clock
    end: int
    args: Dict[str, object]


def program_spans(prof, prefix: str = PREFIX) -> List[Span]:
    """Every host event of the profile whose name starts with ``prefix``,
    with its arguments (the profile holds them as the event's stats), in
    start order."""
    out = [Span(e.name, int(e.start_ns), int(e.end_ns), dict(e.stats))
           for plane in prof.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(prefix)]
    return sorted(out, key=lambda s: (s.start, -s.end))


def from_profile(prof) -> tracefile.Trace:
    """``tracefile.from_profile``, with ``program_spans`` besides."""
    tr = tracefile.from_profile(prof)
    tr.program_spans = program_spans(prof)
    return tr


def load(trace_dir: str) -> tracefile.Trace:
    import glob
    import os
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


def in_window(tr: tracefile.Trace, spans: List[Span],
              name: str) -> List[Span]:
    s, e = tr.window
    return [sp for sp in spans if sp.name == name and s <= sp.start
            and sp.end <= e]


def first_chip(tr: tracefile.Trace) -> str:
    plane = next((k for k, v in sorted(tr.runs.items()) if v), None)
    if plane is None:
        raise ValueError("the trace holds no program run on any chip")
    return plane


def _steps(tr: tracefile.Trace, spans: List[Span], prefix: str,
           runs: list) -> List[List[Tuple[Span, int, int]]]:
    """Per kind of step: (step span, its ``.sync``'s close, the index of
    its paired run) for each step of the window."""
    out = []
    for kind, program in STEPS.items():
        outer = in_window(tr, spans, prefix + kind)
        head = f"jit_{program}("
        paired = [i for i, r in enumerate(runs) if r[2].startswith(head)]
        if len(outer) != len(paired):
            raise ValueError(
                f"{len(outer)} {prefix}{kind} spans in the window and "
                f"{len(paired)} runs of jit_{program} do not pair one to "
                "one")
        syncs = [sp for sp in spans if sp.name == f"{prefix}{kind}.sync"]
        starts = [sp.start for sp in syncs]
        steps = []
        for sp, i in zip(outer, paired):
            j = bisect.bisect_right(starts, sp.end) - 1
            inside = j >= 0 and syncs[j].start >= sp.start
            steps.append((sp, syncs[j].end if inside else sp.end, i))
        out.append(steps)
    if not any(out):
        raise ValueError(f"no {prefix}prefill or {prefix}decode span in "
                         "the window to align")
    return out


def feasible_offsets(tr: tracefile.Trace, spans: List[Span],
                     prefix: str = PREFIX) -> Tuple[int, int]:
    """(least, greatest) ns that, added to the host's clock, keep every
    step's run of its jitted step starting inside the step's span, and
    every run its ``.sync`` waits on (the span itself where it has none)
    ending before that closed. Raises where the spans and the runs do not
    pair one to one, or no offset fits.

    The sync waits on every program dispatched before it, which the chip
    runs in order. Runs after the step's own that start before the sync
    closed, on the least offset the steps' own runs allow, were dispatched
    before it; the count of them that most steps of a kind show (a decode
    step's argmax) is taken as awaited in each step of that kind, where
    the chip started one late."""
    runs = tr.runs[first_chip(tr)]
    kinds = _steps(tr, sorted(spans, key=lambda s: (s.start, -s.end)),
                   prefix, runs)

    def least(after):
        return max((runs[i + a] if i + a < len(runs) else runs[i])[1] - close
                   for steps, a in zip(kinds, after) for _, close, i in steps)

    def awaited(i, close, lo):
        n = 0
        while i + n + 1 < len(runs) and runs[i + n + 1][0] < close + lo:
            n += 1
        return n

    lo = least([0] * len(kinds))
    after = [collections.Counter(awaited(i, close, lo)
                                 for _, close, i in steps).most_common(1)
             for steps in kinds]
    lo = least([a[0][0] if a else 0 for a in after])
    hi = min(runs[i][0] - sp.start for steps in kinds for sp, _, i in steps)
    if lo > hi:
        raise ValueError(f"no clock offset keeps every run inside its span: "
                         f"the sync side needs at least {lo} ns, the open "
                         f"side at most {hi} ns")
    return lo, hi


def clock_offset(tr: tracefile.Trace, spans: Optional[List[Span]] = None,
                 prefix: str = PREFIX) -> int:
    """Nanoseconds to add to a host span to put it on the device's clock:
    the sync side of ``feasible_offsets``."""
    if spans is None:
        spans = tr.program_spans
    return feasible_offsets(tr, spans, prefix)[0]


class Aligned:
    """The first chip's busy time and program runs, and host spans moved
    onto its clock by ``offset`` ns."""

    def __init__(self, tr: tracefile.Trace, offset: int):
        plane = first_chip(tr)
        self.tr = tr
        self.offset = offset
        self.busy = tracefile.merge([(o.start, o.end)
                                     for o in tr.device_ops.get(plane, ())])
        self._starts = [a for a, _ in self.busy]
        self._cum = [0]
        for a, b in self.busy:
            self._cum.append(self._cum[-1] + b - a)
        self.runs = sorted(tr.runs[plane])
        self._run_starts = [r[0] for r in self.runs]

    def _busy_before(self, t: int) -> int:
        j = bisect.bisect_right(self._starts, t) - 1
        if j < 0:
            return 0
        a, b = self.busy[j]
        return self._cum[j] + min(t, b) - a

    def busy_ns(self, a: int, b: int) -> int:
        """Busy ns of the chip in [a, b) on its own clock."""
        return self._busy_before(b) - self._busy_before(a) if b > a else 0

    def idle_ns(self, span: Span) -> int:
        """The span's length less the chip's busy time inside it."""
        a, b = span.start + self.offset, span.end + self.offset
        return (b - a) - self.busy_ns(a, b)

    def programs(self, span: Span) -> List[str]:
        """The programs whose run starts inside the span."""
        a, b = span.start + self.offset, span.end + self.offset
        i = bisect.bisect_left(self._run_starts, a)
        j = bisect.bisect_left(self._run_starts, b)
        return [r[2] for r in self.runs[i:j]]

    def pieces(self, spans: List[Span]) -> List[Tuple[int, int, str]]:
        """The window cut into (start, end, innermost span's name) on the
        chip's clock, ``OUTSIDE`` where no span is open. Spans of one
        thread nest, so the innermost is the last opened."""
        s, e = self.tr.window
        moved = sorted(((max(sp.start + self.offset, s),
                         min(sp.end + self.offset, e), sp.name)
                        for sp in spans), key=lambda x: (x[0], -x[1]))
        moved = [m for m in moved if m[1] > m[0]]
        out, stack, t, i = [], [], s, 0
        while t < e:
            nxt_open = moved[i][0] if i < len(moved) else e
            nxt_close = stack[-1][1] if stack else e
            nxt = min(nxt_open, nxt_close, e)
            if nxt > t:
                out.append((t, nxt, stack[-1][2] if stack else OUTSIDE))
                t = nxt
            if stack and nxt_close <= nxt_open:
                stack.pop()
            elif i < len(moved):
                stack.append(moved[i])
                i += 1
        return out

    def idle_by_span(self, spans: List[Span]) -> Dict[str, int]:
        """The window's idle ns of the chip by innermost span: they sum to
        the window less its busy time."""
        out: Dict[str, int] = {}
        for a, b, name in self.pieces(spans):
            out[name] = out.get(name, 0) + (b - a) - self.busy_ns(a, b)
        return out

    def idle_gaps(self, spans: List[Span], n: int = 10) -> List[List]:
        """The ``n`` longest stretches of the window in which the chip ran
        nothing, each named by the innermost span around its middle."""
        s, e = self.tr.window
        gaps, cur = [], s
        for a, b in tracefile.clip(self.busy, s, e):
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if cur < e:
            gaps.append((cur, e))
        cuts = self.pieces(spans)
        starts = [p[0] for p in cuts]
        out = []
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            k = bisect.bisect_right(starts, (a + b) // 2) - 1
            out.append([cuts[k][2], (b - a) / 1e9])
        return out


def aligned(tr: tracefile.Trace) -> Aligned:
    return Aligned(tr, clock_offset(tr))


def decode_host_ms(tr: tracefile.Trace) -> Optional[float]:
    """Median, over the window's decode steps, of the chip's idle ms
    inside the step's ``serve.decode`` span."""
    steps = in_window(tr, tr.program_spans, PREFIX + "decode")
    if not steps:
        return None
    al = aligned(tr)
    return statistics.median(al.idle_ns(sp) for sp in steps) / 1e6


def programs_per_decode(tr: tracefile.Trace) -> Optional[float]:
    """Programs whose run started inside a ``serve.decode`` span, over the
    number of those spans."""
    steps = in_window(tr, tr.program_spans, PREFIX + "decode")
    if not steps:
        return None
    al = aligned(tr)
    return sum(len(al.programs(sp)) for sp in steps) / len(steps)


def stalls(tr: tracefile.Trace) -> Optional[float]:
    """Step spans of the window longer than twice the median of their kind
    (``serve.prefill`` and ``serve.decode`` apart)."""
    n, seen = 0, False
    for kind in STEPS:
        secs = [sp.end - sp.start
                for sp in in_window(tr, tr.program_spans, PREFIX + kind)]
        if secs:
            seen = True
            med = statistics.median(secs)
            n += sum(d > 2 * med for d in secs)
    return float(n) if seen else None


def report(tr: tracefile.Trace) -> dict:
    """Where the window's idle time went, by the program's spans."""
    lo, hi = feasible_offsets(tr, tr.program_spans)
    al = Aligned(tr, lo)
    idle = al.idle_by_span(tr.program_spans)
    window = tr.window_seconds()
    return {"offset_ns": [lo, hi],
            "metrics": {"serve.decode_host_ms": decode_host_ms(tr),
                        "serve.programs_per_decode": programs_per_decode(tr),
                        "serve.stalls": stalls(tr)},
            "window_s": window,
            "idle_share": 100.0 * sum(idle.values()) / 1e9 / window,
            "idle_s_by_span": {k: v / 1e9 for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])},
            "idle_gaps": al.idle_gaps(tr.program_spans)}


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import sys
    import time
    import jax
    from bench import harness
    t_process = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    files = harness.Files()
    entry = next(w for w in harness.load_spec()["workloads"]
                 if w["name"] == args.workload)
    try:
        harness.require_chips(int(entry["chips"]))
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir",
                      harness.compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    class Ctx(harness.Ctx):
        def window_end(self):
            # as the harness's, reading the program's spans besides
            self._counting = False
            jax.monitoring.unregister_event_duration_listener(self._on_event)
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            try:
                self.trace_data = load(self._trace_dir)
            finally:
                shutil.rmtree(self._trace_dir, ignore_errors=True)

    ctx = Ctx(files, args.workload, args.seed, args.seconds, True, t_process)
    run = files.driver(ctx.workload["driver"]).run(ctx)
    print(json.dumps(dict(report(ctx.trace_data), correct=run.correct)),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
