"""The program's spans on the device's clock (``bench.spans``): the clock
offset, device idle time by host call, and the three metrics that read
them, on a trace written by hand and on traces recorded on the chip."""
import gzip
import os

import pytest

from bench import harness, spans, tracefile

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1_000_000                      # ps in a microsecond
#: the hand-written trace's device clock reads the host's less 2 us
OFFSET_NS = -2000

# Host spans in us on the host's clock: (name, start, end, args). A
# prefill, then three decode steps of batch 1; the third is held up 23 us
# in its sync.
HOST = [("bench.window", 0, 80, {}),
        ("serve.prefill", 2, 10, {"batch_id": 1, "pos": 0}),
        ("serve.prefill.dispatch", 2, 3, {}),
        ("serve.prefill.sync", 3, 8, {}),
        ("serve.prefill.sample", 8, 9, {})]
for _k, (_a, _sync) in enumerate([(12, 5), (26, 5), (40, 23)]):
    HOST += [("serve.decode", _a, _a + 7 + _sync, {"batch_id": 1,
                                                  "pos": 8 + _k}),
             ("serve.decode.inputs", _a, _a + 2, {}),
             ("serve.decode.dispatch", _a + 2, _a + 4, {}),
             ("serve.decode.sample", _a + 4, _a + 5, {}),
             ("serve.decode.sync", _a + 5, _a + 5 + _sync, {})]
# Program runs in us on the device's clock, one operation each: the
# prefill's step ends on the device as its sync returns on the host (so
# the offset is tight there), its argmax runs after; each decode step runs
# the pos scalar, the token broadcast, the step and the argmax.
DEVICE = [("jit_prefill_step(1)", 2, 6), ("jit__argmax(2)", 7, 7.5)]
for _d in (10, 24, 38):
    DEVICE += [("jit_convert_element_type(3)", _d + .5, _d + 1),
               ("jit_broadcast_in_dim(4)", _d + 1.2, _d + 1.6),
               ("jit_decode_step(5)", _d + 3, _d + 8),
               ("jit__argmax(2)", _d + 8, _d + 8.5)]


def text(host=HOST, device=DEVICE) -> str:
    """The trace as a text proto."""
    names = sorted({n for n, *_ in device})
    ev = "\n".join(
        f"events {{ metadata_id: {names.index(n) + 1} offset_ps: "
        f"{round(a * US)} duration_ps: {round((b - a) * US)} }}"
        for n, a, b in device)
    meta = "\n".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                     f'name: "{n}" }} }}' for i, n in enumerate(names))
    hnames = sorted({h[0] for h in host})
    hev = "\n".join(
        f"events {{ metadata_id: {hnames.index(n) + 1} offset_ps: "
        f"{round(a * US)} duration_ps: {round((b - a) * US)} "
        + " ".join(f"stats {{ metadata_id: {100 + i} int64_value: {v} }}"
                   for i, v in ((("batch_id", "pos").index(k), v)
                                for k, v in args.items())) + " }"
        for n, a, b, args in host)
    hmeta = "\n".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1}'
                      f' name: "{n}" }} }}' for i, n in enumerate(hnames))
    return f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ev} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {ev} }}
  {meta}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {hev} }}
  {hmeta}
  stat_metadata {{ key: 100 value {{ id: 100 name: "batch_id" }} }}
  stat_metadata {{ key: 101 value {{ id: 101 name: "pos" }} }}
}}
'''


def read(proto: str) -> tracefile.Trace:
    from jax.profiler import ProfileData
    return spans.from_profile(ProfileData.from_text_proto(proto))


@pytest.fixture(scope="module")
def hand():
    return read(text())


def run_of(trace) -> harness.Run:
    return harness.Run(e2e={}, attempted=1, failed=0, checks={},
                       trace=trace)


def metric(name, trace):
    return harness.Files().metric(name).read(run_of(trace))


def test_program_spans_are_read_with_their_arguments(hand):
    got = [(s.name, s.start, s.end, s.args) for s in hand.program_spans]
    want = sorted((n, a * 1000, b * 1000, args) for n, a, b, args in HOST
                  if n.startswith("serve."))
    assert sorted(got) == want
    decode = [s for s in hand.program_spans if s.name == "serve.decode"]
    assert [s.args["pos"] for s in decode] == [8, 9, 10]
    assert {s.args["batch_id"] for s in decode} == {1}


def test_the_old_fields_read_the_same_without_the_program_spans(hand):
    bare = read(text([h for h in HOST if not h[0].startswith("serve.")]))
    assert bare.host_spans == hand.host_spans == [("bench.window", 0,
                                                   80_000)]
    assert tracefile.idle_gaps(bare) == tracefile.idle_gaps(hand)
    assert tracefile.top_ops(bare) == tracefile.top_ops(hand)
    assert bare.busy_seconds() == hand.busy_seconds()
    assert bare.program_spans == []


def test_clock_offset_is_recovered_from_the_sync_side(hand):
    # the prefill's step ends as its sync closes: -2 us; the decode steps'
    # open sides allow at most 1 us, the prefill's 0
    assert spans.feasible_offsets(hand, hand.program_spans) == (OFFSET_NS,
                                                                0)
    assert spans.clock_offset(hand) == OFFSET_NS


def test_a_late_argmax_still_bounds_the_sync_side():
    # the prefill's sync returns 1 us late, so the steps' own runs allow
    # -3 us; on that offset the second decode step's argmax starts after its
    # sync closed, but the other steps show that the sync waits on the run
    # after the step's own, so it ended by then: back to -2 us
    host = [("serve.prefill.sync", 3, 9, {}) if h[0] == "serve.prefill.sync"
            else ("serve.prefill.sample", 9, 9.5, {})
            if h[0] == "serve.prefill.sample" else h for h in HOST]
    device = [("jit__argmax(2)", 33.5, 34) if d == ("jit__argmax(2)", 32,
                                                    32.5) else d
              for d in DEVICE]
    tr = read(text(host=host, device=device))
    assert spans.feasible_offsets(tr, tr.program_spans) == (OFFSET_NS, 0)
    assert spans.programs_per_decode(tr) == 4.0


def test_clock_offset_fails_where_spans_and_runs_do_not_pair():
    dropped = [d for i, d in enumerate(DEVICE)
               if not (d[0].startswith("jit_decode_step") and i > 10)]
    with pytest.raises(ValueError, match="3 serve.decode spans .* 2 runs "
                                         "of jit_decode_step"):
        spans.clock_offset(read(text(device=dropped)))
    with pytest.raises(ValueError, match="no serve.prefill or serve.decode"):
        spans.clock_offset(read(text(host=HOST[:1], device=DEVICE[1:2])))


def test_clock_offset_fails_where_no_offset_fits():
    # the first decode span opens 4 us late: its step then starts 3 us
    # before it on any offset the prefill's sync allows
    late = [("serve.decode", 16, 24, h[3]) if h[0] == "serve.decode"
            and h[1] == 12 else h for h in HOST]
    with pytest.raises(ValueError, match="at least -2000 ns, the open side "
                                         "at most -3000 ns"):
        spans.clock_offset(read(text(host=late)))


def test_idle_time_by_innermost_span_sums_to_the_window_idle(hand):
    al = spans.aligned(hand)
    idle = al.idle_by_span(hand.program_spans)
    assert idle == {"serve.prefill.dispatch": 1000,
                    "serve.prefill.sync": 1000,
                    "serve.prefill.sample": 1000, "serve.prefill": 500,
                    spans.OUTSIDE: 18000, "serve.decode.inputs": 3300,
                    "serve.decode.dispatch": 3000, "serve.decode.sample": 0,
                    "serve.decode.sync": 22500, "serve.decode": 6000}
    busy = hand.busy_seconds() * 1e9
    assert sum(idle.values()) == pytest.approx(80_000 - busy)
    # [46.5, 80) us, the held-up sync and past it; [18.5, 24.5) us,
    # centred after the first decode step's sync returned
    gaps = al.idle_gaps(hand.program_spans, n=2)
    assert gaps == [["serve.decode.sync", pytest.approx(33.5e-6)],
                    ["serve.decode", pytest.approx(6e-6)]]


def test_each_program_span_gives_its_idle_time_and_programs(hand):
    al = spans.aligned(hand)
    first = next(s for s in hand.program_spans if s.name == "serve.decode")
    assert al.idle_ns(first) == 12_000 - 6_400
    assert al.programs(first) == ["jit_convert_element_type(3)",
                                  "jit_broadcast_in_dim(4)",
                                  "jit_decode_step(5)", "jit__argmax(2)"]
    prefill = next(s for s in hand.program_spans
                   if s.name == "serve.prefill")
    assert al.programs(prefill) == ["jit_prefill_step(1)", "jit__argmax(2)"]


def test_the_three_metrics_read_their_hand_computed_values(hand):
    # idle 5.6, 5.6 and 23.6 us in the three decode spans
    assert metric("serve.decode_host_ms", hand) == pytest.approx(0.0056)
    assert metric("serve.programs_per_decode", hand) == 4.0
    # decode spans of 12, 12 and 30 us: one over twice the median
    assert metric("serve.stalls", hand) == 1.0


@pytest.mark.parametrize("name", ["serve.decode_host_ms",
                                  "serve.programs_per_decode",
                                  "serve.stalls"])
def test_the_metrics_read_nothing_without_the_program_spans(name, hand):
    from jax.profiler import ProfileData
    assert metric(name, None) is None
    assert metric(name, tracefile.from_profile(
        ProfileData.from_text_proto(text()))) is None
    bare = read(text([h for h in HOST if not h[0].startswith("serve.")]))
    assert metric(name, bare) is None


def recorded(name):
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, name)) as f:
        return spans.from_profile(ProfileData.from_serialized_xspace(
            f.read()))


@pytest.fixture(scope="module")
def old_chip():
    """The serve driver on one TPU v5e at the control test's size, before
    the program had spans of its own: one prefill, 18 decode steps."""
    return recorded("small_serve.xplane.pb.gz")


def test_the_benchmark_spans_align_a_trace_without_program_spans(old_chip):
    # bench.prefill and bench.decode stand in for the steps' spans, with no
    # sync inside
    assert old_chip.program_spans == []
    bench = [spans.Span(n, a, b, {}) for n, a, b in old_chip.host_spans]
    lo, hi = spans.feasible_offsets(old_chip, bench, prefix="bench.")
    # the prefill's step ran 0.99 ms before bench.prefill opened
    assert hi == -988_598
    assert -2_300_000 < lo < hi
    short = [s for s in bench
             if not (s.name == "bench.decode" and s.start > 90_000_000)]
    with pytest.raises(ValueError, match="do not pair"):
        spans.feasible_offsets(old_chip, short, prefix="bench.")


@pytest.fixture(scope="module")
def chip():
    """The serve driver on one TPU v5e at the control test's size, with
    the program's spans: one prefill, 18 decode steps of batch 2."""
    return recorded("small_serve_spans.xplane.pb.gz")


def test_a_chip_trace_aligns_its_program_spans(chip):
    decode = spans.in_window(chip, chip.program_spans, "serve.decode")
    assert [s.args["pos"] for s in decode] == list(range(128, 146))
    assert {s.args["batch_id"] for s in chip.program_spans if s.args} == {2}
    # the host clock lies 0.66 to 1.67 ms ahead of the device's
    assert spans.feasible_offsets(chip, chip.program_spans) == (-1_674_965,
                                                                -664_206)
    with pytest.raises(ValueError, match="17 serve.decode spans .* 18 runs"):
        spans.clock_offset(chip, [s for s in chip.program_spans
                                  if s is not decode[5]])


def test_a_chip_trace_reads_the_three_metrics(chip):
    # each decode step dispatches the pos scalar, the token broadcast, the
    # step and the argmax, with the chip idle about 1.9 ms of the step
    assert metric("serve.programs_per_decode", chip) == 4.0
    assert metric("serve.decode_host_ms", chip) == pytest.approx(1.933651)
    assert metric("serve.stalls", chip) == 0.0


def test_a_chip_trace_splits_its_idle_time_by_host_call(chip):
    idle = spans.aligned(chip).idle_by_span(chip.program_spans)
    total = sum(idle.values())
    assert total / 1e9 == pytest.approx(
        chip.window_seconds() - chip.busy_seconds())
    share = {k: v / total for k, v in idle.items()}
    assert share == pytest.approx({
        "serve.decode.inputs": 0.3356, spans.OUTSIDE: 0.2334,
        "serve.decode.dispatch": 0.2030, "serve.decode.sync": 0.1082,
        "serve.decode.sample": 0.0791, "serve.decode": 0.0061,
        "serve.prefill.sync": 0.0142, "serve.prefill.dispatch": 0.0113,
        "serve.prefill.sample": 0.0072, "serve.prefill": 0.0018},
        abs=1e-4)
