"""The reduction from a profiler trace to busy time, kernel time and idle
gaps, on a trace written by hand and on one recorded on the chip."""
import os

import pytest

from bench import tracefile

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# A window of 10 us; on the chip, inside a run of the program jit_step(7)
# over [0, 10) us: a loop [1, 5) us around a transpose fusion of the flash
# wrapper [1, 3) us and the flash kernel [2, 5) us (found by its op name),
# a decode kernel [7, 8) us (found by its instruction name, at the head of
# the whole instruction as the chip names its events), and an op at
# [11, 12) us outside the window. The host is in bench.decode over [5, 9)
# us.
HAND = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000
             stats { metadata_id: 9 str_value: "jit(prefill_step)/jit(flash_attention)/transpose" } }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000
             stats { metadata_id: 9 str_value: "jit(prefill_step)/while/body/closed_call/jit(flash_attention)/pallas_call" } }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 0 duration_ps: 4000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.3" } }
  event_metadata { key: 3 value { id: 3 name: "%decode_attention.7 = (f32[2,1,1,2,128]{4,3,2,1,0}) custom-call(bf16[2,1,2,128]{3,2,1,0} %bitcast.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step(7)" } }
  event_metadata { key: 5 value { id: 5 name: "%while.2 = (s32[]) while(s32[] %tuple.1), body=%region_0" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.decode" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction" } }
}
'''


@pytest.fixture(scope="module")
def hand():
    from jax.profiler import ProfileData
    return tracefile.from_profile(ProfileData.from_text_proto(HAND))


def test_busy_is_the_union_of_operations_in_the_window(hand):
    assert hand.window == (0, 10_000)
    assert hand.window_seconds() == pytest.approx(10e-6)
    # [1, 5) us and [7, 8) us: the overlap counts once, the op past the
    # window not at all, and the "XLA Modules" line is not an operation
    assert hand.busy_seconds() == pytest.approx(5e-6)


def kernel_ops(trace, kernel):
    match = tracefile.kernel_match(kernel)
    return [(o.name, o.end - o.start) for o in trace.ops(*trace.window)
            if match(o)]


def test_kernel_events_are_found_by_name_or_op_name(hand):
    # the transpose inside jit(flash_attention) is not the kernel
    assert kernel_ops(hand, "flash_attention") == [("custom-call.3", 3000)]
    assert kernel_ops(hand, "decode_attention") == [
        ("decode_attention.7", 1000)]
    assert kernel_ops(hand, "decode") == []


def test_kernel_events_are_attributed_to_the_program_run_they_ran_in(hand):
    assert hand.kernel_per_run("decode_attention", "step") == [
        (1, pytest.approx(1e-6))]
    assert hand.kernel_per_run("flash_attention", "step") == [
        (1, pytest.approx(3e-6))]
    assert hand.kernel_per_run("decode_attention", "decode_step") == []
    step = {"kind": "decode"}
    assert tracefile.traced_steps(hand, [step], "decode_attention", "step",
                                  1) == [(step, pytest.approx(1e-6))]
    assert tracefile.traced_steps(hand, [step], "decode_attention", "step",
                                  2) == []
    assert tracefile.traced_steps(hand, [step, step], "decode_attention",
                                  "step", 1) == []


def test_idle_gaps_are_named_by_the_host_span(hand):
    gaps = tracefile.idle_gaps(hand)
    assert gaps[0][1] == pytest.approx(2e-6)                # longest first
    assert ["host idle", pytest.approx(2e-6)] in gaps       # [8, 10) us
    assert ["bench.decode", pytest.approx(2e-6)] in gaps    # [5, 7) us
    assert ["host idle", pytest.approx(1e-6)] in gaps       # [0, 1) us
    assert len(gaps) == 3


def test_top_ops_sum_each_name(hand):
    top = dict((k, v) for k, v in tracefile.top_ops(hand))
    assert top == {"jit_step(7)/custom-call.3": pytest.approx(3e-6),
                   "jit_step(7)/fusion.1": pytest.approx(2e-6),
                   "jit_step(7)/decode_attention.7": pytest.approx(1e-6)}


def test_summary_names_what_the_trace_held(hand):
    text = tracefile.summary(hand)
    assert "/device:TPU:0: {'XLA Ops': 5, 'XLA Modules': 1}" in text
    assert "host spans: {'bench.window': 1, 'bench.decode': 1}" in text
    assert "holds 4 device operations" in text
    assert ("operations that name a Pallas or custom call: "
            "['custom-call.3', 'decode_attention.7']") in text


@pytest.fixture(scope="module")
def chip():
    """A traced window of the serve driver on one TPU v5e at the control
    test's size (2 layers, B2, prompt 128): one prefill step, 18 decode
    steps."""
    import gzip
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, "small_serve.xplane.pb.gz")) as f:
        return tracefile.from_profile(ProfileData.from_serialized_xspace(
            f.read()))


def test_a_chip_trace_gives_each_step_its_kernel_calls(chip):
    assert set(chip.runs) == {"/device:TPU:0"}
    assert chip.kernel_per_run("flash_attention", "prefill_step") == [
        (2, pytest.approx(8.818e-6))]
    per = chip.kernel_per_run("decode_attention", "decode_step")
    assert [n for n, _ in per] == [2] * 18
    assert all(t == pytest.approx(5.028e-5, rel=1e-3) for _, t in per)
    # the host clock lies a millisecond off the device's: the prefill's
    # kernels ran before its host span opened
    (a, b), = [(a, b) for n, a, b in chip.host_spans if n == "bench.prefill"]
    flash = tracefile.kernel_match("flash_attention")
    assert [o.end < a for o in chip.ops(*chip.window) if flash(o)] == [
        True, True]


def test_a_chip_trace_reads_busy_time_and_the_heaviest_operations(chip):
    assert chip.window_seconds() == pytest.approx(0.05178, rel=1e-3)
    assert 0 < chip.busy_seconds() < chip.window_seconds()
    top = dict(tracefile.top_ops(chip))
    name = next(k for k in top if k.endswith("/decode_attention.7"))
    assert name.startswith("jit_decode_step(")
    assert top[name] == pytest.approx(18 * 5.028e-5, rel=1e-3)
    assert not any(tracefile.CONTROL.fullmatch(k.split("/")[-1])
                   for k in top)


def test_the_roofline_readers_read_the_chip_trace(chip):
    from bench import harness
    files = harness.Files()
    peak = harness.peak_for(files.peaks(), "TPU v5 lite")
    steps = [{"kind": "prefill", "B": 2, "S": 128}] + [
        {"kind": "decode", "B": 2, "live": 129 + i} for i in range(18)]
    run = harness.Run(e2e={}, attempted=2, failed=0, checks={}, steps=steps,
                      info={"dims": {"d": 256, "H": 4, "KV": 2, "hd": 64,
                                     "F": 512, "V": 4096, "L": 2}},
                      trace=chip, peak=peak)
    for metric in ("flash_prefill_roofline", "flash_decode_roofline"):
        assert 0 < files.metric(metric).read(run) < 100


def test_merge_and_clip():
    assert tracefile.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4),
                                                                 (5, 8)]
    assert tracefile.union_ns([(0, 10), (2, 3)]) == 10
    assert tracefile.clip([(0, 4), (6, 9), (10, 12)], 2, 8) == [(2, 4),
                                                                (6, 8)]
