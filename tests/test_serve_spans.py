"""The serve path's profiler spans (``DecodeServer.prefill_batch`` and
``decode_step``): their names, how they nest, and the batch and position
they carry, read back from a profile taken on the CPU at smoke size."""
import glob
import os
import types

import jax
import pytest

from repro.configs.registry import smoke_config
from repro.launch import serve
from repro.parallel.sharding import ParallelConfig

STEPS = 3
PROMPT = 16
INNER = {"serve.prefill": ["serve.prefill.dispatch", "serve.prefill.sync",
                           "serve.prefill.sample"],
         "serve.decode": ["serve.decode.inputs", "serve.decode.dispatch",
                          "serve.decode.sample", "serve.decode.sync"]}


def make_server():
    return serve.DecodeServer(
        smoke_config("gemma-2b"),
        ParallelConfig(flash_threshold=1 << 30, logits_chunk=0), batch=2,
        prompt_len=PROMPT, decode_steps=STEPS)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(seconds each call returned, the profile's ``serve.*`` host events
    as (name, start, end, arguments) in start order)."""
    from jax.profiler import ProfileData
    server = make_server()
    batch = server.input_batch()
    server.warmup(batch)
    out = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(out)
    try:
        secs = [server.prefill_batch(batch)]
        secs += [server.decode_step() for _ in range(STEPS)]
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    prof = ProfileData.from_file(path)
    events = sorted(((e.name, int(e.start_ns), int(e.end_ns), dict(e.stats))
                     for p in prof.planes if p.name.startswith("/host:")
                     for line in p.lines for e in line.events
                     if e.name.startswith("serve.")),
                    key=lambda e: (e[1], -e[2]))
    return secs, events


def test_each_step_opens_one_outer_span_with_its_calls_inside(traced):
    _, events = traced
    outer = [e for e in events if e[0] in INNER]
    assert [e[0] for e in outer] == ["serve.prefill"] + ["serve.decode"] * 3
    inner = [e for e in events if e[0] not in INNER]
    assert len(inner) == 3 + 4 * STEPS
    for name, a, b, _ in outer:
        inside = [e for e in inner if a <= e[1] and e[2] <= b]
        assert [e[0] for e in inside] == INNER[name]
        # the calls follow one another, each closing before the next opens
        assert all(x[2] <= y[1] for x, y in zip(inside, inside[1:]))
    # every inner span lies in exactly one outer span: its parent
    assert sum(len(INNER[e[0]]) for e in outer) == len(inner)


def test_outer_spans_carry_one_batch_and_a_rising_position(traced):
    _, events = traced
    outer = [e for e in events if e[0] in INNER]
    assert {e[3]["batch_id"] for e in outer} == {1}
    assert [e[3]["pos"] for e in outer] == [0] + [PROMPT + i
                                                  for i in range(STEPS)]
    assert all(not e[3] for e in events if e[0] not in INNER)


def test_each_call_returns_positive_seconds(traced):
    secs, _ = traced
    assert len(secs) == 1 + STEPS and all(s > 0 for s in secs)


def test_returned_seconds_come_from_perf_counter(monkeypatch):
    server = make_server()
    batch = server.input_batch()
    ticks = iter(range(100))

    def wall():
        raise AssertionError("time.time is not a step timer")

    monkeypatch.setattr(serve, "time", types.SimpleNamespace(
        perf_counter=lambda: 0.25 * next(ticks), time=wall))
    assert server.prefill_batch(batch) == 0.25
    assert server.decode_step() == 0.25
    assert server.batch_id == 1
    server.prefill_batch(batch)
    assert server.batch_id == 2
