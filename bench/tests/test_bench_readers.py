"""Per-layer readers: what they read from a run, and what the harness does
when one that the benchmark lists for a cell finds nothing."""
import json
import time

import pytest

from bench import flops, harness

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DIMS = {"d": 256, "H": 4, "KV": 2, "hd": 128, "F": 512, "V": 1024, "L": 2}


class Trace:
    """Kernel events per run of a step's program, as
    ``tracefile.Trace.kernel_per_run`` gives them."""

    def __init__(self, kernel, per_run):
        self.kernel, self.per_run = kernel, per_run

    def kernel_per_run(self, kernel, program):
        return self.per_run if kernel == self.kernel else []


def decode_run(per_run):
    return harness.Run(
        e2e={}, attempted=0, failed=0, checks={},
        steps=[{"kind": "decode", "t0": 0.0, "t1": 1.0, "B": 8,
                "live": 100 + i} for i in range(3)],
        info={"dims": DIMS}, trace=Trace("decode_attention", per_run),
        peak=PEAK)


def decode_least(i):
    return flops.least_seconds(*flops.flash_decode_cost(
        8, 100 + i, 4, 2, 128), PEAK) * DIMS["L"]


def test_flash_decode_roofline_is_least_time_over_kernel_time():
    reader = harness.Files().metric("flash_decode_roofline")
    least = sum(decode_least(i) for i in range(3))
    assert reader.read(decode_run([(2, 1e-3)] * 3)) == pytest.approx(
        100 * least / 3e-3)
    # split and combine kernels: two calls in every layer of every step
    assert reader.read(decode_run([(4, 2e-3)] * 3)) == pytest.approx(
        100 * least / 6e-3)


def test_flash_decode_roofline_counts_only_steps_recorded_whole():
    reader = harness.Files().metric("flash_decode_roofline")
    run = decode_run([(2, 1e-3), (1, 4e-4), (2, 1e-3)])
    assert reader.read(run) == pytest.approx(
        100 * (decode_least(0) + decode_least(2)) / 2e-3)


@pytest.mark.parametrize("per_run", [[(0, 0.0)] * 3, [(1, 1e-3)] * 3,
                                      [(2, 1e-3)] * 2])
def test_flash_decode_roofline_reads_nothing_without_a_whole_step(per_run):
    reader = harness.Files().metric("flash_decode_roofline")
    assert reader.read(decode_run(per_run)) is None


def test_flash_prefill_roofline_needs_one_call_per_layer_and_step():
    reader = harness.Files().metric("flash_prefill_roofline")
    steps = [{"kind": "prefill", "t0": 0.0, "t1": 1.0, "B": 2, "S": 512}]
    run = harness.Run(e2e={}, attempted=0, failed=0, checks={}, steps=steps,
                      info={"dims": DIMS}, peak=PEAK,
                      trace=Trace("flash_attention", [(2, 1e-4)]))
    least = flops.least_seconds(*flops.flash_prefill_cost(2, 512, 4, 2, 128),
                                PEAK) * 2
    assert reader.read(run) == pytest.approx(100 * least / 1e-4)
    run.trace = Trace("flash_attention", [(3, 1e-4)])
    assert reader.read(run) is None


DRIVER = '''
from bench.harness import Run


def run(ctx):
    ctx.window_start()
    ctx.window_end()
    return Run(e2e={"tokens_per_s": 1.0}, attempted=1, failed=0,
               checks={"gap": {"value": 0.0, "limit": 1.0}})
'''


def test_a_listed_metric_that_reads_nothing_stops_the_run(tmp_path):
    for kind, name, body in (("configs", "c", "{}"), ("traffic", "t", "{}"),
                             ("workloads", "c.t", json.dumps(
                                 {"config": "c", "traffic": "t",
                                  "driver": "fake"})),
                             ("drivers", "fake", DRIVER),
                             ("metrics", "empty",
                              "def read(run):\n    return None\n")):
        (tmp_path / kind).mkdir()
        ext = ".py" if kind in ("drivers", "metrics") else ".json"
        (tmp_path / kind / f"{name}{ext}").write_text(body)
    files = harness.Files(str(tmp_path), harness.BENCH)
    spec = {"workloads": [{"name": "c.t", "config": "c", "traffic": "t",
                           "chips": 1}],
            "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": [{"name": "empty", "unit": "%",
                           "moves": "tokens_per_s", "workloads": ["c.t"]}]}
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    res = harness.run_cell(files, spec, "c.t", 1, 0.0, False,
                           time.perf_counter(), device, None)
    assert res["correct"] and "tokens_per_s" in res["metrics"]
    with pytest.raises(RuntimeError, match="empty found nothing"):
        harness.run_cell(files, spec, "c.t", 1, 0.0, True,
                         time.perf_counter(), device, None)
