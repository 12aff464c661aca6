"""The comparison that decides ``correct`` fails what it must fail.

At a size a CPU test run holds (Pallas in interpret mode): the program's
served tokens stay within the test cell's limit, while the float8 control,
and a run whose timed path is broken underneath, do not.
"""
import json
import math
import time

import pytest

from bench import calibrate, harness

LIMIT = 0.015
CONFIG = {"source": "test", "hidden_size": 256, "intermediate_size": 512,
          "num_hidden_layers": 2, "num_attention_heads": 4,
          "num_key_value_heads": 2, "vocab_size": 4096, "hidden_act": "silu",
          "rms_norm_eps": 1e-5, "rope_theta": 10000,
          "tie_word_embeddings": False, "torch_dtype": "bfloat16"}
MIX = {"loop": "closed", "clients": 2, "prompt_tokens": 128,
       "output_tokens": 192}
CELL = {"config": "small", "traffic": "small_mix", "driver": "serve",
        "kernels": {"flash": {"block_q": 128, "block_kv": 128},
                    "decode": {"block_kv": 128, "num_splits": 1,
                               "combine": "jax"}},
        "check": {"requests": 2, "rows": 2, "served_logit_gap": LIMIT}}
SPEC = {"workloads": [{"name": "small.serve", "config": "small",
                       "traffic": "small_mix", "chips": 1}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    d = tmp_path_factory.mktemp("cell")
    for kind, name, body in (("configs", "small", CONFIG),
                             ("traffic", "small_mix", MIX),
                             ("workloads", "small.serve", CELL)):
        (d / kind).mkdir()
        (d / kind / f"{name}.json").write_text(json.dumps(body))
    return harness.Files(str(d), harness.BENCH)


def run(files, seed):
    return harness.run_cell(files, SPEC, "small.serve", seed, 5.0, False,
                            time.perf_counter(),
                            {"platform": "cpu", "kind": "cpu", "count": 1},
                            None)


def test_control_fails_and_program_passes(files):
    ctx = harness.Ctx(files, "small.serve", 0, 0.0, False, 0.0)
    for row in calibrate.readings(ctx, [1, 2], out=lambda s: None):
        assert row["program"] <= LIMIT < row["control"], row
        assert calibrate.judged(row["program"], LIMIT)
        for name in ("control",) + tuple(calibrate.FAULTS):
            assert not calibrate.judged(row[name], LIMIT), (name, row)


def test_sound_run_is_correct(files):
    res = run(files, 2**32 + 9)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= MIX["clients"] and res["failed"] == 0


def test_a_token_altered_where_it_is_produced_fails(files, monkeypatch):
    from repro.launch.serve import DecodeServer
    step = DecodeServer.decode_step

    def altered(self):
        dt = step(self)
        self.toks = (self.toks + 1) % CONFIG["vocab_size"]
        return dt

    monkeypatch.setattr(DecodeServer, "decode_step", altered)
    res = run(files, 1)
    assert not res["correct"]
    assert res["checks"]["served_logit_gap"]["value"] > 10 * LIMIT


def test_a_decode_step_that_returns_its_cache_unchanged_fails(files,
                                                              monkeypatch):
    from repro.launch.serve import DecodeServer
    derive = DecodeServer._derive

    def stale(self):
        derive(self)
        decode = self.decode
        self.decode = lambda p, c, b, pos: (decode(p, c, b, pos)[0], c)

    monkeypatch.setattr(DecodeServer, "_derive", stale)
    res = run(files, 1)
    assert not res["correct"]
    assert math.isfinite(res["checks"]["served_logit_gap"]["value"])
