"""The serve driver's end-to-end metrics from the window's rounds and
steps, on timings written by hand."""
import numpy as np
import pytest

from bench import harness

serve = harness.Files().driver("serve")


def rounds_and_steps(n_rounds, clients, out, step_s, prefill_s):
    """Closed-loop rounds of ``clients`` requests, each a prefill of
    ``prefill_s`` then ``out - 1`` decode steps of ``step_s``."""
    rounds, steps, t = [], [], 0.0
    for i in range(n_rounds):
        t_sub = t
        t += prefill_s
        steps.append({"kind": "prefill", "t0": t_sub, "t1": t})
        times = [t]
        for _ in range(out - 1):
            steps.append({"kind": "decode", "t0": t, "t1": t + step_s})
            t += step_s
            times.append(t)
        rounds.append({"round": i, "t_sub": t_sub, "times": times,
                       "tokens": np.zeros((clients, out), np.int32)})
    return rounds, steps, t


def test_rate_covers_all_the_work_and_all_the_time():
    rounds, steps, t1 = rounds_and_steps(3, 4, 10, 0.05, 0.5)
    m = serve.window_metrics(rounds, steps, 0.0, t1, 4)
    assert m["tokens_per_s"] == pytest.approx(3 * 4 * 10 / t1)
    assert m["ttft_p95_ms"] == pytest.approx(500.0)
    assert m["tpot_ms"] == pytest.approx(50.0)


def test_time_per_token_is_decode_time_over_decode_steps():
    rounds, steps, t1 = rounds_and_steps(2, 2, 6, 0.1, 1.0)
    steps[3]["t1"] += 0.5                   # one slow step
    m = serve.window_metrics(rounds, steps, 0.0, t1, 2)
    assert m["tpot_ms"] == pytest.approx((10 * 0.1 + 0.5) / 10 * 1e3)


def test_ttft_tail_is_over_every_request():
    rounds, steps, t1 = rounds_and_steps(20, 8, 2, 0.2, 1.0)
    rounds[7]["times"][0] += 1.0            # one round's batch is late
    m = serve.window_metrics(rounds, steps, 0.0, t1, 8)
    assert m["ttft_p95_ms"] == pytest.approx(
        np.percentile(np.repeat([1.0] * 19 + [2.0], 8), 95) * 1e3)


def test_decode_time_under_a_quarter_second_is_not_reported():
    rounds, steps, t1 = rounds_and_steps(1, 2, 4, 0.01, 0.5)
    assert serve.window_metrics(rounds, steps, 0.0, t1, 2)["tpot_ms"] is None
