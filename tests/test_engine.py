"""Ask/tell engine: golden sequential parity, batching, dedup, resume.

The golden traces in tests/golden/seed_traces.json were captured from the
pre-refactor blocking-loop implementation (seed commit) on the toy objective:
every strategy's full journal (key, value, af) for budget=40 at seeds 0/1.
``batch_size=1, workers=1`` must reproduce them bit-for-bit.
"""
import json
import math
import os
import time

import numpy as np
import pytest

from repro.core.engine import ParallelTuningEngine
from repro.core.gp import GP
from repro.core.gp_fast import IncrementalGP
from repro.core.objectives import Objective, SimulatedObjective
from repro.core.runner import run_strategy
from repro.core.searchspace import Param, SearchSpace
from repro.core.strategies import make_strategy
from repro.core.strategies.base import Proposal, Strategy, StrategyContext
from repro.store.records import TuningRecordStore

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "seed_traces.json")


def _toy_objective(seed=0, n=400, invalid_frac=0.2):
    """Must stay identical to the objective the golden traces were captured
    on (test_strategies._toy_objective at the seed commit)."""
    rng = np.random.default_rng(seed)
    space = SearchSpace([Param("a", tuple(range(20))),
                         Param("b", tuple(range(20)))], name="toy")
    x = space.X_norm
    times = 1.0 + 5 * ((x[:, 0] - 0.3) ** 2 + (x[:, 1] - 0.7) ** 2) \
        + 0.3 * np.sin(7 * x[:, 0]) * np.cos(5 * x[:, 1])
    inv = rng.choice(n, int(invalid_frac * n), replace=False)
    times = times.astype(np.float64)
    times[inv] = math.nan
    return SimulatedObjective(space, times, name="toy")


class SlowObjective(Objective):
    """Per-eval sleep: models the expensive compile-and-run step."""

    def __init__(self, inner: Objective, delay_s: float):
        self.inner, self.delay_s = inner, delay_s
        self.space, self.name = inner.space, "slow_" + inner.name

    def __call__(self, idx: int) -> float:
        time.sleep(self.delay_s)
        return self.inner(idx)


class DyingObjective(Objective):
    """Raises after k evaluations — simulates a run killed mid-batch."""

    def __init__(self, inner: Objective, k: int):
        self.inner, self.k, self.count = inner, k, 0
        self.space, self.name = inner.space, inner.name

    def __call__(self, idx: int) -> float:
        self.count += 1
        if self.count > self.k:
            raise RuntimeError("killed")
        return self.inner(idx)


# ---------------------------------------------------------------------------
# golden sequential parity (acceptance: batch_size=1 == seed sequential)
# ---------------------------------------------------------------------------
with open(GOLDEN) as f:
    _GOLDEN = json.load(f)


@pytest.mark.parametrize("case", sorted(_GOLDEN))
def test_batch1_reproduces_seed_sequential_exactly(case):
    strat, seed = case.rsplit(":", 1)
    res = run_strategy(make_strategy(strat), _toy_objective(), budget=40,
                       seed=int(seed))
    got = [[o.key, None if not math.isfinite(o.value) else o.value, o.af]
           for o in res.journal]
    assert got == _GOLDEN[case]["journal"], f"{case}: journal diverged"
    got_trace = [None if not math.isfinite(v) else v for v in res.trace]
    assert got_trace == _GOLDEN[case]["trace"], f"{case}: best_trace diverged"
    assert res.unique_evals == _GOLDEN[case]["unique_evals"]


# ---------------------------------------------------------------------------
# batching / parallelism
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("strat", ["ei", "advanced_multi", "random",
                                   "genetic_algorithm"])
def test_batched_parallel_run_is_valid(strat):
    """workers>1 keeps every invariant: budget, unique journal keys, a best
    value no worse than random luck allows on this easy space."""
    obj = _toy_objective()
    res = run_strategy(make_strategy(strat), obj, budget=48, seed=0,
                       batch_size=8, workers=8)
    assert res.unique_evals <= 48
    keys = [o.key for o in res.journal]
    assert len(keys) == len(set(keys)), "re-evaluated a config"
    assert math.isfinite(res.best_value)
    assert len(res.worker_stats) > 1, "work never fanned out"


def test_bo_batch_suggest_distinct_and_rolled_back():
    """suggest(n) returns n distinct configs and leaves the GP untouched."""
    obj = _toy_objective()
    strat = make_strategy("ei")
    rng = np.random.default_rng(0)
    strat.reset(StrategyContext(space=obj.space, budget=40, rng=rng))
    # drive through init sequentially
    while True:
        props = strat.suggest(1)
        assert props, "init phase never ended"
        strat.observe(props[0], obj(props[0].idx))
        if strat._phase == "bo":
            break
    t_before = strat.gp.gp.t
    batch = strat.suggest(6)
    assert len(batch) == 6
    idxs = [p.idx for p in batch]
    assert len(set(idxs)) == 6, "constant-liar batch suggested duplicates"
    assert strat.gp.gp.t == t_before, "fantasy observations not rolled back"
    # async ask without tell: the next ask must avoid in-flight configs
    more = strat.suggest(4)
    assert not (set(p.idx for p in more) & set(idxs))


def test_throughput_workers_beat_sequential():
    """Sleep-dominated objective: 8 workers ≳ 4× faster than 1 (the engine
    acceptance bar; the full-size version lives in benchmarks/engine_bench)."""
    obj = SlowObjective(_toy_objective(), 0.01)
    t0 = time.time()
    r1 = run_strategy(make_strategy("random"), obj, budget=32, seed=0)
    t_seq = time.time() - t0
    t0 = time.time()
    r8 = run_strategy(make_strategy("random"), obj, budget=32, seed=0,
                      batch_size=8, workers=8)
    t_par = time.time() - t0
    assert r1.unique_evals == r8.unique_evals == 32
    assert [o.key for o in r1.journal] == [o.key for o in r8.journal]
    assert t_seq / t_par >= 2.5, f"only {t_seq / t_par:.1f}x"


def test_process_backend_matches_thread():
    obj = _toy_objective()   # picklable: no lambda restrictions
    res_p = run_strategy(make_strategy("random"), obj, budget=24, seed=0,
                         batch_size=8, workers=2, backend="process")
    res_s = run_strategy(make_strategy("random"), obj, budget=24, seed=0)
    assert [o.key for o in res_p.journal] == [o.key for o in res_s.journal]
    assert all(w.startswith("pid-") for w in res_p.worker_stats)


def test_process_backend_refused_on_tpu_host(monkeypatch):
    """Worker processes would each open the parent's chip: the engine
    refuses them up front on a TPU backend instead of hanging."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="belongs to one process"):
        ParallelTuningEngine(_toy_objective(), 8, batch_size=2, workers=2,
                             backend="process")
    ParallelTuningEngine(_toy_objective(), 8, batch_size=2, workers=2,
                         backend="thread")


def test_max_in_flight_caps_concurrency():
    class Gauge(Objective):
        def __init__(self, inner):
            self.inner, self.space, self.name = inner, inner.space, inner.name
            self.live, self.peak = 0, 0
            import threading
            self.lock = threading.Lock()

        def __call__(self, idx):
            with self.lock:
                self.live += 1
                self.peak = max(self.peak, self.live)
            time.sleep(0.002)
            with self.lock:
                self.live -= 1
            return self.inner(idx)

    gauge = Gauge(_toy_objective())
    eng = ParallelTuningEngine(gauge, 32, batch_size=8, workers=8,
                               max_in_flight=3)
    eng.run(make_strategy("random"), seed=0)
    assert gauge.peak <= 3


def test_per_worker_budget_accounting():
    obj = SlowObjective(_toy_objective(), 0.003)
    res = run_strategy(make_strategy("random"), obj, budget=32, seed=0,
                       batch_size=8, workers=4)
    assert sum(w["n_evals"] for w in res.worker_stats.values()) == 32
    assert all(w["busy_s"] > 0 for w in res.worker_stats.values())
    assert all(o.dur > 0 for o in res.journal)


# ---------------------------------------------------------------------------
# checkpoint/resume mid-batch (acceptance: lossless with workers > 1)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("strat", ["ei", "genetic_algorithm"])
def test_checkpoint_resume_mid_batch_with_workers(tmp_path, strat):
    obj = _toy_objective()
    ck = str(tmp_path / "ck.json")
    with pytest.raises(RuntimeError):
        run_strategy(make_strategy(strat), DyingObjective(obj, 17), budget=40,
                     seed=0, checkpoint_path=ck, batch_size=4, workers=4)
    recorded = TuningRecordStore(ck).records()
    assert 0 < len(recorded) <= 17, "journal not an evaluation-prefix"
    res = run_strategy(make_strategy(strat), obj, budget=40, seed=0,
                       checkpoint_path=ck, resume=True, batch_size=4,
                       workers=4)
    assert res.unique_evals == 40
    keys = [o.key for o in res.journal]
    assert len(keys) == len(set(keys)), "resume re-evaluated a config"
    # the checkpointed prefix survived verbatim
    assert [o.key for o in res.journal[:len(recorded)]] \
        == [r.key for r in recorded]


def test_journal_order_deterministic_under_parallelism():
    """Ordered journal writes: completion order may scramble, acceptance
    order may not."""
    obj = _toy_objective()
    runs = [run_strategy(make_strategy("random"), obj, budget=32, seed=0,
                         batch_size=8, workers=8) for _ in range(2)]
    assert [o.key for o in runs[0].journal] == [o.key for o in runs[1].journal]


# ---------------------------------------------------------------------------
# speculative GP add/rollback
# ---------------------------------------------------------------------------
def test_incremental_gp_rollback_exact():
    rng = np.random.default_rng(0)
    Xc = rng.random((80, 3))
    g = IncrementalGP(Xc, max_obs=16, ell=1.5)
    for i in range(5):
        g.add(Xc[i], float(rng.normal()))
    mu0, sd0 = g.predict()
    ssq0 = g.ssq.copy()
    g.mark()
    for i in range(5, 9):
        g.add(Xc[i], float(rng.normal()))
    assert g.t == 9
    g.rollback()
    assert g.t == 5
    mu1, sd1 = g.predict()
    np.testing.assert_array_equal(mu0, mu1)   # exact, not approximate
    np.testing.assert_array_equal(sd0, sd1)
    np.testing.assert_array_equal(ssq0, g.ssq)
    # the slot is reusable after rollback
    g.add(Xc[20], 1.0)
    assert g.t == 6


def test_jax_gp_rollback_exact():
    rng = np.random.default_rng(1)
    Xc = rng.random((40, 3)).astype(np.float32)
    g = GP(3, max_obs=16, ell=1.5)
    for i in range(4):
        g.add(Xc[i], float(rng.normal()))
    mu0, sd0 = g.predict(Xc)
    g.mark()
    g.add(Xc[10], 5.0)
    g.add(Xc[11], -5.0)
    g.rollback()
    assert g.n == 4
    mu1, sd1 = g.predict(Xc)
    np.testing.assert_array_equal(np.asarray(mu0), np.asarray(mu1))
    np.testing.assert_array_equal(np.asarray(sd0), np.asarray(sd1))


def test_rollback_without_mark_is_noop():
    rng = np.random.default_rng(2)
    Xc = rng.random((20, 2))
    g = IncrementalGP(Xc, max_obs=8, ell=2.0)
    g.add(Xc[0], 1.0)
    g.rollback()
    assert g.t == 1


# ---------------------------------------------------------------------------
# engine bookkeeping edge cases
# ---------------------------------------------------------------------------
def test_engine_stops_on_strategy_exhaustion():
    """Random search on a tiny space: strategy runs dry before the budget."""
    space = SearchSpace([Param("a", (1, 2, 3))], name="tiny")
    obj = SimulatedObjective(space, np.array([3.0, 1.0, 2.0]))
    res = run_strategy(make_strategy("random"), obj, budget=50, seed=0,
                       batch_size=4, workers=2)
    assert res.unique_evals == 3
    assert res.best_value == 1.0


def test_engine_budget_counts_in_flight():
    """Dispatching a full batch near the budget edge must not overshoot."""
    obj = SlowObjective(_toy_objective(), 0.002)
    res = run_strategy(make_strategy("random"), obj, budget=10, seed=0,
                       batch_size=8, workers=8)
    assert res.unique_evals == 10


def test_outside_space_proposals_consume_budget_in_engine():
    space = SearchSpace([Param("a", (1, 2, 4, 8)), Param("b", (1, 2, 4, 8))],
                        [lambda c: c["a"] * c["b"] <= 8], name="constrained")
    times = np.linspace(1, 2, space.size)
    obj = SimulatedObjective(space, times)
    res = run_strategy(make_strategy("bayesopt_ucb"), obj, budget=30, seed=0)
    outside = [o for o in res.journal if o.idx is None]
    assert len(outside) > 0
    assert all(not math.isfinite(o.value) for o in outside)
