"""Runtime substrate sanitizer (repro.analysis.sanitizer; DESIGN.md §13).

* armed pools/engines pass untouched on clean workloads (the wrappers
  change nothing but the checking);
* each injected corruption class — counter drift, heap staleness, live-id
  desync, ledger imbalance, non-finite outputs — raises SanitizerError;
* the retire-under-load stress: the lazily-invalidated spread heap and
  the O(1) total_in_flight counter must agree with their O(n)
  recomputations after every retire() in a randomized take/release/retire
  storm (the full-check-after-retire path);
* the env gate: REPRO_SANITIZE unset/0 attaches nothing.
"""
import dataclasses

import numpy as np
import pytest

from repro.analysis import sanitizer
from repro.analysis.sanitizer import (
    SanitizerError,
    attach_engine,
    attach_pool,
    check_engine_conservation,
    check_finite,
    check_open_loop,
    check_pool,
    check_telemetry_readonly,
)
from repro.core.lifecycle import FunctionInstance, InstanceState
from repro.core.policy import MinosPolicy
from repro.core.substrate import InstancePool
from repro.sim import (
    FaaSPlatform,
    FunctionSpec,
    PlatformProfile,
    VariationModel,
)
from repro.sim.arrivals import PoissonProcess, run_open_loop

SPEC = FunctionSpec(name="sanitize", prepare_ms=80.0, body_ms=150.0,
                    benchmark_ms=40.0, cold_start_ms=60.0)
VM = VariationModel(sigma=0.2)
PROFILE = PlatformProfile.gcf_gen1()


def _warm(pool, speed=1.0, now=0.0):
    inst = FunctionInstance(speed_factor=speed, created_at_ms=now,
                            idle_timeout_ms=1e9)
    inst.state = InstanceState.WARM
    inst.last_used_ms = now
    pool.add_warm(inst)
    return inst


def _policy():
    return MinosPolicy(elysium_threshold=float("inf"), enabled=False)


def _platform(*, seed=0, max_instances=3, queue_capacity=None):
    knobs = dataclasses.replace(PROFILE.knobs(), max_instances=max_instances,
                                queue_capacity=queue_capacity)
    return FaaSPlatform(SPEC, VM, _policy(), seed=seed, profile=PROFILE,
                        knobs=knobs)


# ---------------------------------------------------------------------------
# Env gate
# ---------------------------------------------------------------------------


def test_enabled_gates_on_env(monkeypatch):
    monkeypatch.delenv(sanitizer.ENV_VAR, raising=False)
    assert not sanitizer.enabled()
    monkeypatch.setenv(sanitizer.ENV_VAR, "0")
    assert not sanitizer.enabled()
    monkeypatch.setenv(sanitizer.ENV_VAR, "1")
    assert sanitizer.enabled()


def test_engine_not_armed_by_default(monkeypatch):
    monkeypatch.delenv(sanitizer.ENV_VAR, raising=False)
    engine = _platform()
    assert not getattr(engine, "_sanitizer_armed", False)


def test_engine_armed_under_env(monkeypatch):
    monkeypatch.setenv(sanitizer.ENV_VAR, "1")
    engine = _platform()
    assert engine._sanitizer_armed
    assert engine.pool._sanitizer_armed


# ---------------------------------------------------------------------------
# Pool checks
# ---------------------------------------------------------------------------


def test_clean_pool_lifecycle_passes():
    pool = InstancePool(order="spread", concurrency=2)
    attach_pool(pool)
    insts = [_warm(pool) for _ in range(4)]
    taken = [pool.take(0.0) for _ in range(5)]
    for inst in taken:
        assert inst is not None
        pool.release(inst, 1.0)
    pool.retire(insts[0])
    check_pool(pool)
    assert pool.total_in_flight == 0


def test_corrupted_in_flight_counter_raises():
    pool = InstancePool(order="lifo")
    _warm(pool)
    pool.take(0.0)
    pool._in_flight += 1  # inject counter drift
    with pytest.raises(SanitizerError, match="_in_flight diverged"):
        check_pool(pool)


def test_corrupted_live_ids_raises():
    pool = InstancePool(order="lifo")
    _warm(pool)
    pool._live_ids.add(999_999)
    with pytest.raises(SanitizerError, match="_live_ids"):
        check_pool(pool)


def test_duplicate_available_entry_raises():
    pool = InstancePool(order="lifo")
    inst = _warm(pool)
    pool.available.append(inst)  # bypass the API (the forbidden mutation)
    with pytest.raises(SanitizerError, match="duplicate|_avail_seq"):
        check_pool(pool)


def test_stale_spread_heap_raises():
    pool = InstancePool(order="spread", concurrency=4)
    a, b = _warm(pool), _warm(pool)
    pool.take(0.0)  # loads a (FIFO tie-break); b (load 0) is now the argmin
    # corrupt the latest-push marker for b: every heap entry naming the
    # true argmin goes stale, so the heap would serve a instead of b
    pool._spread_latest[b.instance_id] = -1
    with pytest.raises(SanitizerError, match="spread heap"):
        check_pool(pool)


def test_armed_pool_catches_corruption_at_retire():
    pool = InstancePool(order="spread", concurrency=2)
    attach_pool(pool)
    insts = [_warm(pool) for _ in range(3)]
    pool._in_flight = 7  # drift injected between mutator calls
    with pytest.raises(SanitizerError, match="_in_flight"):
        pool.retire(insts[-1])


def test_retire_under_load_stress():
    """Satellite check: the lazily-invalidated spread heap and the O(1)
    total_in_flight stay equal to their O(n) recomputes after retire()
    under a randomized take/release/retire storm. attach_pool runs the
    full structural check after every retire."""
    rng = np.random.RandomState(42)
    pool = InstancePool(order="spread", concurrency=3,
                        recycle_lifetime_ms=50_000.0,
                        rng=np.random.RandomState(7))
    attach_pool(pool)
    held = []
    for step in range(600):
        op = rng.rand()
        if op < 0.45:
            inst = pool.take(float(step))
            if inst is not None:
                held.append(inst)
            elif len(pool._live_ids) < 12:
                held.append(_warm(pool, now=float(step)))
                pool.take(float(step))
        elif op < 0.85 and held:
            pool.release(held.pop(rng.randint(len(held))), float(step))
        elif held:
            # retire the engine way: only at load 1 (pool invariant)
            solo = [i for i in held if pool.load(i) == 1]
            if solo:
                victim = solo[rng.randint(len(solo))]
                held.remove(victim)
                pool.retire(victim)  # full check fires here
        assert pool.total_in_flight == sum(pool._active.values())
    for inst in held:
        pool.release(inst, 1e6)
    check_pool(pool)
    assert pool.total_in_flight == 0


# ---------------------------------------------------------------------------
# Engine ledger + telemetry
# ---------------------------------------------------------------------------


def test_armed_engine_clean_run(monkeypatch):
    monkeypatch.setenv(sanitizer.ENV_VAR, "1")
    engine = _platform(seed=3)
    done = []
    for i in range(12):
        engine.submit({"user": f"u{i}"}, done.append)
    engine.loop.run_all()
    assert len(done) == 12
    check_engine_conservation(engine, where="test")
    check_pool(engine.pool, where="test")


def test_conservation_violation_raises(monkeypatch):
    monkeypatch.setenv(sanitizer.ENV_VAR, "1")
    engine = _platform(seed=4)
    engine.submit({"user": "u"}, lambda res: None)
    engine.requests_arrived += 1  # forge an arrival with no disposition
    with pytest.raises(SanitizerError, match="conservation"):
        engine.loop.run_all()


def test_telemetry_readonly_holds_and_detects():
    engine = _platform(seed=5)
    check_telemetry_readonly(engine.telemetry)  # real view: must pass

    class Writable:
        pass

    with pytest.raises(SanitizerError, match="Telemetry accepted"):
        check_telemetry_readonly(Writable())


def test_open_loop_under_sanitizer(monkeypatch):
    monkeypatch.setenv(sanitizer.ENV_VAR, "1")
    engine = _platform(seed=6, queue_capacity=4)
    run = run_open_loop(engine, PoissonProcess(20.0),
                        rng=np.random.RandomState(0), duration_ms=20_000.0,
                        drain=True)
    assert run.n_arrived == (len(run.results) + run.n_dropped
                             + run.n_pending_at_end)


def test_check_open_loop_mismatch_raises():
    with pytest.raises(SanitizerError, match="open-loop conservation"):
        check_open_loop(n_arrived=10, n_completed=5, n_dropped=2,
                        n_pending_at_end=1)


# ---------------------------------------------------------------------------
# Output guards
# ---------------------------------------------------------------------------


def test_check_finite_passes_and_raises():
    check_finite({"ok": np.ones(3), "ints": np.arange(3)})
    with pytest.raises(SanitizerError, match="non-finite"):
        check_finite({"bad": np.array([1.0, np.nan])})
    with pytest.raises(SanitizerError, match="non-finite"):
        check_finite({"bad": np.array([np.inf])})


def test_vectorized_summary_guard(monkeypatch):
    monkeypatch.setenv(sanitizer.ENV_VAR, "1")
    from repro.sim.vectorized import arm_from_spec, simulate_arms, stack_arms

    arm = arm_from_spec(SPEC, VM, profile=PROFILE, gate="off")
    res = simulate_arms(stack_arms([arm]), seeds=[0], n_steps=64,
                        pool_size=4)
    assert np.isfinite(res.summary["mean_latency_ms"]).all()


@pytest.mark.parametrize("lane,mutation,match", [
    (0, {"bill_n": (0, 1, 0)}, "passes \\+ reuses != completed"),
    (1, {"n_started": 3.0}, "terminations \\+ passes != started"),
])
def test_check_closed_summary_raises(lane, mutation, match):
    """A double-billed request or an instance lost between start and probe
    breaks the closed-loop scan's per-lane conservation."""
    summary = {"bill_n": np.array([[[1.0, 3.0, 5.0], [0.0, 3.0, 5.0]]]),
               "n_completed": np.full((1, 2), 8.0),
               "n_started": np.array([[4.0, 3.0]])}
    sanitizer.check_closed_summary(summary)  # consistent ledger passes
    key, value = next(iter(mutation.items()))
    bad = {k: v.copy() for k, v in summary.items()}
    bad[key][0, lane] = bad[key][0, lane] + np.asarray(value)
    with pytest.raises(SanitizerError, match=match):
        sanitizer.check_closed_summary(bad)


# ---------------------------------------------------------------------------
# Fleet conservation ledger (repro.fleet; DESIGN.md §14)
# ---------------------------------------------------------------------------

_FLEET_OK = dict(
    n_arrived=10, n_completed=7, n_dropped=1, n_pending=2,
    n_hedges=3, n_hedge_dropped=1, n_hedge_cancelled=2,
    per_fleet_arrived=(8, 5), per_fleet_completed=(6, 3),
    per_fleet_dropped=(1, 1), per_fleet_parked=(1, 1))


def test_fleet_conservation_accepts_consistent_ledger():
    sanitizer.check_fleet_conservation(**_FLEET_OK)


@pytest.mark.parametrize("mutation,match", [
    ({"n_pending": 3}, "logical conservation"),
    # one extra engine arrival nobody logged: the double-dispatch shape
    ({"per_fleet_arrived": (9, 5), "per_fleet_parked": (2, 1)},
     "double dispatch"),
    ({"n_hedge_cancelled": 1}, "completion ledger"),
    ({"n_hedge_dropped": 0}, "drop ledger"),
    ({"per_fleet_parked": (0, 1)}, "per-fleet conservation"),
])
def test_fleet_conservation_raises_on_each_imbalance(mutation, match):
    bad = dict(_FLEET_OK)
    bad.update(mutation)
    with pytest.raises(SanitizerError, match=match):
        sanitizer.check_fleet_conservation(**bad)


def test_fleet_run_checks_ledger_when_armed(monkeypatch):
    monkeypatch.setenv(sanitizer.ENV_VAR, "1")
    from repro.core.policy import MinosPolicy as _MP
    from repro.fleet import (FleetRouter, FleetSpec, RandomRoutingPolicy,
                             run_fleet_open_loop)
    from repro.sim.arrivals import PoissonProcess

    fleets = [
        FleetSpec(name=f"s{i}", spec=SPEC, variation=VM, profile=PROFILE,
                  knobs=dataclasses.replace(PROFILE.knobs(),
                                            max_instances=2),
                  policy=_MP(elysium_threshold=float("inf"),
                             enabled=False))
        for i in range(2)
    ]
    router = FleetRouter(fleets, RandomRoutingPolicy(), seed=0,
                         hedge_after_ms=800.0)
    run = run_fleet_open_loop(router, PoissonProcess(2.0),
                              rng=np.random.RandomState(4),
                              duration_ms=15_000.0)
    assert run.n_arrived == run.n_completed + run.n_dropped \
        + run.n_pending_at_end
