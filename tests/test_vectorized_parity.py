"""Statistical parity: the vectorized fast path vs the event engine
(DESIGN.md §11; sim/vectorized.py).

The jitted scan is a *fast path for parameter exploration*, not a
replacement — golden digests stay on the event engine — so what it must
prove is distributional agreement on the scenarios it models: closed-loop
VU streams (single- and multi-stream; see also
tests/test_multistream_vectorized.py) and open-loop Poisson arrivals
against a capped supply (second half of this file). Both engines run the
SAME config (spec, profile, threshold, think time) on pinned seeds; the
checks are the ISSUE's bounds:

* two-sample KS on per-request analysis / latency / billed-duration
  distributions,
* gated-vs-baseline mean speedup within ±1pp,
* probe pass-rate within ±2pp,

on the gcf-gen1 / gcf-gen2 / lambda platform profiles. A skip-marked slow
variant sweeps a fuller grid.
"""
import dataclasses
import math
import os

import numpy as np
import pytest
from scipy import stats
from scipy.stats import ks_2samp

from repro.core.policy import AdaptiveMinosPolicy, MinosPolicy
from repro.sim import FaaSPlatform, FunctionSpec, PlatformProfile, VariationModel
from repro.sim.arrivals import PoissonProcess, run_open_loop
from repro.sim.vectorized import (
    arm_from_spec,
    jit_stats,
    run_event_chain,
    simulate_arms,
    simulate_open_arms,
    stack_arms,
)

# Churny config: recycle every ~8 s keeps cold probes flowing, so the
# pass-rate estimate has real sample mass on both sides.
SPEC = FunctionSpec(
    name="parity", prepare_ms=600.0, body_ms=1500.0, benchmark_ms=300.0,
    cold_start_ms=250.0, recycle_lifetime_ms=8_000.0, contention_rho=0.95,
    benchmark_noise=0.08,
)
VM = VariationModel(sigma=0.15)
THINK_MS = 500.0
N_REQUESTS = 600
# The probe pass rate varies by ~0.026 per seed in both engines (measured
# over 100 event / 300 scan seeds per cell); 40 seeds each put the ±2pp
# bound at ~3.4 standard errors of the engines' gap.
EVENT_SEEDS = range(40)
VEC_SEEDS = range(40)
GATES = ("off", "fixed", "adaptive")

# analytic f=0.4 probe-duration quantile (probes are lognormal with
# log-std sqrt(sigma^2 + noise^2)); both engines judge against this number
THRESHOLD = SPEC.benchmark_ms * math.exp(
    stats.norm.ppf(0.4) * math.sqrt(VM.sigma ** 2 + SPEC.benchmark_noise ** 2))


def _profile(name: str) -> PlatformProfile:
    prof = {"gcf-gen1": PlatformProfile.gcf_gen1,
            "gcf-gen2": PlatformProfile.gcf_gen2,
            "lambda": PlatformProfile.aws_lambda}[name]()
    return dataclasses.replace(prof, recycle_lifetime_ms=8_000.0)


def _policy(gate: str):
    if gate == "off":
        return MinosPolicy(elysium_threshold=float("inf"), enabled=False)
    if gate == "fixed":
        return MinosPolicy(elysium_threshold=THRESHOLD, max_retries=5)
    return AdaptiveMinosPolicy(0.4, max_retries=5)


def _pooled_pass_rate(res, arm: int) -> float:
    """1 - terminations / probes over all seeds: the event engine's pooled
    estimator. The mean of per-seed rates differs from it where seeds see
    few probes, since seeds that fail more probes also probe more."""
    term = np.asarray(res.summary["n_terminated"][arm]).sum()
    return 1.0 - float(term) / max(float(np.asarray(res.summary["n_probes"][arm]).sum()), 1.0)


@pytest.fixture(scope="module")
def runs():
    """Both engines over (3 profiles × 3 gates), computed once."""
    event = {}
    for pname in ("gcf-gen1", "gcf-gen2", "lambda"):
        for gate in GATES:
            an, lat, nterm, nprobe = [], [], 0, 0
            billed_ms = cost = 0.0
            for seed in EVENT_SEEDS:
                plat = FaaSPlatform(SPEC, VM, _policy(gate), seed=seed,
                                    profile=_profile(pname))
                rs = run_event_chain(plat, N_REQUESTS, THINK_MS)
                an += [r.analysis_ms for r in rs]
                lat += [r.latency_ms for r in rs]
                nterm += plat.instances_terminated
                nprobe += len(plat.benchmark_observations)
                c = plat.cost
                billed_ms += c.d_term_ms + c.d_pass_ms + c.d_reuse_ms
                cost += c.total
            n_req = len(list(EVENT_SEEDS)) * N_REQUESTS
            event[(pname, gate)] = {
                "analysis": np.asarray(an), "latency": np.asarray(lat),
                "pass_rate": 1.0 - nterm / max(nprobe, 1),
                "billed_mean": billed_ms / n_req,
                "cost_per_req": cost / n_req,
            }
    arms, keys = [], []
    for pname in ("gcf-gen1", "gcf-gen2", "lambda"):
        for gate in GATES:
            arms.append(arm_from_spec(
                SPEC, VM, profile=_profile(pname), gate=gate,
                threshold=THRESHOLD, pass_fraction=0.4,
                think_time_ms=THINK_MS))
            keys.append((pname, gate))
    res = simulate_arms(stack_arms(arms), seeds=VEC_SEEDS,
                        n_steps=N_REQUESTS, collect_requests=True)
    vec = {}
    for i, key in enumerate(keys):
        vec[key] = {
            "analysis": res.requests["analysis_ms"][i].ravel(),
            "latency": res.requests["latency_ms"][i].ravel(),
            "billed": res.requests["billed_ms"][i].ravel(),
            "pass_rate": _pooled_pass_rate(res, i),
            "cost_per_req": float(res.summary["cost"][i].mean()) / N_REQUESTS,
        }
    return event, vec


PROFILES = ("gcf-gen1", "gcf-gen2", "lambda")


@pytest.mark.parametrize("pname", PROFILES)
@pytest.mark.parametrize("gate", GATES)
def test_ks_duration_distributions(runs, pname, gate):
    """Per-request analysis & latency distributions agree (two-sample KS).

    The bound is on the KS *statistic* D, not its p-value: requests within
    one run are autocorrelated (a warm chain shares its instance's drifted
    speed), so the iid p-value is wildly anti-conservative — across seed
    partitions of a single engine D itself fluctuates in ~[0.01, 0.04].
    D < 0.05 holds for matching models and fails decisively for real
    modeling errors (e.g. mis-billed cold starts shift D by >0.1). Pinned
    seeds make the check deterministic."""
    event, vec = runs
    for field in ("analysis", "latency"):
        ks = ks_2samp(event[(pname, gate)][field], vec[(pname, gate)][field])
        assert ks.statistic < 0.05, (pname, gate, field, ks)


@pytest.mark.parametrize("pname", PROFILES)
@pytest.mark.parametrize("gate", GATES)
def test_billed_duration_and_cost(runs, pname, gate):
    """Fig-3 billing agrees: terminations billed startup+probe, passes
    cold(+)ready+body, reuses duration only. The event engine exposes
    billing as per-run WorkflowCost totals (not per-request), so the
    cross-engine check is on mean billed ms per request and mean $ per
    request; per-request coherence (billed never exceeds latency — the
    requeue overhead is unbilled wait) is asserted on the vec stream."""
    event, vec = runs
    ev, v = event[(pname, gate)], vec[(pname, gate)]
    assert np.all(v["billed"] <= v["latency"] + 1e-3)
    vec_billed_mean = float(v["billed"].mean())
    assert vec_billed_mean == pytest.approx(ev["billed_mean"], rel=0.02), \
        (pname, gate, ev["billed_mean"], vec_billed_mean)
    assert v["cost_per_req"] == pytest.approx(
        ev["cost_per_req"], rel=0.02), (pname, gate)


@pytest.mark.parametrize("pname", PROFILES)
@pytest.mark.parametrize("gate", ("fixed", "adaptive"))
def test_pass_rate_within_2pp(runs, pname, gate):
    event, vec = runs
    d = abs(event[(pname, gate)]["pass_rate"] - vec[(pname, gate)]["pass_rate"])
    assert d < 0.02, (pname, gate, event[(pname, gate)]["pass_rate"],
                      vec[(pname, gate)]["pass_rate"])


@pytest.mark.parametrize("pname", PROFILES)
@pytest.mark.parametrize("gate", ("fixed", "adaptive"))
def test_mean_speedup_within_1pp(runs, pname, gate):
    """Gated-vs-baseline analysis improvement matches across engines."""
    event, vec = runs
    imp_ev = 1.0 - (event[(pname, gate)]["analysis"].mean()
                    / event[(pname, "off")]["analysis"].mean())
    imp_vec = 1.0 - (vec[(pname, gate)]["analysis"].mean()
                     / vec[(pname, "off")]["analysis"].mean())
    assert abs(imp_ev - imp_vec) < 0.01, (pname, gate, imp_ev, imp_vec)


def test_jit_cache_hits_on_same_shape(runs):
    """A second batch with identical static shape must not recompile."""
    arms = stack_arms([
        arm_from_spec(SPEC, VM, profile=_profile("gcf-gen1"), gate=g,
                      threshold=THRESHOLD, think_time_ms=THINK_MS)
        for g in GATES])
    simulate_arms(arms, seeds=range(2), n_steps=50)
    before = jit_stats["compiles"]
    simulate_arms(arms, seeds=range(2), n_steps=50)
    assert jit_stats["compiles"] == before


def test_seeded_determinism(runs):
    """Identical (arms, seeds) produce bit-identical summaries."""
    arms = stack_arms([
        arm_from_spec(SPEC, VM, profile=_profile("gcf-gen1"), gate="fixed",
                      threshold=THRESHOLD, think_time_ms=THINK_MS)])
    a = simulate_arms(arms, seeds=[7], n_steps=80)
    b = simulate_arms(arms, seeds=[7], n_steps=80)
    for k in a.summary:
        np.testing.assert_array_equal(a.summary[k], b.summary[k])


# ---------------------------------------------------------------------------
# Open-loop parity: both engines consume Poisson arrivals at the same offered
# rate against the same K-instance supply cap and must agree on the resulting
# latency (wait + service) distribution — i.e. the queueing physics, not just
# the per-request service model, matches.
#
# Model note (DESIGN.md §12): a failed probe frees its server slot at judge
# time in BOTH engines. The vec scan parks the gated request in a retry ring
# (ready at probe_end + requeue overhead) and drains up to
# `drains_per_step` matured retries before each arrival's own dispatch, so
# retries keep their FIFO priority over later arrivals exactly as the event
# queue's (enqueued_at, seq) ordering grants it. At the default drain budget
# the measured gated P99 gap is < 1% (the earlier atomic-retry-chain model,
# which held the slot through the whole crash chain, sat at ~5–12%), so one
# 5% P99 bound applies to every cell. Scan rows are (drains..., arrival) per
# step; only rows flagged `completed` carry a finished request — consumers
# MUST mask, the rest is ring padding / drops / defers.
# ---------------------------------------------------------------------------

OPEN_RATE_PER_S = 0.9     # offered load; with K=4 and ~2.1 s service, rho≈0.55
OPEN_SERVERS = 4
OPEN_DURATION_MS = 400_000.0
OPEN_STEPS = 360          # ≈ rate × duration arrivals per vec seed
OPEN_EVENT_SEEDS = range(8)
OPEN_VEC_SEEDS = range(16)
OPEN_PROFILES = ("gcf-gen1", "lambda")
OPEN_GATES = ("off", "fixed")


@pytest.fixture(scope="module")
def open_runs():
    """Both engines over (2 profiles × 2 gates) open-loop, computed once.

    All four vec arms stack into ONE simulate_open_arms call so the scan
    compiles once; the event side is 8 capped-supply runs per cell."""
    event = {}
    for pname in OPEN_PROFILES:
        for gate in OPEN_GATES:
            lat, nterm, nprobe, n_req = [], 0, 0, 0
            billed_ms = 0.0
            for seed in OPEN_EVENT_SEEDS:
                prof = _profile(pname)
                knobs = dataclasses.replace(
                    prof.knobs(), max_instances=OPEN_SERVERS)
                plat = FaaSPlatform(SPEC, VM, _policy(gate), seed=seed,
                                    profile=prof, knobs=knobs)
                run = run_open_loop(
                    plat, PoissonProcess(OPEN_RATE_PER_S),
                    rng=np.random.RandomState(1000 + seed),
                    duration_ms=OPEN_DURATION_MS)
                # nothing is ever lost at rho≈0.55 with an uncapped queue
                assert run.n_arrived == (run.n_completed + run.n_dropped
                                         + run.n_pending_at_end)
                assert run.n_dropped == 0 and run.n_pending_at_end == 0
                lat += [r.latency_ms for r in run.results]
                nterm += plat.instances_terminated
                nprobe += len(plat.benchmark_observations)
                c = plat.cost
                billed_ms += c.d_term_ms + c.d_pass_ms + c.d_reuse_ms
                n_req += run.n_completed
            event[(pname, gate)] = {
                "latency": np.asarray(lat),
                "pass_rate": 1.0 - nterm / max(nprobe, 1),
                "billed_mean": billed_ms / n_req,
            }
    arms, keys = [], []
    for pname in OPEN_PROFILES:
        for gate in OPEN_GATES:
            arms.append(arm_from_spec(
                SPEC, VM, profile=_profile(pname), gate=gate,
                threshold=THRESHOLD, think_time_ms=0.0))
            keys.append((pname, gate))
    proc = PoissonProcess(OPEN_RATE_PER_S)
    iats = np.stack([proc.iats_ms(np.random.RandomState(5000 + i), OPEN_STEPS)
                     for i in OPEN_VEC_SEEDS])
    res = simulate_open_arms(stack_arms(arms), seeds=OPEN_VEC_SEEDS,
                             iats_ms=iats, n_servers=OPEN_SERVERS,
                             collect_requests=True)
    vec = {}
    for i, key in enumerate(keys):
        # in-scan conservation, per seed and exact: every arrival either
        # completed, dropped, or is still parked when the horizon ends
        np.testing.assert_array_equal(
            np.asarray(res.summary["n_requests"][i]),
            np.asarray(res.summary["n_completed"][i])
            + np.asarray(res.summary["n_dropped"][i])
            + np.asarray(res.summary["n_parked_end"][i]))
        comp = np.asarray(res.requests["completed"][i]).astype(bool)
        vec[key] = {
            "latency": np.asarray(res.requests["latency_ms"][i])[comp],
            "billed": np.asarray(res.requests["billed_ms"][i])[comp],
            "wait": np.asarray(res.requests["wait_ms"][i])[comp],
            "pass_rate": float(res.summary["pass_rate"][i].mean()),
        }
    return event, vec


@pytest.mark.parametrize("pname", OPEN_PROFILES)
@pytest.mark.parametrize("gate", OPEN_GATES)
def test_open_loop_ks_latency(open_runs, pname, gate):
    """End-to-end latency (wait + service) distributions agree.

    Same D-statistic bound rationale as test_ks_duration_distributions;
    measured D at these pinned seeds is 0.016–0.046."""
    event, vec = open_runs
    ks = ks_2samp(event[(pname, gate)]["latency"], vec[(pname, gate)]["latency"])
    assert ks.statistic < 0.06, (pname, gate, ks)


@pytest.mark.parametrize("pname", OPEN_PROFILES)
@pytest.mark.parametrize("gate", OPEN_GATES)
def test_open_loop_p99(open_runs, pname, gate):
    """Tail latency agrees within 5% on every cell, gated included: the
    retry-as-park drain model gives failed probes the same slot-release
    and FIFO-priority semantics as the event queue (header note above).
    Measured gaps at these pinned seeds are 0.4–4.3%."""
    event, vec = open_runs
    p99_ev = float(np.percentile(event[(pname, gate)]["latency"], 99))
    p99_v = float(np.percentile(vec[(pname, gate)]["latency"], 99))
    assert abs(p99_v - p99_ev) / p99_ev < 0.05, (pname, gate, p99_ev, p99_v)


@pytest.mark.parametrize("pname", OPEN_PROFILES)
@pytest.mark.parametrize("gate", OPEN_GATES)
def test_open_loop_billing(open_runs, pname, gate):
    """Mean billed ms per request agrees; waits are never billed."""
    event, vec = open_runs
    ev, v = event[(pname, gate)], vec[(pname, gate)]
    assert float(v["billed"].mean()) == pytest.approx(
        ev["billed_mean"], rel=0.03), (pname, gate)
    # billed covers service only: strictly less than latency whenever the
    # request waited for a slot
    waited = v["wait"] > 1e-6
    assert np.all(v["billed"][waited] < v["latency"][waited])


@pytest.mark.parametrize("pname", OPEN_PROFILES)
def test_open_loop_pass_rate_within_2pp(open_runs, pname):
    event, vec = open_runs
    d = abs(event[(pname, "fixed")]["pass_rate"]
            - vec[(pname, "fixed")]["pass_rate"])
    assert d < 0.02, (pname, event[(pname, "fixed")]["pass_rate"],
                      vec[(pname, "fixed")]["pass_rate"])


def test_open_loop_jit_cache_and_determinism(open_runs):
    """Same (arms, seeds, iats shape): no recompile, bit-identical output."""
    arms = stack_arms([arm_from_spec(
        SPEC, VM, profile=_profile("gcf-gen1"), gate="fixed",
        threshold=THRESHOLD)])
    iats = PoissonProcess(2.0).iats_ms(np.random.RandomState(3), 40)
    a = simulate_open_arms(arms, seeds=[5], iats_ms=iats, n_servers=2)
    before = jit_stats["compiles"]
    b = simulate_open_arms(arms, seeds=[5], iats_ms=iats, n_servers=2)
    assert jit_stats["compiles"] == before
    for k in a.summary:
        np.testing.assert_array_equal(a.summary[k], b.summary[k])


@pytest.mark.slow
@pytest.mark.skipif(not os.environ.get("RUN_SLOW_GRID"),
                    reason="full-grid parity sweep; set RUN_SLOW_GRID=1")
def test_full_grid_parity_slow():
    """Pass-fraction × σ grid: vec pass rates track the analytic lognormal
    quantile target and the event engine across the full grid."""
    fracs = np.linspace(0.15, 0.85, 8)
    sigmas = (0.08, 0.15, 0.22)
    arms, metas = [], []
    for s in sigmas:
        vm = VariationModel(sigma=float(s))
        for f in fracs:
            thr = SPEC.benchmark_ms * math.exp(
                stats.norm.ppf(float(f))
                * math.sqrt(s ** 2 + SPEC.benchmark_noise ** 2))
            arms.append(arm_from_spec(
                SPEC, vm, profile=_profile("gcf-gen1"), gate="fixed",
                threshold=thr, think_time_ms=THINK_MS))
            metas.append((float(s), float(f), thr))
    res = simulate_arms(stack_arms(arms), seeds=range(8), n_steps=1200)
    rates = res.mean_over_seeds("pass_rate")
    for (s, f, thr), got in zip(metas, rates):
        assert abs(got - f) < 0.04, (s, f, got)
    # spot-check three cells against the event engine
    for i in (0, len(metas) // 2, len(metas) - 1):
        s, f, thr = metas[i]
        nterm = nprobe = 0
        for seed in range(4):
            plat = FaaSPlatform(
                SPEC, VariationModel(sigma=s),
                MinosPolicy(elysium_threshold=thr, max_retries=5),
                seed=seed, profile=_profile("gcf-gen1"))
            run_event_chain(plat, 600, THINK_MS)
            nterm += plat.instances_terminated
            nprobe += len(plat.benchmark_observations)
        assert abs((1 - nterm / nprobe) - rates[i]) < 0.02, (s, f)
