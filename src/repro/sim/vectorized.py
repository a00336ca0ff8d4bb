"""Vectorized Monte-Carlo fast path for the single-stage Minos model
(DESIGN.md §11).

Every headline number in this repo comes from Monte-Carlo sweeps over the
pure-Python event engine, which runs seeds one at a time through a
heapq-callback loop — wide grids (pass-fraction × σ × platform × gate) are
unaffordable there. This module expresses the paper's *single-stage* loop —
cold start → probe → elysium gate → requeue-with-penalty → warm reuse with
AR(1) contention drift and diurnal speed, Fig-3 billing — as one
``lax.scan`` over invocation steps, ``vmap``-ed over (arms × seeds), so
thousands of parameter arms run as a single XLA program
(``benchmarks/grid_sweep.py`` measures the speedup; the parity bounds live
in tests/test_vectorized_parity.py).

Model scope — what the fast path deliberately is:

* a **closed-loop pool of ``n_streams`` request streams** (the event
  engine at ``n_vus=n_streams``): each scan step is the next stream's
  invocation driven to completion, think time between a stream's
  requests. At ``n_streams=1`` this is the paper's single-stage loop
  bit-for-bit (the original fast path); at ``n_streams>1`` pool slots
  carry live in-flight occupancy (derived each step from the stream
  completion horizons), the select tournament honors the least-loaded
  "spread" order, warm bodies pay the ``load**alpha`` self-contention
  factor, and ``gate_load_aware`` judges cold probes at the pool's live
  mean occupancy — the load-aware arms that previously fell back to the
  event engine.
* the classic decision stack only: gate off (baseline), a fixed elysium
  threshold, or the §IV adaptive policy (P² quantile + EMA republish,
  the exact :class:`~repro.core.policy.AdaptiveMinosPolicy` estimator,
  running on-device via :class:`~repro.core.estimators.P2State`).
  Workflows, serving bodies, re-probing and the other control-plane
  handlers stay on the event engine; static admission bounds and finite
  queue buffers run in-scan on the open-loop variant
  (:func:`simulate_open_arms`).
* a fixed-capacity array pool: LIFO/FIFO/spread reuse orders are gather
  indices over (validity-masked) slot arrays; idle-timeout and exponential
  recycle deadlines reclaim slots exactly where the event pool would.

On-device estimates reuse the JAX estimator states from
:mod:`repro.core.estimators`: :class:`WelfordState` folds probe /
log-probe / body / latency streams inside the scan (what
``SubstrateEngine`` maintains for Telemetry), and :class:`P2State` + EMA
maintain the adaptive threshold.

Everything is float32; latencies are accumulated as durations (never as
differences of large absolute times), so precision holds over long
horizons. Deterministic per (seed, arm index).
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitizer as _sanitizer
from repro.core.cost import Pricing
from repro.core.estimators import (
    P2State,
    WelfordState,
    p2_init,
    p2_update,
    p2_value,
    welford_init,
    welford_merge,
    welford_std,
    welford_update,
    welford_update_masked,
)

GATE_OFF = 0        # baseline arm: every instance accepted unjudged
GATE_FIXED = 1      # pre-tested elysium threshold (paper §III-A)
GATE_ADAPTIVE = 2   # §IV online threshold: P² quantile + EMA republish

ORDER_CODES = {"lifo": 0, "fifo": 1, "spread": 2}


class ArmParams(NamedTuple):
    """One parameter arm — every leaf a float32 scalar (stack arms along
    axis 0 with :func:`stack_arms` for the vmapped grid)."""

    # variation model
    sigma: Any
    day_factor: Any
    diurnal_amplitude: Any
    diurnal_phase_h: Any
    # function spec (unit-speed durations + noise scales)
    prepare_ms: Any
    prepare_jitter: Any
    body_ms: Any
    body_jitter: Any
    benchmark_ms: Any
    benchmark_noise: Any
    contention_rho: Any
    # hosting knobs
    cold_start_ms: Any
    cold_start_jitter: Any
    idle_timeout_ms: Any
    recycle_lifetime_ms: Any   # inf = never recycled
    bill_cold_start: Any       # 0.0 / 1.0
    requeue_overhead_ms: Any
    requeue_penalty_ms: Any    # backend migration penalty (sim backend: 0)
    order: Any                 # 0 lifo / 1 fifo / 2 spread (int32)
    # gate
    gate_mode: Any             # GATE_OFF / GATE_FIXED / GATE_ADAPTIVE (int32)
    threshold: Any             # fixed elysium threshold (GATE_FIXED)
    pass_fraction: Any         # adaptive quantile (GATE_ADAPTIVE)
    max_retries: Any           # emergency-exit bound (int32)
    warmup_reports: Any        # adaptive warm-up (int32)
    republish_every: Any       # adaptive EMA republish cadence (int32)
    smoothing_alpha: Any       # adaptive EMA smoothing
    # workload + pricing
    think_time_ms: Any
    cost_per_invocation: Any
    cost_per_ms: Any
    # load-aware slots (defaults reproduce the single-stream model)
    concurrency: Any = 1           # per-slot request capacity (int32)
    load_slowdown_alpha: Any = 0.0  # body pays load**alpha when load > 1
    gate_load_aware: Any = 0.0     # 1.0: judge probes at live mean load
    # open-loop loss/admission (inf = knob disabled)
    queue_capacity: Any = math.inf  # arrivals finding >= this many waiting drop
    admit_bound: Any = math.inf    # defer while in_service + waiting >= bound


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Static (compile-time) shape of one vectorized run."""

    n_steps: int
    # One slot is exact for the single-stream model: a cold start only
    # happens when NO pooled instance is valid (so every slot is dead and
    # placement reuses slot 0), and a warm serve rewrites its own slot —
    # the pool can never hold two live instances. Multi-stream runs need
    # pool_size >= n_streams (enforced by simulate_arms): at any cold
    # start the other n_streams-1 streams occupy at most n_streams-1
    # slots, so a load-0 slot — necessarily dead, else it would have
    # served warm — always exists for placement.
    pool_size: int = 1
    max_attempts: int = 6      # must exceed every arm's max_retries
    collect_requests: bool = False
    adaptive: bool = True      # False: no arm uses GATE_ADAPTIVE — skip P²
    diurnal: bool = True       # False: every arm has amplitude 0 — skip cos
    # Closed-loop virtual users sharing the slot pool (event engine's
    # n_vus). 1 keeps the original single-stream step (and its compiled
    # program) untouched; >1 switches to the slot-occupancy step.
    n_streams: int = 1


class _ColdResult(NamedTuple):
    """Outcome of the cold retry chain for one step (scalars per lane)."""

    elapsed: Any      # ms burned by failed attempts (cold+probe+requeue)
    retries: Any      # failed attempts (i32)
    log_speed: Any    # accepted instance's hidden speed (log)
    cold_ms: Any      # accepted attempt's cold-start duration
    ready_ms: Any     # max(prepare, probe) — body start offset
    analysis_ms: Any  # accepted attempt's body duration
    place_rel: Any    # accepted instance's placement time (rel. to step start)
    n_term: Any
    d_term: Any
    probe_w: WelfordState      # probe durations
    log_probe_w: WelfordState  # log probe durations (lognormal fit)
    p2: Any                    # P2State | None
    ema: Any
    ema_init: Any
    since_publish: Any
    n_probes: Any


class _Pool(NamedTuple):
    """Fixed-capacity warm pool as K tuples of per-lane scalars.

    Tuple-of-scalars instead of (K,) arrays: every pool operation
    (validity, reuse-order tournament, placement) is then an unrolled
    chain of elementwise selects, which XLA fuses into the surrounding
    step kernel — batched gathers/argmax/scatter over a (K,) axis each
    cost a separate kernel pass on CPU, and the profiler showed those
    passes dominating the sweep wall-clock.

    Multi-stream runs (``n_streams > 1``) store ``(K,)`` *arrays* in the
    same fields instead: at K = n_streams = 8 the unrolled select chains
    exploded XLA's compile time (minutes), while argmin/scatter over a
    tiny (K,) axis compiles in seconds — and the multi-stream step is
    cold-chain-dominated anyway, so the per-step gather cost is noise."""

    log_speed: Any     # log-space: AR(1) drift needs no log/exp
    last_used: Any
    recycle: Any       # absolute deadline (inf = never)
    alive: Any
    # Multi-stream only (None prunes it from single-stream carries): the
    # time a cold-placed slot finishes its first serve. Until then the
    # slot is mid-cold-start and not reusable — the event pool's
    # admit_cold instance, in flight but never yet released.
    avail_from: Any = None
    # Multi-stream only: the time the slot last ENTERED the event pool's
    # available list — the heap key's ``_avail_seq`` position rendered as
    # a timestamp. Instances enter at their first release (admit_cold)
    # and re-enter when a completion drops them back below capacity;
    # while they hover below capacity the seq is FROZEN, so load ties
    # break by a near-static priority order. That staleness is
    # load-bearing for parity: the last slot in the priority order is
    # starved of tie traffic and only receives arrivals in synchronized
    # bursts when it is strictly least-loaded — bursts co-complete, the
    # slot drains to idle, and the pool shrinks at the event engine's
    # rate. (Tie-breaking on any *recency* signal instead spreads ties
    # evenly, phase-locks the streams, and the shrink never happens —
    # measured: a 3-slot pool with zero drains over 1400 s vs the event
    # engine's one per 30–180 s.)
    avail_seq: Any = None
    # Multi-stream only: the take time that filled the slot to capacity
    # (inf = currently in the available list). The first completion after
    # it re-enters the slot into the list with a fresh avail_seq.
    filled_at: Any = None


class _Streams(NamedTuple):
    """Closed-loop virtual users (n_streams > 1): (S,) arrays.

    Per-slot in-flight occupancy is DERIVED each step from these
    completion horizons (``load_k = Σ_s [slot_s == k ∧ ended_s > t]``)
    rather than carried as counters: the scan processes stream events in
    ``next_ready`` order, so a carried counter could only be decremented
    when the *completed* stream's next request is processed — after other
    streams already observed a stale count. The derived form charges each
    completion at its true completion time."""

    next_ready: Any  # when the stream next dispatches (submit or retry)
    ended: Any       # the stream's in-flight horizon on its slot
    slot: Any        # pool slot that served it (int32; -1 = none yet)
    # Retry-as-step bookkeeping (one scan step = ONE cold attempt; a
    # TERMINATEd probe re-fires the stream at the requeue time instead of
    # looping inside the step — see _step_multi):
    req_start: Any   # current request's first dispatch time (latency anchor)
    retries: Any     # failed attempts of the current request (i32)
    pend_bill: Any   # billed ms of those failed attempts (request row total)


class VecState(NamedTuple):
    t: Any                       # absolute sim time (ms)
    pool: _Pool
    probe_w: WelfordState        # cold probe durations
    log_probe_w: WelfordState    # log of the same (lognormal fit)
    body_w: WelfordState         # observed body durations
    latency_w: WelfordState      # request latencies
    reuse_w: WelfordState        # 1.0 warm-served / 0.0 cold-served
    p2: Any                      # P2State | None (pruned when not adaptive)
    ema: Any
    ema_init: Any
    since_publish: Any
    n_probes: Any
    n_started: Any
    n_terminated: Any
    nb_term: Any                 # Fig-3 billing terms, six scalars
    nb_pass: Any
    nb_reuse: Any
    db_term: Any
    db_pass: Any
    db_reuse: Any
    streams: Any = None          # _Streams when n_streams > 1, else pruned


def _diurnal(t_ms, amplitude, phase_h):
    hour = (t_ms / 3.6e6) % 24.0
    return 1.0 + amplitude * jnp.cos(2.0 * jnp.pi * (hour - phase_h) / 24.0)


def _wsel(mask, new, old):
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(mask, a, b), new, old)


def _attempt_values(params: ArmParams, consts, su, J, day_mean, log_day, i):
    """Attempt ``i``'s sampled quantities from the pre-scaled draw row.

    Draw layout per attempt (base b=3+5i): z0 instance speed, z1 cold
    start, z2 prepare, z3 probe observation noise, z4 body. ``J=exp(su)``
    was computed in one vectorized exp, so everything here is
    multiply/add: speed = exp(σz0)·day_mean, probe = B·exp(bn·z3)/speed,
    body = body_ms·exp(bj·z4)/speed."""
    b = 3 + 5 * i
    cold = params.cold_start_ms * J[b + 1]
    download = params.prepare_ms * J[b + 2]
    inv_speed_rel = J[b + 3] / J[b]
    bench = (params.benchmark_ms / day_mean) * inv_speed_rel
    log_bench = consts["log_bench_ms"] + su[b + 3] - su[b] - log_day
    analysis = (params.body_ms / day_mean) * (J[b + 4] / J[b])
    log_speed = su[b] + log_day
    return cold, download, bench, log_bench, analysis, log_speed


def _cold_chain_fixed(params, cfg, consts, su, J, day_mean, log_day,
                      served_cold, state, judge_mult=None) -> _ColdResult:
    """The retry chain for attempt-invariant gates (off / fixed
    threshold): an unrolled chain of scalar selects — no P², no
    sequential estimator feedback — the grid sweep's hot path.

    ``judge_mult`` (load-aware gating, multi-stream only; ``None`` keeps
    the single-stream graph byte-identical) inflates the JUDGED probe
    duration to the effective speed at the pool's live occupancy — the
    raw observation still feeds the Welford/threshold estimators, exactly
    as :meth:`~repro.core.control.ElysiumGate.judge` records raw and
    judges effective."""
    f32 = jnp.float32
    z = jnp.zeros((), f32)
    pending = served_cold
    thr = jnp.where(params.gate_mode == GATE_FIXED, params.threshold, jnp.inf)
    elapsed = z
    retries = jnp.zeros((), jnp.int32)
    n_term = z
    d_term = z
    cb = z
    s_b = z
    s_b2 = z
    s_lb = z
    s_lb2 = z
    acc_cold = z
    acc_ready = z
    acc_body = z
    acc_logsp = z
    acc_place = z
    for i in range(cfg.max_attempts):
        cold, download, bench, log_bench, analysis, log_speed = \
            _attempt_values(params, consts, su, J, day_mean, log_day, i)
        probed = (params.gate_mode > 0) & (i < params.max_retries)
        b_eff = bench if judge_mult is None else bench * judge_mult
        passes = (~probed) | (b_eff <= thr)
        feed = jnp.asarray(pending & probed, f32)
        accept = pending & passes
        fail = jnp.asarray(pending & ~passes, f32)
        # batched Welford moments of this step's probe stream (merged
        # below via Chan — exact up to FP association order)
        cb = cb + feed
        s_b = s_b + feed * bench
        s_b2 = s_b2 + feed * bench * bench
        s_lb = s_lb + feed * log_bench
        s_lb2 = s_lb2 + feed * log_bench * log_bench
        ready = jnp.where(probed, jnp.maximum(download, bench), download)
        acc_cold = jnp.where(accept, cold, acc_cold)
        acc_ready = jnp.where(accept, ready, acc_ready)
        acc_body = jnp.where(accept, analysis, acc_body)
        acc_logsp = jnp.where(accept, log_speed, acc_logsp)
        acc_place = jnp.where(accept, elapsed, acc_place)
        n_term = n_term + fail
        d_term = d_term + fail * (params.bill_cold_start * cold + bench)
        elapsed = elapsed + fail * (cold + bench + params.requeue_overhead_ms
                                    + params.requeue_penalty_ms)
        retries = retries + jnp.asarray(pending & ~passes, jnp.int32)
        pending = pending & ~passes

    def merged(w: WelfordState, s, s2) -> WelfordState:
        mean_b = s / jnp.maximum(cb, 1.0)
        m2_b = jnp.maximum(s2 - cb * mean_b * mean_b, 0.0)
        return welford_merge(w, WelfordState(count=cb, mean=mean_b, m2=m2_b))

    return _ColdResult(
        elapsed=elapsed, retries=retries, log_speed=acc_logsp,
        cold_ms=acc_cold, ready_ms=acc_ready, analysis_ms=acc_body,
        place_rel=acc_place, n_term=n_term, d_term=d_term,
        probe_w=merged(state.probe_w, s_b, s_b2),
        log_probe_w=merged(state.log_probe_w, s_lb, s_lb2),
        p2=state.p2, ema=state.ema, ema_init=state.ema_init,
        since_publish=state.since_publish,
        n_probes=state.n_probes + cb.astype(jnp.int32),
    )


def _cold_chain_adaptive(params, cfg, consts, su, J, day_mean, log_day,
                         served_cold, state, judge_mult=None) -> _ColdResult:
    """The retry chain when the §IV adaptive threshold is live: every
    probed attempt reports to the on-device P² quantile + EMA republish
    (the exact :class:`~repro.core.policy.AdaptiveMinosPolicy` estimator)
    BEFORE being judged, so attempts are sequential within the step.
    ``judge_mult``: see :func:`_cold_chain_fixed` — estimators always see
    the raw observation; only the pass/terminate comparison inflates."""
    f32 = jnp.float32
    z = jnp.zeros((), f32)
    c = _ColdResult(
        elapsed=z, retries=jnp.zeros((), jnp.int32), log_speed=z,
        cold_ms=z, ready_ms=z, analysis_ms=z, place_rel=z,
        n_term=z, d_term=z,
        probe_w=state.probe_w, log_probe_w=state.log_probe_w,
        p2=state.p2, ema=state.ema, ema_init=state.ema_init,
        since_publish=state.since_publish, n_probes=state.n_probes,
    )
    pending = served_cold
    for i in range(cfg.max_attempts):
        cold, download, bench, log_bench, analysis, log_speed = \
            _attempt_values(params, consts, su, J, day_mean, log_day, i)
        probed = (params.gate_mode > 0) & (i < params.max_retries)
        feed = pending & probed
        probe_w = welford_update_masked(c.probe_w, bench, feed)
        log_probe_w = welford_update_masked(c.log_probe_w, log_bench, feed)
        n_probes = c.n_probes + jnp.asarray(feed, jnp.int32)
        p2 = _wsel(feed, p2_update(c.p2, bench), c.p2)
        since = c.since_publish + jnp.asarray(feed, jnp.int32)
        publish = feed & (since >= params.republish_every)
        p2v = p2_value(p2)
        ema = jnp.where(
            publish,
            jnp.where(c.ema_init,
                      params.smoothing_alpha * p2v
                      + (1.0 - params.smoothing_alpha) * c.ema,
                      p2v),
            c.ema)
        ema_init = c.ema_init | publish
        since = jnp.where(publish, 0, since)
        thr_adaptive = jnp.where(
            n_probes >= params.warmup_reports,
            jnp.where(ema_init, ema, p2v), jnp.inf)
        thr = jnp.where(params.gate_mode == GATE_FIXED, params.threshold,
                        jnp.where(params.gate_mode == GATE_ADAPTIVE,
                                  thr_adaptive, jnp.inf))
        b_eff = bench if judge_mult is None else bench * judge_mult
        passes = (~probed) | (b_eff <= thr)
        accept = pending & passes
        fail = pending & ~passes
        failf = jnp.asarray(fail, f32)
        ready = jnp.where(probed, jnp.maximum(download, bench), download)
        c = _ColdResult(
            elapsed=c.elapsed + failf * (cold + bench
                                         + params.requeue_overhead_ms
                                         + params.requeue_penalty_ms),
            retries=c.retries + jnp.asarray(fail, jnp.int32),
            log_speed=jnp.where(accept, log_speed, c.log_speed),
            cold_ms=jnp.where(accept, cold, c.cold_ms),
            ready_ms=jnp.where(accept, ready, c.ready_ms),
            analysis_ms=jnp.where(accept, analysis, c.analysis_ms),
            place_rel=jnp.where(accept, c.elapsed, c.place_rel),
            n_term=c.n_term + failf,
            d_term=c.d_term + failf * (params.bill_cold_start * cold + bench),
            probe_w=probe_w, log_probe_w=log_probe_w,
            p2=p2, ema=ema, ema_init=ema_init, since_publish=since,
            n_probes=n_probes,
        )
        pending = pending & ~passes
    return c


def _step(params: ArmParams, cfg: SimConfig, consts: dict,
          state: VecState, draws):
    f32 = jnp.float32
    K = cfg.pool_size
    u, ex = draws
    # one vectorized exp covers every lognormal factor of the step
    # (scale<=0 gives exactly exp(0)=1, preserving sample_jitter's
    # disabled-noise contract)
    su = u * consts["scale_vec"]
    J = jnp.exp(su)
    t0 = state.t
    if cfg.diurnal:
        dv = _diurnal(t0, params.diurnal_amplitude, params.diurnal_phase_h)
        day_mean = params.day_factor * dv
        log_day = consts["log_df"] + jnp.log(dv)
    else:
        day_mean = params.day_factor
        log_day = consts["log_df"]

    # ---- warm take: unrolled validity + reuse-order tournament ---------
    pool = state.pool
    valid = [pool.alive[k]
             & ((t0 - pool.last_used[k]) <= params.idle_timeout_ms)
             & (t0 < pool.recycle[k])
             for k in range(K)]
    any_warm = valid[0]
    for k in range(1, K):
        any_warm = any_warm | valid[k]
    served_cold = ~any_warm
    # lifo takes the most recently used valid slot, fifo/spread the
    # oldest (single-stream: pooled loads are all 0, so spread's
    # least-loaded order degenerates to fifo) — maximize a signed score
    sign = jnp.where(params.order == 0, 1.0, -1.0)
    ninf = jnp.asarray(-jnp.inf, f32)
    score = [jnp.where(valid[k], sign * pool.last_used[k], ninf)
             for k in range(K)]
    oh = [None] * K
    oh[0] = score[0] >= ninf  # True; same dtype/shape as the other flags
    best = score[0]
    for k in range(1, K):
        take = score[k] > best
        best = jnp.where(take, score[k], best)
        for j in range(k):
            oh[j] = oh[j] & ~take
        oh[k] = take
    log_i = pool.log_speed[0]
    rc_i = pool.recycle[0]
    for k in range(1, K):
        log_i = jnp.where(oh[k], pool.log_speed[k], log_i)
        rc_i = jnp.where(oh[k], pool.recycle[k], rc_i)

    # ---- warm path: AR(1) drift (pure log-space arithmetic) ------------
    rho = params.contention_rho
    log_drifted = jnp.where(
        rho >= 1.0, log_i,
        log_day + rho * (log_i - log_day)
        + jnp.sqrt(jnp.maximum(1.0 - rho * rho, 0.0)) * su[0])
    download_w = params.prepare_ms * J[1]
    analysis_w = params.body_ms * J[2] * jnp.exp(-log_drifted)
    dur_w = download_w + analysis_w

    # ---- cold path -----------------------------------------------------
    chain = _cold_chain_adaptive if cfg.adaptive else _cold_chain_fixed
    c = chain(params, cfg, consts, su, J, day_mean, log_day,
              served_cold, state)

    # ---- merge warm/cold outcomes --------------------------------------
    analysis = jnp.where(served_cold, c.analysis_ms, analysis_w)
    latency = jnp.where(
        served_cold, c.elapsed + c.cold_ms + c.ready_ms + c.analysis_ms, dur_w)
    billed_final = jnp.where(
        served_cold,
        params.bill_cold_start * c.cold_ms + c.ready_ms + c.analysis_ms,
        dur_w)
    t_end = t0 + latency
    log_speed_served = jnp.where(served_cold, c.log_speed, log_drifted)

    # ---- pool update (unrolled one-hot blend) --------------------------
    # A cold start implies every slot failed validity (all dead), so cold
    # placement always lands in slot 0; a warm serve rewrites its own slot.
    # inf lifetime (no platform recycling) must stay inf even when the
    # exponential draw is exactly 0.0 (0·inf = NaN would kill the slot)
    recycle_new = (t0 + c.place_rel) + jnp.where(
        jnp.isinf(params.recycle_lifetime_ms), jnp.inf,
        ex * params.recycle_lifetime_ms)
    recycle_upd = jnp.where(served_cold, recycle_new, rc_i)
    upd = [served_cold | oh[0]] + [~served_cold & oh[k] for k in range(1, K)]
    new_pool = _Pool(
        log_speed=tuple(
            jnp.where(upd[k], log_speed_served, pool.log_speed[k])
            for k in range(K)),
        last_used=tuple(
            jnp.where(upd[k], t_end, pool.last_used[k]) for k in range(K)),
        recycle=tuple(
            jnp.where(upd[k], recycle_upd, pool.recycle[k])
            for k in range(K)),
        alive=tuple(valid[k] | upd[k] for k in range(K)),
    )

    # ---- Fig-3 billing + telemetry estimators --------------------------
    coldf = jnp.asarray(served_cold, f32)
    warmf = jnp.asarray(any_warm, f32)
    new_state = VecState(
        t=t_end + params.think_time_ms,
        pool=new_pool,
        probe_w=c.probe_w, log_probe_w=c.log_probe_w,
        body_w=welford_update(state.body_w, analysis),
        latency_w=welford_update(state.latency_w, latency),
        reuse_w=welford_update(state.reuse_w, warmf),
        p2=c.p2, ema=c.ema, ema_init=c.ema_init,
        since_publish=c.since_publish, n_probes=c.n_probes,
        n_started=state.n_started + coldf * (
            jnp.asarray(c.retries, f32) + 1.0),
        n_terminated=state.n_terminated + c.n_term,
        nb_term=state.nb_term + c.n_term,
        nb_pass=state.nb_pass + coldf,
        nb_reuse=state.nb_reuse + warmf,
        db_term=state.db_term + c.d_term,
        db_pass=state.db_pass + coldf * billed_final,
        db_reuse=state.db_reuse + warmf * billed_final,
    )
    if cfg.collect_requests:
        out = {
            "latency_ms": latency,
            "analysis_ms": analysis,
            "billed_ms": coldf * c.d_term + billed_final,
            "served_by_cold": served_cold,
            "retries": jnp.where(served_cold, c.retries, 0),
            "instance_speed": jnp.exp(log_speed_served),
        }
    else:
        out = None
    return new_state, out


def _judge_one(params, cfg, est, bench, log_bench, probed):
    """One gate judgment in the retry-as-step models: feed the raw probe
    observation to the estimator stack (Welford moments, plus the
    P²/EMA republish pipeline when ``cfg.adaptive``), then return the
    active threshold to compare the judged — possibly load-inflated —
    duration against. ``est`` is the 7-tuple ``(probe_w, log_probe_w,
    n_probes, p2, ema, ema_init, since_publish)`` pulled off a
    :class:`VecState` or :class:`OpenState` carry; the updated tuple is
    returned alongside ``thr`` so a step can judge several dispatches
    sequentially (the open-loop step judges a parked re-offer and the
    step's own arrival in one pass)."""
    probe_w, log_probe_w, n_probes, p2, ema, ema_init, since = est
    probe_w = welford_update_masked(probe_w, bench, probed)
    log_probe_w = welford_update_masked(log_probe_w, log_bench, probed)
    n_probes = n_probes + jnp.asarray(probed, jnp.int32)
    if cfg.adaptive:
        p2 = _wsel(probed, p2_update(p2, bench), p2)
        since = since + jnp.asarray(probed, jnp.int32)
        publish = probed & (since >= params.republish_every)
        p2v = p2_value(p2)
        ema = jnp.where(
            publish,
            jnp.where(ema_init,
                      params.smoothing_alpha * p2v
                      + (1.0 - params.smoothing_alpha) * ema,
                      p2v),
            ema)
        ema_init = ema_init | publish
        since = jnp.where(publish, 0, since)
        thr_adaptive = jnp.where(
            n_probes >= params.warmup_reports,
            jnp.where(ema_init, ema, p2v), jnp.inf)
        thr = jnp.where(params.gate_mode == GATE_FIXED, params.threshold,
                        jnp.where(params.gate_mode == GATE_ADAPTIVE,
                                  thr_adaptive, jnp.inf))
    else:
        thr = jnp.where(params.gate_mode == GATE_FIXED, params.threshold,
                        jnp.inf)
    return (probe_w, log_probe_w, n_probes, p2, ema, ema_init, since), thr


def _step_multi(params: ArmParams, cfg: SimConfig, consts: dict,
                state: VecState, draws):
    """One invocation step of the ``n_streams > 1`` closed-loop model.

    The step fires the stream with the earliest ``next_ready`` (ties →
    lowest index, the event loop's FIFO order at equal timestamps), so
    step times are non-decreasing and every stream completion earlier
    than the current dispatch has already been accounted. Pool slots
    carry live in-flight occupancy (see :class:`_Streams`): warm
    selection masks full slots, ``order="spread"`` picks the least
    loaded, warm bodies pay the ``(load+1)**alpha`` self-contention
    factor at their observed occupancy, and ``gate_load_aware`` arms
    judge every cold attempt at the pool's live mean occupancy. A cold
    TERMINATE does not loop inside the step: the stream re-fires at the
    requeue time (retry-as-step), so each retry is judged at fresh
    occupancy and can be rescued by a warm slot that freed meanwhile —
    the event dispatcher's requeue semantics. One scan step is therefore
    one dispatch ATTEMPT; steps whose probe fails complete no request
    (``completed`` in the collected rows, ``n_completed`` in summaries).

    Unlike the single-stream step's tuple-of-scalars pool, this step
    keeps ``(K,)``/``(S,)`` arrays: the tournaments become ``argmin``
    reductions instead of unrolled select chains — at K = S = 8 the
    unrolled form made XLA's fusion search blow past minutes of compile
    time, while the array form compiles in seconds and the (small)
    per-step gather cost is dwarfed by the cold-chain math."""
    f32 = jnp.float32
    i32 = jnp.int32
    K = cfg.pool_size
    S = cfg.n_streams
    u, ex = draws
    su = u * consts["scale_vec"]
    J = jnp.exp(su)

    st = state.streams
    # ---- which stream fires (argmin keeps the lowest index on ties,
    # the event loop's FIFO order at equal timestamps) ------------------
    s_star = jnp.argmin(st.next_ready)
    t0 = st.next_ready[s_star]

    if cfg.diurnal:
        dv = _diurnal(t0, params.diurnal_amplitude, params.diurnal_phase_h)
        day_mean = params.day_factor * dv
        log_day = consts["log_df"] + jnp.log(dv)
    else:
        day_mean = params.day_factor
        log_day = consts["log_df"]

    # ---- per-slot live occupancy, exact at t0 --------------------------
    pool = state.pool
    in_flight = (st.slot >= 0) & (st.ended > t0)
    load = jnp.zeros((K,), i32).at[jnp.clip(st.slot, 0)].add(
        in_flight.astype(i32))
    # fold available-list re-entries: a slot taken to capacity left the
    # list (filled_at finite); the first completion after that re-admits
    # it with a fresh position seq. Completions stay visible from their
    # end time until the stream fires again — and the firing step folds
    # before it overwrites — so the earliest qualifying end is never lost.
    vis = (st.slot >= 0) & (st.ended <= t0)
    rejoin_ok = vis & (st.ended > pool.filled_at[jnp.clip(st.slot, 0)])
    rejoin = jnp.full((K,), jnp.inf, f32).at[jnp.clip(st.slot, 0)].min(
        jnp.where(rejoin_ok, st.ended, jnp.inf))
    rejoined = jnp.isfinite(pool.filled_at) & jnp.isfinite(rejoin)
    avail_seq = jnp.where(rejoined, rejoin, pool.avail_seq)
    filled_at = jnp.where(rejoined, jnp.inf, pool.filled_at)

    # ---- warm validity -------------------------------------------------
    # Busy slots (load > 0) stay takeable while they have spare capacity,
    # regardless of idle/recycle deadlines (the event pool only reclaims
    # IDLE instances); idle slots must clear both deadlines; a slot mid
    # cold start (avail_from > t0) is in flight but was never released —
    # the event pool's admit_cold instance — and is not reusable yet.
    idle_ok = ((t0 - pool.last_used) <= params.idle_timeout_ms) \
        & (t0 < pool.recycle)
    valid = pool.alive & (pool.avail_from <= t0) \
        & (load < params.concurrency) & ((load > 0) | idle_ok)
    any_warm = jnp.any(valid)
    served_cold = ~any_warm

    # ---- reuse-order tournament (lifo / fifo / spread) -----------------
    # spread = least loaded, ties by available-list position (see
    # _Pool.avail_seq — at concurrency 1 the position is the release
    # time, so this degenerates to fifo exactly as the single-stream
    # step documents). lifo/fifo ARE list-position orders, so they use
    # the same seq. argmin over a masked key keeps the lowest index on
    # exact ties.
    inf = jnp.asarray(jnp.inf, f32)
    time_key = jnp.where(params.order == 0, -avail_seq, avail_seq)
    min_load = jnp.min(jnp.where(valid, load, jnp.asarray(2**31 - 1, i32)))
    spread_cand = valid & (load == min_load)
    key = jnp.where(params.order == 2,
                    jnp.where(spread_cand, avail_seq, inf),
                    jnp.where(valid, time_key, inf))
    k_warm = jnp.argmin(key)
    log_i = pool.log_speed[k_warm]
    rc_i = pool.recycle[k_warm]
    load_sel = load[k_warm]

    # ---- cold placement: first dead slot -------------------------------
    # (pool_size >= n_streams guarantees one exists on a cold start: the
    # other streams hold < n_streams slots busy, and a load-0 slot that
    # cleared its deadlines would have served warm instead)
    dead = ~pool.alive | ((load == 0) & ~idle_ok)
    k_cold = jnp.argmax(dead)  # first True
    k_upd = jnp.where(served_cold, k_cold, k_warm)
    upd = jnp.arange(K) == k_upd

    # ---- warm path: AR(1) drift + load**alpha self-contention ----------
    rho = params.contention_rho
    log_drifted = jnp.where(
        rho >= 1.0, log_i,
        log_day + rho * (log_i - log_day)
        + jnp.sqrt(jnp.maximum(1.0 - rho * rho, 0.0)) * su[0])
    eff_load = jnp.asarray(load_sel + 1, f32)  # incl. this request
    lmult = jnp.where(
        (params.load_slowdown_alpha > 0.0) & (eff_load > 1.0),
        jnp.power(eff_load, params.load_slowdown_alpha), 1.0)
    download_w = params.prepare_ms * J[1]
    analysis_w = params.body_ms * J[2] * jnp.exp(-log_drifted) * lmult
    dur_w = download_w + analysis_w

    # ---- load-aware gate factor (pool mean occupancy at dispatch) ------
    # counts this request and its cold instance, like the event engine's
    # Telemetry at judge time (admit_cold puts the probing instance in
    # the pool with one in-flight request before the gate fires). Each
    # retry attempt is its own step, so the judge re-reads occupancy at
    # every re-dispatch exactly like the event controller.
    live = pool.alive & ((load > 0) | idle_ok)
    total_if = jnp.sum(load)
    n_live = jnp.sum(live.astype(i32))
    mean_load = jnp.maximum(
        1.0, jnp.asarray(total_if + 1, f32) / jnp.asarray(n_live + 1, f32))
    judge_mult = jnp.where(
        (params.gate_load_aware > 0.5) & (params.load_slowdown_alpha > 0.0),
        jnp.power(mean_load, params.load_slowdown_alpha), 1.0)

    # ---- cold path: ONE probe attempt per step (retry-as-step) ---------
    # The event engine requeues a TERMINATEd cold probe through the
    # dispatcher: the retry re-dispatches ~requeue_overhead_ms after the
    # probe ends, re-reads pool occupancy, and can land on a warm slot
    # that freed meanwhile. Folding the whole retry chain into the step
    # that started it (the single-stream model) freezes one occupancy
    # snapshot across the chain and hides the probing instances from
    # concurrent streams — under load-aware gating that severs the
    # saturation → harsh judge → terminate → still-saturated feedback the
    # event engine exhibits (measured: the frozen snapshot never leaves
    # mean load 1.0, while the event judges 18% of probes at 1.75–2.5).
    # A failed attempt completes no request and leaves no trace in the
    # pool — the event judges and drops the instance synchronously at
    # dispatch time — and the stream re-fires at the requeue time.
    cold_ms, download_c, bench, log_bench, analysis_c, log_speed_c = \
        _attempt_values(params, consts, su, J, day_mean, log_day, 0)
    r_cur = st.retries[s_star]
    req_start = jnp.where(r_cur > 0, st.req_start[s_star], t0)
    probed = served_cold & (params.gate_mode > 0) \
        & (r_cur < params.max_retries)
    est = (state.probe_w, state.log_probe_w, state.n_probes, state.p2,
           state.ema, state.ema_init, state.since_publish)
    est, thr = _judge_one(params, cfg, est, bench, log_bench, probed)
    probe_w, log_probe_w, n_probes, p2, ema, ema_init, since = est
    # estimators see the raw observation; only the verdict inflates, as
    # ElysiumGate.judge records raw and judges effective
    passes = (~probed) | (bench * judge_mult <= thr)
    completed = any_warm | passes
    cold_pass = served_cold & passes
    cold_passf = jnp.asarray(cold_pass, f32)
    failf = jnp.asarray(served_cold & ~passes, f32)

    # ---- merge warm/cold outcomes --------------------------------------
    ready_c = jnp.where(probed, jnp.maximum(download_c, bench), download_c)
    analysis = jnp.where(served_cold, analysis_c, analysis_w)
    t_end = t0 + jnp.where(served_cold, cold_ms + ready_c + analysis_c,
                           dur_w)
    probe_end = t0 + cold_ms + bench
    latency = t_end - req_start
    billed_final = jnp.where(
        served_cold, params.bill_cold_start * cold_ms + ready_c + analysis_c,
        dur_w)
    bill_fail = params.bill_cold_start * cold_ms + bench
    log_speed_served = jnp.where(served_cold, log_speed_c, log_drifted)

    # ---- pool update ---------------------------------------------------
    recycle_new = t0 + jnp.where(
        jnp.isinf(params.recycle_lifetime_ms), jnp.inf,
        ex * params.recycle_lifetime_ms)
    ninf = jnp.asarray(-jnp.inf, f32)
    recycle_upd = jnp.where(served_cold,
                            jnp.where(passes, recycle_new, ninf), rc_i)
    # lazy reclaim exactly like the event pool's sweep: an idle slot past
    # its deadline dies; busy slots (load > 0) always survive. A failed
    # probe never enters the pool at all: the event judges and drops the
    # instance synchronously at dispatch time, so concurrent requests
    # never observe it — mirrored here by not raising `alive` on a fail.
    keep = pool.alive & ((load > 0) | idle_ok)
    new_pool = _Pool(
        log_speed=jnp.where(upd, log_speed_served, pool.log_speed),
        last_used=jnp.where(upd, jnp.where(completed, t_end, ninf),
                            pool.last_used),
        recycle=jnp.where(upd, recycle_upd, pool.recycle),
        alive=keep | (upd & completed),
        avail_from=jnp.where(
            upd & served_cold,
            jnp.where(passes, t_end, jnp.inf), pool.avail_from),
        # a cold-placed slot enters the available list at its first
        # release (t_end); a warm take that fills the slot to capacity
        # removes it from the list until a completion re-admits it
        avail_seq=jnp.where(upd & served_cold, t_end, avail_seq),
        filled_at=jnp.where(
            upd,
            jnp.where(~served_cold & (load_sel + 1 >= params.concurrency),
                      t0, jnp.inf),
            filled_at),
    )

    # A stream whose probe failed holds no slot while it waits to requeue
    # (the event drops the instance at judge time), so it contributes no
    # in-flight load to anyone's occupancy reads until it re-dispatches.
    chosen_idx = jnp.where(completed, k_upd.astype(i32),
                           jnp.asarray(-1, i32))
    s_oh = jnp.arange(S) == s_star
    pend_bill = st.pend_bill[s_star]
    requeue_at = probe_end + params.requeue_overhead_ms \
        + params.requeue_penalty_ms
    new_streams = _Streams(
        next_ready=jnp.where(
            s_oh,
            jnp.where(completed, t_end + params.think_time_ms, requeue_at),
            st.next_ready),
        ended=jnp.where(s_oh, jnp.where(completed, t_end, probe_end),
                        st.ended),
        slot=jnp.where(s_oh, chosen_idx, st.slot),
        req_start=jnp.where(s_oh, req_start, st.req_start),
        retries=jnp.where(s_oh, jnp.where(completed, 0, r_cur + 1),
                          st.retries),
        pend_bill=jnp.where(
            s_oh, jnp.where(completed, 0.0, pend_bill + bill_fail),
            st.pend_bill),
    )

    # ---- Fig-3 billing + telemetry estimators --------------------------
    coldf = jnp.asarray(served_cold, f32)
    warmf = jnp.asarray(any_warm, f32)
    new_state = VecState(
        t=jnp.maximum(state.t, jnp.where(completed, t_end, probe_end)),
        pool=new_pool,
        probe_w=probe_w, log_probe_w=log_probe_w,
        body_w=welford_update_masked(state.body_w, analysis, completed),
        latency_w=welford_update_masked(state.latency_w, latency, completed),
        reuse_w=welford_update_masked(state.reuse_w, warmf, completed),
        p2=p2, ema=ema, ema_init=ema_init,
        since_publish=since, n_probes=n_probes,
        n_started=state.n_started + coldf,
        n_terminated=state.n_terminated + failf,
        nb_term=state.nb_term + failf,
        nb_pass=state.nb_pass + cold_passf,
        nb_reuse=state.nb_reuse + warmf,
        db_term=state.db_term + failf * bill_fail,
        db_pass=state.db_pass + cold_passf * billed_final,
        db_reuse=state.db_reuse + warmf * billed_final,
        streams=new_streams,
    )
    if cfg.collect_requests:
        out = {
            "latency_ms": latency,
            "analysis_ms": analysis,
            "billed_ms": pend_bill + billed_final,
            "served_by_cold": served_cold,
            "retries": r_cur,
            "instance_speed": jnp.exp(log_speed_served),
            # retry-as-step: a failed attempt completes no request — rows
            # with completed=False are attempt records and must be masked
            # out of per-request statistics by consumers
            "completed": completed,
            # slot-accounting stream for the O(n) replay property test
            "slot": chosen_idx,
            "stream": s_star.astype(i32),
            "t_start_ms": t0,
            "t_end_ms": jnp.where(completed, t_end, probe_end),
            # occupancy of the serving slot excluding this request (a
            # cold-placed slot is empty by construction)
            "load_at_start": jnp.where(served_cold, 0, load_sel),
        }
    else:
        out = None
    return new_state, out


def _simulate_chain(params: ArmParams, key, cfg: SimConfig):
    f32 = jnp.float32
    K = cfg.pool_size
    # multi-stream steps run ONE cold attempt each (retry-as-step), so
    # they only consume attempt-0 draws
    ma = 1 if cfg.n_streams > 1 else cfg.max_attempts
    k_normal, k_exp = jax.random.split(key)
    u_all = jax.random.normal(k_normal, (cfg.n_steps, 3 + 5 * ma), f32)
    ex_all = jax.random.exponential(k_exp, (cfg.n_steps,), f32)
    # Draw layout: u[0] warm drift, u[1] warm prepare, u[2] warm body;
    # attempt i at base 3+5i: z0 speed, z1 cold, z2 prepare, z3 probe
    # noise, z4 body — scale_vec turns the whole row into log-factors.
    pj, bj = params.prepare_jitter, params.body_jitter
    cj, bn, sg = params.cold_start_jitter, params.benchmark_noise, params.sigma
    consts = {
        "scale_vec": jnp.stack([sg, pj, bj] + [sg, cj, pj, bn, bj] * ma),
        "log_df": jnp.log(params.day_factor),
        "log_bench_ms": jnp.log(params.benchmark_ms),
    }
    z = jnp.zeros((), f32)
    S = cfg.n_streams
    multi = S > 1
    state = VecState(
        t=z,
        pool=_Pool(
            log_speed=jnp.zeros((K,), f32) if multi else (z,) * K,
            last_used=jnp.zeros((K,), f32) if multi else (z,) * K,
            recycle=(jnp.full((K,), jnp.inf, f32) if multi
                     else (jnp.asarray(jnp.inf, f32),) * K),
            alive=(jnp.zeros((K,), bool) if multi
                   else (jnp.zeros((), bool),) * K),
            avail_from=jnp.zeros((K,), f32) if multi else None,
            avail_seq=jnp.zeros((K,), f32) if multi else None,
            filled_at=jnp.full((K,), jnp.inf, f32) if multi else None,
        ),
        # every stream submits at t=0 (workload.run_closed_loop's n_vus
        # start); ties resolve in index order like the event loop's FIFO
        streams=_Streams(
            next_ready=jnp.zeros((S,), f32),
            ended=jnp.zeros((S,), f32),
            slot=jnp.full((S,), -1, jnp.int32),
            req_start=jnp.zeros((S,), f32),
            retries=jnp.zeros((S,), jnp.int32),
            pend_bill=jnp.zeros((S,), f32),
        ) if multi else None,
        probe_w=welford_init(), log_probe_w=welford_init(),
        body_w=welford_init(), latency_w=welford_init(),
        reuse_w=welford_init(),
        # None prunes the adaptive estimator from the scan carry entirely
        # when no arm needs it (pytree None = empty subtree)
        p2=p2_init(params.pass_fraction) if cfg.adaptive else None,
        ema=z if cfg.adaptive else None,
        ema_init=jnp.zeros((), bool) if cfg.adaptive else None,
        since_publish=jnp.zeros((), jnp.int32) if cfg.adaptive else None,
        n_probes=jnp.zeros((), jnp.int32),
        n_started=z, n_terminated=z,
        nb_term=z, nb_pass=z, nb_reuse=z,
        db_term=z, db_pass=z, db_reuse=z,
    )
    step_fn = _step_multi if multi else _step
    final, requests = jax.lax.scan(
        lambda s, x: step_fn(params, cfg, consts, s, x), state,
        (u_all, ex_all), unroll=1 if cfg.adaptive else 4)
    cost = params.cost_per_ms * (final.db_term + final.db_pass
                                 + final.db_reuse) \
        + params.cost_per_invocation * (final.nb_term + final.nb_pass
                                        + final.nb_reuse)
    summary = {
        "n_requests": jnp.asarray(cfg.n_steps, f32),
        # retry-as-step (n_streams > 1): a step whose cold probe fails
        # completes no request, so completions = steps - terminations
        "n_completed": (jnp.asarray(cfg.n_steps, f32) - final.n_terminated
                        if multi else jnp.asarray(cfg.n_steps, f32)),
        "n_started": final.n_started,
        "n_terminated": final.n_terminated,
        "n_probes": jnp.asarray(final.n_probes, f32),
        "reuse_rate": final.reuse_w.mean,
        "mean_analysis_ms": final.body_w.mean,
        "std_analysis_ms": welford_std(final.body_w),
        "mean_latency_ms": final.latency_w.mean,
        "probe_mean_ms": final.probe_w.mean,
        "probe_log_mean": final.log_probe_w.mean,
        "probe_log_std": welford_std(final.log_probe_w),
        "pass_rate": 1.0 - final.n_terminated
        / jnp.maximum(jnp.asarray(final.n_probes, f32), 1.0),
        "bill_n": jnp.stack([final.nb_term, final.nb_pass, final.nb_reuse]),
        "bill_d": jnp.stack([final.db_term, final.db_pass, final.db_reuse]),
        "cost": cost,
        "horizon_ms": final.t,
    }
    return summary, requests
# ---------------------------------------------------------------------------
# Open-loop (arrival-driven) scan — DESIGN.md §12
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OpenSimConfig:
    """Static shape of one open-loop vectorized run.

    ``n_servers`` is the autoscaling supply cap (the event engine's
    ``SubstrateKnobs.max_instances``): K server slots, each serving one
    request at a time. The scan runs the event dispatcher's admission
    pipeline in-scan (DESIGN.md §12): a static admission bound
    (``ArmParams.admit_bound``, the controller's ``on_admit``) defers
    arrivals when in-flight work reaches it, a finite
    ``ArmParams.queue_capacity`` (the engine's ``submit``) drops them
    when the wait queue is full, and a failed cold probe releases its
    slot immediately and parks the request until its requeue time
    (retry-as-park) instead of holding the slot through the whole retry
    chain. ``queue_ring`` bounds how many requests can be parked at once
    (deferred + awaiting retry); parking past the ring counts as a
    drop, never a silent loss."""

    n_steps: int
    n_servers: int = 4
    queue_ring: int = 32
    drains_per_step: int = 3
    collect_requests: bool = False
    adaptive: bool = True
    diurnal: bool = True


class OpenState(NamedTuple):
    """Scan carry for the open-loop variant. Slot state is ``(K,)``
    arrays. The park ring (``(W,)``, ``W = cfg.queue_ring``) holds
    requests not currently occupying a slot: admission-deferred arrivals
    (``park_retries == 0``) and failed probes waiting out the requeue
    delay (``park_ready`` = earliest re-dispatch time, ``inf`` = empty
    entry). ``starts`` is a circular log of recent dispatch start times:
    an arrival's wait-queue depth is the number of logged starts still
    in the future — requests with a slot promised but not yet begun
    service. The estimator tail (probe_w … since_publish, n_probes)
    matches the 7-tuple :func:`_judge_one` threads."""

    t_arr: Any                   # previous arrival's absolute time
    busy: Any                    # (K,) per-slot busy-until horizon
    log_speed: Any               # (K,)
    last_used: Any               # (K,) per-slot last completion time
    recycle: Any                 # (K,) absolute recycle deadline
    alive: Any                   # (K,)
    starts: Any                  # (W,) dispatch-start log (queue depth)
    starts_idx: Any              # i32 circular cursor into ``starts``
    park_ready: Any              # (W,) re-dispatch time, inf = empty
    park_start: Any              # (W,) original arrival (latency anchor)
    park_retries: Any            # (W,) i32 failed probes so far
    park_bill: Any               # (W,) billed ms of those failed probes
    park_wait: Any               # (W,) queue wait at FIRST dispatch
    probe_w: WelfordState
    log_probe_w: WelfordState
    body_w: WelfordState
    latency_w: WelfordState
    wait_w: WelfordState         # queue waits (the open-loop metric)
    reuse_w: WelfordState
    p2: Any
    ema: Any
    ema_init: Any
    since_publish: Any
    n_probes: Any
    n_started: Any
    n_terminated: Any
    n_completed: Any
    n_dropped: Any
    n_deferred: Any
    nb_term: Any
    nb_pass: Any
    nb_reuse: Any
    db_term: Any
    db_pass: Any
    db_reuse: Any


def _open_dispatch(params: ArmParams, cfg: OpenSimConfig, consts: dict,
                   slots, est, su, ex, t_req, rc_cur, active):
    """Place and serve ONE open-loop request dispatching at ``t_req``.

    ``slots`` is the ``(busy, log_speed, last_used, recycle, alive)``
    tuple of ``(K,)`` arrays; ``su`` one pre-scaled 8-draw block (warm
    drift/prepare/body + one cold attempt, the layout
    :func:`_attempt_values` reads at ``i=0``); ``rc_cur`` how many
    probes this request already failed (past ``max_retries`` the gate
    accepts anything, the event policy's retry budget). When ``active``
    is false all state threads through untouched and every output is a
    don't-care the caller masks.

    A failed probe is retry-as-park: the attempt bills its cold start +
    benchmark but occupies the slot for ZERO wall time — the event
    engine judges and terminates the instance synchronously at dispatch,
    so no concurrent request ever waits behind it — and the caller parks
    the request until ``requeue_at``. Each re-dispatch therefore sees
    fresh slot state and can be rescued by a slot that freed meanwhile,
    the event dispatcher's requeue semantics."""
    f32 = jnp.float32
    busy, log_speed, last_used, recycle, alive = slots
    J = jnp.exp(su)

    free = busy <= t_req
    idle_ok = ((t_req - last_used) <= params.idle_timeout_ms) \
        & (t_req < recycle)
    valid = alive & free & idle_ok
    any_valid = jnp.any(valid)
    any_free = jnp.any(free)

    # case A — warm now: reuse-order tournament (one request per slot:
    # lifo = most recently used, fifo/spread = oldest; argmax keeps the
    # lowest index on exact ties, the event pool's stable list order)
    sign = jnp.where(params.order == 0, 1.0, -1.0)
    score = jnp.where(valid, sign * last_used, -jnp.inf)
    k_a = jnp.argmax(score)
    # case B — no valid warm slot but a free one exists (dead or
    # idle/recycle-expired): cold start now, into the first free slot
    k_b = jnp.argmax(free)
    # case C — every slot busy: wait for the earliest completion; the
    # freed slot serves warm unless its recycle deadline passed while it
    # was busy (idle gap is zero by construction)
    k_c = jnp.argmin(busy)
    case_c = ~any_free
    k = jnp.where(any_valid, k_a, jnp.where(any_free, k_b, k_c))
    t_start = jnp.where(case_c, jnp.maximum(busy[k_c], t_req), t_req)
    recycled_c = case_c & (t_start >= recycle[k_c])
    served_cold = (~any_valid & any_free) | recycled_c
    any_warm = ~served_cold
    log_i = log_speed[k]

    if cfg.diurnal:
        dv = _diurnal(t_start, params.diurnal_amplitude,
                      params.diurnal_phase_h)
        day_mean = params.day_factor * dv
        log_day = consts["log_df"] + jnp.log(dv)
    else:
        day_mean = params.day_factor
        log_day = consts["log_df"]

    # warm path: AR(1) drift, prepare + body. One request per slot means
    # no load**alpha self-contention and a judge load factor of 1 — the
    # event Telemetry at per-instance concurrency 1.
    rho = params.contention_rho
    log_drifted = jnp.where(
        rho >= 1.0, log_i,
        log_day + rho * (log_i - log_day)
        + jnp.sqrt(jnp.maximum(1.0 - rho * rho, 0.0)) * su[0])
    download_w = params.prepare_ms * J[1]
    analysis_w = params.body_ms * J[2] * jnp.exp(-log_drifted)
    dur_w = download_w + analysis_w

    # cold path: ONE probe attempt (retries re-enter via the park ring)
    cold_ms, download_c, bench, log_bench, analysis_c, log_speed_c = \
        _attempt_values(params, consts, su, J, day_mean, log_day, 0)
    probed = active & served_cold & (params.gate_mode > 0) \
        & (rc_cur < params.max_retries)
    est, thr = _judge_one(params, cfg, est, bench, log_bench, probed)
    passes = (~probed) | (bench <= thr)
    completed = active & (any_warm | passes)
    fail = active & served_cold & ~passes

    ready_c = jnp.where(probed, jnp.maximum(download_c, bench), download_c)
    analysis = jnp.where(served_cold, analysis_c, analysis_w)
    service = jnp.where(served_cold, cold_ms + ready_c + analysis_c, dur_w)
    t_end = t_start + service
    probe_end = t_start + cold_ms + bench
    billed = jnp.where(
        served_cold, params.bill_cold_start * cold_ms + ready_c + analysis_c,
        dur_w)
    bill_fail = params.bill_cold_start * cold_ms + bench
    requeue_at = probe_end + params.requeue_overhead_ms \
        + params.requeue_penalty_ms
    log_speed_served = jnp.where(served_cold, log_speed_c, log_drifted)
    recycle_new = t_start + jnp.where(
        jnp.isinf(params.recycle_lifetime_ms), jnp.inf,
        ex * params.recycle_lifetime_ms)

    # a failed probe leaves no trace in the slot arrays (alive only
    # rises on a completed cold placement)
    upd = completed & (jnp.arange(busy.shape[0]) == k)
    slots = (
        jnp.where(upd, t_end, busy),
        jnp.where(upd, log_speed_served, log_speed),
        jnp.where(upd, t_end, last_used),
        jnp.where(upd, jnp.where(served_cold, recycle_new, recycle[k]),
                  recycle),
        alive | upd,
    )
    o = {
        "t_start": t_start, "t_end": t_end,
        "served_cold": active & served_cold, "completed": completed,
        "fail": fail, "analysis": analysis, "billed": billed,
        "bill_fail": bill_fail, "requeue_at": requeue_at,
    }
    return slots, est, o


def _open_step(params: ArmParams, cfg: OpenSimConfig, consts: dict,
               state: OpenState, draws):
    """One arrival of the open-loop scan, in event-dispatcher order.

    Phase 1 drains up to ``cfg.drains_per_step`` matured park-ring
    entries in FIFO-by-ready order (deferred arrivals and requeued
    retries whose ``park_ready`` has passed) through full placements —
    a drained dispatch runs at its OWN ``park_ready`` timestamp, not at
    this step's arrival time, so retry timing is exact as long as the
    drain budget keeps up. Phase 2 runs the admission pipeline on the
    step's own arrival — defer first (static ``admit_bound`` on
    in-flight work, the controller's ``on_admit``), then drop (finite
    ``queue_capacity`` on the wait queue, the engine's ``submit``) —
    and dispatches it when admitted. Each step emits
    ``drains_per_step + 1`` rows (drains first, arrival last) with
    ``completed`` / ``dropped`` / ``deferred`` masks; consumers filter.

    Approximations vs the event loop, all second-order at the parity
    operating points (measured in EXPERIMENTS.md): a fail burst larger
    than the drain budget lets a later arrival book a slot ahead of a
    matured retry (FIFO inversion); an item is deferred at most once,
    re-offered at the earliest busy horizon rather than at every
    completion; and re-offers skip the drop check (the event re-offer
    can still drop at submit)."""
    f32 = jnp.float32
    i32 = jnp.int32
    W = cfg.queue_ring
    D = cfg.drains_per_step
    u, ex, iat = draws
    su = u * consts["scale_blocks"]
    t_arr = state.t_arr + iat

    slots = (state.busy, state.log_speed, state.last_used, state.recycle,
             state.alive)
    est = (state.probe_w, state.log_probe_w, state.n_probes, state.p2,
           state.ema, state.ema_init, state.since_publish)
    park_ready, park_start = state.park_ready, state.park_start
    park_retries, park_bill = state.park_retries, state.park_bill
    park_wait = state.park_wait
    starts, sidx = state.starts, state.starts_idx

    wf = {"body_w": state.body_w, "latency_w": state.latency_w,
          "wait_w": state.wait_w, "reuse_w": state.reuse_w}
    acc = {k: getattr(state, k) for k in (
        "n_started", "n_terminated", "n_completed", "n_dropped",
        "n_deferred", "nb_term", "nb_pass", "nb_reuse",
        "db_term", "db_pass", "db_reuse")}
    rows: list = []

    def account(o, lat, wait, wait_mask, bill_prev, rc, dropped, deferred):
        cdone = o["completed"]
        warm = cdone & ~o["served_cold"]
        cp = cdone & o["served_cold"]
        failf = jnp.asarray(o["fail"], f32)
        wf["body_w"] = welford_update_masked(
            wf["body_w"], o["analysis"], cdone)
        wf["latency_w"] = welford_update_masked(wf["latency_w"], lat, cdone)
        wf["wait_w"] = welford_update_masked(wf["wait_w"], wait, wait_mask)
        wf["reuse_w"] = welford_update_masked(
            wf["reuse_w"], jnp.asarray(warm, f32), cdone)
        acc["n_started"] += jnp.asarray(o["served_cold"], f32)
        acc["n_terminated"] += failf
        acc["n_completed"] += jnp.asarray(cdone, f32)
        acc["n_dropped"] += jnp.asarray(dropped, f32)
        acc["n_deferred"] += jnp.asarray(deferred, f32)
        acc["nb_term"] += failf
        acc["nb_pass"] += jnp.asarray(cp, f32)
        acc["nb_reuse"] += jnp.asarray(warm, f32)
        acc["db_term"] += failf * o["bill_fail"]
        acc["db_pass"] += jnp.asarray(cp, f32) * o["billed"]
        acc["db_reuse"] += jnp.asarray(warm, f32) * o["billed"]
        if cfg.collect_requests:
            rows.append({
                "latency_ms": lat, "wait_ms": wait,
                "analysis_ms": o["analysis"],
                # a retry completion's bill includes its failed attempts
                "billed_ms": bill_prev + o["billed"],
                "served_by_cold": o["served_cold"],
                "retries": rc, "t_completed_ms": o["t_end"],
                # rows with completed=False are attempt/defer/drop
                # records — consumers must mask them out of per-request
                # statistics
                "completed": cdone, "dropped": dropped,
                "deferred": deferred})

    fz = jnp.zeros((), bool)
    # ---- phase 1: drain matured parked requests, FIFO by ready time ----
    for di in range(D):
        j = jnp.argmin(park_ready)
        ready_j = park_ready[j]
        drain = jnp.isfinite(ready_j) & (ready_j <= t_arr)
        rc_d = park_retries[j]
        start_d = park_start[j]
        bill_prev = park_bill[j]
        slots, est, d = _open_dispatch(
            params, cfg, consts, slots, est, su[8 * di:8 * di + 8], ex[di],
            jnp.where(drain, ready_j, t_arr), rc_d, drain)
        oh = (jnp.arange(W) == j) & drain
        park_ready = jnp.where(
            oh, jnp.where(d["fail"], d["requeue_at"], jnp.inf), park_ready)
        park_retries = jnp.where(oh & d["fail"], rc_d + 1, park_retries)
        park_bill = jnp.where(
            oh, jnp.where(d["fail"], bill_prev + d["bill_fail"], 0.0),
            park_bill)
        # queue wait = until FIRST dispatch, back-dated to arrival for
        # deferred items (run_open_loop's submitted_at_ms); requeues do
        # not reset it (Invocation.first_dispatched_at_ms), so retries
        # carry theirs through the ring
        wait_d = jnp.where(rc_d > 0, park_wait[j], d["t_start"] - start_d)
        park_wait = jnp.where(oh & d["fail"], wait_d, park_wait)
        # log the drained dispatch's start so queue-depth counts see it
        starts = jnp.where(
            drain, starts.at[sidx % W].set(d["t_start"]), starts)
        sidx = sidx + jnp.asarray(drain, i32)
        account(d, d["t_end"] - start_d, wait_d,
                drain & (rc_d == 0), bill_prev, rc_d, fz, fz)

    # ---- phase 2: admission pipeline on the step's own arrival ---------
    busy1 = slots[0]
    parked = jnp.isfinite(park_ready)
    # in-flight work the admission bound sees: in service, slot promised
    # but not yet started, or mid retry-chain. Admission-deferred parks
    # (park_retries == 0) are the event loop's pending deque — NOT
    # in-flight, exactly as ``on_admit`` counts.
    in_service = jnp.sum((busy1 > t_arr).astype(i32))
    q_wait = jnp.sum((starts > t_arr).astype(i32))
    n_retry = jnp.sum((parked & (park_retries > 0)).astype(i32))
    in_flight = in_service + q_wait + n_retry
    defer = jnp.asarray(in_flight, f32) >= params.admit_bound
    # the engine's submit drops when the wait queue is at capacity —
    # checked after admission, run_open_loop's offer → submit order
    drop = ~defer & (jnp.asarray(q_wait, f32) >= params.queue_capacity)
    admitted = ~defer & ~drop

    slots, est, a = _open_dispatch(
        params, cfg, consts, slots, est, su[8 * D:], ex[D], t_arr,
        jnp.zeros((), i32), admitted)
    starts = jnp.where(
        admitted, starts.at[sidx % W].set(a["t_start"]), starts)
    sidx = sidx + jnp.asarray(admitted, i32)

    # park the arrival when deferred, or when its probe failed (retry);
    # a full ring drops the request (counted, never silent)
    want_park = defer | a["fail"]
    empty = ~jnp.isfinite(park_ready)
    j2 = jnp.argmax(empty)
    overflow = want_park & ~jnp.any(empty)
    oh2 = (jnp.arange(W) == j2) & want_park & ~overflow
    # a deferred item re-offers at the next completion (earliest busy
    # horizon), the event loop's done → re-offer hook
    reoffer_at = jnp.maximum(jnp.min(busy1), t_arr)
    park_ready = jnp.where(
        oh2, jnp.where(defer, reoffer_at, a["requeue_at"]), park_ready)
    park_start = jnp.where(oh2, t_arr, park_start)
    park_retries = jnp.where(oh2, jnp.where(defer, 0, 1), park_retries)
    park_bill = jnp.where(oh2, jnp.where(defer, 0.0, a["bill_fail"]),
                          park_bill)
    park_wait = jnp.where(oh2, jnp.where(defer, 0.0, a["t_start"] - t_arr),
                          park_wait)
    account(a, a["t_end"] - t_arr, a["t_start"] - t_arr, admitted,
            jnp.zeros((), f32), jnp.zeros((), i32),
            drop | overflow, defer & ~overflow)

    new_state = OpenState(
        t_arr=t_arr,
        busy=slots[0], log_speed=slots[1], last_used=slots[2],
        recycle=slots[3], alive=slots[4],
        starts=starts, starts_idx=sidx,
        park_ready=park_ready, park_start=park_start,
        park_retries=park_retries, park_bill=park_bill,
        park_wait=park_wait,
        probe_w=est[0], log_probe_w=est[1],
        body_w=wf["body_w"], latency_w=wf["latency_w"],
        wait_w=wf["wait_w"], reuse_w=wf["reuse_w"],
        p2=est[3], ema=est[4], ema_init=est[5], since_publish=est[6],
        n_probes=est[2],
        **acc,
    )
    if cfg.collect_requests:
        out = {k: jnp.stack([r[k] for r in rows]) for k in rows[0]}
    else:
        out = None
    return new_state, out


def _simulate_open_chain(params: ArmParams, key, cfg: OpenSimConfig, iats):
    f32 = jnp.float32
    i32 = jnp.int32
    K = cfg.n_servers
    W = cfg.queue_ring
    D = cfg.drains_per_step
    k_normal, k_exp = jax.random.split(key)
    # one 8-draw dispatch block per drain slot plus one for the arrival —
    # retries consume the drain block of whichever later step drains them
    u_all = jax.random.normal(k_normal, (cfg.n_steps, 8 * (D + 1)), f32)
    ex_all = jax.random.exponential(k_exp, (cfg.n_steps, D + 1), f32)
    pj, bj = params.prepare_jitter, params.body_jitter
    cj, bn, sg = params.cold_start_jitter, params.benchmark_noise, params.sigma
    block = [sg, pj, bj, sg, cj, pj, bn, bj]
    consts = {
        "scale_blocks": jnp.stack(block * (D + 1)),
        "log_df": jnp.log(params.day_factor),
        "log_bench_ms": jnp.log(params.benchmark_ms),
    }
    z = jnp.zeros((), f32)
    state = OpenState(
        t_arr=z,
        busy=jnp.zeros((K,), f32),
        log_speed=jnp.zeros((K,), f32),
        last_used=jnp.zeros((K,), f32),
        recycle=jnp.full((K,), jnp.inf, f32),
        alive=jnp.zeros((K,), bool),
        # -inf: an unused log entry is never counted as a future start
        starts=jnp.full((W,), -jnp.inf, f32),
        starts_idx=jnp.zeros((), i32),
        park_ready=jnp.full((W,), jnp.inf, f32),
        park_start=jnp.zeros((W,), f32),
        park_retries=jnp.zeros((W,), i32),
        park_bill=jnp.zeros((W,), f32),
        park_wait=jnp.zeros((W,), f32),
        probe_w=welford_init(), log_probe_w=welford_init(),
        body_w=welford_init(), latency_w=welford_init(),
        wait_w=welford_init(), reuse_w=welford_init(),
        p2=p2_init(params.pass_fraction) if cfg.adaptive else None,
        ema=z if cfg.adaptive else None,
        ema_init=jnp.zeros((), bool) if cfg.adaptive else None,
        since_publish=jnp.zeros((), i32) if cfg.adaptive else None,
        n_probes=jnp.zeros((), i32),
        n_started=z, n_terminated=z,
        n_completed=z, n_dropped=z, n_deferred=z,
        nb_term=z, nb_pass=z, nb_reuse=z,
        db_term=z, db_pass=z, db_reuse=z,
    )
    final, requests = jax.lax.scan(
        lambda s, x: _open_step(params, cfg, consts, s, x), state,
        (u_all, ex_all, jnp.asarray(iats, f32)),
        unroll=1 if cfg.adaptive else 4)
    cost = params.cost_per_ms * (final.db_term + final.db_pass
                                 + final.db_reuse) \
        + params.cost_per_invocation * (final.nb_term + final.nb_pass
                                        + final.nb_reuse)
    n_steps_f = jnp.asarray(cfg.n_steps, f32)
    summary = {
        "n_requests": n_steps_f,
        # conservation (tested): every arrival completes, drops, or is
        # still parked (deferred / awaiting retry) at the horizon
        "n_completed": final.n_completed,
        "n_dropped": final.n_dropped,
        "n_deferred": final.n_deferred,
        "n_parked_end": jnp.sum(jnp.isfinite(final.park_ready).astype(f32)),
        "drop_rate": final.n_dropped / n_steps_f,
        "defer_rate": final.n_deferred / n_steps_f,
        "n_started": final.n_started,
        "n_terminated": final.n_terminated,
        "n_probes": jnp.asarray(final.n_probes, f32),
        "reuse_rate": final.reuse_w.mean,
        "mean_analysis_ms": final.body_w.mean,
        "mean_latency_ms": final.latency_w.mean,
        "mean_wait_ms": final.wait_w.mean,
        "std_wait_ms": welford_std(final.wait_w),
        "probe_mean_ms": final.probe_w.mean,
        "probe_log_std": welford_std(final.log_probe_w),
        "pass_rate": 1.0 - final.n_terminated
        / jnp.maximum(jnp.asarray(final.n_probes, f32), 1.0),
        "bill_n": jnp.stack([final.nb_term, final.nb_pass, final.nb_reuse]),
        "bill_d": jnp.stack([final.db_term, final.db_pass, final.db_reuse]),
        "cost": cost,
        "horizon_ms": final.t_arr,
    }
    return summary, requests


# ---------------------------------------------------------------------------
# Host entry points
# ---------------------------------------------------------------------------

#: compile/call accounting, so sweeps and CI can assert the jit cache hits
#: on the second arm-batch (same shapes → no recompile).
jit_stats = {"compiles": 0, "calls": 0}

_JIT_CACHE: dict = {}


def _get_sim_fn(cfg: SimConfig, batch_shape: tuple):
    cache_key = (cfg, batch_shape)
    if cache_key not in _JIT_CACHE:
        jit_stats["compiles"] += 1

        def run(params, seeds, arm_ids):
            def lane(p, seed, arm):
                key = jax.random.fold_in(jax.random.PRNGKey(seed), arm)
                return _simulate_chain(p, key, cfg)

            per_seed = jax.vmap(lane, in_axes=(None, 0, None))
            return jax.vmap(per_seed, in_axes=(0, None, 0))(
                params, seeds, arm_ids)

        _JIT_CACHE[cache_key] = jax.jit(run)
    return _JIT_CACHE[cache_key]


@dataclasses.dataclass
class VecResult:
    """Grid results as numpy arrays: summary leaves have shape
    (n_arms, n_seeds); per-request leaves (n_arms, n_seeds, n_steps)."""

    summary: dict
    requests: Optional[dict]
    n_arms: int
    n_seeds: int
    n_steps: int

    def mean_over_seeds(self, name: str) -> np.ndarray:
        return np.asarray(self.summary[name]).mean(axis=1)


def simulate_arms(
    arms: ArmParams,
    *,
    seeds,
    n_steps: int,
    pool_size: Optional[int] = None,
    n_streams: int = 1,
    max_attempts: Optional[int] = None,
    collect_requests: bool = False,
) -> VecResult:
    """Run every arm × seed lane through the jitted scan; returns numpy.

    ``n_streams`` is the number of closed-loop virtual users sharing the
    slot pool (the event engine's ``n_vus``; ``n_steps`` stays the TOTAL
    request count across streams). ``pool_size`` defaults to
    ``max(1, n_streams)`` — the smallest pool that can always place a
    cold start — and must be at least ``n_streams`` when given."""
    if n_streams < 1:
        raise ValueError(f"n_streams must be >= 1, got {n_streams}")
    if pool_size is None:
        pool_size = max(1, n_streams)
    if pool_size < n_streams:
        raise ValueError(
            f"pool_size={pool_size} < n_streams={n_streams}: a cold start "
            "could find no free slot (need pool_size >= n_streams)")
    leaves = [np.atleast_1d(np.asarray(x)) for x in arms]
    n_arms = max(leaf.shape[0] for leaf in leaves)
    stacked = ArmParams(*[
        jnp.asarray(np.broadcast_to(leaf, (n_arms,)),
                    jnp.int32 if leaf.dtype.kind in "iu" else jnp.float32)
        for leaf in leaves])
    seeds = np.atleast_1d(np.asarray(seeds, np.uint32))
    max_r = int(np.max(np.asarray(arms.max_retries)))
    if max_attempts is None:
        max_attempts = max_r + 1
    if max_attempts < max_r + 1:
        raise ValueError(
            f"max_attempts={max_attempts} cannot cover max_retries={max_r}")
    adaptive = bool(np.any(np.asarray(arms.gate_mode) == GATE_ADAPTIVE))
    diurnal = bool(np.any(np.asarray(arms.diurnal_amplitude) != 0.0))
    cfg = SimConfig(n_steps=int(n_steps), pool_size=int(pool_size),
                    max_attempts=int(max_attempts),
                    collect_requests=bool(collect_requests),
                    adaptive=adaptive, diurnal=diurnal,
                    n_streams=int(n_streams))
    fn = _get_sim_fn(cfg, (n_arms, len(seeds)))
    jit_stats["calls"] += 1
    summary, requests = fn(stacked, jnp.asarray(seeds),
                           jnp.arange(n_arms, dtype=jnp.uint32))
    summary = {k: np.asarray(v) for k, v in summary.items()}
    if requests is not None:
        # vmap axes lead, scan's step axis last → (arms, seeds, steps)
        requests = {k: np.asarray(v) for k, v in requests.items()}
    if _sanitizer.enabled():
        _sanitizer.check_closed_summary(summary, where="simulate_arms")
    return VecResult(summary=summary, requests=requests, n_arms=n_arms,
                     n_seeds=len(seeds), n_steps=int(n_steps))


def _get_open_sim_fn(cfg: OpenSimConfig, batch_shape: tuple):
    cache_key = (cfg, batch_shape)
    if cache_key not in _JIT_CACHE:
        jit_stats["compiles"] += 1

        def run(params, seeds, arm_ids, iats):
            def lane(p, seed, arm, iat_row):
                key = jax.random.fold_in(jax.random.PRNGKey(seed), arm)
                return _simulate_open_chain(p, key, cfg, iat_row)

            # the arrival stream varies per SEED lane (one realization per
            # seed) and is shared across arms — every arm answers the same
            # offered traffic, which is what makes arms comparable
            per_seed = jax.vmap(lane, in_axes=(None, 0, None, 0))
            return jax.vmap(per_seed, in_axes=(0, None, 0, None))(
                params, seeds, arm_ids, iats)

        _JIT_CACHE[cache_key] = jax.jit(run)
    return _JIT_CACHE[cache_key]


#: one-shot latch for the think-time contract warning below (tests reset
#: it to re-assert the warning fires).
_OPEN_THINK_WARNED = False


def simulate_open_arms(
    arms: ArmParams,
    *,
    seeds,
    iats_ms: np.ndarray,
    n_servers: int = 4,
    max_attempts: Optional[int] = None,
    queue_ring: int = 32,
    drains_per_step: int = 3,
    collect_requests: bool = False,
) -> VecResult:
    """Open-loop variant of :func:`simulate_arms`: instead of a think-time
    loop, the scan consumes ``iats_ms`` — host-generated inter-arrival
    times, shape ``(n_steps,)`` (shared by every seed lane; bit-exact
    trace replay) or ``(n_seeds, n_steps)`` (one realization per seed,
    from :mod:`repro.sim.arrivals`). Each arrival runs the admission
    pipeline (defer at ``ArmParams.admit_bound``, drop at
    ``ArmParams.queue_capacity``) and then waits for the earliest of
    ``n_servers`` slots (the FIFO M/G/K queue at an autoscaling cap of
    ``max_instances = n_servers``); a failed cold probe parks and
    requeues without holding its slot (``queue_ring`` bounds the park
    ring, see :class:`OpenSimConfig`).

    Contract: ``ArmParams.think_time_ms`` is IGNORED here — arrivals
    come from ``iats_ms``, never from a think-time loop. Arms built by
    :func:`arm_from_spec` carry its default ``think_time_ms=1000``, so
    this is warned once per process rather than raised. ``max_attempts``
    is accepted for call-site compatibility and only validated: retries
    cross scan steps via the park ring, so no per-step attempt budget
    shapes the draws."""
    global _OPEN_THINK_WARNED
    if not _OPEN_THINK_WARNED and np.any(
            np.asarray(arms.think_time_ms) != 0.0):
        warnings.warn(
            "simulate_open_arms ignores ArmParams.think_time_ms: arrivals "
            "come from iats_ms, not a think-time loop (arm_from_spec "
            "defaults think_time_ms=1000, so this is expected for arms "
            "shared with the closed-loop scan). Warned once per process.",
            stacklevel=2)
        _OPEN_THINK_WARNED = True
    leaves = [np.atleast_1d(np.asarray(x)) for x in arms]
    n_arms = max(leaf.shape[0] for leaf in leaves)
    stacked = ArmParams(*[
        jnp.asarray(np.broadcast_to(leaf, (n_arms,)),
                    jnp.int32 if leaf.dtype.kind in "iu" else jnp.float32)
        for leaf in leaves])
    seeds = np.atleast_1d(np.asarray(seeds, np.uint32))
    iats = np.asarray(iats_ms, np.float32)
    if iats.ndim == 1:
        iats = np.broadcast_to(iats, (len(seeds), iats.shape[0]))
    if iats.ndim != 2 or iats.shape[0] != len(seeds):
        raise ValueError(
            f"iats_ms must be (n_steps,) or (n_seeds, n_steps); got "
            f"{np.asarray(iats_ms).shape} for {len(seeds)} seeds")
    n_steps = int(iats.shape[1])
    max_r = int(np.max(np.asarray(arms.max_retries)))
    if max_attempts is not None and max_attempts < max_r + 1:
        raise ValueError(
            f"max_attempts={max_attempts} cannot cover max_retries={max_r}")
    caps = np.asarray(arms.queue_capacity, float)
    finite_cap = caps[np.isfinite(caps)]
    if finite_cap.size and float(np.max(finite_cap)) > queue_ring:
        raise ValueError(
            f"queue_capacity={float(np.max(finite_cap)):g} exceeds "
            f"queue_ring={queue_ring}; the in-scan wait-queue counter "
            f"saturates at the ring size, so the drop gate would never "
            f"fire — raise queue_ring")
    adaptive = bool(np.any(np.asarray(arms.gate_mode) == GATE_ADAPTIVE))
    diurnal = bool(np.any(np.asarray(arms.diurnal_amplitude) != 0.0))
    cfg = OpenSimConfig(n_steps=n_steps, n_servers=int(n_servers),
                        queue_ring=int(queue_ring),
                        drains_per_step=int(drains_per_step),
                        collect_requests=bool(collect_requests),
                        adaptive=adaptive, diurnal=diurnal)
    fn = _get_open_sim_fn(cfg, (n_arms, len(seeds)))
    jit_stats["calls"] += 1
    summary, requests = fn(stacked, jnp.asarray(seeds),
                           jnp.arange(n_arms, dtype=jnp.uint32),
                           jnp.asarray(iats))
    summary = {k: np.asarray(v) for k, v in summary.items()}
    if requests is not None:
        requests = {k: np.asarray(v) for k, v in requests.items()}
    if _sanitizer.enabled():
        _sanitizer.check_open_summary(summary, n_steps,
                                      where="simulate_open_arms")
    return VecResult(summary=summary, requests=requests, n_arms=n_arms,
                     n_seeds=len(seeds), n_steps=n_steps)


# ---------------------------------------------------------------------------
# Arm builders (mirror FaaSPlatform's spec/profile knob resolution)
# ---------------------------------------------------------------------------


def arm_from_spec(
    spec,
    variation,
    *,
    profile=None,
    pricing: Optional[Pricing] = None,
    gate: str = "fixed",
    threshold: float = math.inf,
    pass_fraction: float = 0.4,
    max_retries: int = 5,
    warmup_reports: int = 5,
    republish_every: int = 4,
    smoothing_alpha: float = 0.7,
    think_time_ms: float = 1000.0,
    admit_bound: Optional[float] = None,
) -> ArmParams:
    """Build one arm from the event engine's own config objects
    (:class:`~repro.sim.platform.FunctionSpec`,
    :class:`~repro.sim.platform.PlatformProfile`,
    :class:`~repro.sim.variation.VariationModel`) so a parity test or grid
    sweep describes *one* scenario for both engines. ``gate`` is "off"
    (baseline arm), "fixed" (pre-tested ``threshold``) or "adaptive"
    (:class:`~repro.core.policy.AdaptiveMinosPolicy` defaults).

    Per-instance concurrency, the load-slowdown alpha, load-aware gating
    and the finite queue buffer come from the resolved knobs (profile or
    spec); ``admit_bound`` is the static admission cap the open-loop scan
    defers at (:func:`repro.core.control.static_admission_bound` computes
    the event engine's equivalent), ``None`` = admission disabled."""
    gate_mode = {"off": GATE_OFF, "fixed": GATE_FIXED,
                 "adaptive": GATE_ADAPTIVE}[gate]
    if gate_mode == GATE_FIXED and not math.isfinite(threshold):
        raise ValueError("gate='fixed' needs a finite threshold")
    if profile is not None:
        knobs = profile.knobs()
        if pricing is None:
            pricing = profile.pricing
    else:
        from repro.core.substrate import SubstrateKnobs
        knobs = SubstrateKnobs(
            cold_start_ms=spec.cold_start_ms,
            cold_start_jitter=spec.cold_start_jitter,
            idle_timeout_ms=spec.idle_timeout_ms,
            recycle_lifetime_ms=spec.recycle_lifetime_ms,
            bill_cold_start=spec.bill_cold_start,
            requeue_overhead_ms=spec.requeue_overhead_ms,
        )
    if pricing is None:
        raise ValueError("pricing is required when no profile is given")
    return ArmParams(
        sigma=float(variation.sigma),
        day_factor=float(variation.day_factor),
        diurnal_amplitude=float(variation.diurnal_amplitude),
        diurnal_phase_h=float(variation.diurnal_phase_h),
        prepare_ms=float(spec.prepare_ms),
        prepare_jitter=float(spec.prepare_jitter),
        body_ms=float(spec.body_ms),
        body_jitter=float(spec.body_jitter),
        benchmark_ms=float(spec.benchmark_ms),
        benchmark_noise=float(spec.benchmark_noise),
        contention_rho=float(spec.contention_rho),
        cold_start_ms=float(knobs.cold_start_ms),
        cold_start_jitter=float(knobs.cold_start_jitter),
        idle_timeout_ms=float(knobs.idle_timeout_ms),
        recycle_lifetime_ms=(
            math.inf if knobs.recycle_lifetime_ms is None
            else float(knobs.recycle_lifetime_ms)),
        bill_cold_start=1.0 if knobs.bill_cold_start else 0.0,
        requeue_overhead_ms=float(knobs.requeue_overhead_ms),
        requeue_penalty_ms=0.0,
        order=int(ORDER_CODES[knobs.warm_pool_order]),
        gate_mode=int(gate_mode),
        threshold=float(threshold),
        pass_fraction=float(pass_fraction),
        max_retries=int(max_retries),
        warmup_reports=int(warmup_reports),
        republish_every=int(republish_every),
        smoothing_alpha=float(smoothing_alpha),
        think_time_ms=float(think_time_ms),
        cost_per_invocation=float(pricing.cost_per_invocation),
        cost_per_ms=float(pricing.cost_per_ms),
        concurrency=int(knobs.per_instance_concurrency),
        load_slowdown_alpha=float(knobs.load_slowdown_alpha),
        gate_load_aware=1.0 if knobs.gate_load_aware else 0.0,
        queue_capacity=(
            math.inf if knobs.queue_capacity is None
            else float(knobs.queue_capacity)),
        admit_bound=math.inf if admit_bound is None else float(admit_bound),
    )


def stack_arms(arms: list) -> ArmParams:
    """Stack a list of scalar :class:`ArmParams` into one batched pytree."""
    if not arms:
        raise ValueError("need at least one arm")
    return ArmParams(*[
        np.asarray([getattr(a, f) for a in arms]) for f in ArmParams._fields])


# ---------------------------------------------------------------------------
# Event-engine reference chain (the exact scenario the fast path models)
# ---------------------------------------------------------------------------


def run_event_chain(platform, n_requests: int,
                    think_time_ms: float = 1000.0, n_vus: int = 1) -> list:
    """Drive a :class:`~repro.sim.platform.FaaSPlatform` with ``n_vus``
    closed-loop virtual users for exactly ``n_requests`` total
    completions — the event-engine scenario :func:`simulate_arms`
    vectorizes (``n_vus`` maps to its ``n_streams``). All users submit at
    t=0 (like :func:`repro.sim.workload.run_closed_loop`), each resubmits
    ``think_time_ms`` after its completion while the budget lasts. Used
    by the parity tests and as the sweeps' per-arm timing reference."""
    results: list = []
    # budget is reserved at SCHEDULING time, so concurrent completions
    # (n_vus > 1) can never over-submit past n_requests
    budget = n_requests

    def on_complete(res) -> None:
        nonlocal budget
        results.append(res)
        if budget > 0:
            budget -= 1
            platform.loop.after(
                think_time_ms, lambda: platform.submit(None, on_complete))

    for _ in range(min(n_vus, n_requests)):
        budget -= 1
        platform.submit(None, on_complete)
    platform.loop.run_all()
    assert len(results) == n_requests
    return results


__all__ = [
    "ArmParams",
    "GATE_ADAPTIVE",
    "GATE_FIXED",
    "GATE_OFF",
    "ORDER_CODES",
    "OpenSimConfig",
    "SimConfig",
    "VecResult",
    "arm_from_spec",
    "jit_stats",
    "run_event_chain",
    "simulate_arms",
    "simulate_open_arms",
    "stack_arms",
]
