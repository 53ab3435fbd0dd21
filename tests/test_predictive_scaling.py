"""Predictive vs reactive scaling on a seeded rate-step trace.

A deterministic discrete-time queue simulator drives the real scaling
policies (:class:`ForecastPolicy` vs :class:`ThresholdHysteresisPolicy` vs
:class:`PIDScalingPolicy`) on the same arrival trace: a noisy rate step
served at ``MU`` records/s/device, where every rescale pays a migration
pause that is fed back to the policies as
``MetricsSnapshot.state_migration_ms``, the signal the forecast policy's
migration gate consumes.

The controller mechanics mirror ``ElasticController``: cooldown gated
before the policy is consulted, relative deltas in lease units, absolute
targets rounded up on grow and down on shrink, clamped to
``[MIN_DEVICES, MAX_DEVICES]``. Two costs are integrated over the run and
the forecast policy must win both: ``lag_seconds`` (the backlog integral,
the SLO side) and ``device_seconds`` (devices held, the cost side).
"""
import math
import random

import pytest

from repro.elastic import (
    ForecastPolicy,
    MetricsSnapshot,
    PIDScalingPolicy,
    ThresholdHysteresisPolicy,
)

DT = 0.25               # simulator tick (s) == controller interval
MU = 50.0               # true service rate (records/s/device)
MIGRATION_S = 0.4       # rescale pause: quiesce + snapshot + restore
MIN_DEVICES, MAX_DEVICES = 1, 8
COOLDOWN = 1.0          # ElasticConfig.cooldown
MIGRATION_COST_FRAC = 0.1  # ElasticConfig.migration_cost_frac (amortization)
SEED = 7

#: (duration_s, arrival records/s): calm, surge, partial relief, calm
TRACE = ((20.0, 40.0), (30.0, 220.0), (25.0, 120.0), (25.0, 40.0))


def _policies():
    return {
        "threshold": ThresholdHysteresisPolicy(
            high_lag=80.0, low_lag=15.0, up_stable=2, down_stable=3),
        "pid": PIDScalingPolicy(
            target_lag=40.0, kp=1.0, ki=0.1, kd=0.0, lag_per_device=100.0),
        # gain ratio > 1: a one-device nudge while the backlog drains is
        # not worth a migration pause; the big corrections still clear it
        "forecast": ForecastPolicy(
            target_lag=20.0, horizon=3.0, headroom=0.1,
            min_observations=3, migration_gain_ratio=2.0),
    }


def _rate_at(trace, t):
    for dur, rate in trace:
        if t < dur:
            return rate
        t -= dur
    return trace[-1][1]


def simulate(policy, trace, *, seed=SEED):
    """Run one policy over the trace; identical seeded arrival noise per
    policy, so the comparison is purely the scaling behavior."""
    rng = random.Random(seed)
    n_ticks = int(round(sum(d for d, _ in trace) / DT))
    devices, lag = MIN_DEVICES, 0.0
    pause_left = 0.0
    migration_ms, migration_t = 0.0, 0.0
    last_action_t = -COOLDOWN
    lag_seconds = device_seconds = 0.0
    # throughput gauge averaged since the last policy-visible snapshot, like
    # a real bus gauge, and consistent with d(lag)/dt over the same window
    # (per-tick rates would break flow conservation across a pause)
    served_acc, cap_acc, snap_t = 0.0, 0.0, -DT

    for i in range(n_ticks):
        t = i * DT
        arrivals = _rate_at(trace, t) * max(rng.gauss(1.0, 0.03), 0.0) * DT
        capacity = 0.0 if pause_left > 0 else MU * devices * DT
        pause_left = max(pause_left - DT, 0.0)
        served = min(lag + arrivals, capacity)
        lag = lag + arrivals - served
        lag_seconds += lag * DT
        device_seconds += devices * DT
        served_acc += served
        cap_acc += capacity

        # ElasticController.step: cooldown and the migration-amortization
        # deferral both gate BEFORE the policy runs, so gated ticks produce
        # no snapshot for the policy to observe
        if t - last_action_t < COOLDOWN:
            continue
        if migration_ms > 0 and \
                t - migration_t < (migration_ms / 1e3) / MIGRATION_COST_FRAC:
            continue
        window = t - snap_t
        snap = MetricsSnapshot(
            t=t, lag=lag, records_per_sec=served_acc / window,
            processing_delay=0.0, scheduling_delay=0.0,
            busy_frac=served_acc / cap_acc if cap_acc > 0 else 1.0,
            devices_total=MAX_DEVICES, devices_leased=devices,
            utilization=devices / MAX_DEVICES, pipeline_devices=devices,
            state_migration_ms=migration_ms, state_migration_t=migration_t,
        )
        served_acc, cap_acc, snap_t = 0.0, 0.0, t
        decision = policy.decide(snap)
        delta = decision.delta_devices
        if delta == 0:
            continue
        if decision.absolute:
            n = abs(delta)
            want = math.ceil(n) if delta > 0 else n  # lease step == 1 device
        else:
            want = abs(delta)
        target = devices + want if delta > 0 else devices - want
        target = max(MIN_DEVICES, min(MAX_DEVICES, target))
        if target == devices:
            continue
        devices = target
        last_action_t = t
        pause_left = MIGRATION_S  # the rescale pause starts next tick
        migration_ms, migration_t = MIGRATION_S * 1e3, t

    return {"lag_seconds": lag_seconds, "device_seconds": device_seconds}


@pytest.mark.parametrize("scale", [0.5, 1.0])
def test_forecast_policy_beats_reactive_policies_on_both_costs(scale):
    """On the same seeded rate step, the forecast policy accrues less
    backlog and holds fewer device-seconds than the better of threshold
    hysteresis and PID, on the half-length and the full-length trace."""
    trace = tuple((d * scale, r) for d, r in TRACE)
    results = {name: simulate(p, trace) for name, p in _policies().items()}
    fc = results.pop("forecast")
    for cost in ("lag_seconds", "device_seconds"):
        best_reactive = min(r[cost] for r in results.values())
        assert fc[cost] < best_reactive, (cost, fc, results)
