"""Streaming arrival gateway tests: bounded admission / backpressure,
deterministic deadline + priority shedding, the bounded retry + backoff
ladder (and its fall-through into ``ReplanController``), chaos gateway
events, ``ContinuousBatcher`` hardening, and the composed-fault soak with
bitwise replay against the real rollout."""
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.runtime.chaos import FaultSchedule                  # noqa: E402
from repro.runtime.gateway import (ArrivalSchedule,            # noqa: E402
                                   DeviceStallError, GatewayConfig,
                                   LoadGenerator,
                                   SERVED, SHED_DEGRADED,
                                   SHED_DEVICE_FAILURE, SHED_EXPIRED,
                                   SHED_QUEUE_FULL, SHED_REASONS,
                                   StreamingGateway)
from repro.runtime.serve_loop import (ContinuousBatcher,       # noqa: E402
                                      ReplanController, Request)


def stub_solver(T, U, latency=0.01, infeasible_frames=(), record=None):
    """A trace-shaped stand-in: ``feasible [1, T]`` / ``source_latency
    [1, T, U]`` — the only fields the gateway reads from a window."""
    infeasible = set(infeasible_frames)

    def solve(w, arr):
        if record is not None:
            record.append((w, arr.copy()))
        feas = np.ones((1, T), bool)
        for g in infeasible:
            if w * T <= g < (w + 1) * T:
                feas[0, g - w * T] = False
        return SimpleNamespace(
            feasible=feas,
            source_latency=np.full((1, T, U), latency, np.float64))
    return solve


def make_gateway(T=4, U=3, schedule=None, solve=None, record=None,
                 controller=None, sleeps=None, **cfg):
    cfg.setdefault("window_frames", T)
    cfg.setdefault("frame_s", 1.0)
    cfg.setdefault("queue_capacity", 16)
    cfg.setdefault("frame_capacity", 2)
    cfg.setdefault("retry_base_backoff_s", 0.01)
    solve = solve if solve is not None else stub_solver(T, U, record=record)
    sleep = sleeps.append if sleeps is not None else (lambda s: None)
    return StreamingGateway(solve_fn=solve, n_uavs=U, schedule=schedule,
                            controller=controller, sleep=sleep,
                            config=GatewayConfig(**cfg))


# ---------------------------------------------------------------------------
# Arrival sources
# ---------------------------------------------------------------------------


class TestLoadGenerator:
    def test_frame_draws_are_order_independent(self):
        gen = LoadGenerator(4, kind="poisson", rate=2.0, seed=9,
                            deadline_s=5.0, deadline_jitter_s=1.0,
                            priorities=(0, 1))
        fwd = [gen.arrivals(f) for f in range(6)]
        rev = [LoadGenerator(4, kind="poisson", rate=2.0, seed=9,
                             deadline_s=5.0, deadline_jitter_s=1.0,
                             priorities=(0, 1)).arrivals(f)
               for f in reversed(range(6))]
        assert fwd == rev[::-1]

    def test_flood_factor_scales_offered_load(self):
        gen = LoadGenerator(3, kind="poisson", rate=2.0, seed=0)
        n1 = sum(len(gen.arrivals(f)) for f in range(300))
        n4 = sum(len(gen.arrivals(f, flood_factor=4.0))
                 for f in range(300))
        assert n4 > 2.5 * n1          # ~4x in expectation

    def test_flood_kind_is_deterministic_count(self):
        gen = LoadGenerator(3, kind="flood", rate=3.0, seed=0)
        assert all(len(gen.arrivals(f)) == 3 for f in range(5))
        assert len(gen.arrivals(0, flood_factor=2.0)) == 6

    def test_burst_kind_spikes_on_schedule(self):
        gen = LoadGenerator(3, kind="burst", rate=1.0, burst_every=8,
                            burst_frames=2, burst_rate=30.0, seed=1)
        burst = len(gen.arrivals(0)) + len(gen.arrivals(1))
        quiet = sum(len(gen.arrivals(f)) for f in range(2, 8))
        assert burst > quiet

    def test_validation(self):
        with pytest.raises(ValueError):
            LoadGenerator(3, kind="nope")
        with pytest.raises(ValueError):
            LoadGenerator(3, deadline_s=1.0, deadline_jitter_s=2.0)
        with pytest.raises(ValueError):
            LoadGenerator(3, uav_weights=[1.0, 1.0])       # wrong length
        with pytest.raises(ValueError):
            LoadGenerator(3, priorities=(0, 1),
                          priority_weights=[-1.0, 2.0])


class TestArrivalSchedule:
    def test_chained_script_replays_exactly(self):
        ev = (ArrivalSchedule(frames=8)
              .at(2, uav=1, deadline_s=5.0)
              .at(2, uav=0, deadline_s=3.0, priority=0, count=2))
        assert ev.arrivals(2) == [(1, 5.0, 1), (0, 3.0, 0), (0, 3.0, 0)]
        assert ev.arrivals(3) == []
        # scripted counts are explicit: floods don't scale them
        assert ev.arrivals(2, flood_factor=10.0) == ev.arrivals(2)

    def test_validation(self):
        with pytest.raises(ValueError):
            ArrivalSchedule(8).at(9, 0, 1.0)
        with pytest.raises(ValueError):
            ArrivalSchedule(8).at(0, 0, 0.0)
        with pytest.raises(ValueError):
            ArrivalSchedule(8).at(0, 0, 1.0, count=0)


# ---------------------------------------------------------------------------
# Admission: backpressure, expiry, degraded shedding
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_queue_full_sheds_and_never_blocks(self):
        gw = make_gateway(queue_capacity=3)
        outs = [gw.submit(0, 100.0) for _ in range(5)]
        assert [r.admitted for r in outs] == [True] * 3 + [False] * 2
        assert {r.outcome for r in outs[3:]} == {SHED_QUEUE_FULL}
        assert gw.backpressure == 1.0
        assert gw.shed_counts[SHED_QUEUE_FULL] == 2
        assert len(gw.requests) == 5          # every submit is recorded

    def test_expired_on_arrival(self):
        gw = make_gateway()
        r = gw.submit(0, 0.0)
        assert r.outcome == SHED_EXPIRED and not r.admitted

    def test_degraded_token_bucket_sheds_deterministically(self):
        gw = make_gateway(degraded_admit_fraction=0.5)
        gw.degraded = True
        outs = [gw.submit(0, 100.0).admitted for _ in range(8)]
        assert sum(outs) == 4                 # exactly half pass
        # replay: the bucket is state, not randomness
        gw2 = make_gateway(degraded_admit_fraction=0.5)
        gw2.degraded = True
        assert [gw2.submit(0, 100.0).admitted for _ in range(8)] == outs

    def test_invalid_uav_raises(self):
        gw = make_gateway(U=3)
        with pytest.raises(ValueError):
            gw.submit(3, 1.0)


# ---------------------------------------------------------------------------
# Scheduling: deadlines, priorities, slots, expiry before device time
# ---------------------------------------------------------------------------


class TestScheduling:
    def test_earliest_feasible_frame_wins(self):
        rec = []
        gw = make_gateway(record=rec)
        gw.submit(1, 2.5)                       # frames 0/1 only (done 1, 2)
        gw.serve(None, n_windows=1)
        (w, arr), = rec
        assert w == 0 and arr[0, 0, 1] == 1.0 and arr.sum() == 1.0

    def test_expired_is_shed_before_any_device_time(self):
        rec = []
        gw = make_gateway(record=rec, frame_capacity=1)
        # three same-deadline rivals for ONE frame-0 slot; deadline dies
        # before frame 1 completes, so two must be shed pre-device
        rs = [gw.submit(0, 1.0) for _ in range(3)]
        gw.serve(None, n_windows=1)
        assert [r.outcome for r in rs] == [SERVED, SHED_EXPIRED,
                                           SHED_EXPIRED]
        (w, arr), = rec
        assert arr.sum() == 1.0                # shed work never staged
        assert gw.shed_counts[SHED_EXPIRED] == 2

    def test_priority_then_deadline_then_rid(self):
        gw = make_gateway(frame_capacity=1, T=2)
        lo = gw.submit(0, 2.0, priority=5)
        hi = gw.submit(1, 2.0, priority=0)
        gw.serve(None, n_windows=1)
        assert hi.frame == 0 and lo.frame == 1  # urgent class served first

    def test_rid_breaks_ties_bitwise(self):
        def run():
            gw = make_gateway(frame_capacity=1, T=1, queue_capacity=8)
            rs = [gw.submit(u, 1.0) for u in (2, 0, 1)]
            gw.serve(None, n_windows=1)
            return [r.outcome for r in rs]
        assert run() == run() == [SERVED, SHED_EXPIRED, SHED_EXPIRED]

    def test_source_slot_cap_respected(self):
        rec = []
        gw = make_gateway(U=4, record=rec, frame_capacity=4, T=1)
        gw.slots = 2                           # rollout would solve 2 slots
        for u in range(4):
            gw.submit(u, 100.0)
        gw.serve(None, n_windows=2, drain=False)
        for _, arr in rec:
            assert np.count_nonzero(arr[0, 0]) <= 2

    def test_patient_requests_roll_to_the_next_window(self):
        gw = make_gateway(frame_capacity=1, T=1)
        a = gw.submit(0, 50.0)
        b = gw.submit(1, 50.0)
        gw.serve(None, n_windows=2)
        assert (a.outcome, b.outcome) == (SERVED, SERVED)
        assert a.frame == 0 and b.frame == 1   # b waited one window


# ---------------------------------------------------------------------------
# Retry ladder: stalls, backoff, exhaustion, controller fall-through
# ---------------------------------------------------------------------------


class TestRetryLadder:
    def test_stall_absorbed_with_backoff(self):
        sched = FaultSchedule(3, 8, seed=0).device_stall(1, attempts=2)
        sleeps = []
        gw = make_gateway(schedule=sched, sleeps=sleeps, max_attempts=4,
                          retry_base_backoff_s=0.01,
                          retry_max_backoff_s=0.5)
        r = gw.submit(0, 100.0)
        gw.serve(None, n_windows=1)
        assert r.outcome == SERVED
        assert gw.retries == 2
        assert sleeps == [0.01, 0.02]          # exponential backoff
        assert gw.device_failures == 0 and not gw.degraded

    def test_backoff_is_capped(self):
        sched = FaultSchedule(3, 8, seed=0).device_stall(0, attempts=4)
        sleeps = []
        gw = make_gateway(schedule=sched, sleeps=sleeps, max_attempts=8,
                          retry_base_backoff_s=0.01,
                          retry_max_backoff_s=0.04)
        gw.serve(None, n_windows=1)
        assert sleeps == [0.01, 0.02, 0.04, 0.04]

    def test_exhaustion_sheds_window_and_degrades(self):
        sched = FaultSchedule(3, 8, seed=0).device_stall(0, attempts=5)
        gw = make_gateway(schedule=sched, max_attempts=2,
                          degraded_admit_fraction=0.5)
        r = gw.submit(0, 100.0)
        gw.serve(None, n_windows=1, drain=False)
        assert r.outcome == SHED_DEVICE_FAILURE
        assert gw.device_failures == 1 and gw.degraded
        # degraded-mode admission sheds deterministically...
        outs = [gw.submit(0, 100.0).admitted for _ in range(6)]
        assert sum(outs) == 3
        assert gw.shed_counts[SHED_DEGRADED] == 3
        # ...until the next window succeeds (window 1 has no stall)
        gw.serve(None, n_windows=1, drain=False)
        assert not gw.degraded

    def test_always_failing_solver_stays_bounded(self):
        def boom(w, arr):
            raise DeviceStallError("device on fire")
        gw = make_gateway(solve=boom, max_attempts=2)
        for _ in range(3):
            gw.submit(0, 1000.0)
        rep = gw.serve(None, n_windows=3)      # returns — no deadlock
        assert rep["windows_failed"] == 3
        assert rep["served"] == 0
        assert gw.shed_counts[SHED_DEVICE_FAILURE] >= 1

    def test_program_error_propagates_out_of_serve(self):
        """A failed trace, lowering or compile is not a device fault: it
        must surface, not be retried and shed as device failure."""
        attempts = []

        def broken(w, arr):
            attempts.append(w)
            raise ValueError("Mosaic failed to compile TPU kernel")
        gw = make_gateway(solve=broken, max_attempts=3)
        gw.submit(0, 1000.0)
        with pytest.raises(ValueError, match="Mosaic"):
            gw.serve(None, n_windows=2)
        gw.close()
        assert attempts == [0]
        assert gw.retries == 0 and gw.windows_failed == 0
        assert SHED_DEVICE_FAILURE not in gw.shed_counts

    def test_fall_through_to_replan_controller_ladder(self):
        class HealthyStub:
            """Minimal PeriodicReplanner double that always meets SLO."""
            plan = SimpleNamespace(latency=np.array([1.0]), positions=None)
            rollout = None
            horizon = None
            refreshes = 0
            infeasible_refreshes = 0
            nominal_latency = 1.0

        ctl = ReplanController(HealthyStub())
        sched = FaultSchedule(3, 8, seed=0).device_stall(0, attempts=5)
        gw = make_gateway(schedule=sched, max_attempts=2, controller=ctl)
        gw.serve(None, n_windows=2, drain=False)   # window 0 dies, 1 heals
        assert ctl.mode == ctl.NOMINAL and not ctl.shedding
        m = ctl.metrics()
        assert m["n_events"] == 1 and m["n_unrecovered"] == 0
        ev = m["events"][0]
        assert ev["kind"] == "device_exhausted"
        assert ev["rungs"] == [ctl.DEGRADED]
        assert ev["frames_to_recover"] == 4        # one window later


class TestClockSkew:
    def test_negative_skew_expires_otherwise_servable_work(self):
        ok = make_gateway()
        r_ok = ok.submit(0, 2.0)
        ok.serve(None, n_windows=1)
        sched = FaultSchedule(3, 8, seed=0).clock_skew(0, -2.0)
        gw = make_gateway(schedule=sched)
        r_skew = gw.submit(0, 2.0)
        gw.serve(None, n_windows=1)
        assert r_ok.outcome == SERVED
        assert r_skew.outcome == SHED_EXPIRED      # deadline drifted past
        assert r_skew.deadline_s == r_ok.deadline_s - 2.0


# ---------------------------------------------------------------------------
# ContinuousBatcher hardening (satellite)
# ---------------------------------------------------------------------------


class TestContinuousBatcherHardening:
    def _batcher(self, **kw):
        cfg = SimpleNamespace(family="dense")
        scfg = SimpleNamespace(max_seq=32, temperature=0.0, max_batch=2,
                               eos_id=1)
        return ContinuousBatcher(object(), cfg, scfg, None, **kw)

    def test_submit_reports_backpressure_at_capacity(self):
        b = self._batcher(max_pending=2)
        assert b.submit(Request(0, [2, 3]))
        assert b.submit(Request(1, [2, 3]))
        assert not b.submit(Request(2, [2, 3]))    # bounded, not silent
        assert len(b.pending) == 2 and b.rejected == 1

    def test_unbounded_default_keeps_legacy_behavior(self):
        b = self._batcher()
        assert all(b.submit(Request(i, [2])) for i in range(64))
        assert len(b.pending) == 64

    def test_seed_is_injectable(self):
        assert self._batcher(seed=7).seed == 7
        with pytest.raises(ValueError):
            self._batcher(max_pending=0)


# ---------------------------------------------------------------------------
# The soak: composed chaos against the real rollout, replayed bitwise
# ---------------------------------------------------------------------------


class TestSoakComposedChaos:
    """Arrival flood + device stall + correlated crash burst through the
    REAL fused rollout: the gateway must never deadlock, shed every
    unservable request exactly once with a recorded reason, keep the
    deadline-hit-rate of served requests at 100%, and replay the whole
    event stream bitwise — at zero retraces."""

    U, T, WINDOWS = 4, 4, 5

    def _schedule(self):
        return (FaultSchedule(self.U, self.T * self.WINDOWS, seed=5)
                .burst(frame=6, size=2, persistence=0.7)
                .crash(frame=10, uav=0, frames=4)
                .arrival_flood(8, 3.0, frames=4)
                .device_stall(4, attempts=1)
                .clock_skew(12, -1.0, frames=4))

    def _run(self, cache):
        from repro.configs.lenet import LENET
        from repro.core import (RadioChannel, RadioParams, RolloutSpec,
                                cnn_cost, make_devices)
        from repro.core.positions import hex_init
        from repro.runtime.fleet_rollout import FleetRollout

        devs = make_devices(self.U, mem_frac=2e-4)     # forced chain split
        base = hex_init(self.U, 40.0, jitter=0.5, seed=1)
        ro = FleetRollout(RadioChannel(RadioParams()), devs,
                          cnn_cost(LENET),
                          RolloutSpec(frames=self.T, requests_per_frame=3,
                                      recovery_prob=0.5),
                          plan_cache=cache, seed=0)
        gw = StreamingGateway(
            ro, base, GatewayConfig(window_frames=self.T, frame_s=1.0,
                                    queue_capacity=24, frame_capacity=3,
                                    retry_base_backoff_s=0.001,
                                    max_attempts=3),
            schedule=self._schedule(), seed=0)
        gen = LoadGenerator(self.U, kind="burst", rate=1.0, deadline_s=9.0,
                            seed=7, priorities=(0, 1),
                            priority_weights=(0.2, 0.8))
        report = gw.serve(gen, n_windows=self.WINDOWS)
        return gw, report

    def test_soak_invariants_and_bitwise_replay(self):
        from repro.runtime.scenario_engine import PlanFnCache

        cache = PlanFnCache()
        gw, report = self._run(cache)

        # exactly one terminal outcome per submitted request
        outcomes = [r.outcome for r in gw.requests]
        assert all(o == SERVED or o in SHED_REASONS for o in outcomes)
        assert report["served"] + report["shed_total"] == \
            report["submitted"]
        assert report["served"] == outcomes.count(SERVED)
        # the composed faults actually exercised every path
        assert report["retries"] >= 1                  # the stall
        assert gw.shed_counts.get(SHED_QUEUE_FULL, 0) > 0    # the flood
        assert gw.shed_counts.get(SHED_EXPIRED, 0) > 0       # the skew
        # served requests ALL met their deadline
        assert report["deadline_hit_rate"] == 1.0
        for r in gw.served:
            assert (r.frame + 1) * 1.0 <= r.deadline_s
            assert np.isfinite(r.latency_s)

        # bitwise replay: same event stream, fresh stack, shared cache
        gw2, report2 = self._run(cache)
        assert report2 == report
        assert len(gw.arrival_tensors) == len(gw2.arrival_tensors)
        for a, b in zip(gw.arrival_tensors, gw2.arrival_tensors):
            assert np.array_equal(a, b)
        assert [r.outcome for r in gw2.requests] == outcomes

        # zero retraces: both passes rode ONE compiled window program
        assert sum(cache.traces.values()) == 1
