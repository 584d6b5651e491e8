"""Tests for the runtime physics guardrail layer (ISSUE 8).

Covers: the detector primitives (non-finite, calibrated force envelope,
tier ladder helpers), engine-level raise/mark triage with the sampled
LEE probe, typed GuardrailViolation delivery through the single-engine
scheduler, typed per-request deadlines (``RequestTimeout``), the
consecutive-error counter reset pin on the replica worker, tiered-pool
escalation with bit-identical re-runs at the higher tier, the
circuit-breaker quarantine + cold-restart path, the stall watchdog
against the fault injector's engine-lock stall, the four-surface
NaN-poison acceptance (direct engine, scheduler, 4-replica pool,
MDEngine — a caller never receives a silent NaN), MD checkpoint
monitors (non-finite + energy drift), and session-level precision-tier
escalation of a drifting MD chunk.
"""
import dataclasses
import time

import jax
import numpy as np
import pytest

from repro.cluster import ClusterConfig, ClusterPool, Replica
from repro.guardrails import (EscalationRecord, ForceEnvelope,
                              GuardrailConfig, GuardrailViolation, TIER_ORDER,
                              check_finite_tree, check_result, next_tier,
                              tier_rank)
from repro.md.engine import MDConfig, MDEngine
from repro.models import so3krates as so3
from repro.server.scheduler import (MicroBatchScheduler, RequestHandle,
                                    RequestTimeout, SchedulerConfig,
                                    SchedulerClosed, SchedulerOverloaded)
from repro.serving import Graph, QuantizedEngine, ServeConfig
from repro.serving.engine import MoleculeResult
from repro.serving.qparams import quantize_so3_params
from repro.sessions import SessionConfig, SessionManager

CFG = so3.So3kratesConfig(feat=16, vec_feat=4, n_layers=1, n_rbf=4,
                          dir_bits=6, cutoff=3.0)
# the dense path is the one NaN coordinates propagate through (the
# sparse host edge build drops NaN-distance pairs), so every poison
# test below forces it
SERVE4 = ServeConfig(mode="w4a8", bucket_sizes=(16,), max_batch=4,
                     path="dense")
SERVE8 = dataclasses.replace(SERVE4, mode="w8a8")
WAIT_S = 600
# hair-trigger envelope: any real molecule's forces exceed 1e-9 eV/A,
# so every finite result flags "force_outlier" (suspect)
HAIR = GuardrailConfig(envelope=ForceEnvelope(limits=((16, 1e-9),)))


def _graph(n=10, seed=0, density=0.1):
    rng = np.random.default_rng(seed)
    side = (n / density) ** (1.0 / 3.0)
    return Graph(species=rng.integers(0, CFG.n_species, n).astype(np.int32),
                 coords=rng.uniform(0, side, size=(n, 3)).astype(np.float32))


def _poison(n=10, seed=3):
    g = _graph(n, seed)
    coords = g.coords.copy()
    coords[0] = np.nan
    return Graph(species=g.species, coords=coords)


@pytest.fixture(scope="module")
def params():
    return so3.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def qp(params):
    return {t: quantize_so3_params(params, t) for t in ("w4a8", "w8a8")}


@pytest.fixture(scope="module")
def guarded_engine(qp):
    # default guardrails: non-finite check on, on_flag="raise"
    return QuantizedEngine.from_quantized(CFG, qp["w4a8"], SERVE4)


@pytest.fixture(scope="module")
def ref8(qp):
    return QuantizedEngine.from_quantized(CFG, qp["w8a8"], SERVE8)


# -- detectors (pure numpy) --------------------------------------------------

class TestDetectors:
    def test_nonfinite_is_fatal_and_first(self):
        cfg = GuardrailConfig(envelope=ForceEnvelope(limits=((16, 1e-9),)))
        flags = check_result(np.nan, np.full((16, 3), np.nan), 16, cfg)
        assert len(flags) == 1            # garbage norms are not reported
        assert flags[0].reason == "nonfinite" and flags[0].fatal

    def test_envelope_flags_suspect_outlier(self):
        cfg = GuardrailConfig(envelope=ForceEnvelope(limits=((16, 0.5),)))
        f = np.zeros((16, 3), np.float32)
        f[3, 0] = 2.0
        flags = check_result(-1.0, f, 16, cfg)
        assert [x.reason for x in flags] == ["force_outlier"]
        assert not flags[0].fatal
        assert flags[0].value == pytest.approx(2.0)
        assert flags[0].limit == pytest.approx(0.5)
        # unknown bucket -> no limit -> no flag
        assert check_result(-1.0, f, 32, cfg) == ()

    def test_clean_result_unflagged(self):
        cfg = GuardrailConfig(envelope=ForceEnvelope(limits=((16, 10.0),)))
        assert check_result(-1.0, np.ones((16, 3), np.float32), 16, cfg) == ()

    def test_calibrate_builds_per_bucket_limits(self):
        def res(cap, peak):
            f = np.zeros((cap, 3), np.float32)
            f[0, 0] = peak
            return MoleculeResult(energy=-1.0, forces=f, n_atoms=cap,
                                  bucket_capacity=cap, batch_size=1)
        env = ForceEnvelope.calibrate(
            [res(16, 2.0), res(16, 3.0), res(32, 0.01)],
            factor=4.0, floor=1.0)
        assert env.limit_for(16) == pytest.approx(12.0)   # 4 x max observed
        assert env.limit_for(32) == pytest.approx(1.0)    # floored
        assert env.limit_for(64) is None

    def test_check_finite_tree(self):
        clean = {"a": np.ones(3), "b": np.zeros((2, 2))}
        assert check_finite_tree(clean) is None
        clean["b"] = np.array([[1.0, np.inf], [0.0, 0.0]])
        assert check_finite_tree(clean) == "b"

    def test_config_validation(self):
        with pytest.raises(ValueError, match="on_flag"):
            GuardrailConfig(on_flag="explode")
        with pytest.raises(ValueError, match="lee_probe_every"):
            GuardrailConfig(lee_probe_every=-1)
        assert not GuardrailConfig(check_finite=False).active
        assert GuardrailConfig().active

    def test_tier_ladder(self):
        assert TIER_ORDER == ("w4a8", "w8a8", "fp32")
        assert [tier_rank(t) for t in TIER_ORDER] == [0, 1, 2]
        assert next_tier("w4a8") == "w8a8"
        assert next_tier("w8a8") == "fp32"
        assert next_tier("fp32") is None
        with pytest.raises(ValueError):
            tier_rank("w2a4")


# -- engine surface ----------------------------------------------------------

class TestEngineGuardrails:
    def test_poison_raises_typed_violation(self, guarded_engine):
        with pytest.raises(GuardrailViolation) as ei:
            guarded_engine.infer_batch([_poison()])
        assert ei.value.reason == "nonfinite"
        assert ei.value.severity == "fatal"
        assert ei.value.detail["mode"] == "w4a8"

    def test_mark_mode_annotates_instead_of_raising(self, guarded_engine):
        results = guarded_engine.infer_batch([_graph(), _poison()],
                                             on_flag="mark")
        assert results[0].flags == ()
        assert [f.reason for f in results[1].flags] == ["nonfinite"]
        snap = guarded_engine.guard_snapshot()
        assert snap["checked"] >= 2
        assert snap["flagged_nonfinite"] >= 1

    def test_envelope_marks_every_result(self, qp):
        eng = QuantizedEngine.from_quantized(CFG, qp["w4a8"], SERVE4,
                                             guardrails=HAIR)
        results = eng.infer_batch([_graph(8), _graph(12, seed=1)],
                                  on_flag="mark")
        for r in results:
            assert [f.reason for f in r.flags] == ["force_outlier"]
            assert np.isfinite(r.energy)
        assert eng.guard_snapshot()["flagged_outlier"] >= 2

    def test_lee_probe_samples_batches(self, qp):
        # generous limit: the probe runs but never flags clean traffic
        eng = QuantizedEngine.from_quantized(
            CFG, qp["w4a8"], SERVE4,
            guardrails=GuardrailConfig(lee_probe_every=1, lee_limit=1e6))
        results = eng.infer_batch([_graph(), _graph(seed=1)], on_flag="mark")
        assert all(r.flags == () for r in results)
        assert eng.guard_snapshot()["lee_probes"] == 1
        # hair-trigger limit: the same probe flags every molecule
        eng2 = QuantizedEngine.from_quantized(
            CFG, qp["w4a8"], SERVE4,
            guardrails=GuardrailConfig(lee_probe_every=1, lee_limit=0.0))
        flagged = eng2.infer_batch([_graph()], on_flag="mark")
        assert [f.reason for f in flagged[0].flags] == ["lee"]
        assert eng2.guard_snapshot()["flagged_lee"] == 1

    def test_inactive_config_skips_checks(self, qp):
        eng = QuantizedEngine.from_quantized(
            CFG, qp["w4a8"], SERVE4,
            guardrails=GuardrailConfig(check_finite=False))
        # the unguarded A/B baseline: NaN passes through unflagged
        r = eng.infer_batch([_poison()])[0]
        assert not np.isfinite(r.energy)
        assert r.flags == ()
        assert eng.guard_snapshot()["checked"] == 0


# -- scheduler surface -------------------------------------------------------

class TestSchedulerGuardrails:
    def test_poison_resolves_typed_error_clean_unaffected(self,
                                                          guarded_engine):
        with MicroBatchScheduler(
                guarded_engine,
                SchedulerConfig(max_batch=4, deadline_ms=2.0,
                                warmup=False)) as sched:
            clean = [sched.submit(_graph(seed=s)) for s in range(3)]
            bad = sched.submit(_poison())
            for h in clean:
                assert np.isfinite(h.result(timeout=WAIT_S).energy)
            with pytest.raises(GuardrailViolation) as ei:
                bad.result(timeout=WAIT_S)
            assert ei.value.reason == "nonfinite"
            assert sched.stats()["n_guard_flagged"] >= 1


# -- typed deadlines (satellite a) -------------------------------------------

class TestRequestTimeout:
    def test_unresolved_handle_times_out_typed(self):
        h = RequestHandle(None, time.monotonic())
        t0 = time.monotonic()
        with pytest.raises(RequestTimeout):
            h.result(timeout_s=0.05)
        assert time.monotonic() - t0 < 5.0
        assert issubclass(RequestTimeout, TimeoutError)

    def test_timeout_s_wins_over_legacy_timeout(self):
        h = RequestHandle(None, time.monotonic())
        with pytest.raises(RequestTimeout):
            h.result(timeout=30.0, timeout_s=0.05)

    def test_legacy_timeout_stays_catchable_as_timeouterror(self):
        # pre-PR-8 callers catch TimeoutError; the typed error is a
        # subclass, so the legacy kwarg keeps working unchanged
        h = RequestHandle(None, time.monotonic())
        with pytest.raises(TimeoutError):
            h.result(timeout=0.05)


# -- replica error-counter reset pin (satellite c) ---------------------------

class _ScriptedEngine:
    """Minimal engine stub: pops one scripted outcome per flush —
    an exception instance raises, anything else returns clean results."""

    def __init__(self, script):
        self.serve = SERVE4
        self.device = None
        self.artifact_version = ""
        self.script = list(script)

    def warmup(self):
        return 0.0

    def infer_batch(self, graphs, on_flag=None):
        act = self.script.pop(0)
        if isinstance(act, BaseException):
            raise act
        return [MoleculeResult(energy=-1.0,
                               forces=np.zeros((16, 3), np.float32),
                               n_atoms=g.n_atoms, bucket_capacity=16,
                               batch_size=len(graphs)) for g in graphs]


class TestConsecutiveErrorReset:
    def test_mid_window_success_resets_counter(self):
        """Two errors, a success, two errors again: the consecutive
        error counter must reset on the success, so the replica (with
        MAX_CONSECUTIVE_ERRORS=3) survives 4 total errors — only 3 in
        a row kill it."""
        boom = [RuntimeError(f"boom{i}") for i in range(7)]
        script = [boom[0], boom[1], "ok", boom[2], boom[3], "ok"]
        failures = []
        rep = Replica(0, _ScriptedEngine(script),
                      SchedulerConfig(max_batch=1, deadline_ms=0.0,
                                      warmup=False, max_queue=None),
                      on_failure=lambda r, orphans, e: failures.append(e),
                      warmup=False)
        try:
            for want_error in (True, True, False, True, True, False):
                h = RequestHandle(_graph(), time.monotonic(),
                                  bucket_capacity=16)
                assert rep.try_submit(h)
                if want_error:
                    with pytest.raises(RuntimeError, match="boom"):
                        h.result(timeout=WAIT_S)
                else:
                    assert h.result(timeout=WAIT_S).energy == -1.0
            assert rep.accepting
            assert failures == []
            # ...and three in a row still kill it
            rep2 = Replica(1, _ScriptedEngine([boom[4], boom[5], boom[6]]),
                           SchedulerConfig(max_batch=1, deadline_ms=0.0,
                                           warmup=False, max_queue=None),
                           on_failure=lambda r, orphans, e:
                               failures.append(e),
                           warmup=False)
            try:
                for _ in range(3):
                    h = RequestHandle(_graph(), time.monotonic(),
                                      bucket_capacity=16)
                    assert rep2.try_submit(h)
                    with pytest.raises(RuntimeError, match="boom"):
                        h.result(timeout=WAIT_S)
                deadline = time.monotonic() + 10.0
                while rep2.accepting and time.monotonic() < deadline:
                    time.sleep(0.01)
                assert not rep2.accepting
                assert len(failures) == 1
            finally:
                rep2.close()
        finally:
            rep.close()


# -- tiered escalation -------------------------------------------------------

@pytest.fixture(scope="module")
def tiered_pool(qp):
    """Two hair-trigger w4a8 traffic replicas + one w8a8 escalation
    replica: every finite w4a8 result flags suspect and escalates."""
    engines = [
        QuantizedEngine.from_quantized(CFG, qp["w4a8"], SERVE4,
                                       guardrails=HAIR),
        QuantizedEngine.from_quantized(CFG, qp["w4a8"], SERVE4,
                                       guardrails=HAIR),
        QuantizedEngine.from_quantized(CFG, qp["w8a8"], SERVE8),
    ]
    pool = ClusterPool(engines, ClusterConfig(n_replicas=3, max_batch=4,
                                              deadline_ms=2.0, warmup=False,
                                              max_escalations=1))
    yield pool
    pool.close()


class TestTieredEscalation:
    def test_escalated_result_is_bit_identical_to_direct_w8a8(
            self, tiered_pool, ref8):
        g = _graph(10, seed=11)
        r = tiered_pool.submit(g).result(timeout=WAIT_S)
        assert len(r.escalations) == 1
        rec = r.escalations[0]
        assert isinstance(rec, EscalationRecord)
        assert rec.from_tier == "w4a8"
        assert rec.to_tier == "w8a8"
        assert rec.reason == "force_outlier"
        assert r.replica_id == 2          # served by the escalation replica
        assert r.flags == ()              # w8a8 has no envelope
        direct = ref8.infer_batch([g])[0]
        assert r.energy == direct.energy
        assert np.array_equal(np.asarray(r.forces),
                              np.asarray(direct.forces))

    def test_escalation_budget_then_typed_fatal(self, tiered_pool):
        """NaN flags fatal at w4a8, re-runs once at w8a8 (still NaN),
        and with the budget spent resolves a typed error — never a
        silent NaN."""
        h = tiered_pool.submit(_poison(seed=23))
        with pytest.raises(GuardrailViolation) as ei:
            h.result(timeout=WAIT_S)
        assert ei.value.reason == "nonfinite"
        assert ei.value.detail["mode"] == "w8a8"   # failed at the top hop
        assert len(h.escalations) == 1
        assert h.escalations[0].reason == "nonfinite"

    def test_stats_expose_tiers_and_escalations(self, tiered_pool):
        st = tiered_pool.stats()
        assert st["tiers"] == {"w4a8": 2, "w8a8": 1}
        gr = st["guardrails"]
        assert gr["n_flagged"] >= 2
        assert gr["n_escalated"] >= 2
        assert gr["detectors"]["flagged_outlier"] >= 1


# -- circuit breaker / quarantine --------------------------------------------

class TestCircuitBreaker:
    def test_flag_storm_trips_breaker_and_respawns(self, qp):
        """A single-tier fleet whose every result flags suspect: the
        watchdog's breaker must quarantine + cold-restart a replica
        while every submitted request still resolves (zero lost)."""
        engines = [QuantizedEngine.from_quantized(CFG, qp["w8a8"], SERVE8,
                                                  guardrails=HAIR)
                   for _ in range(2)]
        pool = ClusterPool(engines, ClusterConfig(
            n_replicas=2, max_batch=4, deadline_ms=2.0, warmup=False,
            breaker_window=8, breaker_flag_rate=0.5, breaker_min_events=4,
            watchdog_interval_s=0.05, probation_s=30.0, max_quarantines=1))
        try:
            delivered = 0
            for i in range(16):
                # stop feeding once the breaker fired: a second trip
                # with the first replica still on probation would leave
                # an outstanding handle nowhere to requeue
                if pool.stats()["guardrails"]["n_breaker_trips"] >= 1:
                    break
                try:
                    r = pool.submit(_graph(seed=i)).result(timeout=WAIT_S)
                except (SchedulerOverloaded, SchedulerClosed):
                    time.sleep(0.05)
                    continue              # fleet momentarily unroutable
                # suspect with no higher tier -> delivered annotated
                assert np.isfinite(r.energy)
                assert [f.reason for f in r.flags] == ["force_outlier"]
                delivered += 1
            assert delivered >= 4         # enough to arm the breaker
            # the trip is counted before the watchdog's quarantine has
            # cold-restarted the replica: wait for the restart as well
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                gr = pool.stats()["guardrails"]
                if gr["n_breaker_trips"] >= 1 and gr["n_respawned"] >= 1:
                    break
                time.sleep(0.05)
            gr = pool.stats()["guardrails"]
            assert gr["n_breaker_trips"] >= 1
            assert gr["n_quarantined"] >= 1
            assert gr["n_respawned"] >= 1
            # respawned replica is held on probation, not serving
            snaps = pool.stats()["replicas"]
            assert any(s["on_probation"] for s in snaps)
        finally:
            pool.close()


# -- stall watchdog ----------------------------------------------------------

class TestStallWatchdog:
    def test_stalled_worker_quarantined_requests_failover(self, qp):
        pool = ClusterPool(
            [QuantizedEngine.from_quantized(CFG, qp["w8a8"], SERVE8)
             for _ in range(2)],
            # warmup=True: the watchdog cannot tell a first-flush
            # compile from a stall, so a watchdog fleet pre-compiles
            ClusterConfig(n_replicas=2, max_batch=4, deadline_ms=2.0,
                          warmup=True, stall_timeout_s=0.4,
                          watchdog_interval_s=0.05, probation_s=0.1))
        try:
            rep0 = pool._replicas[0]
            rep0.inject_stall(30.0)
            # pin one request to the stalling replica, spread a few more
            pinned = RequestHandle(_graph(seed=41), time.monotonic(),
                                   bucket_capacity=16)
            assert rep0.try_submit(pinned)
            others = [pool.submit(_graph(seed=50 + i)) for i in range(3)]
            t0 = time.monotonic()
            results = [pinned.result(timeout=WAIT_S)] \
                + [h.result(timeout=WAIT_S) for h in others]
            # failover beat the stall: nothing waited out the 30s sleep
            assert time.monotonic() - t0 < 25.0
            for r in results:
                assert np.isfinite(r.energy)
            assert pinned.n_requeues >= 1
            assert pinned.replica_id == 1   # survivor completed it
            gr = pool.stats()["guardrails"]
            assert gr["n_stalls_detected"] >= 1
            assert gr["n_quarantined"] >= 1
            # failover resolves the handles before the cold restart
            # finishes (warmup=True re-JITs): poll for the respawn
            deadline = time.monotonic() + 60.0
            while (pool.stats()["guardrails"]["n_respawned"] < 1
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert pool.stats()["guardrails"]["n_respawned"] >= 1
        finally:
            pool.close()


# -- MD checkpoint monitors --------------------------------------------------

def _md_batch(n=8, seed=5):
    rng = np.random.default_rng(seed)
    side = (n / 0.1) ** (1.0 / 3.0)
    species = rng.integers(0, CFG.n_species, (1, n)).astype(np.int32)
    coords = rng.uniform(0, side, size=(1, n, 3)).astype(np.float32)
    mask = np.ones((1, n), bool)
    masses = np.full(n, 12.0, np.float32)
    return species, coords, mask, masses


class TestMDGuardrails:
    def test_clean_run_passes_finite_check(self, params):
        eng = MDEngine(CFG, params=params,
                       md=MDConfig(mode="w8a8", dt_fs=0.25, record_every=5))
        sp, co, mask, masses = _md_batch()
        st = eng.init_state(jax.random.PRNGKey(1), sp, co, mask, masses)
        _, rec = eng.run(st, sp, mask, masses, n_steps=10)
        assert np.isfinite(rec["e_tot"]).all()

    def test_nonfinite_state_raises_typed(self, params):
        eng = MDEngine(CFG, params=params,
                       md=MDConfig(mode="w8a8", dt_fs=0.25, record_every=5))
        sp, co, mask, masses = _md_batch()
        st = eng.init_state(jax.random.PRNGKey(1), sp, co, mask, masses)
        st = st._replace(veloc=np.full_like(np.asarray(st.veloc), np.nan))
        with pytest.raises(GuardrailViolation) as ei:
            eng.run(st, sp, mask, masses, n_steps=10)
        assert ei.value.reason == "nonfinite"
        assert ei.value.severity == "fatal"
        assert ei.value.detail["mode"] == "w8a8"

    def test_drift_limit_raises_suspect(self, params):
        eng = MDEngine(CFG, params=params,
                       md=MDConfig(mode="w4a8", dt_fs=0.5, record_every=5,
                                   drift_limit=1e-12))
        sp, co, mask, masses = _md_batch(seed=9)
        st = eng.init_state(jax.random.PRNGKey(2), sp, co, mask, masses)
        with pytest.raises(GuardrailViolation) as ei:
            eng.run(st, sp, mask, masses, n_steps=20)
        assert ei.value.reason == "energy_drift"
        assert ei.value.severity == "suspect"
        assert ei.value.detail["mode"] == "w4a8"
        assert ei.value.detail["value"] > ei.value.detail["limit"]

    def test_drift_limit_validation(self):
        with pytest.raises(ValueError, match="drift_limit"):
            MDConfig(drift_limit=0.0)


# -- session-level tier escalation -------------------------------------------

class TestSessionEscalation:
    def test_drifting_chunk_escalates_then_fails_typed(self, params,
                                                       tmp_path):
        """drift_limit=1e-12 fails every tier: the manager re-runs the
        chunk once at w8a8 (min_tier routing), then surfaces the typed
        error from the escalated tier."""
        pool = ClusterPool.from_tiers(
            CFG, params=params, serve=SERVE4,
            tier_plan={"w4a8": 1, "w8a8": 1},
            cluster=ClusterConfig(n_replicas=2, max_batch=4,
                                  deadline_ms=2.0, warmup=False))
        try:
            mgr = SessionManager(pool, str(tmp_path))
            rng = np.random.default_rng(13)
            n = 10
            side = (n / 0.1) ** (1.0 / 3.0)
            session = mgr.start(
                rng.integers(0, CFG.n_species, n).astype(np.int32),
                rng.uniform(0, side, size=(n, 3)).astype(np.float32),
                np.full(n, 12.0, np.float32),
                config=SessionConfig(
                    n_steps=20, chunk_steps=20, record_every=5,
                    max_escalations=1,
                    md=MDConfig(mode="w4a8", dt_fs=0.5, record_every=5,
                                drift_limit=1e-12)),
                seed=7)
            with pytest.raises(GuardrailViolation) as ei:
                session.wait(WAIT_S)
            assert ei.value.reason == "energy_drift"
            assert ei.value.detail["mode"] == "w8a8"   # the escalated tier
            assert session.status == "failed"
            assert session.n_escalations == 1
            st = pool.stats()
            assert st["sessions"]["chunk_escalations"] == 1
            assert st["sessions"]["failed"] == 1
            mgr.close()
        finally:
            pool.close()


# -- four-surface NaN-poison acceptance (satellite d) ------------------------

@pytest.fixture(scope="module")
def pool4(qp):
    pool = ClusterPool.from_quantized(
        CFG, qp["w4a8"], SERVE4,
        cluster=ClusterConfig(n_replicas=4, max_batch=4, deadline_ms=2.0,
                              warmup=False))
    yield pool
    pool.close()


class TestFourSurfacePoison:
    """One NaN molecule through each serving surface: a typed error (or
    tier escalation, covered above) every time — never a silent NaN."""

    def test_direct_engine(self, guarded_engine):
        with pytest.raises(GuardrailViolation):
            guarded_engine.infer_batch([_poison(seed=31)])

    def test_scheduler(self, guarded_engine):
        with MicroBatchScheduler(
                guarded_engine,
                SchedulerConfig(max_batch=4, deadline_ms=2.0,
                                warmup=False)) as sched:
            with pytest.raises(GuardrailViolation):
                sched.submit(_poison(seed=32)).result(timeout=WAIT_S)

    def test_replica_pool(self, pool4):
        clean = [pool4.submit(_graph(seed=60 + i)) for i in range(4)]
        bad = pool4.submit(_poison(seed=33))
        for h in clean:
            assert np.isfinite(h.result(timeout=WAIT_S).energy)
        with pytest.raises(GuardrailViolation) as ei:
            bad.result(timeout=WAIT_S)
        assert ei.value.reason == "nonfinite"
        # single-tier pool: fatal resolves locally, no escalation hops
        assert bad.escalations == []

    def test_md_engine(self, params):
        eng = MDEngine(CFG, params=params,
                       md=MDConfig(mode="w4a8", dt_fs=0.25, record_every=5))
        sp, co, mask, masses = _md_batch(seed=21)
        st = eng.init_state(jax.random.PRNGKey(3), sp, co, mask, masses)
        st = st._replace(coords=np.where(mask[..., None],
                                         np.nan, np.asarray(st.coords)))
        with pytest.raises(GuardrailViolation):
            eng.run(st, sp, mask, masses, n_steps=10)

    # kept last: the injected stalls linger on pool4's replicas until
    # their next unit of work, so nothing else should reuse the fixture
    def test_pool_result_deadline_is_typed(self, pool4):
        for rep in pool4._replicas:
            rep.inject_stall(1.0)
        h = pool4.submit(_graph(seed=70))
        with pytest.raises(RequestTimeout):
            h.result(timeout_s=0.05)
        # the same handle still resolves once the stall clears
        assert np.isfinite(h.result(timeout=WAIT_S).energy)
