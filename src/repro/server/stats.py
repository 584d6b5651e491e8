"""Latency/throughput accounting shared by the scheduler, the traffic
harness, and ``benchmarks/server_bench.py``.

Percentiles are computed over *request* latencies (one sample per
molecule, not per batch) with linear interpolation — the convention the
serving literature reports p50/p95/p99 in. Open-loop latency is measured
from the request's **scheduled arrival time**, not from when the driver
thread actually managed to submit it, so a driver that falls behind under
overload cannot hide queueing delay (coordinated omission).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.obs.trace import RUNTIME, stage

__all__ = ["latency_summary", "FlushRecord", "FlushClock", "flush_summary"]


def latency_summary(latencies_s: Sequence[float],
                    span_s: Optional[float] = None) -> Dict[str, float]:
    """p50/p95/p99/mean/max latency (milliseconds) + throughput over the
    span (requests/s). ``span_s`` is first-arrival -> last-completion;
    when omitted only the latency fields are filled."""
    lat = np.asarray(latencies_s, dtype=np.float64)
    if lat.size == 0:
        raise ValueError("no latency samples")
    out = {
        "n_requests": int(lat.size),
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p95_ms": float(np.percentile(lat, 95) * 1e3),
        "p99_ms": float(np.percentile(lat, 99) * 1e3),
        "mean_ms": float(lat.mean() * 1e3),
        "max_ms": float(lat.max() * 1e3),
    }
    if span_s is not None:
        out["span_s"] = float(span_s)
        out["throughput_rps"] = float(lat.size / max(span_s, 1e-9))
    return out


@dataclasses.dataclass(frozen=True)
class FlushRecord:
    """One scheduler flush: which shape class ran, where, and why."""
    capacity: int        # bucket the flushed queue belongs to
    n_requests: int      # real molecules in the flush
    reason: str          # "full" | "deadline" | "drain"
    queue_depth: int     # total requests waiting across all queues, pre-pop
    wait_s: float        # oldest request's queue residence at flush time
    service_s: float     # infer_batch wall clock for the flush
    path: str            # execution path the batch took (dense/sparse)
    batch_size: int = 0  # compiled batch rows (incl. alignment dummies)
    replica_id: int = 0  # replica that served the flush (0: single engine)
    # obs linkage: trace ids of the requests in this flush (empty when
    # tracing is disabled); joins flush telemetry to per-request traces
    trace_ids: tuple = ()
    # per-flush serve-time breakdown from the engine's stage spans
    # (repro.obs.trace.stage): prep (padding), dispatch (kernel submit),
    # device sync, unpack (rows into MoleculeResults), guard (guardrail
    # checks and the LEE probe)
    prep_s: float = 0.0
    dispatch_s: float = 0.0
    sync_s: float = 0.0
    # monotonic flush start time: places the flush on the fleet
    # timeline (repro.obs.timeline); 0.0 = recorded pre-timeline
    t_start: float = 0.0
    unpack_s: float = 0.0
    guard_s: float = 0.0
    # worker time from the previous flush's sync end to this flush's
    # dispatch end, when the device holds no work of this worker's
    # (0.0 on a worker's first flush), and the part of it spent waiting
    # for a flush to trigger (sched.wait)
    gap_s: float = 0.0
    idle_s: float = 0.0
    # process-wide runtime counters since the worker's previous flush:
    # XLA compiles plus compile-cache loads, and Python GC pause seconds
    compiles: int = 0
    gc_s: float = 0.0


class FlushClock:
    """Stage spans and ``FlushRecord`` of one serving worker's flushes.

    The single-engine scheduler and every cluster replica run one each,
    so their spans and records cannot drift apart. Per flush the worker
    thread shows, flat and in order on the profiler's host plane:
    ``sched.wait`` (:meth:`wait`), the engine's ``engine.prep`` /
    ``engine.dispatch`` / ``engine.sync`` / ``engine.unpack`` /
    ``engine.guard``, then ``sched.resolve`` (:meth:`resolve`). No span
    encloses a whole flush: the flush's index rides each worker span as
    its ``flush`` argument instead. :meth:`record` builds the
    ``FlushRecord`` from the engine's breakdown and the runtime counters.
    """

    def __init__(self):
        RUNTIME.install()
        self.n = 0                      # flushes recorded
        self._idle: Dict[str, float] = {}
        self._synced: Optional[float] = None
        self._compiles = RUNTIME.compiles
        self._gc_s = RUNTIME.gc_s

    def wait(self) -> stage:
        """Span of the worker looking for work until a flush triggers."""
        return stage("sched.wait", self._idle, "idle_s", flush=self.n)

    def resolve(self) -> stage:
        """Span of the flush's bookkeeping and its handles' resolution."""
        return stage("sched.resolve", flush=self.n)

    def record(self, engine, **fields) -> FlushRecord:
        """The flush's record: ``fields`` plus the breakdown ``engine``
        left for its last ``infer_batch`` (stub engines leave none)."""
        bd = getattr(engine, "last_infer_breakdown", None) or {}
        compiles, gc_s = RUNTIME.compiles, RUNTIME.gc_s
        first = self._synced is None or "t_dispatched" not in bd
        rec = FlushRecord(
            **fields,
            prep_s=bd.get("prep_s", 0.0),
            dispatch_s=bd.get("dispatch_s", 0.0),
            sync_s=bd.get("sync_s", 0.0),
            unpack_s=bd.get("unpack_s", 0.0),
            guard_s=bd.get("guard_s", 0.0),
            gap_s=0.0 if first else bd["t_dispatched"] - self._synced,
            idle_s=0.0 if first else self._idle.get("idle_s", 0.0),
            compiles=compiles - self._compiles,
            gc_s=gc_s - self._gc_s)
        self._synced = bd.get("t_synced")
        self._compiles, self._gc_s = compiles, gc_s
        self._idle.clear()
        self.n += 1
        return rec


def flush_summary(flushes: Sequence[FlushRecord]) -> Dict[str, object]:
    """Aggregate flush telemetry: batch-size distribution (the bucket
    occupancy dynamic batching achieved), flush reasons, queue depths,
    and the per-replica breakdown that verifies cluster routing balance
    (degenerate single-replica schedulers report one entry for id 0)."""
    if not flushes:
        return {"n_flushes": 0}
    sizes = np.asarray([f.n_requests for f in flushes], np.float64)
    depths = np.asarray([f.queue_depth for f in flushes], np.float64)
    reasons: Dict[str, int] = {}
    per_bucket: Dict[int, List[int]] = {}
    per_replica: Dict[int, List[FlushRecord]] = {}
    for f in flushes:
        reasons[f.reason] = reasons.get(f.reason, 0) + 1
        per_bucket.setdefault(f.capacity, []).append(f.n_requests)
        per_replica.setdefault(f.replica_id, []).append(f)
    return {
        "n_flushes": len(flushes),
        "mean_batch": float(sizes.mean()),
        "max_batch": int(sizes.max()),
        "mean_queue_depth": float(depths.mean()),
        "max_queue_depth": int(depths.max()),
        "flush_reasons": reasons,
        "mean_batch_per_bucket": {
            str(cap): float(np.mean(v)) for cap, v in sorted(
                per_bucket.items())},
        "per_replica": {
            str(rid): {
                "n_flushes": len(fs),
                "n_requests": int(sum(f.n_requests for f in fs)),
                "mean_batch": float(np.mean([f.n_requests for f in fs])),
            } for rid, fs in sorted(per_replica.items())},
    }
