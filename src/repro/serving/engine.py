"""`QuantizedEngine` — batched, bucketed, quantized inference.

The deployment entry point this repo's ROADMAP builds toward: variable-size
molecular graphs in, per-molecule energies/forces out, with

* **bucketing** (``repro.serving.bucketing``) bounding the number of
  compiled shapes regardless of traffic mix,
* **two execution paths** (``repro.serving.forward``): the dense O(n^2)
  oracle and the sparse O(E) edge-list path with its fused
  segment-softmax kernel; ``ServeConfig.path`` selects, and ``"auto"``
  dispatches each batch sparse whenever its cutoff graph fits the
  bucket's edge capacity (falling back to dense when it doesn't),
* **real quantized weights** (``repro.serving.qparams``) streamed through
  the fused W8A8/W4A8 Pallas kernels — on the CPU backend they run
  under the Pallas interpreter, the test path (``interpret``),
* **masked batching**: padded atoms are excluded from results and
  diagnostics exactly, not approximately.

Quickstart (see docs/serving.md):

    from repro.models import so3krates as so3
    from repro.serving import Graph, QuantizedEngine, ServeConfig

    engine = QuantizedEngine.from_config(
        so3.So3kratesConfig(feat=32, vec_feat=8, n_layers=2),
        params=trained_params,                 # or None -> random init
        serve=ServeConfig(mode="w8a8", bucket_sizes=(16, 32), max_batch=8))
    engine.warmup()            # pre-compile every admissible shape class
    results = engine.infer_batch([Graph(species, coords), ...])
    results[0].energy, results[0].forces       # padding already stripped
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import make_codebook, snap_path
from repro.core.lee import random_rotation, random_rotations
from repro.guardrails import (Flag, GuardrailConfig, GuardrailViolation,
                              check_result)
from repro.models import so3krates as so3
from repro.obs.metrics import REGISTRY
from repro.obs.trace import RUNTIME, stage
from repro.serving.bucketing import (BucketSpec, Graph, build_edge_list,
                                     count_edges, pad_graphs, plan_batches)
from repro.serving.forward import (batched_energy_and_forces,
                                   sparse_energy_and_forces)
from repro.serving.qparams import (fp32_bytes, quantize_so3_params,
                                   serving_bytes, serving_fp32_equiv)

__all__ = ["ServeConfig", "MoleculeResult", "QuantizedEngine"]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving-side knobs, orthogonal to the model architecture config."""
    mode: str = "w8a8"                       # "fp32" | "w8a8" | "w4a8"
    bucket_sizes: tuple = (16, 32, 64, 128)  # atom-capacity ladder
    max_batch: int = 64                      # molecules per compiled batch
    # MDDQ on l=1 features at serve time; None = follow the mode
    # (on for quantized modes, off for fp32 so fp32 is a true reference)
    quant_vectors: Optional[bool] = None
    pad_species: int = 0
    # execution path: "dense" (O(n^2) oracle), "sparse" (always prefer the
    # O(E) edge list), or "auto" (edge list only for buckets where it is
    # profitable — see QuantizedEngine._sparse_profitable — so
    # small-molecule traffic keeps the faster dense path). Both
    # sparse-preferring modes run a batch dense when its cutoff graph
    # overflows the bucket's edge capacity — counted in
    # engine.dispatch_stats["sparse_fallback"] — so warmup() compiles
    # dense shapes for every path.
    path: str = "auto"
    # per-molecule edge slots; None = bucketing.default_edge_capacity(cap)
    edge_capacity: Optional[int] = None
    # fused segment-softmax Pallas kernel; None = auto (kernel on TPU,
    # XLA segment ops on CPU — see kernels.ops.edge_softmax)
    edge_kernel: Optional[bool] = None
    # route serve-time vector quantization through the MDDQ Pallas encode
    # kernel (kernels.ops.mddq_qdq_kernel) instead of the pure-jnp
    # fake-quant reference
    mddq_kernel: bool = False

    def __post_init__(self):
        if self.path not in ("dense", "sparse", "auto"):
            raise ValueError(f"unknown path {self.path!r}")

    @property
    def vectors_quantized(self) -> bool:
        if self.quant_vectors is None:
            return self.mode != "fp32"
        return self.quant_vectors

    def buckets(self) -> List[BucketSpec]:
        return [BucketSpec(capacity=c, max_batch=self.max_batch,
                           edge_capacity=self.edge_capacity)
                for c in self.bucket_sizes]


@dataclasses.dataclass(frozen=True)
class MoleculeResult:
    """Per-molecule inference output with padding stripped."""
    energy: float
    forces: np.ndarray       # (n_atoms, 3)
    n_atoms: int
    bucket_capacity: int     # shape class the molecule rode in
    batch_size: int          # compiled batch rows (incl. alignment dummies)
    path: str = "dense"      # execution path the molecule's batch took
    # which cluster replica served the batch (0 outside a cluster; set
    # by repro.cluster's replica worker, not by the engine itself)
    replica_id: int = 0
    # content tag of the packed artifact the serving weights came from
    # ("" for engines built straight from fp32 params) — lets a client
    # verify which weights answered during a rolling hot swap
    artifact_version: str = ""
    # guardrail flags that fired on this molecule (repro.guardrails
    # Flag tuples). Empty for clean results; fatal flags never reach a
    # caller as a result — suspect flags annotate results that were
    # delivered because no higher precision tier remained
    flags: tuple = ()
    # precision-escalation audit trail (EscalationRecord tuples): each
    # entry is one re-run up the w4a8 -> w8a8 -> fp32 ladder a cluster
    # performed before this result was produced
    escalations: tuple = ()
    # obs linkage: the request trace this result answers ("" when tracing
    # is disabled or the result came from a direct infer_batch call)
    trace_id: str = ""


class QuantizedEngine:
    """Batched quantized-inference engine for the SO3krates force field."""

    def __init__(self, model_cfg: so3.So3kratesConfig,
                 params: Optional[Dict[str, jnp.ndarray]], serve: ServeConfig,
                 *, qparams=None, fp32_nbytes: Optional[int] = None,
                 device: Optional[jax.Device] = None,
                 artifact_version: str = "",
                 guardrails: Optional[GuardrailConfig] = None):
        """Build from fp32 ``params`` (quantized here, the training->serving
        hand-off) or directly from serving-format ``qparams`` (the packed-
        artifact cold-start path, ``repro.server.artifact`` — no fp32 tree
        is ever materialized). Exactly one of the two must be given;
        ``fp32_nbytes`` carries the fp32 footprint for ``memory_report``
        when no fp32 tree exists.

        ``device`` pins the engine to one JAX device: weights, codebook,
        and every batch are committed there, so the jitted forwards
        compile and execute on it — this is how ``repro.cluster`` stands
        up one engine per device (simulated on CPU via
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N``). None
        keeps the default-device behavior. ``artifact_version`` is the
        content tag of the packed artifact the weights came from, echoed
        into every :class:`MoleculeResult`.

        ``guardrails`` configures the runtime result detectors
        (``repro.guardrails``; None = the default config, non-finite
        check on). It is an engine argument, not part of ``ServeConfig``,
        so artifacts and the cluster's shared-config invariant stay
        unchanged — detectors are a property of the serving process,
        not of the weights."""
        if (params is None) == (qparams is None):
            raise ValueError("pass exactly one of params / qparams")
        self.model_cfg = model_cfg
        self.serve = serve
        self.device = device
        self.artifact_version = artifact_version
        self.guardrails = (guardrails if guardrails is not None
                           else GuardrailConfig())
        if qparams is None:
            self._fp32_bytes = fp32_bytes(params)  # fp32 tree is not retained
            self.qparams = quantize_so3_params(params, serve.mode)
        else:
            self._fp32_bytes = (fp32_nbytes if fp32_nbytes is not None
                                else serving_fp32_equiv(qparams))
            self.qparams = qparams
        # committed placement: with a device given, weights/codebook move
        # there once and inputs follow per batch (_put), so jit compiles
        # for exactly that device
        self._put = ((lambda x: jax.device_put(x, device))
                     if device is not None else jnp.asarray)
        if device is not None:
            self.qparams = jax.device_put(self.qparams, device)
        quant_vec = serve.vectors_quantized
        self._codebook = (self._put(make_codebook(model_cfg.dir_bits))
                          if quant_vec else None)
        self._buckets = serve.buckets()
        use_kernels = serve.mode != "fp32"
        # the direction snap each forward traces (the Pallas encode
        # kernel scans every codeword); counted per compiled shape in
        # mddq_snap_programs_total, so a run shows which one it served
        self._m_snap = (REGISTRY.counter(
            "mddq_snap_programs_total", mode=serve.mode,
            path="scan" if serve.mddq_kernel
            else snap_path(model_cfg.mddq())) if quant_vec else None)

        def _fwd_dense(species, coords, mask):
            return batched_energy_and_forces(
                self.qparams, self.model_cfg, species, coords, mask,
                self._codebook, quant_vectors=quant_vec,
                use_kernels=use_kernels, mddq_kernel=serve.mddq_kernel)

        def _fwd_sparse(species, coords, mask, senders, receivers,
                        edge_mask):
            return sparse_energy_and_forces(
                self.qparams, self.model_cfg, species, coords, mask,
                senders, receivers, edge_mask, self._codebook,
                quant_vectors=quant_vec, use_kernels=use_kernels,
                edge_kernel=serve.edge_kernel,
                mddq_kernel=serve.mddq_kernel)

        self._forward_dense = jax.jit(_fwd_dense)
        self._forward_sparse = jax.jit(_fwd_sparse)
        self.compiled_shapes = set()
        # batches dispatched per path; "sparse_fallback" counts batches a
        # sparse-preferring config had to run dense (edge-capacity overflow)
        self.dispatch_stats = {"dense": 0, "sparse": 0, "sparse_fallback": 0}
        # guardrail telemetry: molecules checked / flagged per detector,
        # LEE probes run (all counts only advance when guardrails.active)
        self.guard_stats = {"checked": 0, "flagged_nonfinite": 0,
                            "flagged_outlier": 0, "flagged_lee": 0,
                            "lee_probes": 0}
        self._n_infer_calls = 0         # LEE probe sampling counter
        # dual-write handles into the process-wide metrics plane
        # (repro.obs.metrics): the plain dicts above stay the exact
        # per-engine view (tests/benches subtract snapshots and expect
        # reset_stats to zero them); the registry instruments are keyed
        # by (name, labels) so the same counters keep accumulating across
        # engine exchanges — ClusterPool.swap_artifact and quarantine
        # cold-restarts no longer lose fleet-lifetime totals
        self._m_dispatch = {
            k: REGISTRY.counter("engine_dispatch_total",
                                mode=serve.mode, path=k)
            for k in self.dispatch_stats}
        self._m_guard = {
            k: REGISTRY.counter("engine_guard_total",
                                mode=serve.mode, event=k)
            for k in self.guard_stats}
        # per-(bucket, batch_size, path) warmup/compile accounting and
        # the last infer_batch's stage breakdown (obs profiling hooks)
        self.warmup_report: List[Dict] = []
        self.last_infer_breakdown: Dict[str, float] = {}
        RUNTIME.install()

    # -- construction -------------------------------------------------------

    @classmethod
    def from_config(cls, model_cfg: so3.So3kratesConfig,
                    params: Optional[Dict[str, jnp.ndarray]] = None,
                    serve: ServeConfig = ServeConfig(),
                    seed: int = 0,
                    device: Optional[jax.Device] = None,
                    guardrails: Optional[GuardrailConfig] = None
                    ) -> "QuantizedEngine":
        """Build an engine from a model config and (optionally) trained
        fp32 params; random init when params is None (benchmarks, smoke)."""
        if params is None:
            params = so3.init_params(jax.random.PRNGKey(seed), model_cfg)
        return cls(model_cfg, params, serve, device=device,
                   guardrails=guardrails)

    @classmethod
    def from_quantized(cls, model_cfg: so3.So3kratesConfig, qparams,
                       serve: ServeConfig,
                       fp32_nbytes: Optional[int] = None,
                       device: Optional[jax.Device] = None,
                       artifact_version: str = "",
                       guardrails: Optional[GuardrailConfig] = None
                       ) -> "QuantizedEngine":
        """Build an engine from already-serving-format parameters — the
        packed-artifact cold-start path (``repro.server.artifact``) and
        the per-replica construction path of ``repro.cluster``: no fp32
        materialization, no quantization pass. ``qparams`` must have
        been produced by ``quantize_so3_params(params, serve.mode)`` (or
        loaded from an artifact saved from such an engine)."""
        return cls(model_cfg, None, serve, qparams=qparams,
                   fp32_nbytes=fp32_nbytes, device=device,
                   artifact_version=artifact_version, guardrails=guardrails)

    # -- introspection ------------------------------------------------------

    @property
    def interpret(self) -> bool:
        """True when the Pallas kernels run in CPU interpret mode (no TPU)."""
        return jax.default_backend() == "cpu"

    @property
    def backend(self) -> str:
        return jax.default_backend()

    def memory_report(self) -> Dict[str, int]:
        served = serving_bytes(self.qparams)
        return {"fp32_bytes": self._fp32_bytes, "served_bytes": served,
                "compression_x": round(self._fp32_bytes / max(served, 1), 2)}

    def stats_snapshot(self) -> Dict[str, int]:
        """Immutable copy of the dispatch counters — take one before and
        one after a phase and subtract to attribute batches to it."""
        return dict(self.dispatch_stats)

    def guard_snapshot(self) -> Dict[str, int]:
        """Immutable copy of the guardrail counters (checked/flagged per
        detector, LEE probes run)."""
        return dict(self.guard_stats)

    def reset_stats(self) -> Dict[str, int]:
        """Zero the dispatch + guardrail counters, returning the
        pre-reset dispatch snapshot. Both otherwise accumulate for the
        engine's lifetime, so benches/servers reset after warmup to keep
        steady-state phases unpolluted."""
        snap = self.stats_snapshot()
        for k in self.dispatch_stats:
            self.dispatch_stats[k] = 0
        for k in self.guard_stats:
            self.guard_stats[k] = 0
        return snap

    # -- serving ------------------------------------------------------------

    def warmup(self, buckets: Optional[Sequence[int]] = None,
               batch_sizes: Optional[Sequence[int]] = None) -> float:
        """Pre-compile the forward pass for the given shape classes.

        By default every admissible batch class of every bucket is
        compiled, for every path this config can dispatch — sparse paths
        also warm their dense shapes, because edge-capacity overflow
        falls back to dense at dispatch time. That is the complete
        (finite) set of shapes ``infer_batch`` can ever hit, so a warmed
        engine never compiles under traffic. Pass ``buckets`` and/or
        ``batch_sizes`` to restrict. Returns monotonic seconds spent
        compiling; ``warmup_report`` holds the per-(bucket, batch_size,
        path) breakdown — the measurement substrate for ROADMAP item 2's
        scale-from-zero accounting.
        """
        t0 = time.monotonic()
        self.warmup_report = []

        def _timed(path: str, cap: int, bsz: int, fn) -> None:
            s0 = time.monotonic()
            fn()
            dt = time.monotonic() - s0
            # t0 places the compile on the fleet timeline
            # (repro.obs.timeline renders one slice per compile)
            self.warmup_report.append(
                {"bucket": cap, "batch_size": bsz, "path": path,
                 "mode": self.serve.mode, "seconds": dt, "t0": s0})
            REGISTRY.histogram("engine_warmup_compile_seconds",
                               mode=self.serve.mode, path=path).observe(dt)

        caps = list(buckets) if buckets else [b.capacity
                                              for b in self._buckets]
        for cap in caps:
            spec = next(b for b in self._buckets if b.capacity == cap)
            if batch_sizes:
                sizes = list(batch_sizes)
            else:
                # distinct batch classes for 1..max_batch graphs
                sizes = sorted({spec.batch_class(n)
                                for n in range(1, spec.max_batch + 1)})
            for bsz in sizes:
                species = np.zeros((bsz, cap), np.int32)
                coords = np.zeros((bsz, cap, 3), np.float32)
                mask = np.zeros((bsz, cap), bool)
                # dense is always warmed: it is the overflow fallback of
                # every sparse-preferring config, so even path="sparse"
                # can dispatch it under traffic
                _timed("dense", cap, bsz,
                       lambda: self._run_dense(species, coords, mask))
                if self._wants_sparse(spec):
                    el = build_edge_list(coords, mask, self.model_cfg.cutoff,
                                         spec.edges)
                    _timed("sparse", cap, bsz,
                           lambda: self._run_sparse(species, coords,
                                                    mask, el))
        return time.monotonic() - t0

    def _compiling(self, key) -> None:
        """Note a shape class the forwards run; the first run of one
        compiles a program."""
        if key not in self.compiled_shapes:
            self.compiled_shapes.add(key)
            if self._m_snap is not None:
                self._m_snap.inc()

    def _run_dense(self, species, coords, mask):
        self._compiling(species.shape)
        return self._forward_dense(self._put(species), self._put(coords),
                                   self._put(mask))

    def _run_sparse(self, species, coords, mask, el):
        self._compiling(("sparse",) + species.shape + (el.edge_capacity,))
        return self._forward_sparse(
            self._put(species), self._put(coords), self._put(mask),
            self._put(el.senders), self._put(el.receivers),
            self._put(el.edge_mask))

    # "auto" dispatches sparse only when the dense pairwise work is at
    # least this many times the padded edge-slot count — the gather /
    # segment-reduction overhead means break-even needs headroom, and 4x
    # matches the measured CPU crossover (dense wins at 16/32 atoms,
    # sparse from 64 up; see BENCH_serving.json)
    _SPARSE_PROFIT_FACTOR = 4

    def _sparse_profitable(self, spec: BucketSpec) -> bool:
        """Whether the edge-list path is expected to beat dense for this
        bucket: n^2 pairwise work >= 4x the padded edge slots."""
        return spec.capacity ** 2 >= self._SPARSE_PROFIT_FACTOR * spec.edges

    def _wants_sparse(self, spec: BucketSpec) -> bool:
        if self.serve.path == "sparse":
            return True              # explicit override, even if slower
        return self.serve.path == "auto" and self._sparse_profitable(spec)

    def _dispatch(self, species, coords, mask, spec: BucketSpec):
        """Run one padded batch down the configured path. Returns
        (energies, forces, path_taken)."""
        if self._wants_sparse(spec):
            el = build_edge_list(coords, mask, self.model_cfg.cutoff,
                                 spec.edges)
            if el is not None:
                self.dispatch_stats["sparse"] += 1
                self._m_dispatch["sparse"].inc()
                e, f = self._run_sparse(species, coords, mask, el)
                return e, f, "sparse"
            # cutoff graph denser than the bucket's edge capacity
            self.dispatch_stats["sparse_fallback"] += 1
            self._m_dispatch["sparse_fallback"].inc()
        self.dispatch_stats["dense"] += 1
        self._m_dispatch["dense"].inc()
        e, f = self._run_dense(species, coords, mask)
        return e, f, "dense"

    def infer_batch(self, graphs: Sequence[Graph],
                    on_flag: Optional[str] = None) -> List[MoleculeResult]:
        """Energies and forces for a heterogeneous list of molecules.

        Graphs are bucketed, padded, batched, and dispatched through the
        quantized forward (sparse edge-list path when configured and the
        batch's cutoff graph fits the edge capacity); results come back
        in input order with padding (and dummy alignment molecules)
        stripped.

        Results then pass the configured runtime guardrails
        (``repro.guardrails``): non-finite energy/forces, force-norm
        outliers vs the calibrated envelope, and the sampled LEE probe.
        ``on_flag`` overrides ``GuardrailConfig.on_flag`` for this call:
        ``"raise"`` (the direct-call default — a typed
        :class:`~repro.guardrails.GuardrailViolation` instead of a bad
        result) or ``"mark"`` (scheduler/cluster surfaces — flagged
        results come back with ``MoleculeResult.flags`` set and the
        caller triages: typed error, annotated delivery, or a precision
        escalation).

        Each stage runs in a :class:`repro.obs.trace.stage` span
        (``engine.prep``/``dispatch``/``sync``/``unpack``/``guard``);
        ``last_infer_breakdown`` keeps their seconds, plus the monotonic
        end of the first dispatch (``t_dispatched``) and of the last sync
        (``t_synced``), for the serving worker's ``FlushRecord``.
        """
        bd: Dict[str, float] = {}
        try:
            results = self._infer_raw(graphs, bd)
            with stage("engine.guard", bd):
                return self._guard(graphs, results, on_flag)
        finally:
            # read by the scheduler/replica worker right after
            # infer_batch returns, on the same thread
            self.last_infer_breakdown = bd

    def _guard(self, graphs: Sequence[Graph],
               results: List[MoleculeResult],
               on_flag: Optional[str]) -> List[MoleculeResult]:
        """The guardrail pass of ``infer_batch``."""
        g = self.guardrails
        if not g.active:
            return results
        self._n_infer_calls += 1
        self.guard_stats["checked"] += len(results)
        self._m_guard["checked"].inc(len(results))
        flagged: Dict[int, tuple] = {}
        for i, r in enumerate(results):
            flags = check_result(r.energy, r.forces, r.bucket_capacity, g)
            if flags:
                flagged[i] = flags
        if g.lee_probe_every > 0 \
                and self._n_infer_calls % g.lee_probe_every == 0:
            for i, flag in self._lee_probe(graphs, results):
                flagged[i] = flagged.get(i, ()) + (flag,)
        if not flagged:
            return results
        for flags in flagged.values():
            for f in flags:
                key = {"nonfinite": "flagged_nonfinite",
                       "force_outlier": "flagged_outlier",
                       "lee": "flagged_lee"}.get(f.reason)
                if key is not None:
                    self.guard_stats[key] += 1
                    self._m_guard[key].inc()
        mode = on_flag if on_flag is not None else g.on_flag
        if mode == "raise":
            worst = max((f for flags in flagged.values() for f in flags),
                        key=lambda f: f.fatal)
            raise GuardrailViolation(
                f"guardrail {worst.reason} on {len(flagged)}/{len(results)} "
                f"molecule(s) (mode={self.serve.mode})", reason=worst.reason,
                severity=worst.severity,
                detail={"value": worst.value, "limit": worst.limit,
                        "mode": self.serve.mode})
        return [dataclasses.replace(r, flags=flagged[i]) if i in flagged
                else r for i, r in enumerate(results)]

    def _infer_raw(self, graphs: Sequence[Graph],
                   bd: Optional[Dict[str, float]] = None
                   ) -> List[MoleculeResult]:
        """The bucket/pad/dispatch pipeline with no guardrail pass —
        also the re-run path of the LEE probe and ``lee_diagnostic``
        (probing the probe would recurse). Stage seconds accumulate into
        ``bd`` when given (see ``infer_batch``)."""
        plans = plan_batches(graphs, self._buckets)
        results: List[Optional[MoleculeResult]] = [None] * len(graphs)
        for plan in plans:
            with stage("engine.prep", bd):
                species, coords, mask = pad_graphs(
                    graphs, plan, pad_species=self.serve.pad_species)
            with stage("engine.dispatch", bd) as st:
                e, f, path = self._dispatch(species, coords, mask,
                                            plan.bucket)
            if bd is not None:
                bd.setdefault("t_dispatched", st.t1)
            # np.asarray forces device->host transfer: the sync point
            with stage("engine.sync", bd) as st:
                e = np.asarray(e)
                f = np.asarray(f)
            if bd is not None:
                bd["t_synced"] = st.t1
            with stage("engine.unpack", bd):
                for row, gi in enumerate(plan.graph_indices):
                    n = graphs[gi].n_atoms
                    results[gi] = MoleculeResult(
                        energy=float(e[row]), forces=f[row, :n],
                        n_atoms=n, bucket_capacity=plan.bucket.capacity,
                        batch_size=plan.batch_size, path=path,
                        artifact_version=self.artifact_version)
        return results  # type: ignore[return-value]

    def _lee_probe(self, graphs: Sequence[Graph],
                   results: Sequence[MoleculeResult]):
        """Sampled equivariance check: re-run the batch under one
        seeded rotation and compare rotated vs counter-rotated forces
        (paper Eq. 1, online). Returns ``(index, Flag)`` pairs for
        molecules whose LEE exceeds the limit."""
        g = self.guardrails
        self.guard_stats["lee_probes"] += 1
        self._m_guard["lee_probes"].inc()
        key = jax.random.PRNGKey(g.lee_seed + self._n_infer_calls)
        R = np.asarray(random_rotation(key))
        rotated = [Graph(gr.species, np.asarray(gr.coords) @ R.T)
                   for gr in graphs]
        out = []
        level = 0.0
        for i, (r0, r1) in enumerate(zip(results,
                                         self._infer_raw(rotated))):
            if not np.isfinite(r0.forces).all():
                continue            # nonfinite already flagged as fatal
            err = float(np.linalg.norm(r1.forces - r0.forces @ R.T))
            if np.isfinite(err):
                level = max(level, err / max(g.lee_limit, 1e-12))
            if not np.isfinite(err) or err > g.lee_limit:
                out.append((i, Flag("lee", "suspect", value=err,
                                    limit=g.lee_limit)))
        # SLO feed: worst probed LEE as a fraction of the limit
        # (> 1.0 breaches the lee_probe_level objective)
        REGISTRY.gauge("engine_lee_probe_level",
                       mode=self.serve.mode).set(level)
        return out

    # -- MD bridge ----------------------------------------------------------

    def md_engine(self, md=None):
        """A device-resident :class:`repro.md.engine.MDEngine` sharing
        this engine's quantized weights and codebook — serve traffic and
        run MD off one set of serving-format parameters. ``md`` is an
        ``MDConfig`` whose ``mode`` must match (default: one is built
        from this engine's mode). See docs/md.md.
        """
        from repro.md.engine import MDConfig, MDEngine
        if md is None:
            md = MDConfig(mode=self.serve.mode)
        if md.mode != self.serve.mode:
            raise ValueError(
                f"MDConfig.mode {md.mode!r} != ServeConfig.mode "
                f"{self.serve.mode!r}: the quantized weights are shared")
        return MDEngine(self.model_cfg, md=md, qparams=self.qparams,
                        codebook=self._codebook)

    # -- diagnostics --------------------------------------------------------

    def edge_occupancy(self, graphs: Sequence[Graph]) -> Dict[str, float]:
        """How full the sparse path's edge slots would be for this traffic:
        per-plan real-edge counts vs capacity. Useful for sizing
        ``ServeConfig.edge_capacity``."""
        plans = plan_batches(graphs, self._buckets)
        occ, overflow = [], 0
        for plan in plans:
            _, coords, mask = pad_graphs(graphs, plan,
                                         pad_species=self.serve.pad_species)
            counts = count_edges(coords, mask, self.model_cfg.cutoff)
            cap_e = plan.bucket.edges
            occ.append(float(counts.max()) / cap_e)
            overflow += int((counts > cap_e).sum())
        return {"max_occupancy": max(occ) if occ else 0.0,
                "mean_occupancy": float(np.mean(occ)) if occ else 0.0,
                "molecules_overflowing": overflow}

    def lee_diagnostic(self, graphs: Sequence[Graph], key: jax.Array,
                       n_rotations: int = 4) -> Dict[str, float]:
        """Local Equivariance Error of the *served* (quantized, batched)
        model: || F(R.G) - R F(G) || per molecule, averaged over random
        rotations, with padded atoms excluded by construction (forces on
        them are exactly zero on both sides).
        """
        rots = np.asarray(random_rotations(key, n_rotations))
        base = self._infer_raw(graphs)
        errs = []
        for R in rots:
            rotated = [Graph(g.species, np.asarray(g.coords) @ R.T)
                       for g in graphs]
            rot_res = self._infer_raw(rotated)
            for r0, r1 in zip(base, rot_res):
                errs.append(float(np.linalg.norm(
                    r1.forces - r0.forces @ R.T)))
        return {"lee_mean": float(np.mean(errs)),
                "lee_max": float(np.max(errs)),
                "n_rotations": n_rotations, "n_graphs": len(graphs)}
