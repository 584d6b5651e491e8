"""Device-resident MD engine over the quantized sparse forward.

The deployment claim behind the paper's Fig. 3 — "stable, energy-
conserving MD for nanosecond timescales" on a quantized model — is a
throughput claim at heart: an MD run is 10^4-10^6 force calls, so any
per-step host work (neighbour-list rebuilds in Python, numpy round-trips
of forces, dispatch overhead) multiplies into the wall clock. This
module keeps the whole integration loop on device:

* **velocity-Verlet inside ``lax.scan``** — one compiled program
  integrates ``record_every`` steps per record; the host sees data only
  at record checkpoints (and once at the end of ``run``).
* **Verlet-skin neighbour lists** (``md/neighbor.py``) — the edge list
  is built at ``cutoff + skin`` and rebuilt on device under ``lax.cond``
  only when some atom has moved further than ``skin / 2``; before every
  force call the mask is refined back to the true cutoff
  (``kernels.ops.refine_edge_mask``), so forces are *exactly* those of a
  fresh list every step. Capacity overflow sets a sticky flag checked at
  the end of each ``run`` instead of syncing per step.
* **quantized sparse forward** — forces come from
  ``serving.forward.sparse_energy_and_forces``: the O(E) edge-list path
  through the fused W8A8/W4A8 matmul kernels, differentiated via their
  straight-through VJPs. The per-step energy is the same forward's value
  output, so recording total energy costs nothing extra.
* **batched replicas** — state is a padded ``(B, cap, ...)`` bucket of
  molecules integrated simultaneously through the batched forward,
  amortizing kernel launches across replicas; padded atoms have exactly
  zero force and never move.

``benchmarks/md_bench.py`` measures this against the legacy per-step
host loop and writes ``BENCH_md.json``; see docs/md.md for the
architecture notes and the skin heuristic.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import make_codebook
from repro.guardrails import GuardrailViolation, check_finite_tree
from repro.kernels import ops
from repro.md.neighbor import NeighborList, build_neighbor_list, maybe_rebuild
from repro.md.nve import _FS
from repro.obs.metrics import REGISTRY
from repro.obs.trace import stage
from repro.models import so3krates as so3
from repro.serving.bucketing import EDGE_LANE, count_edges
from repro.serving.forward import sparse_energy_and_forces
from repro.serving.qparams import QuantizedParams, quantize_so3_params

__all__ = ["MDConfig", "ReplicaState", "MDEngine", "pad_replicas"]

_KB = 8.617333e-5  # eV / K


@dataclasses.dataclass(frozen=True)
class MDConfig:
    """MD-side knobs, orthogonal to the model architecture config."""
    mode: str = "w8a8"               # "fp32" | "w8a8" | "w4a8"
    dt_fs: float = 0.5               # integration step, femtoseconds
    # skin radius (Angstrom): the edge list is built at cutoff + skin and
    # stays valid until some atom moves skin/2. Larger skin = fewer
    # rebuilds but more edge slots (every per-edge op pays for the
    # extras); 0 degenerates to rebuild-every-step. 0.45 balances the
    # two on the measured CPU profile (see BENCH_md.json).
    skin: float = 0.45
    record_every: int = 50           # steps between energy records
    # per-molecule edge slots for the skin list; None = sized at
    # init_state from the initial configuration's cutoff+skin edge count
    # times the safety factor, rounded up to EDGE_LANE
    edge_capacity: Optional[int] = None
    edge_capacity_safety: float = 1.3
    # MDDQ on l=1 features; None = follow the mode (on for quantized)
    quant_vectors: Optional[bool] = None
    # route matmuls through the Pallas kernels; None = auto (kernels on
    # TPU, the integer-jnp ref path on CPU — identical forward values,
    # same STE backward; the interpreter has nothing to fuse *for* on
    # CPU, same rule edge_kernel=None applies to the segment softmax)
    use_kernels: Optional[bool] = None
    # fused segment-softmax kernel; None = auto (TPU only)
    edge_kernel: Optional[bool] = None
    # serve-time MDDQ through the Pallas encode kernel
    mddq_kernel: bool = False
    # verification mode: count cutoff edges missed by the skin list every
    # step (O(cap^2) extra work — tests/benchmark audits only)
    track_missed: bool = False
    # -- runtime guardrails (checked at each record checkpoint, where
    # run() syncs to the host anyway — zero extra device work) --
    # raise a typed GuardrailViolation when a checkpoint's energies go
    # non-finite (an exploded trajectory is garbage from that point on)
    check_finite: bool = True
    # max admissible |e_tot - e_tot(first checkpoint)| per replica (eV);
    # None = drift monitor off. An NVE integrator at a sane dt conserves
    # e_tot — sustained drift is the quantized forward leaving its trust
    # region, the signal the session layer escalates a precision tier on
    drift_limit: Optional[float] = None

    def __post_init__(self):
        if self.mode not in ("fp32", "w8a8", "w4a8"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.skin < 0:
            raise ValueError("skin must be >= 0")
        if self.drift_limit is not None and self.drift_limit <= 0:
            raise ValueError("drift_limit must be > 0 or None")

    @property
    def vectors_quantized(self) -> bool:
        if self.quant_vectors is None:
            return self.mode != "fp32"
        return self.quant_vectors


class ReplicaState(NamedTuple):
    """Integration state for a padded batch of replicas. Everything a
    step touches lives here so ``lax.scan`` carries it on device."""
    coords: jnp.ndarray      # (B, cap, 3) Angstrom
    veloc: jnp.ndarray       # (B, cap, 3) A / t*
    forces: jnp.ndarray      # (B, cap, 3) eV / A
    e_pot: jnp.ndarray       # (B,) potential energy at coords
    nlist: NeighborList      # skin edge list + rebuild bookkeeping
    missed: jnp.ndarray      # () int32, cumulative missed cutoff edges
    #                          (only advanced when MDConfig.track_missed)


def pad_replicas(species: np.ndarray, coords: np.ndarray, n_replicas: int,
                 capacity: Optional[int] = None):
    """Tile one molecule into a padded replica batch.

    species (n,), coords (n, 3) -> (species (B, cap) int32,
    coords (B, cap, 3) f32, mask (B, cap) bool) with B = n_replicas and
    cap = capacity (default n). Replicas start identical; distinct
    initial velocities come from ``MDEngine.init_state``'s RNG.
    """
    n = int(species.shape[0])
    cap = n if capacity is None else capacity
    if cap < n:
        raise ValueError(f"capacity {cap} < molecule size {n}")
    sp = np.zeros((n_replicas, cap), np.int32)
    co = np.zeros((n_replicas, cap, 3), np.float32)
    mask = np.zeros((n_replicas, cap), bool)
    sp[:, :n] = np.asarray(species, np.int32)
    co[:, :n] = np.asarray(coords, np.float32)
    mask[:, :n] = True
    return sp, co, mask


class MDEngine:
    """Batched, device-resident NVE integrator for the quantized model."""

    def __init__(self, model_cfg: so3.So3kratesConfig,
                 params: Optional[Dict[str, jnp.ndarray]] = None,
                 md: MDConfig = MDConfig(),
                 qparams: Optional[QuantizedParams] = None,
                 codebook: Optional[jnp.ndarray] = None, seed: int = 0):
        """Build from trained fp32 ``params`` (quantized here per
        ``md.mode``) or from pre-quantized ``qparams`` (e.g. shared with
        a ``QuantizedEngine`` via ``engine.md_engine()``)."""
        self.model_cfg = model_cfg
        self.md = md
        if qparams is None:
            if params is None:
                params = so3.init_params(jax.random.PRNGKey(seed), model_cfg)
            qparams = quantize_so3_params(params, md.mode)
        self.qparams = qparams
        self._quant_vec = md.vectors_quantized
        if codebook is None and self._quant_vec:
            codebook = make_codebook(model_cfg.dir_bits)
        self._codebook = codebook
        if md.use_kernels is None:
            self._use_kernels = (md.mode != "fp32"
                                 and jax.default_backend() == "tpu")
        else:
            self._use_kernels = md.use_kernels
        # one compiled program per segment length: run() dispatches
        # n_records identical record_every-step segments (plus at most
        # one remainder segment), so total step count never recompiles.
        # Donation lets XLA reuse the state buffers across segments; the
        # caller's own input state is protected by a device copy in
        # run(), not by a second (donation-free) compilation of the
        # segment program. CPU does not support donation and would warn
        # on every call.
        self._donate = jax.default_backend() != "cpu"
        self._segment_jit = jax.jit(
            self._segment_impl, static_argnames=("length",),
            donate_argnums=(0,) if self._donate else ())

    # -- forces --------------------------------------------------------------

    def _energy_forces(self, species, coords, mask, nlist: NeighborList):
        """Quantized sparse forward at the true cutoff: the skin list's
        mask is refined to d < cutoff at the current coordinates (fused
        into the forward's geometry pass via ``refine_cutoff``), so the
        edge set equals a fresh rebuild's exactly."""
        return sparse_energy_and_forces(
            self.qparams, self.model_cfg, species, coords, mask,
            nlist.senders, nlist.receivers, nlist.edge_mask,
            self._codebook, quant_vectors=self._quant_vec,
            use_kernels=self._use_kernels,
            edge_kernel=self.md.edge_kernel,
            mddq_kernel=self.md.mddq_kernel, refine_cutoff=True)

    def _count_missed(self, coords, mask, nlist: NeighborList):
        """Cutoff edges absent from the refined skin list (must be 0 —
        the conservativeness audit behind MDConfig.track_missed)."""
        B, cap = mask.shape
        cutoff = self.model_cfg.cutoff
        rij = coords[:, :, None, :] - coords[:, None, :, :]
        d2 = jnp.sum(rij * rij, axis=-1)
        fresh = ((d2 < cutoff * cutoff) & ~jnp.eye(cap, dtype=bool)[None]
                 & mask[:, :, None] & mask[:, None, :])
        em = ops.refine_edge_mask(coords.reshape(-1, 3), nlist.senders,
                                  nlist.receivers, nlist.edge_mask, cutoff)
        b = nlist.receivers // cap
        have = jnp.zeros((B, cap, cap), jnp.int32).at[
            b, nlist.receivers % cap, nlist.senders % cap
        ].add(em.astype(jnp.int32)) > 0
        return jnp.sum(fresh & ~have).astype(jnp.int32)

    # -- integration ---------------------------------------------------------

    def _step(self, s: ReplicaState, species, mask, inv_m, dt):
        v_half = s.veloc + 0.5 * dt * s.forces * inv_m
        r_new = s.coords + dt * v_half
        # rebuild BEFORE the force call: while max displacement stays
        # under skin/2 the old list is provably conservative, and the
        # moment it is not, the list is rebuilt at these coordinates
        nlist = maybe_rebuild(s.nlist, r_new, mask, self.model_cfg.cutoff,
                              self.md.skin)
        e_pot, f_new = self._energy_forces(species, r_new, mask, nlist)
        v_new = v_half + 0.5 * dt * f_new * inv_m
        missed = s.missed
        if self.md.track_missed:
            missed = missed + self._count_missed(r_new, mask, nlist)
        return ReplicaState(r_new, v_new, f_new, e_pot, nlist, missed)

    def _segment_impl(self, state: ReplicaState, species, mask, masses,
                      length: int):
        """``length`` velocity-Verlet steps in one device program,
        returning the state plus one energy/temperature record."""
        dt = self.md.dt_fs * _FS
        inv_m = jnp.where(mask, 1.0 / jnp.maximum(masses, 1e-9),
                          0.0)[..., None]

        def one_step(s, _):
            return self._step(s, species, mask, inv_m, dt), None

        state, _ = jax.lax.scan(one_step, state, None, length=length)
        m_eff = jnp.where(mask, masses, 0.0)
        e_kin = 0.5 * jnp.sum(m_eff[..., None] * state.veloc ** 2,
                              axis=(1, 2))
        # 3N - 3 degrees of freedom: init_state removes the per-replica
        # centre-of-mass momentum and NVE conserves it at zero
        n_dof = jnp.maximum(3.0 * mask.sum(-1).astype(jnp.float32) - 3.0,
                            1.0)
        rec = {"e_pot": state.e_pot, "e_tot": state.e_pot + e_kin,
               "temperature_K": 2.0 * e_kin / (n_dof * _KB)}
        return state, rec

    # -- public API ----------------------------------------------------------

    def init_state(self, key: jax.Array, species, coords, mask, masses,
                   temperature_K: float = 300.0,
                   edge_capacity: Optional[int] = None) -> ReplicaState:
        """Maxwell-Boltzmann initialization of a padded replica batch.

        species (B, cap) int32, coords (B, cap, 3), mask (B, cap) bool,
        masses (cap,) or (B, cap) amu (padded entries may hold any
        positive value — padded atoms never move). Sizes the skin list's
        edge capacity from this configuration unless given, builds it,
        and evaluates initial forces. Raises if the initial cutoff+skin
        graph overflows the capacity.
        """
        species = jnp.asarray(species, jnp.int32)
        coords = jnp.asarray(coords, jnp.float32)
        mask = jnp.asarray(mask, bool)
        masses = jnp.broadcast_to(jnp.asarray(masses, jnp.float32),
                                  mask.shape)
        B, cap = mask.shape

        ec = self.md.edge_capacity if edge_capacity is None else edge_capacity
        if ec is None:
            counts = count_edges(np.asarray(coords), np.asarray(mask),
                                 self.model_cfg.cutoff + self.md.skin)
            ec = int(counts.max()) * self.md.edge_capacity_safety
            ec = -(-max(int(ec), 1) // EDGE_LANE) * EDGE_LANE
            ec = min(ec, -(-cap * cap // EDGE_LANE) * EDGE_LANE)
        if ec % EDGE_LANE != 0:
            raise ValueError(
                f"edge_capacity {ec} is not a multiple of {EDGE_LANE}")

        nlist = build_neighbor_list(coords, mask, self.model_cfg.cutoff,
                                    self.md.skin, ec)
        if bool(nlist.overflow):
            raise ValueError(
                f"initial cutoff+skin graph overflows edge_capacity={ec}; "
                "raise MDConfig.edge_capacity or edge_capacity_safety")

        std = jnp.sqrt(_KB * temperature_K
                       / jnp.maximum(masses, 1e-9))[..., None]
        v = jax.random.normal(key, coords.shape) * std * mask[..., None]
        # remove per-replica centre-of-mass drift over real atoms
        m = (masses * mask)[..., None]
        p = jnp.sum(m * v, axis=1, keepdims=True) \
            / jnp.maximum(jnp.sum(m, axis=1, keepdims=True), 1e-9)
        v = (v - p) * mask[..., None]

        e_pot, forces = self._energy_forces(species, coords, mask, nlist)
        return ReplicaState(coords=coords, veloc=v, forces=forces,
                            e_pot=e_pot, nlist=nlist,
                            missed=jnp.zeros((), jnp.int32))

    def run(self, state: ReplicaState, species, mask, masses,
            n_steps: int, record_every: Optional[int] = None
            ) -> Tuple[ReplicaState, Dict[str, np.ndarray]]:
        """Integrate ``n_steps`` of NVE, one device dispatch per record.

        Each ``record_every``-step segment is a single compiled scan —
        the host syncs only at record checkpoints (where it also checks
        the overflow flag, raising if an on-device skin rebuild exceeded
        the edge capacity — the trajectory is invalid past that point).
        Returns the final state and a record dict: ``e_pot`` / ``e_tot``
        / ``temperature_K`` arrays of shape ``(n_records, B)`` sampled
        every ``record_every`` steps (one extra, shorter-interval sample
        covers any remainder — no steps are dropped), plus scalar
        ``n_rebuilds`` and ``missed_edges`` counters.
        """
        if record_every is None:
            record_every = self.md.record_every
        species = jnp.asarray(species, jnp.int32)
        mask = jnp.asarray(mask, bool)
        masses = jnp.broadcast_to(jnp.asarray(masses, jnp.float32),
                                  mask.shape)
        if self._donate:
            # the first segment would otherwise donate the caller's
            # buffers (e.g. an init_state kept around to restart)
            state = jax.tree_util.tree_map(jnp.copy, state)
        n_records, tail = divmod(n_steps, record_every)
        lengths = [record_every] * n_records + ([tail] if tail else [])
        recs = []
        e_ref: Optional[np.ndarray] = None   # first checkpoint's e_tot
        for k, length in enumerate(lengths):
            # host spans on the profiler's clock: the segment's dispatch,
            # then the checkpoint's host reads (the per-segment sync)
            with stage("md.segment", segment=k):
                state, rec = self._segment_jit(state, species, mask, masses,
                                               length=length)
            with stage("md.sync", segment=k):
                e_ref = self._checkpoint(state, rec, e_ref)
            recs.append(rec)
        with stage("md.sync", segment=len(lengths)):
            records = {k: np.stack([np.asarray(r[k]) for r in recs])
                       for k in recs[0]} if recs else {}
            records["n_rebuilds"] = int(state.nlist.n_rebuilds)
            records["missed_edges"] = int(state.missed)
        return state, records

    def _checkpoint(self, state: ReplicaState, rec: Dict,
                    e_ref: Optional[np.ndarray]) -> Optional[np.ndarray]:
        """The host checks at one record checkpoint: the overflow flag,
        then the guardrails. Returns the drift reference (the first
        checkpoint's e_tot)."""
        if bool(state.nlist.overflow):   # the per-checkpoint host sync
            raise RuntimeError(
                "skin neighbour list overflowed its edge capacity "
                f"({state.nlist.edge_capacity}) during the run; raise "
                "MDConfig.edge_capacity / edge_capacity_safety")
        # guardrails ride the same host sync: non-finite energies and
        # (when armed) per-replica e_tot drift vs the first checkpoint
        if self.md.check_finite or self.md.drift_limit is not None:
            e_tot = np.asarray(rec["e_tot"])
            if self.md.check_finite:
                bad = check_finite_tree(
                    {"e_tot": e_tot, "e_pot": np.asarray(rec["e_pot"])})
                if bad is not None:
                    raise GuardrailViolation(
                        f"non-finite {bad} at an MD checkpoint (mode "
                        f"{self.md.mode}) — the trajectory exploded",
                        reason="nonfinite", severity="fatal",
                        detail={"mode": self.md.mode, "array": bad})
            if self.md.drift_limit is not None:
                if e_ref is None:
                    e_ref = e_tot
                else:
                    drift = float(np.abs(e_tot - e_ref).max())
                    # SLO feed: drift as a fraction of the limit
                    # (> 1.0 breaches md_energy_drift) — published
                    # whether or not the guardrail trips, so the
                    # health plane sees drift *approaching* the
                    # limit too
                    REGISTRY.gauge(
                        "md_energy_drift_ratio",
                        mode=self.md.mode).set(
                        drift / self.md.drift_limit)
                    if drift > self.md.drift_limit:
                        raise GuardrailViolation(
                            f"energy drift {drift:.4g} eV exceeds "
                            f"drift_limit={self.md.drift_limit} eV "
                            f"(mode {self.md.mode})",
                            reason="energy_drift", severity="suspect",
                            detail={"mode": self.md.mode,
                                    "value": drift,
                                    "limit": self.md.drift_limit})
        return e_ref

    # -- introspection -------------------------------------------------------

    @property
    def backend(self) -> str:
        return jax.default_backend()
