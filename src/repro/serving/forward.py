"""Batched, masked, quantized SO3krates forward passes: dense and sparse.

Two executions of the same architecture (see ``repro.models.so3krates``,
whose geometry/attention helpers both paths share):

* **Dense** (``batched_energy``) — the original O(B * n^2) path: pairwise
  (B, n, n, .) radial-basis and coefficient tensors, masked softmax over
  full rows. Exact and simple; kept as the correctness oracle and as the
  fallback for molecules denser than a bucket's edge capacity.
* **Sparse** (``sparse_energy``) — the O(E) edge-list path: the cutoff
  graph arrives as padded ``(senders, receivers, edge_mask)`` arrays from
  ``bucketing.build_edge_list``; attention, rbf gating, and both
  equivariant message terms are computed on *gathered edge features* and
  reduced with a segment softmax / segment sum — one fused
  ``edge_softmax`` launch per layer carrying the scalar message AND both
  equivariant message terms in a single value matrix. Memory and FLOPs
  scale with the number of edges, not atoms squared, which is what lets
  molecules far beyond the ~64-atom dense regime fit.

Both paths run every per-atom matmul through ``qparams.qmatmul`` (fused
W8A8/W4A8 Pallas kernels; ``use_kernels=False`` swaps in the pure-jnp
integer-accumulation reference) and share identical padding guarantees:
padded atoms never enter any edge or pair, contribute exactly zero
energy, and receive exactly zero force. ``tests/test_serving.py`` and
``tests/test_sparse_serving.py`` pin sparse == dense <= 1e-5 on energies
and forces.

Both paths name their stages with ``jax.named_scope`` — ``geometry``;
per layer ``layer{i}`` holding ``trunk``, ``radial``, ``attention``,
``messages``, ``update``, ``mddq_snap`` and ``vnorm_feedback``; then
``readout`` — so a profile attributes device time to a stage by name
whatever the compiler calls its fusions. The force pass inherits the
names through JAX's name stack. Scopes are op metadata only: the
compiled program is the same with or without them.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import make_codebook, mddq_fake_quant
from repro.core.attention_norm import l2_normalize
from repro.kernels import ops
from repro.models.so3krates import (So3kratesConfig, _layernorm, _rbf,
                                    _vnorm, cosine_logits, pair_geometry)
from repro.serving.qparams import (QuantizedParams, concat_qtensors, qmatmul,
                                   ref_qmatmul)

__all__ = ["batched_energy", "batched_energy_and_forces",
           "sparse_energy", "sparse_energy_and_forces"]

# the per-layer "trunk": every projection taken from the same layernormed
# activations. The sparse path fuses them into as few matmuls as the
# weight kinds allow (w8a8/fp32: one; w4a8: one w8 + one w4 group) — an
# exact rewrite (see qparams.concat_qtensors), so sparse == dense stays
# pinned at 1e-5 while each layer runs one activation-quantization pass
# and one (kernel or integer-jnp) matmul instead of five. The MD engine
# hits this every step, so the op count is the CPU steps/sec lever.
_TRUNK = ("wq", "wk", "wm", "wa", "wb")


def _trunk_matmul(qparams, layer: str, xn: jnp.ndarray, mm) -> jnp.ndarray:
    """One fused projection pass: returns (N, 3F + 2Fv) columns ordered
    q | k | msg | a-coeff | b-coeff. Consecutive same-kind weights share
    a matmul; output column order is the `_TRUNK` order regardless of
    how the kinds group."""
    qts = [qparams[f"{layer}/{n}"] for n in _TRUNK]
    outs = []
    lo = 0
    for hi in range(1, len(qts) + 1):
        if hi == len(qts) or qts[hi].kind != qts[lo].kind:
            group = qts[lo:hi]
            qt = group[0] if len(group) == 1 else concat_qtensors(group)
            outs.append(mm(xn, qt))
            lo = hi
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def _dense(x: jnp.ndarray, qt, use_kernels: bool) -> jnp.ndarray:
    """(B, n, F_in) @ W -> (B, n, F_out) through one flattened matmul."""
    B, n, f = x.shape
    mm = qmatmul if use_kernels else ref_qmatmul
    y = mm(x.reshape(B * n, f), qt)
    return y.reshape(B, n, -1)


def _quant_vectors(v: jnp.ndarray, cfg: So3kratesConfig,
                   codebook: jnp.ndarray, mddq_kernel: bool) -> jnp.ndarray:
    """Serve-time MDDQ on l=1 features: the pure-jnp fake-quant reference,
    or the Pallas encode kernel (``ServeConfig.mddq_kernel``) whose
    backward runs the same Geometric-STE gradients. Padded atoms keep
    v == 0 forever; both implementations map zero vectors to exactly zero
    and are NaN-safe there (core/mddq._split).
    """
    with jax.named_scope("mddq_snap"):
        if mddq_kernel:
            return ops.mddq_qdq_kernel(v, cfg.mddq(), codebook)
        return mddq_fake_quant(v, cfg.mddq(), codebook)


def batched_energy(qparams: QuantizedParams, cfg: So3kratesConfig,
                   species: jnp.ndarray, coords: jnp.ndarray,
                   mask: jnp.ndarray,
                   codebook: Optional[jnp.ndarray] = None,
                   *, quant_vectors: bool = True,
                   use_kernels: bool = True,
                   mddq_kernel: bool = False) -> jnp.ndarray:
    """Per-molecule energies for a padded batch — dense O(n^2) path.

    species: (B, n) int32, coords: (B, n, 3) f32, mask: (B, n) bool
    (True = real atom). Returns (B,) f32 — padded rows yield the energy of
    the empty molecule (0 contributions), masked callers should ignore
    them via the plan's graph indices.
    """
    B, n = species.shape
    if codebook is None and quant_vectors:
        codebook = make_codebook(cfg.dir_bits)

    with jax.named_scope("geometry"):
        _, u, rbf, pair_mask = pair_geometry(coords, cfg, mask)  # (B,n,n,.)

    x = qparams["embed"][species] * mask[..., None]          # (B, n, F)
    v = jnp.zeros((B, n, cfg.vec_feat, 3))

    # scopes follow the computation's order, so a stage whose work is
    # interleaved with another's (trunk, radial) is entered more than once
    for i in range(cfg.n_layers):
        L = f"layer{i}"
        with jax.named_scope(L):
            with jax.named_scope("trunk"):
                xn = _layernorm(x, qparams[f"{L}/ln_g"],
                                qparams[f"{L}/ln_b"])
                q = _dense(xn, qparams[f"{L}/wq"], use_kernels)
                k = _dense(xn, qparams[f"{L}/wk"], use_kernels)
            with jax.named_scope("radial"):
                bias = (rbf @ qparams[f"{L}/rbf_bias"])[..., 0]  # (B,n,n)
            with jax.named_scope("attention"):
                logits = cosine_logits(q, k, bias, cfg, cfg.robust_attention)
                logits = jnp.where(pair_mask, logits, -1e9)
                alpha = jax.nn.softmax(logits, axis=-1)      # (B, n, n)

            # invariant messages (gate is rbf-masked -> padded pairs drop
            # out)
            with jax.named_scope("trunk"):
                msg = _dense(xn, qparams[f"{L}/wm"], use_kernels)
            with jax.named_scope("radial"):
                gate = rbf @ qparams[f"{L}/rbf_m"]           # (B, n, n, F)
            with jax.named_scope("messages"):
                x = x + jnp.einsum("bij,bijf->bif", alpha,
                                   gate * msg[:, None, :, :])
            with jax.named_scope("update"):
                h = jax.nn.silu(_dense(x, qparams[f"{L}/w_upd1"],
                                       use_kernels))
                x = x + _dense(h, qparams[f"{L}/w_upd2"], use_kernels)

            # equivariant messages: invariant coefficients x geometric
            # directions
            with jax.named_scope("trunk"):
                pa = _dense(xn, qparams[f"{L}/wa"], use_kernels)[:, None]
            with jax.named_scope("radial"):
                ra = rbf @ qparams[f"{L}/rbf_a"]
            with jax.named_scope("messages"):
                ca = pa * ra                                 # (B,n,n,Fv)
            with jax.named_scope("trunk"):
                pb = _dense(xn, qparams[f"{L}/wb"], use_kernels)[:, None]
            with jax.named_scope("radial"):
                rb = rbf @ qparams[f"{L}/rbf_b"]
            with jax.named_scope("messages"):
                cb = pb * rb
                dv = jnp.einsum("bij,bijc,bijd->bicd", alpha, ca, u) \
                    + jnp.einsum("bij,bijc,bjcd->bicd", alpha, cb, v)
                v = v + dv
            if quant_vectors:
                v = _quant_vectors(v, cfg, codebook, mddq_kernel)

            with jax.named_scope("vnorm_feedback"):
                x = x + _dense(jax.nn.silu(_vnorm(v)),
                               qparams[f"{L}/w_vnorm"], use_kernels)

    with jax.named_scope("readout"):
        feats = jnp.concatenate([x, _vnorm(v)], axis=-1)
        e_hid = jax.nn.silu(_dense(feats, qparams["ro_w1"], use_kernels))
        e_atom = _dense(e_hid, qparams["ro_w2"], use_kernels)[..., 0]
        return jnp.sum(e_atom * mask, axis=-1)               # (B,)


def batched_energy_and_forces(qparams, cfg, species, coords, mask,
                              codebook=None, *, quant_vectors=True,
                              use_kernels=True, mddq_kernel=False):
    """Energies (B,) and conservative forces (B, n, 3) = -dE/dr.

    Differentiates through the quantized kernels via the straight-through
    VJP in ``qparams.qmatmul``; padded atoms receive exactly zero force.
    """
    def total_energy(c):
        e = batched_energy(qparams, cfg, species, c, mask, codebook,
                           quant_vectors=quant_vectors,
                           use_kernels=use_kernels, mddq_kernel=mddq_kernel)
        return jnp.sum(e), e

    (_, energies), neg_f = jax.value_and_grad(total_energy,
                                              has_aux=True)(coords)
    return energies, -neg_f


# ---------------------------------------------------------------------------
# sparse edge-list path
# ---------------------------------------------------------------------------

def sparse_energy(qparams: QuantizedParams, cfg: So3kratesConfig,
                  species: jnp.ndarray, coords: jnp.ndarray,
                  mask: jnp.ndarray, senders: jnp.ndarray,
                  receivers: jnp.ndarray, edge_mask: jnp.ndarray,
                  codebook: Optional[jnp.ndarray] = None,
                  *, quant_vectors: bool = True, use_kernels: bool = True,
                  edge_kernel: Optional[bool] = None,
                  mddq_kernel: bool = False,
                  refine_cutoff: bool = False) -> jnp.ndarray:
    """Per-molecule energies over a padded edge list — the O(E) path.

    species/coords/mask as in ``batched_energy``; senders/receivers are
    flat int32 indices into the ``(B * n,)`` node axis and edge_mask the
    per-slot validity bit, all laid out per the ``bucketing.EdgeList``
    contract (per-molecule slot ranges, receiver-sorted). ``edge_kernel``
    selects the fused Pallas segment-softmax (None = auto: kernel on TPU,
    the blocked XLA path elsewhere). ``refine_cutoff=True`` treats
    ``edge_mask`` as a Verlet-skin list built at an enlarged radius and
    tightens it to ``d < cfg.cutoff`` at the current coordinates using
    the internally computed distances (the MD engine's per-step
    refinement, fused here so it shares the geometry pass — same
    predicate as ``kernels.ops.refine_edge_mask``). Returns (B,) f32.
    """
    B, n = species.shape
    N = B * n
    F, Fv = cfg.feat, cfg.vec_feat
    if codebook is None and quant_vectors:
        codebook = make_codebook(cfg.dir_bits)
    mm = qmatmul if use_kernels else ref_qmatmul

    # edge geometry from gathered coordinates: the energy stays a function
    # of coords, so forces flow through the gathers; masked slots are
    # self-loops -> d ~ 0, and every use below is edge_mask-gated
    with jax.named_scope("geometry"):
        coords_f = coords.reshape(N, 3)
        rij = ops.edge_gather(coords_f, senders, n) \
            - ops.edge_gather(coords_f, receivers, n)        # (E, 3) rj-ri
        d2 = jnp.sum(rij ** 2, -1)
        if refine_cutoff:
            edge_mask = edge_mask & (d2 < cfg.cutoff * cfg.cutoff)
        d = jnp.sqrt(d2 + 1e-12)
        u = rij / d[..., None]                               # (E, 3)
        rbf_e = _rbf(d, cfg) * edge_mask[..., None]          # (E, K)

    mask_f = mask.reshape(N)
    x = qparams["embed"][species.reshape(N)] * mask_f[:, None]   # (N, F)
    v = jnp.zeros((N, Fv, 3))

    for i in range(cfg.n_layers):
        L = f"layer{i}"
        with jax.named_scope(L):
            # fused trunk projection (q | k | msg | a | b, see
            # _trunk_matmul)
            with jax.named_scope("trunk"):
                xn = _layernorm(x, qparams[f"{L}/ln_g"],
                                qparams[f"{L}/ln_b"])
                trunk = _trunk_matmul(qparams, L, xn, mm)    # (N, 3F+2Fv)
                q, k = trunk[:, :F], trunk[:, F:2 * F]
            with jax.named_scope("attention"):
                if cfg.robust_attention:
                    q_s = cfg.tau * l2_normalize(q)
                    k_s = l2_normalize(k)
                else:
                    q_s = q / jnp.sqrt(q.shape[-1])
                    k_s = k

            # fused radial gemm: bias | scalar gate | a-gate | b-gate ride
            # one (E, K) @ (K, 1+F+2Fv) product (exact column split)
            with jax.named_scope("radial"):
                rg = rbf_e @ jnp.concatenate(
                    [qparams[f"{L}/rbf_bias"], qparams[f"{L}/rbf_m"],
                     qparams[f"{L}/rbf_a"], qparams[f"{L}/rbf_b"]], axis=1)
                bias_e = rg[:, 0]                            # (E,)
                gate_e = rg[:, 1:1 + F]                      # (E, F)

            # fused sender gather: scalar messages, both coefficient
            # projections, and the vector features come off one (E, .)
            # gather (ops.edge_gather: its VJP is a blocked matmul, not a
            # scatter)
            with jax.named_scope("messages"):
                sf = ops.edge_gather(
                    jnp.concatenate([trunk[:, 2 * F:], v.reshape(N, Fv * 3)],
                                    axis=1), senders, n)
                msg_e = sf[:, :F]                            # (E, F)
                ca_e = sf[:, F:F + Fv] * rg[:, 1 + F:1 + F + Fv]  # (E, Fv)
                cb_e = sf[:, F + Fv:F + 2 * Fv] * rg[:, 1 + F + Fv:]
                # per-edge values for ONE fused softmax-scatter: scalar
                # messages and both equivariant message terms share alpha
                vec_e = ca_e[..., None] * u[:, None, :] \
                    + cb_e[..., None] * sf[:, F + 2 * Fv:].reshape(-1, Fv, 3)
                vals = jnp.concatenate(
                    [gate_e * msg_e, vec_e.reshape(-1, Fv * 3)], axis=1)

            with jax.named_scope("attention"):
                out = ops.edge_softmax(q_s, k_s, bias_e, vals, senders,
                                       receivers, edge_mask, cap=n,
                                       use_kernel=edge_kernel)
            with jax.named_scope("messages"):
                x = x + out[:, :F]
            with jax.named_scope("update"):
                h = jax.nn.silu(mm(x, qparams[f"{L}/w_upd1"]))
                x = x + mm(h, qparams[f"{L}/w_upd2"])
            with jax.named_scope("messages"):
                v = v + out[:, F:].reshape(N, Fv, 3)
            if quant_vectors:
                v = _quant_vectors(v, cfg, codebook, mddq_kernel)

            with jax.named_scope("vnorm_feedback"):
                x = x + mm(jax.nn.silu(_vnorm(v)), qparams[f"{L}/w_vnorm"])

    with jax.named_scope("readout"):
        feats = jnp.concatenate([x, _vnorm(v)], axis=-1)
        e_hid = jax.nn.silu(mm(feats, qparams["ro_w1"]))
        e_atom = mm(e_hid, qparams["ro_w2"])[:, 0]           # (N,)
        return jnp.sum(e_atom.reshape(B, n) * mask, axis=-1)  # (B,)


def sparse_energy_and_forces(qparams, cfg, species, coords, mask,
                             senders, receivers, edge_mask, codebook=None,
                             *, quant_vectors=True, use_kernels=True,
                             edge_kernel=None, mddq_kernel=False,
                             refine_cutoff=False):
    """Sparse-path energies (B,) and conservative forces (B, n, 3).

    The edge list is treated as data (indices carry no gradient); the
    energy differentiates through the gathered coordinates, so padded
    atoms — which appear in no real edge — get exactly zero force.
    """
    def total_energy(c):
        e = sparse_energy(qparams, cfg, species, c, mask, senders,
                          receivers, edge_mask, codebook,
                          quant_vectors=quant_vectors,
                          use_kernels=use_kernels, edge_kernel=edge_kernel,
                          mddq_kernel=mddq_kernel,
                          refine_cutoff=refine_cutoff)
        return jnp.sum(e), e

    (_, energies), neg_f = jax.value_and_grad(total_energy,
                                              has_aux=True)(coords)
    return energies, -neg_f
