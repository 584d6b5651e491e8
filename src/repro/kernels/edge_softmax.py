"""Pallas TPU kernel: fused edge-list attention (segment softmax + scatter).

The hot loop of the sparse serving path: for every directed cutoff-graph
edge e = (j -> i), compute the attention logit q_i . k_e + bias_e, take a
numerically stable softmax over each receiver's segment, and scatter the
alpha-weighted per-edge values back to the receiver nodes — all in one
pass over the edge stream, never materializing an (n, n) pairwise tensor.

Layout contract (produced by ``repro.serving.bucketing.build_edge_list``):

* nodes are flat ``(B * cap, F)`` with molecule b owning rows
  ``[b*cap, (b+1)*cap)``;
* edges are flat ``(B * ec, .)`` with molecule b owning slots
  ``[b*ec, (b+1)*ec)``, real edges first, **receiver-sorted**, padding
  slots masked;
* receiver indices arrive *molecule-local* (in ``[0, cap)``);
* the attention bias rides in the **last feature column** of the key
  (matched by a constant-1 column in the query), with masked edges set to
  a large negative bias — so one row-sum produces ``logit + bias`` and
  masking at once.

The grid is (B, ec/be) with the edge axis innermost. TPU grids execute
sequentially, so the kernel keeps an **online-softmax state** per node in
VMEM scratch — running max m, running denominator l, running weighted
accumulator acc — exactly the flash-attention recurrence, but over ragged
receiver segments instead of dense rows. Scatter within a block uses a
one-hot (be, cap) matrix: per-node max via a masked reduction, gather and
scatter via MXU matmuls. Output for molecule b is written once, on b's
last edge block.

``interpret=True`` runs the identical kernel on CPU (same pattern as
``quant_matmul``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BE = 128   # edges per block; EDGE_LANE in serving.bucketing
NEG_INF = -1e30    # online-softmax init; well below the -1e9 edge mask


def _edge_softmax_kernel(q_ref, k_ref, r_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref):
    e = pl.program_id(1)
    n_eb = pl.num_programs(1)

    @pl.when(e == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                                  # (cap, Fp) node queries
    k = k_ref[...]                                  # (be, Fp) edge keys+bias
    r = r_ref[...]                                  # (be, 1) local receiver idx
    cap = q.shape[0]
    be = k.shape[0]

    # one-hot receiver matrix: R[e, i] = 1 iff edge e scatters to node i
    iota = jax.lax.broadcasted_iota(jnp.int32, (be, cap), 1)
    onehot = r == iota                              # (be, cap) bool
    R = onehot.astype(jnp.float32)

    # gather receiver queries and take the fused logit row-sum (the last
    # q column is 1, the last k column carries bias / the -1e9 edge mask)
    q_e = jnp.dot(R, q, preferred_element_type=jnp.float32)   # (be, Fp)
    logit = jnp.sum(q_e * k, axis=1)                          # (be,)

    # online softmax per receiver segment (flash recurrence over blocks)
    blk = jnp.where(onehot, logit[:, None], NEG_INF)          # (be, cap)
    m_blk = jnp.max(blk, axis=0)                              # (cap,)
    m_old = m_ref[:, 0]
    m_new = jnp.maximum(m_old, m_blk)
    corr = jnp.exp(m_old - m_new)                             # (cap,)
    p = jnp.exp(logit - jnp.dot(R, m_new,
                                preferred_element_type=jnp.float32))
    l_new = l_ref[:, 0] * corr + jnp.dot(
        R.T, p, preferred_element_type=jnp.float32)           # (cap,)
    acc_new = acc_ref[...] * corr[:, None] + jnp.dot(
        R.T, p[:, None] * v_ref[...],
        preferred_element_type=jnp.float32)                   # (cap, W)

    m_ref[:, 0] = m_new
    l_ref[:, 0] = l_new
    acc_ref[...] = acc_new

    @pl.when(e == n_eb - 1)
    def _done():
        # nodes that never appeared as receivers keep l == 0 -> output 0
        o_ref[...] = acc_new / jnp.maximum(l_new, 1e-20)[:, None]


@functools.partial(jax.jit,
                   static_argnames=("cap", "be", "interpret"))
def edge_softmax_kernel(q, k_e, recv_local, values, *, cap: int,
                        be: int = DEFAULT_BE, interpret: bool = False):
    """Fused segment softmax + weighted scatter over per-molecule edges.

    q:          (B * cap, Fp) f32 — node queries, scale folded in, last
                column constant 1 (bias pickup).
    k_e:        (B * ec, Fp) f32 — gathered sender keys; last column is
                the attention bias, -1e9 on masked edge slots.
    recv_local: (B * ec, 1) int32 — receiver index within the molecule,
                as a column: a 1-D int32 block does not match the TPU's
                tiled layout for 1-D arrays, and Mosaic refuses it.
    values:     (B * ec, W) f32 — per-edge values, zero on masked slots.

    Returns (B * cap, W) f32: out[i] = sum_e alpha_e * values[e] over
    edges received by node i, alpha the segment softmax of the logits.
    ec must be a multiple of ``be``; Fp and W should be lane-aligned
    (multiples of 128) for the compiled path — the ops wrapper pads.
    """
    n_nodes, fp = q.shape
    n_edges, w = values.shape
    assert n_nodes % cap == 0, (n_nodes, cap)
    b = n_nodes // cap
    assert n_edges % b == 0, (n_edges, b)
    ec = n_edges // b
    assert ec % be == 0, f"edge capacity {ec} not a multiple of block {be}"
    n_eb = ec // be
    grid = (b, n_eb)
    return pl.pallas_call(
        _edge_softmax_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((cap, fp), lambda i, j: (i, 0)),
            pl.BlockSpec((be, fp), lambda i, j, n_eb=n_eb: (i * n_eb + j, 0)),
            pl.BlockSpec((be, 1), lambda i, j, n_eb=n_eb: (i * n_eb + j, 0)),
            pl.BlockSpec((be, w), lambda i, j, n_eb=n_eb: (i * n_eb + j, 0)),
        ],
        out_specs=pl.BlockSpec((cap, w), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_nodes, w), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((cap, 1), jnp.float32),   # running max m
            pltpu.VMEM((cap, 1), jnp.float32),   # running denom l
            pltpu.VMEM((cap, w), jnp.float32),   # running numerator acc
        ],
        interpret=interpret,
    )(q, k_e, recv_local, values)
