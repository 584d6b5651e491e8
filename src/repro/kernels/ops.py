"""Jit'd public wrappers around the Pallas kernels.

Each wrapper handles: dynamic activation quantization, padding to block
multiples, platform dispatch (the compiled kernels on TPU; the Pallas
interpreter on the CPU backend, which is the test path), and the
packing/layout transforms.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantizers import (abs_max_scale, dequantize_log_magnitude,
                                   pack_int4, quantize)
from . import quant_matmul as _qm
from . import mddq_kernel as _mk
from . import attention_int8kv as _ak
from . import edge_softmax as _es
from . import ref as _ref


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# --- weight preparation (offline) -------------------------------------------

def prepare_w8(w: jnp.ndarray):
    """fp32 (K, N) -> (w_q int8 (K, N), w_scale f32 (1, N)) per-column."""
    scale = abs_max_scale(w, 8, channel_axis=1)
    return quantize(w, scale, 8), scale


def prepare_w4(w: jnp.ndarray):
    """fp32 (K, N) -> (packed uint8 (K, N//2), w_scale f32 (1, N))."""
    scale = abs_max_scale(w, 4, channel_axis=1)
    q = quantize(w, scale, 4)
    return pack_int4(q), scale


def quantize_activations(x: jnp.ndarray, bits: int = 8):
    """fp (M, K) -> (int8 (M, K), scale f32 (M, 1)) per-row dynamic."""
    scale = abs_max_scale(x, bits, channel_axis=0)
    return quantize(x, scale, bits), scale


# --- quantized matmul --------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block",))
def matmul_w8a8(x: jnp.ndarray, w_q: jnp.ndarray, w_scale: jnp.ndarray,
                block: tuple = (128, 128, 128)) -> jnp.ndarray:
    """y = x @ dequant(w). x: (M, K) fp; w_q: (K, N) int8."""
    m, k = x.shape
    n = w_q.shape[1]
    bm, bn, bk = block
    a_q, a_scale = quantize_activations(x)
    a_q = _pad_to(_pad_to(a_q, 0, bm), 1, bk)
    a_scale = _pad_to(a_scale, 0, bm)
    w_pad = _pad_to(_pad_to(w_q, 0, bk), 1, bn)
    s_pad = _pad_to(w_scale, 1, bn)
    out = _qm.w8a8_matmul(a_q, a_scale, w_pad, s_pad, bm=bm, bn=bn, bk=bk,
                          interpret=_interpret())
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("block",))
def matmul_w4a8(x: jnp.ndarray, w_packed: jnp.ndarray, w_scale: jnp.ndarray,
                block: tuple = (128, 128, 128)) -> jnp.ndarray:
    """y = x @ dequant(w). w_packed: (K, N//2) uint8 nibbles."""
    m, k = x.shape
    n = w_packed.shape[1] * 2
    bm, bn, bk = block
    if n > 128:
        # a packed block narrower than the packed array must span whole
        # 128-lane tiles on TPU: 128 bytes = 256 output columns
        bn = max(bn, 256)
    a_q, a_scale = quantize_activations(x)
    a_q = _pad_to(_pad_to(a_q, 0, bm), 1, bk)
    a_scale = _pad_to(a_scale, 0, bm)
    w_pad = _pad_to(_pad_to(w_packed, 0, bk), 1, bn // 2)
    s_pad = _pad_to(w_scale, 1, bn)
    out = _qm.w4a8_matmul(a_q, a_scale, w_pad, s_pad, bm=bm, bn=bn, bk=bk,
                          interpret=_interpret())
    return out[:m, :n]


# --- MDDQ encode --------------------------------------------------------------

def pad_codebook(codebook: jnp.ndarray) -> jnp.ndarray:
    """(C, 3) -> transposed (3, C128) padded with copies of codeword 0."""
    c = codebook.shape[0]
    pad = (-c) % 128
    if pad:
        codebook = jnp.concatenate(
            [codebook, jnp.tile(codebook[:1], (pad, 1))], axis=0)
    return codebook.T.copy()


@functools.partial(jax.jit,
                   static_argnames=("bn", "mag_bits", "m_min", "m_max"))
def mddq_encode(v: jnp.ndarray, codebook_t: jnp.ndarray, bn: int = 1024,
                mag_bits: int = 8, m_min: float = 1e-6, m_max: float = 1e3):
    """v: (..., 3) fp -> (dir_idx int32, mag_code int32) of shape (...)."""
    lead = v.shape[:-1]
    flat = v.reshape(-1, 3)
    n = flat.shape[0]
    npad = (-n) % bn
    if npad:
        flat = jnp.concatenate([flat, jnp.ones((npad, 3), flat.dtype)], 0)
    idx, mag = _mk.mddq_encode_kernel(
        flat[:, 0].copy(), flat[:, 1].copy(), flat[:, 2].copy(), codebook_t,
        bn=min(bn, flat.shape[0]), mag_bits=mag_bits, m_min=m_min,
        m_max=m_max, interpret=_interpret())
    return idx[:n].reshape(lead), mag[:n].reshape(lead)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def mddq_qdq_kernel(v, mddq_cfg, codebook):
    """Serve-time MDDQ quantize-dequantize through the Pallas encode kernel.

    Forward: ``mddq_encode_kernel`` (codebook argmax + log-magnitude code)
    followed by the table decode — the value the serving engine would
    reconstruct from stored codes. Backward: the Geometric-STE gradients
    of the pure-jnp reference ``core.mddq.mddq_fake_quant`` (same pattern
    as ``qmatmul``: integer forward, straight-through backward), so forces
    differentiate through the kernel path. Zero vectors map to exactly
    zero, matching the reference (isolated atoms, padded slots).

    v: (..., 3); mddq_cfg: ``core.mddq.MDDQConfig`` (static, hashable);
    codebook: (C, 3). ``ServeConfig.mddq_kernel`` selects this over the
    fake-quant reference.
    """
    return _mddq_qdq_impl(v, mddq_cfg, codebook)


def _mddq_qdq_impl(v, mddq_cfg, codebook):
    if mddq_cfg.magnitude_domain != "log":
        raise NotImplementedError(
            "mddq_encode_kernel quantizes magnitudes on the log grid only; "
            "use the fake-quant reference for linear-domain configs")
    idx, mag = mddq_encode(v, pad_codebook(codebook),
                           mag_bits=mddq_cfg.magnitude_bits,
                           m_min=mddq_cfg.m_min, m_max=mddq_cfg.m_max)
    m_q = dequantize_log_magnitude(mag, mddq_cfg.magnitude_bits,
                                   mddq_cfg.m_min, mddq_cfg.m_max)
    out = codebook[idx] * m_q[..., None]
    m2 = jnp.sum(v * v, axis=-1, keepdims=True)
    return jnp.where(m2 <= 1e-24, 0.0, out)  # 1e-24 = core.mddq._EPS ** 2


def _mddq_qdq_fwd(v, mddq_cfg, codebook):
    return _mddq_qdq_impl(v, mddq_cfg, codebook), (v, codebook)


def _mddq_qdq_bwd(mddq_cfg, res, g):
    from repro.core.mddq import mddq_fake_quant
    v, codebook = res
    _, vjp = jax.vjp(lambda v_: mddq_fake_quant(v_, mddq_cfg, codebook), v)
    (gv,) = vjp(g)
    return gv, jnp.zeros_like(codebook)  # codebook frozen at serve time


mddq_qdq_kernel.defvjp(_mddq_qdq_fwd, _mddq_qdq_bwd)


# --- fused edge softmax (sparse serving path) ---------------------------------

_NEG_BIAS = -1e9  # masked-edge logit; matches the dense forward's pair mask


def _edge_onehot(idx: jnp.ndarray, cap: int, n_edges: int, n_nodes: int,
                 dtype) -> jnp.ndarray:
    """(B, cap, ec) one-hot of local node index per edge slot — the
    segment-reduction operand of the blocked CPU path: a segment sum over
    receivers (or a gather backward over senders) becomes one batched
    matmul against this, which XLA lowers to gemm instead of the
    serialized scatters ``jax.ops.segment_*`` produce on CPU. Valid only
    under the ``bucketing.EdgeList`` layout (every slot's node index
    inside its molecule's range)."""
    B = n_nodes // cap
    ec = n_edges // B
    local = (idx % cap).reshape(B, 1, ec)
    return (local == jnp.arange(cap, dtype=idx.dtype)[None, :, None]) \
        .astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _edge_gather_blocked(x, idx, cap):
    return x[idx]


def _edge_gather_fwd(x, idx, cap):
    return x[idx], (idx, x.shape[0])


def _edge_gather_bwd(cap, res, g):
    idx, n_nodes = res
    onehot = _edge_onehot(idx, cap, idx.shape[0], n_nodes, g.dtype)
    gx = jnp.matmul(onehot, g.reshape(onehot.shape[0], onehot.shape[2], -1))
    return gx.reshape(n_nodes, *g.shape[1:]), np.zeros(idx.shape,
                                                       jax.dtypes.float0)


_edge_gather_blocked.defvjp(_edge_gather_fwd, _edge_gather_bwd)


def edge_gather(x, idx, cap):
    """``x[idx]`` for edge lists in the ``bucketing.EdgeList`` layout.

    On CPU the gather carries a blocked backward: its VJP is a segment
    sum of the cotangent over ``idx``, implemented as a per-molecule
    one-hot matmul (gemm, B·cap·ec·W MACs) instead of the scatter-add
    XLA emits — CPU backends serialize scatters, so the arithmetic
    inflation wins there; same sums, different (still deterministic)
    summation order. Other backends (TPU/GPU compile scatters natively)
    keep the plain gather and its native scatter-add VJP. x: (N, W)
    node features, idx: (E,) int32 slot indices respecting per-molecule
    ranges; cap static. The sparse forward routes its sender/receiver
    gathers through this.
    """
    if jax.default_backend() == "cpu":
        return _edge_gather_blocked(x, idx, cap)
    return x[idx]


def _edge_softmax_blocked(q_scaled, k, bias, values, senders, receivers,
                          edge_mask, cap):
    """CPU implementation of ``edge_softmax`` under the EdgeList
    layout contract: the W-wide segment reductions (numerator and
    denominator) run blocked per molecule as one batched matmul against
    the (B, cap, ec) one-hot, carrying the value matrix and the
    denominator column together; only the scalar stabilizing max stays a
    scatter. Matches ``ref.edge_softmax_ref`` to ~1e-6 (summation order
    differs; the max subtraction is stop-gradiented, which cancels
    analytically).
    """
    N = q_scaled.shape[0]
    E, w = values.shape
    B = N // cap
    ec = E // B

    logits = jnp.sum(edge_gather(q_scaled, receivers, cap)
                     * edge_gather(k, senders, cap), axis=-1) + bias
    logits = jnp.where(edge_mask, logits, _NEG_BIAS)
    onehot = _edge_onehot(receivers, cap, E, N, values.dtype)
    # the max stays a scatter (one scalar per edge, and stop-gradiented
    # so it has no backward); only the W-wide sums go through the matmul
    seg_max = jax.ops.segment_max(jax.lax.stop_gradient(logits),
                                  receivers, N)
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    p = jnp.exp(logits - seg_max[receivers])               # (E,)
    pv = jnp.concatenate([p[:, None] * (values * edge_mask[:, None]),
                          p[:, None]], axis=1)             # (E, w + 1)
    out = jnp.matmul(onehot, pv.reshape(B, ec, w + 1))     # (B, cap, w+1)
    num = out[..., :w].reshape(N, w)
    denom = out[..., w].reshape(N)
    # double-where: receivers with no edges (denom == 0) must yield 0
    # without 1/denom^2 ever being evaluated in the backward (the
    # oracle's maximum(denom, 1e-20) overflows f32 there: 1e40 * 0 = nan)
    safe = jnp.where(denom > 0, denom, 1.0)[:, None]
    return jnp.where(denom[:, None] > 0, num / safe, 0.0)


def refine_edge_mask(coords_flat: jnp.ndarray, senders: jnp.ndarray,
                     receivers: jnp.ndarray, edge_mask: jnp.ndarray,
                     cutoff: float) -> jnp.ndarray:
    """Dynamic cutoff refinement for Verlet-skin neighbour lists.

    A skin list is built once with an enlarged ``cutoff + skin`` radius
    and reused across MD steps; before each force evaluation the mask is
    tightened to the *true* cutoff at the current coordinates, so the
    edge set entering ``edge_softmax`` is exactly the fresh-rebuild set
    (the predicate ``d^2 < cutoff^2`` matches ``device_edge_list``).
    Lives here because it is mask-layout prep on the kernel input path —
    the same masking ``_edge_softmax_pallas`` folds into the key matrix.
    Boolean output: carries no gradient, like the dense path's pair mask.

    coords_flat: (N, 3) flat node coordinates; senders/receivers:
    (E,) int32; edge_mask: (E,) bool (the skin list's validity bits).
    """
    rij = coords_flat[senders] - coords_flat[receivers]
    d2 = jnp.sum(rij * rij, axis=-1)
    return edge_mask & (d2 < cutoff * cutoff)


def _edge_softmax_pallas(q_scaled, k, bias, values, senders, receivers,
                         edge_mask, cap):
    """Layout prep + kernel launch. Folds the bias into the key's last
    column (queries get a constant-1 column), zeroes masked keys/values,
    localizes receiver indices, and pads feature dims to the 128-lane
    contract before calling ``edge_softmax_kernel``. Each molecule's
    node rows are padded to a multiple of 8, the TPU's sublane tile: an
    MD replica batch keeps the molecule's own atom count as its
    capacity, and a node block of, say, 21 rows does not compile."""
    n, _ = q_scaled.shape
    w = values.shape[1]
    b = n // cap
    cap8 = -(-cap // 8) * 8
    qp = _pad_to(jnp.concatenate(
        [q_scaled, jnp.ones((n, 1), q_scaled.dtype)], axis=1), 1, 128)
    qp = _pad_to(qp.reshape(b, cap, -1), 1, 8).reshape(b * cap8, -1)
    k_e = k[senders] * edge_mask[:, None]
    bias_m = jnp.where(edge_mask, bias, _NEG_BIAS)
    kp = _pad_to(jnp.concatenate([k_e, bias_m[:, None]], axis=1), 1, 128)
    vp = _pad_to(values * edge_mask[:, None], 1, 128)
    recv_local = (receivers % cap).astype(jnp.int32)[:, None]
    out = _es.edge_softmax_kernel(qp, kp, recv_local, vp, cap=cap8,
                                  interpret=_interpret())
    return out.reshape(b, cap8, -1)[:, :cap, :w].reshape(n, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _edge_softmax_fused(q_scaled, k, bias, values, senders, receivers,
                        edge_mask, cap):
    return _edge_softmax_pallas(q_scaled, k, bias, values, senders,
                                receivers, edge_mask, cap)


def _edge_softmax_fwd(q_scaled, k, bias, values, senders, receivers,
                      edge_mask, cap):
    out = _edge_softmax_pallas(q_scaled, k, bias, values, senders,
                               receivers, edge_mask, cap)
    return out, (q_scaled, k, bias, values, senders, receivers, edge_mask)


def _edge_softmax_bwd(cap, res, g):
    # true gradients via the jnp oracle (identical math to the kernel);
    # forces F = -dE/dr differentiate through the fused forward this way
    q_scaled, k, bias, values, senders, receivers, edge_mask = res

    def f(q_, k_, b_, v_):
        return _ref.edge_softmax_ref(q_, k_, b_, senders, receivers,
                                     edge_mask, v_, q_.shape[0])

    _, vjp = jax.vjp(f, q_scaled, k, bias, values)
    gq, gk, gb, gv = vjp(g)
    f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # int/bool inputs
    return gq, gk, gb, gv, f0(senders), f0(receivers), f0(edge_mask)


_edge_softmax_fused.defvjp(_edge_softmax_fwd, _edge_softmax_bwd)


def edge_softmax(q_scaled, k, bias, values, senders, receivers, edge_mask,
                 *, cap: int, use_kernel=None):
    """out[i] = sum_{e: recv(e)=i} alpha_e * values[e], alpha the segment
    softmax of q_scaled[recv] . k[send] + bias over each receiver.

    ``use_kernel=None`` auto-selects by backend: the fused Pallas kernel
    only on TPU (its block specs and VMEM scratch are TPU-specific); on
    CPU the blocked XLA path (``_edge_softmax_blocked``: per-molecule
    one-hot matmuls instead of the scatters CPU backends serialize —
    the interpreter has nothing to fuse *for* there); on GPU the
    scatter-based oracle (``ref.edge_softmax_ref``), whose segment ops
    compile natively — the blocked path's ~cap-fold arithmetic
    inflation only pays off where scatters are serialized. Pass
    True/False to force the kernel on/off (tests force True to exercise
    it under interpret). Inputs must follow the ``bucketing.EdgeList``
    layout (per-molecule slot ranges — the kernel and blocked paths
    localize indices with ``% cap``). All paths agree to ~1e-6 and all
    are differentiable (the kernel via a custom VJP whose backward runs
    the oracle's gradients).
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        return _edge_softmax_fused(q_scaled, k, bias, values, senders,
                                   receivers, edge_mask, cap)
    if jax.default_backend() == "cpu":
        return _edge_softmax_blocked(q_scaled, k, bias, values, senders,
                                     receivers, edge_mask, cap)
    return _ref.edge_softmax_ref(q_scaled, k, bias, senders, receivers,
                                 edge_mask, values, q_scaled.shape[0])


# --- int8-KV decode attention --------------------------------------------------

def prepare_kv_int8(k: jnp.ndarray, v: jnp.ndarray):
    """(BH, S, D) fp -> int8 caches + per-token scales (BH, S)."""
    ks = jnp.maximum(jnp.max(jnp.abs(k), axis=-1), 1e-8) / 127.0
    vs = jnp.maximum(jnp.max(jnp.abs(v), axis=-1), 1e-8) / 127.0
    k_q = jnp.clip(jnp.round(k / ks[..., None]), -127, 127).astype(jnp.int8)
    v_q = jnp.clip(jnp.round(v / vs[..., None]), -127, 127).astype(jnp.int8)
    return k_q, ks, v_q, vs


@functools.partial(jax.jit, static_argnames=("bs",))
def decode_attention_int8kv(q, k_q, k_scale, v_q, v_scale, bs: int = 512):
    """q: (BH, D); int8 KV (BH, S, D) with (BH, S) scales -> (BH, D)."""
    seq = k_q.shape[1]
    bs = min(bs, seq)
    pad = (-seq) % bs
    if pad:
        k_q = _pad_to(k_q, 1, bs)
        v_q = _pad_to(v_q, 1, bs)
        # padded tokens get zero scale -> dequantized to 0; logits = 0 would
        # still get softmax mass, so push them to -inf via a large-negative
        # k scale trick: zero K gives logit 0; instead mask via v_scale=0 and
        # renormalize? Cleanest: set k_scale pad to 0 and subtract mass of
        # pad tokens is wrong. We require S % bs == 0 for exactness.
        raise ValueError(f"S={seq} must be a multiple of bs={bs}")
    return _ak.decode_attention_int8kv(q, k_q, k_scale, v_q, v_scale, bs=bs,
                                       interpret=_interpret())
