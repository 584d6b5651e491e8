"""Serve-time quantized parameters for the SO3krates force field.

QAT (``repro.models.so3krates``) trains with *fake* quantization: fp32
weights passed through quantize-dequantize so the network adapts to the
grid. Serving flips to the *real* representation: each matmul weight is
stored as int8 (W8) or nibble-packed int4 (W4) plus a per-output-channel
fp32 scale, and consumed directly by the fused Pallas kernels in
``repro.kernels.quant_matmul`` — weights stream from HBM at 1/4 (W8) or
1/8 (W4) of the fp32 byte count, which is the paper's Table-IV speedup
mechanism.

Quantization policy (mirrors ``repro.quant.apply`` for LMs, paper §III-D):

* per-atom-feature matmul weights -> quantized. In ``w4a8`` mode the
  equivariant-branch coefficient matrices (``wa``/``wb``) take W4, the
  invariant branch W8 (the paper's W4A8 operating point); ``w8a8`` puts
  W8 everywhere.
* precision-critical / tiny leaves stay fp32: the species embedding,
  layernorm gains/biases, the radial-basis gates (K=16 minor dim — no
  bandwidth to win), and the final energy head ``ro_w2`` (N=1: odd minor
  dim cannot nibble-pack, and the scalar energy readout is the
  error-amplifying leaf).

``qmatmul`` is the single entry point the serving forward pass uses: it
dispatches on the stored kind, runs the Pallas kernel (interpreted on the
CPU backend), and carries a straight-through custom VJP so
conservative forces ``F = -dE/dr`` can still be taken through the integer
kernels — the backward pass multiplies by the *dequantized* weight matrix.
"""
from __future__ import annotations

import functools
from typing import Dict, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantizers import unpack_int4
from repro.kernels import ops

__all__ = ["QTensor", "QuantPolicy", "qmatmul", "concat_qtensors",
           "quantize_so3_params", "serving_bytes", "fp32_bytes",
           "serving_fp32_equiv"]

# names of the equivariant-branch coefficient matrices (paper: W4 in w4a8)
_EQV_SUFFIXES = ("/wa", "/wb")
# matmul weights consumed by qmatmul; everything else stays fp32
_MATMUL_SUFFIXES = ("/wq", "/wk", "/wm", "/w_upd1", "/w_upd2", "/w_vnorm",
                    "/wa", "/wb")
_MATMUL_GLOBALS = ("ro_w1",)


@jax.tree_util.register_pytree_node_class
class QTensor:
    """A weight in its serving representation.

    kind: "fp"  -> data = fp32 (K, N), scale unused
          "w8"  -> data = int8 (K, N), scale = fp32 (1, N) per column
          "w4"  -> data = uint8 (K, N//2) nibble-packed, scale = fp32 (1, N)
    """

    def __init__(self, kind: str, data: jnp.ndarray, scale=None):
        self.kind = kind
        self.data = data
        self.scale = scale

    def tree_flatten(self):
        return (self.data, self.scale), self.kind

    @classmethod
    def tree_unflatten(cls, kind, children):
        return cls(kind, *children)

    @property
    def out_features(self) -> int:
        if self.kind == "w4":
            return self.data.shape[1] * 2
        return self.data.shape[1]

    @property
    def nbytes(self) -> int:
        n = int(self.data.size)
        itemsize = {"fp": 4, "w8": 1, "w4": 1}[self.kind]
        scale_bytes = 0 if self.scale is None else int(self.scale.size) * 4
        return n * itemsize + scale_bytes

    def dequantize(self) -> jnp.ndarray:
        """fp32 view of the stored weight — used by the force backward pass
        and by the pure-jnp reference forward."""
        if self.kind == "fp":
            return self.data
        if self.kind == "w8":
            return self.data.astype(jnp.float32) * self.scale
        if self.kind == "w4":
            return unpack_int4(self.data).astype(jnp.float32) * self.scale
        raise ValueError(self.kind)


QuantizedParams = Dict[str, Union[QTensor, jnp.ndarray]]


# ---------------------------------------------------------------------------
# qmatmul: Pallas forward, straight-through backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _qmm(kind: str, x, data, scale):
    return _qmm_impl(kind, x, data, scale)


def _qmm_impl(kind, x, data, scale):
    if kind == "fp":
        return x @ data
    if kind == "w8":
        return ops.matmul_w8a8(x, data, scale)
    if kind == "w4":
        return ops.matmul_w4a8(x, data, scale)
    raise ValueError(kind)


def _qmm_fwd(kind, x, data, scale):
    return _qmm_impl(kind, x, data, scale), (data, scale)


def _qmm_bwd(kind, res, g):
    data, scale = res
    w_dq = QTensor(kind, data, scale).dequantize()
    gx = g @ w_dq.T
    # weights are frozen at serve time: zero/float0 cotangents
    ct_data = (jnp.zeros_like(data) if jnp.issubdtype(data.dtype, jnp.floating)
               else np.zeros(data.shape, jax.dtypes.float0))
    ct_scale = None if scale is None else jnp.zeros_like(scale)
    return (gx, ct_data, ct_scale)


_qmm.defvjp(_qmm_fwd, _qmm_bwd)


def qmatmul(x: jnp.ndarray, qt: QTensor) -> jnp.ndarray:
    """y = x @ W for a serving-format weight. x: (M, K) fp32 -> (M, N) fp32.

    W8/W4 kinds run the fused dequantize-matmul Pallas kernel (per-row
    dynamic A8 activation quantization inside ``repro.kernels.ops``); the
    backward pass is straight-through against the dequantized weights, so
    ``jax.grad`` through an engine forward (forces) works.
    """
    return _qmm(qt.kind, x, qt.data, qt.scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ref_qmm(kind, x, data, scale):
    return _ref_qmm_impl(kind, x, data, scale)


def _ref_qmm_impl(kind, x, data, scale):
    if kind == "fp":
        return x @ data
    a_q, a_s = ops.quantize_activations(x)
    w_q = data if kind == "w8" else unpack_int4(data)
    acc = jnp.matmul(a_q.astype(jnp.int32), w_q.astype(jnp.int32))
    return acc.astype(jnp.float32) * a_s * scale


def _ref_qmm_fwd(kind, x, data, scale):
    return _ref_qmm_impl(kind, x, data, scale), (data, scale)


_ref_qmm.defvjp(_ref_qmm_fwd, _qmm_bwd)  # same STE backward as the kernels


def ref_qmatmul(x: jnp.ndarray, qt: QTensor) -> jnp.ndarray:
    """Pure-jnp oracle with the same semantics as ``qmatmul`` — identical
    forward value (per-row A8 activations, integer accumulation) and the
    identical straight-through backward (gradients flow as if the matmul
    were against the dequantized weights — a custom VJP, so the forward
    runs the integer path alone with no surrogate fp matmul riding
    along). Used by the per-molecule reference path in tests (both
    energies AND forces must match the kernel-batched engine) and as the
    CPU serving/MD matmul where the Pallas interpreter has nothing to
    fuse for."""
    return _ref_qmm(qt.kind, x, qt.data, qt.scale)


def concat_qtensors(qts) -> QTensor:
    """Fuse weights along the output axis: ``x @ [W1|W2|...]`` equals the
    per-weight matmuls column-for-column, because activation scales are
    per-row (independent of the weight) and weight scales per-column
    (independent of the split) — for fp, w8, and nibble-packed w4 alike
    (each packed width is a whole number of bytes). The serving forward
    fuses each layer's trunk projections through this: one quantized
    matmul (and one activation-quantization pass) instead of five.

    All inputs must share kind and input dimension; w4 widths must be
    even. Output columns are ordered as the inputs are given.
    """
    kind = qts[0].kind
    if any(q.kind != kind for q in qts):
        raise ValueError(f"mixed kinds {[q.kind for q in qts]}")
    if any(q.data.shape[0] != qts[0].data.shape[0] for q in qts):
        raise ValueError("mismatched input dims")
    data = jnp.concatenate([q.data for q in qts], axis=1)
    if kind == "fp":
        return QTensor("fp", data)
    return QTensor(kind, data,
                   jnp.concatenate([q.scale for q in qts], axis=1))


# ---------------------------------------------------------------------------
# parameter-tree conversion
# ---------------------------------------------------------------------------

class QuantPolicy:
    """Maps a SO3krates param name to its serving kind for a given mode."""

    def __init__(self, mode: str):
        assert mode in ("fp32", "w8a8", "w4a8"), mode
        self.mode = mode

    def kind_of(self, name: str, w) -> str:
        is_matmul = (name.endswith(_MATMUL_SUFFIXES)
                     or name in _MATMUL_GLOBALS)
        if self.mode == "fp32" or not is_matmul or w.ndim != 2:
            return "fp"
        if (self.mode == "w4a8" and name.endswith(_EQV_SUFFIXES)
                and w.shape[1] % 2 == 0):
            return "w4"
        return "w8"


def quantize_so3_params(params: Dict[str, jnp.ndarray],
                        mode: str) -> QuantizedParams:
    """Convert a trained fp32 SO3krates param dict to serving format.

    Matmul weights become ``QTensor``s (int8 / packed-int4 + per-column
    scales via ``repro.kernels.ops.prepare_w8/prepare_w4``); everything
    else passes through as fp32 arrays.
    """
    policy = QuantPolicy(mode)
    out: QuantizedParams = {}
    for name, w in params.items():
        kind = policy.kind_of(name, w)
        if kind == "w8":
            q, s = ops.prepare_w8(w)
            out[name] = QTensor("w8", q, s)
        elif kind == "w4":
            q, s = ops.prepare_w4(w)
            out[name] = QTensor("w4", q, s)
        elif name.endswith(_MATMUL_SUFFIXES) or name in _MATMUL_GLOBALS \
                or name == "ro_w2":
            out[name] = QTensor("fp", w)
        else:
            out[name] = w
    return out


def serving_bytes(qparams: QuantizedParams) -> int:
    """Total parameter bytes in the serving representation."""
    total = 0
    for v in qparams.values():
        if isinstance(v, QTensor):
            total += v.nbytes
        else:
            total += int(np.asarray(v).nbytes)
    return total


def fp32_bytes(params: Dict[str, jnp.ndarray]) -> int:
    return int(sum(np.asarray(v).size * 4 for v in params.values()))


def serving_fp32_equiv(qparams: QuantizedParams) -> int:
    """fp32 byte count the qparams tree *would* occupy: the logical
    (unpacked, unscaled) element count at 4 bytes/element. Used when an
    engine is built straight from a packed artifact and no fp32 tree ever
    existed to measure."""
    total = 0
    for v in qparams.values():
        if isinstance(v, QTensor):
            total += int(v.data.shape[0]) * v.out_features * 4 \
                if v.data.ndim == 2 else int(np.asarray(v.data).size) * 4
        else:
            total += int(np.asarray(v).size) * 4
    return total
