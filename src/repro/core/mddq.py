"""Magnitude-Direction Decoupled Quantization (MDDQ) — paper Definition 3.1.

Q(v) = Q_m(||v||) * Q_d(v / ||v||)

* Q_m: scalar quantizer on R_+ — either symmetric-linear (shared scale) or
  log-domain (default; magnitudes are Chi-distributed, log grid keeps relative
  error uniform).
* Q_d: nearest-codeword lookup in a spherical codebook C subset S^2: in
  closed form for the Fibonacci codebook, by a scan over every codeword
  for the octahedral one (``snap_path``); the codes are the same.

Both a *real* path (integer codes, for storage/serving) and a *fake-quant*
path (quantize-dequantize with Geometric STE, for QAT) are provided.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .codebook import fibonacci_snap, make_codebook, nearest_code
from .quantizers import (
    abs_max_scale,
    fake_quant_ste,
    quantize_log_magnitude,
    dequantize_log_magnitude,
)
from .ste import geometric_ste_direction, identity_ste

__all__ = ["MDDQConfig", "mddq_fake_quant", "mddq_encode", "mddq_decode",
           "snap_path"]

_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class MDDQConfig:
    direction_bits: int = 8          # codebook size = 2**direction_bits
    magnitude_bits: int = 8
    codebook_kind: str = "fibonacci"  # or "octahedral"
    magnitude_domain: str = "log"     # or "linear"
    geometric_ste: bool = True        # False -> plain STE (ablation)
    m_min: float = 1e-6
    m_max: float = 1e3

    def codebook(self) -> jnp.ndarray:
        return make_codebook(self.direction_bits, self.codebook_kind)


def snap_path(cfg: MDDQConfig) -> str:
    """How Q_d finds the nearest codeword for ``cfg``: ``"closed_form"``
    (``fibonacci_snap``, 16 candidates per vector) for the Fibonacci
    codebook, ``"scan"`` (``nearest_code``, every codeword) otherwise.
    Both give the same codes."""
    return "closed_form" if cfg.codebook_kind == "fibonacci" else "scan"


def _snap(u: jnp.ndarray, cfg: MDDQConfig, codebook: jnp.ndarray):
    """Q_d: (codes (...,), codewords (..., 3)) of unit vectors u."""
    if snap_path(cfg) == "scan":
        idx = nearest_code(u, codebook)
        return idx, codebook[idx]
    if codebook.shape[0] != 2 ** cfg.direction_bits:
        raise ValueError(
            f"a {codebook.shape[0]}-word codebook for a Fibonacci codebook "
            f"of {cfg.direction_bits} bits")
    return fibonacci_snap(u, codebook)


def _split(v: jnp.ndarray):
    # NaN-safe norm: d||v||/dv at v = 0 is 0/0; clamping the squared norm
    # before the sqrt makes the gradient exactly zero there instead, so
    # zero vectors (isolated atoms, padded batch slots) stay differentiable.
    m2 = jnp.sum(v * v, axis=-1, keepdims=True)
    m = jnp.sqrt(jnp.maximum(m2, _EPS * _EPS))
    u = v / jnp.maximum(m, _EPS)
    return m, u


def mddq_fake_quant(v: jnp.ndarray, cfg: MDDQConfig,
                    codebook: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Differentiable MDDQ for QAT. v: (..., 3) -> (..., 3).

    Gradients: magnitude path uses linear STE; direction path uses Geometric
    STE (tangent projection) unless cfg.geometric_ste is False.
    """
    if codebook is None:
        codebook = cfg.codebook()
    m, u = _split(v)

    # -- direction: snap to nearest codeword (non-differentiable) + STE
    _, q_dir = _snap(jax.lax.stop_gradient(u), cfg, codebook)
    ste = geometric_ste_direction if cfg.geometric_ste else identity_ste
    u_hat = ste(u, q_dir)

    # -- magnitude
    if cfg.magnitude_domain == "log":
        code = quantize_log_magnitude(jax.lax.stop_gradient(m),
                                      cfg.magnitude_bits, cfg.m_min, cfg.m_max)
        m_q = dequantize_log_magnitude(code, cfg.magnitude_bits,
                                       cfg.m_min, cfg.m_max)
        # straight-through on the magnitude: m + stop_grad(m_q - m)
        m_hat = m + jax.lax.stop_gradient(m_q - m)
    else:
        m_hat = fake_quant_ste(m, cfg.magnitude_bits, channel_axis=None)

    # zero vectors stay zero (direction undefined); <= because the safe
    # norm in _split floors m at exactly _EPS for v == 0
    is_zero = m <= _EPS
    return jnp.where(is_zero, 0.0, m_hat * u_hat)


def mddq_encode(v: jnp.ndarray, cfg: MDDQConfig,
                codebook: Optional[jnp.ndarray] = None):
    """Real encoding: (..., 3) float -> (dir_idx int32 (...,), mag_code int32 (...,)).

    Storage cost per vector: direction_bits + magnitude_bits (e.g. 16 bits vs
    96 bits fp32 = 6x compression at the paper's 8+8 setting).
    """
    if codebook is None:
        codebook = cfg.codebook()
    m, u = _split(v)
    dir_idx, _ = _snap(u, cfg, codebook)
    if cfg.magnitude_domain == "log":
        mag = quantize_log_magnitude(m[..., 0], cfg.magnitude_bits,
                                     cfg.m_min, cfg.m_max)
    else:
        scale = abs_max_scale(m, cfg.magnitude_bits)
        mag = jnp.clip(jnp.round(m[..., 0] / scale[..., 0]),
                       0, 2 ** cfg.magnitude_bits - 1).astype(jnp.int32)
    return dir_idx, mag


def mddq_decode(dir_idx: jnp.ndarray, mag_code: jnp.ndarray, cfg: MDDQConfig,
                codebook: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    if codebook is None:
        codebook = cfg.codebook()
    u = codebook[dir_idx]
    if cfg.magnitude_domain != "log":
        raise NotImplementedError("linear-domain decode requires stored scale")
    m = dequantize_log_magnitude(mag_code, cfg.magnitude_bits,
                                 cfg.m_min, cfg.m_max)
    return u * m[..., None]
