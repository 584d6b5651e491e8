"""Plain reference of the served GAQ force field, and the benchmark's weights.

Written from the architecture's description (So3krates with GAQ: W4/W8
per-column weights, A8 per-row activations, MDDQ on the l=1 features),
in straightforward ``jax.numpy`` over dense (B, n, n) pair tensors, with
every float contraction at ``highest`` precision. It imports nothing of
the program under test and takes nothing the program made: the float
weights come from :func:`init_params` here, and the integer weights, their
scales and the direction codebook are computed here again from them.

Semantics it follows (the served model's, per molecule):

* geometry: ``r_ij = r_j - r_i``, ``d = sqrt(|r_ij|^2 + 1e-12)``, the
  cutoff graph ``d < cutoff`` without self pairs, 16 Gaussian radial
  functions under a cosine envelope;
* per layer: layer norm, query/key/message/coefficient projections
  through the quantized matmul, cosine attention ``tau * <q/|q|, k/|k|>``
  plus a radial bias, softmax over each atom's neighbours, the invariant
  message, a two-matmul update, the equivariant message from ``Y_1`` and
  the neighbours' vectors, MDDQ on the vectors, and a feedback from their
  norms;
* readout: a quantized matmul of ``[x, |v|]``, SiLU, a float head.

A quantized matmul quantizes each activation row to ``a_bits`` by its
absolute maximum, multiplies integers exactly, and rescales. Forces are
``-dE/dr`` with the straight-through rules the served model defines:
the matmul's backward uses the dequantized weights, MDDQ passes the
magnitude straight through and projects the direction's gradient onto the
sphere's tangent plane (the paper's Geometric STE).

``Precision`` selects the reference (float32, A8) or a control one step
below it: bfloat16 float math, or A4 activations.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, jnp.ndarray]

# 1 fs in sqrt(amu * A^2 / eV): time unit of velocity Verlet with eV, A, amu
FS = 1.0 / 10.180505
_LAYER_MATMULS = ("wq", "wk", "wm", "wa", "wb", "w_upd1", "w_upd2",
                  "w_vnorm")
_EQUIVARIANT = ("wa", "wb")
_CHUNK = 4096            # codewords scored per step of the direction snap


@dataclasses.dataclass(frozen=True)
class Precision:
    """Arithmetic of one reference evaluation."""
    dtype: str = "float32"   # float math: "float32" or "bfloat16"
    a_bits: int = 8          # activation bits of every quantized matmul


REFERENCE = Precision()
CONTROLS = {"bf16": Precision(dtype="bfloat16"), "a4": Precision(a_bits=4)}


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """``n`` 32-bit words drawn from a seed of any size."""
    return np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)


def prng_key(seed: int) -> jax.Array:
    """A JAX key from a seed of any size (``PRNGKey`` keeps 32 bits)."""
    w = seed_words(seed)
    return jax.random.fold_in(jax.random.PRNGKey(int(w[0])), int(w[1]))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def param_shapes(model: dict) -> Dict[str, tuple]:
    """Name -> shape of every float weight the served model takes."""
    F, Fv, K = model["feat"], model["vec_feat"], model["n_rbf"]
    shapes = {"embed": (model["n_species"], F)}
    for i in range(model["n_layers"]):
        L = f"layer{i}"
        shapes.update({
            f"{L}/wq": (F, F), f"{L}/wk": (F, F), f"{L}/wm": (F, F),
            f"{L}/rbf_m": (K, F), f"{L}/rbf_bias": (K, 1),
            f"{L}/wa": (F, Fv), f"{L}/rbf_a": (K, Fv),
            f"{L}/wb": (F, Fv), f"{L}/rbf_b": (K, Fv),
            f"{L}/w_upd1": (F, F), f"{L}/w_upd2": (F, F),
            f"{L}/w_vnorm": (Fv, F), f"{L}/ln_g": (F,), f"{L}/ln_b": (F,)})
    shapes["ro_w1"] = (F + Fv, F)
    shapes["ro_w2"] = (F, 1)
    return shapes


def init_params(key: jax.Array, model: dict) -> Params:
    """Random float32 weights: N(0, 1/fan_in) for every matrix, 0.5 for
    the embedding, 0.1/sqrt(fan_in) for the energy head, unit layer-norm
    gains and zero biases. Jit it to make the whole tree in one call."""
    shapes = param_shapes(model)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("/ln_g"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("/ln_b"):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            scale = 0.5 if name == "embed" else 1.0 / np.sqrt(shape[0])
            if name == "ro_w2":
                scale *= 0.1
            out[name] = jax.random.normal(k, shape, jnp.float32) * scale
    return out


def make_params(seed: int, model: dict, device=None) -> Params:
    """The benchmark's weights for ``seed``, made on ``device`` in one
    jitted call."""
    key = prng_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(partial(init_params, model=model))(key)


def _qmax(bits: int) -> int:
    return 2 ** (bits - 1) - 1


def weight_bits(name: str, mode: str) -> int:
    """Bits of a matmul weight; 0 for a weight kept in float."""
    base = name.split("/")[-1]
    if name == "ro_w1" or base in _LAYER_MATMULS:
        return 4 if (mode == "w4a8" and base in _EQUIVARIANT) else 8
    return 0


def quantize(params: Params, mode: str) -> dict:
    """Per-column symmetric integer weights (as float values) and scales
    for every matmul weight; other leaves pass through."""
    out = {}
    for name, w in params.items():
        bits = weight_bits(name, mode)
        if not bits:
            out[name] = jnp.asarray(w, jnp.float32)
            continue
        qm = _qmax(bits)
        scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True),
                            1e-8) / qm
        out[name] = (jnp.clip(jnp.round(w / scale), -qm, qm), scale)
    return out


def codebook(bits: int) -> np.ndarray:
    """2**bits near-uniform directions (Fibonacci lattice), float32."""
    n = 2 ** bits
    i = np.arange(n, dtype=np.float64) + 0.5
    polar = np.arccos(1.0 - 2.0 * i / n)
    azim = np.pi * (1.0 + 5.0 ** 0.5) * i
    pts = np.stack([np.sin(polar) * np.cos(azim),
                    np.sin(polar) * np.sin(azim), np.cos(polar)], -1)
    return (pts / np.linalg.norm(pts, axis=-1, keepdims=True)).astype(
        np.float32)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _qmm(x, wq, ws, a_bits):
    qm = _qmax(a_bits)
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / qm
    xq = jnp.clip(jnp.round(x / s), -qm, qm)
    # integers below 2^8 multiply exactly; sums stay below 2^24
    acc = jnp.matmul(xq.astype(jnp.float32), wq.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return (acc.astype(x.dtype) * s) * ws.astype(x.dtype)


def _qmm_fwd(x, wq, ws, a_bits):
    return _qmm(x, wq, ws, a_bits), (wq, ws)


def _qmm_bwd(a_bits, res, g):
    wq, ws = res
    w = (wq * ws).astype(g.dtype)
    gx = jnp.matmul(g, w.T, precision=jax.lax.Precision.HIGHEST)
    return gx, jnp.zeros_like(wq), jnp.zeros_like(ws)


_qmm.defvjp(_qmm_fwd, _qmm_bwd)


def _matmul(x, w, prec: Precision):
    """x (..., K) @ weight; ``w`` is a float array or (ints, scales)."""
    dt = jnp.dtype(prec.dtype)
    if isinstance(w, tuple):
        lead = x.shape[:-1]
        y = _qmm(x.reshape(-1, x.shape[-1]), w[0], w[1], prec.a_bits)
        return y.reshape(*lead, -1)
    return jnp.matmul(x, w.astype(dt), precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def _tangent_ste(u, q):
    """Forward: the codeword ``q``. Backward: the gradient projected onto
    the tangent plane of the sphere at ``u``."""
    return q


def _tangent_fwd(u, q):
    return q, u


def _tangent_bwd(u, g):
    return g - u * jnp.sum(u * g, axis=-1, keepdims=True), jnp.zeros_like(g)


_tangent_ste.defvjp(_tangent_fwd, _tangent_bwd)


def nearest(u: jnp.ndarray, cb: jnp.ndarray) -> jnp.ndarray:
    """Index of the codeword of largest cosine with each unit vector
    (first index on ties). u: (..., 3), cb: (C, 3) -> (...,) int32."""
    flat = u.reshape(-1, 3)
    c = cb.shape[0]
    ch = min(c, _CHUNK)
    chunks = cb.reshape(c // ch, ch, 3)

    def body(carry, blk):
        best, idx, base = carry
        s = (flat[:, 0:1] * blk[:, 0] + flat[:, 1:2] * blk[:, 1]
             + flat[:, 2:3] * blk[:, 2])
        j = jnp.argmax(s, axis=1).astype(jnp.int32)
        sj = jnp.max(s, axis=1)
        take = sj > best
        return (jnp.where(take, sj, best), jnp.where(take, j + base, idx),
                base + ch), None

    init = (jnp.full(flat.shape[:1], -2.0, flat.dtype),
            jnp.zeros(flat.shape[:1], jnp.int32), jnp.int32(0))
    (_, idx, _), _ = jax.lax.scan(body, init, chunks)
    return idx.reshape(u.shape[:-1])


def mddq(v: jnp.ndarray, cb: jnp.ndarray, mag_bits: int = 8,
         m_min: float = 1e-6, m_max: float = 1e3) -> jnp.ndarray:
    """Magnitude-direction quantization of l=1 vectors (..., 3): nearest
    codeword times an 8-bit log-grid magnitude; zero vectors stay zero."""
    m = jnp.sqrt(jnp.maximum(jnp.sum(v * v, axis=-1, keepdims=True),
                             1e-24))
    u = v / jnp.maximum(m, 1e-12)
    q = cb[nearest(jax.lax.stop_gradient(u), cb)]
    levels = 2 ** mag_bits - 1
    lo, hi = np.log(m_min), np.log(m_max)
    t = (jnp.log(jnp.clip(jax.lax.stop_gradient(m), m_min, m_max)) - lo) \
        / (hi - lo)
    code = jnp.clip(jnp.round(t * levels), 0, levels)
    m_q = jnp.exp(lo + code / levels * (hi - lo)).astype(v.dtype)
    m_hat = m + jax.lax.stop_gradient(m_q - m)
    return jnp.where(m <= 1e-12, 0.0, m_hat * _tangent_ste(u, q))


def _layernorm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6) * g + b


def _unit(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-6)


def _vnorm(v):
    return jnp.sqrt(jnp.sum(v * v, axis=-1) + 1e-12)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def energies(qp: dict, model: dict, cb: jnp.ndarray, species, coords, mask,
             prec: Precision = REFERENCE) -> jnp.ndarray:
    """Per-molecule energies (B,) of a padded batch; padded atoms
    (``mask`` False) take part in nothing."""
    dt = jnp.dtype(prec.dtype)
    K, cut = model["n_rbf"], model["cutoff"]
    fl = {k: (v if isinstance(v, tuple) else v.astype(dt))
          for k, v in qp.items()}
    cb = cb.astype(dt)
    coords = coords.astype(dt)
    n = coords.shape[1]
    hi = jax.lax.Precision.HIGHEST

    rij = coords[:, None, :, :] - coords[:, :, None, :]      # [b,i,j]
    d = jnp.sqrt(jnp.sum(rij * rij, axis=-1) + 1e-12)
    pm = ((d < cut) & ~jnp.eye(n, dtype=bool)[None]
          & mask[:, :, None] & mask[:, None, :])
    u = rij / d[..., None]
    centers = jnp.linspace(0.5, cut, K).astype(dt)
    gamma = (K / cut) ** 2
    env = 0.5 * (jnp.cos(jnp.pi * jnp.clip(d / cut, 0.0, 1.0)) + 1.0)
    rbf = jnp.exp(-gamma * (d[..., None] - centers) ** 2) * env[..., None]
    rbf = rbf * pm[..., None]

    x = fl["embed"][species] * mask[..., None]
    v = jnp.zeros(x.shape[:2] + (model["vec_feat"], 3), dt)
    for i in range(model["n_layers"]):
        L = f"layer{i}"
        W = {k: fl[f"{L}/{k}"] for k in _LAYER_MATMULS}
        xn = _layernorm(x, fl[f"{L}/ln_g"], fl[f"{L}/ln_b"])
        q = _matmul(xn, W["wq"], prec)
        k = _matmul(xn, W["wk"], prec)
        bias = _matmul(rbf, fl[f"{L}/rbf_bias"], prec)[..., 0]
        logits = model["tau"] * jnp.einsum(
            "bif,bjf->bij", _unit(q), _unit(k), precision=hi) + bias
        alpha = jax.nn.softmax(jnp.where(pm, logits, -1e9), axis=-1)

        msg = _matmul(xn, W["wm"], prec)
        gate = _matmul(rbf, fl[f"{L}/rbf_m"], prec)           # (B,n,n,F)
        x = x + jnp.einsum("bij,bijf,bjf->bif", alpha, gate, msg,
                           precision=hi)
        h = jax.nn.silu(_matmul(x, W["w_upd1"], prec))
        x = x + _matmul(h, W["w_upd2"], prec)

        ca = _matmul(xn, W["wa"], prec)[:, None] \
            * _matmul(rbf, fl[f"{L}/rbf_a"], prec)
        cbj = _matmul(xn, W["wb"], prec)[:, None] \
            * _matmul(rbf, fl[f"{L}/rbf_b"], prec)
        dv = jnp.einsum("bij,bijc,bijd->bicd", alpha, ca, u, precision=hi) \
            + jnp.einsum("bij,bijc,bjcd->bicd", alpha, cbj, v, precision=hi)
        v = mddq(v + dv, cb)
        x = x + _matmul(jax.nn.silu(_vnorm(v)), W["w_vnorm"], prec)

    feats = jnp.concatenate([x, _vnorm(v)], axis=-1)
    hid = jax.nn.silu(_matmul(feats, fl["ro_w1"], prec))
    e_atom = _matmul(hid, fl["ro_w2"], prec)[..., 0]
    return jnp.sum(e_atom * mask, axis=-1)


def energy_forces(qp, model, cb, species, coords, mask,
                  prec: Precision = REFERENCE):
    """Energies (B,) and forces (B, n, 3) = -dE/dr, as float32."""
    def total(c):
        e = energies(qp, model, cb, species, c, mask, prec)
        return jnp.sum(e.astype(jnp.float32)), e

    (_, e), g = jax.value_and_grad(total, has_aux=True)(
        coords.astype(jnp.dtype(prec.dtype)))
    return e.astype(jnp.float32), -g.astype(jnp.float32)


def verlet(qp, model, cb, species, coords, veloc, forces, mask, masses,
           dt_fs: float, n_steps: int, prec: Precision = REFERENCE):
    """``n_steps`` of velocity Verlet from (coords, veloc, forces), with
    this reference's forces. Returns (coords, veloc, forces, e_pot)."""
    dt = dt_fs * FS
    inv_m = jnp.where(mask, 1.0 / jnp.maximum(masses, 1e-9), 0.0)[..., None]

    def step(s, _):
        r, v, f, _e = s
        v_half = v + 0.5 * dt * f * inv_m
        r = r + dt * v_half
        e, f = energy_forces(qp, model, cb, species, r, mask, prec)
        return (r, v_half + 0.5 * dt * f * inv_m, f, e), None

    e0 = jnp.zeros(coords.shape[:1], jnp.float32)
    out, _ = jax.lax.scan(step, (coords, veloc, forces, e0), None,
                          length=n_steps)
    return out


class Reference:
    """The reference of one configuration at one precision, with its jitted
    programs; ``qp`` and the codebook are computed here from the float
    weights."""

    def __init__(self, params: Params, model: dict, mode: str,
                 prec: Precision = REFERENCE):
        self.model = model
        self.prec = prec
        self.qp = quantize(params, mode)
        self.cb = jnp.asarray(codebook(model["dir_bits"]))
        self._ef = jax.jit(lambda qp, cb, s, c, m: energy_forces(
            qp, model, cb, s, c, m, prec))
        self._verlet = jax.jit(
            lambda qp, cb, s, c, v, f, m, ms, dt_fs, n: verlet(
                qp, model, cb, s, c, v, f, m, ms, dt_fs, n, prec),
            static_argnums=(8, 9))

    def energy_forces(self, species, coords, mask):
        e, f = self._ef(self.qp, self.cb, jnp.asarray(species),
                        jnp.asarray(coords, jnp.float32), jnp.asarray(mask))
        return np.asarray(e), np.asarray(f)

    def verlet(self, species, coords, veloc, forces, mask, masses,
               dt_fs: float, n_steps: int):
        out = self._verlet(self.qp, self.cb, jnp.asarray(species),
                           jnp.asarray(coords, jnp.float32),
                           jnp.asarray(veloc, jnp.float32),
                           jnp.asarray(forces, jnp.float32),
                           jnp.asarray(mask), jnp.asarray(masses, jnp.float32),
                           float(dt_fs), int(n_steps))
        return tuple(np.asarray(a) for a in out)
