"""Spherical codebooks for the direction quantizer Q_d : S^2 -> C.

The paper requires a finite codebook C subset S^2 whose nearest-neighbour map
approximately commutes with rotations. We provide:

* ``fibonacci_sphere`` — near-uniform covering of S^2 (the default; covering
  radius decays ~ 1/sqrt(N), close to optimal for large N).
* ``octahedral_sphere`` — a grid symmetric under the octahedral subgroup of
  SO(3); exact commutation holds for the 24 rotations of that subgroup, which
  empirically lowers the *average* commutation error for small N.
* ``covering_radius`` — Monte-Carlo estimate of delta_d (Eq. 6).
* ``nearest_code`` — the Q_d map itself (argmax of dot products; on S^2 the
  geodesic-nearest codeword is the max-cosine codeword), by a scan over
  every codeword: it serves any codebook.
* ``nearest_fibonacci_code`` / ``fibonacci_snap`` — the same map for a
  ``fibonacci_sphere`` codebook in closed form: 16 candidates per vector,
  identical codes.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

__all__ = [
    "fibonacci_sphere",
    "octahedral_sphere",
    "make_codebook",
    "nearest_code",
    "nearest_fibonacci_code",
    "fibonacci_snap",
    "quantize_direction",
    "covering_radius",
]


def fibonacci_sphere(n: int) -> np.ndarray:
    """n near-uniform points on S^2 via the Fibonacci lattice. (n, 3) float32."""
    i = np.arange(n, dtype=np.float64) + 0.5
    phi = np.arccos(1.0 - 2.0 * i / n)           # polar angle
    golden = np.pi * (1.0 + 5.0 ** 0.5)           # golden angle * 2
    theta = golden * i
    x = np.sin(phi) * np.cos(theta)
    y = np.sin(phi) * np.sin(theta)
    z = np.cos(phi)
    pts = np.stack([x, y, z], axis=-1)
    return (pts / np.linalg.norm(pts, axis=-1, keepdims=True)).astype(np.float32)


def octahedral_sphere(n: int) -> np.ndarray:
    """Codebook closed under the octahedral rotation subgroup.

    Takes a Fibonacci seed restricted to one fundamental domain and replicates
    it by the 24 rotation matrices of the cube/octahedron group, then dedups.
    Resulting size is <= n (rounded to a multiple of orbit sizes).
    """
    group = _octahedral_rotations()
    seed_n = max(1, n // 24)
    seed = fibonacci_sphere(seed_n * 4)  # oversample, keep fundamental domain
    # fundamental domain of the octahedral group: x >= y >= z >= 0 (approx)
    mask = (seed[:, 0] >= seed[:, 1]) & (seed[:, 1] >= seed[:, 2]) & (seed[:, 2] >= 0)
    seed = seed[mask][:seed_n]
    if len(seed) == 0:
        seed = np.array([[1.0, 0.0, 0.0]], dtype=np.float32)
    orbit = np.einsum("gij,nj->gni", group, seed).reshape(-1, 3)
    # dedup points that coincide (seed on a symmetry axis has small orbit)
    rounded = np.round(orbit * 1e5).astype(np.int64)
    _, idx = np.unique(rounded, axis=0, return_index=True)
    pts = orbit[np.sort(idx)]
    return (pts / np.linalg.norm(pts, axis=-1, keepdims=True)).astype(np.float32)


def _octahedral_rotations() -> np.ndarray:
    """The 24 rotation matrices of the octahedral group (signed permutations
    with determinant +1)."""
    mats = []
    import itertools
    for perm in itertools.permutations(range(3)):
        for signs in itertools.product([1, -1], repeat=3):
            m = np.zeros((3, 3))
            for r, c in enumerate(perm):
                m[r, c] = signs[r]
            if np.isclose(np.linalg.det(m), 1.0):
                mats.append(m)
    out = np.stack(mats).astype(np.float32)
    assert out.shape[0] == 24
    return out


@functools.lru_cache(maxsize=None)
def make_codebook(bits: int = 8, kind: str = "fibonacci") -> jnp.ndarray:
    """Codebook with 2**bits entries (or the closest achievable size).

    Cached: the host-side lattice construction is pure in (bits, kind)
    and gets called per forward by serving/engine code — a 16-bit
    codebook is 65536 numpy trig evaluations we only want once. The
    returned jax array is immutable, so sharing one instance is safe.
    The conversion is forced to evaluate eagerly: the first call may
    happen inside a jit trace (e.g. ``sparse_energy(codebook=None)``
    under jit), and staging it there would cache a tracer that escapes
    into every later trace.
    """
    n = 2 ** bits
    if kind == "fibonacci":
        pts = fibonacci_sphere(n)
    elif kind == "octahedral":
        pts = octahedral_sphere(n)
    else:
        raise ValueError(f"unknown codebook kind {kind!r}")
    with jax.ensure_compile_time_eval():
        return jnp.asarray(pts)


_NEAREST_CHUNK = 4096


def _scores(u: jnp.ndarray, codebook: jnp.ndarray) -> jnp.ndarray:
    """u . c for every codeword c, as three f32 multiply-adds: (..., N)."""
    return (u[..., None, 0] * codebook[:, 0] + u[..., None, 1] * codebook[:, 1]
            + u[..., None, 2] * codebook[:, 2])


def nearest_code(u: jnp.ndarray, codebook: jnp.ndarray) -> jnp.ndarray:
    """Index of the geodesic-nearest codeword for each unit vector.

    u: (..., 3); codebook: (N, 3). Returns int32 (...,).
    Large codebooks (16-bit = 65536 entries) are scanned in chunks so the
    score matrix never materializes at full width (the Pallas kernel tiles
    the same way in VMEM).

    The scores are elementwise f32 products, not a matmul. At 16
    direction bits neighbouring codewords' scores differ by ~5e-5, far
    below the single bf16 pass a TPU gives an f32 matmul by default,
    which snapped 92% of random directions to another codeword on a TPU
    v5e. A
    contraction over 3 would also fill 3 of the matrix unit's 128 rows,
    and at ``precision=HIGHEST`` the batched einsum still returned wrong
    codes for 7 in 8 vectors of a (128, 16, 3) input on that chip
    (JAX 0.9.0, libtpu 0.0.34).
    """
    n = codebook.shape[0]
    if n <= _NEAREST_CHUNK:
        return jnp.argmax(_scores(u, codebook), axis=-1).astype(jnp.int32)

    pad = (-n) % _NEAREST_CHUNK
    cb = jnp.concatenate([codebook, jnp.tile(codebook[:1], (pad, 1))]) \
        if pad else codebook
    chunks = cb.reshape(-1, _NEAREST_CHUNK, 3)

    def step(carry, ck):
        best, idx, base = carry
        scores = _scores(u, ck[0])
        s = jnp.max(scores, axis=-1)
        i = jnp.argmax(scores, axis=-1).astype(jnp.int32) + base
        take = s > best
        return (jnp.where(take, s, best), jnp.where(take, i, idx),
                base + _NEAREST_CHUNK), None

    init = (jnp.full(u.shape[:-1], -2.0, u.dtype),
            jnp.zeros(u.shape[:-1], jnp.int32), jnp.int32(0))
    (best, idx, _), _ = jax.lax.scan(step, init, chunks[:, None])
    return idx


_PHI = (1.0 + 5.0 ** 0.5) / 2.0
# fibonacci_sphere puts codeword i at azimuth 2*pi*PHI*(i + 1/2), which is
# 2*pi*i/PHI + pi*PHI (mod 2*pi): the lattice of Keinert et al. 2015
# ("Spherical Fibonacci Mapping"), turned by this constant
_AZIMUTH0 = float(np.mod(np.pi * _PHI, 2.0 * np.pi))
# lattice offsets of the candidate block around the cell's corner: the
# cell's own 4 corners and a ring of neighbours, so float32 rounding of
# the cell never loses the nearest codeword
_BLOCK = np.arange(-1, 3)
# (PHI - 1)/2 in units of 2**-47, split at bit 24, for the exact integer
# part of codeword i's azimuth turns (2i + 1)(PHI - 1)/2
_G = int(round((_PHI - 1.0) * 2.0 ** 46))
_G_HI, _G_LO = _G >> 23, _G & ((1 << 23) - 1)
# candidates whose stored coordinates decide the code: the best four by
# the computed coordinates, whose error (< 4e-7 per coordinate) can
# reorder only codewords whose scores lie within ~1.5e-6 of each other;
# five such codewords would need five lattice points on one small circle
_VERIFY = 4


def _fibonacci_point(i: jnp.ndarray, n: int):
    """``fibonacci_sphere(n)[i]`` computed in float32 from the index, to
    within 4e-7 per coordinate: x, y, z arrays shaped like ``i``.

    z_i = 1 - (2i + 1)/n is exact. The azimuth's turns (2i + 1)(PHI - 1)/2
    are too large for float32 at 16 bits, so their fraction comes from an
    exact 32-bit integer product plus a small float32 remainder."""
    j = 2 * i + 1
    z = 1.0 - j.astype(jnp.float32) / n
    s = jnp.sqrt((1.0 - z) * (1.0 + z))
    ju = j.astype(jnp.uint32)
    # frac(j * _G_HI / 2**24 + 1/2) - 1/2, in units of 2**-24
    m = ((ju * np.uint32(_G_HI) + np.uint32(1 << 23))
         & np.uint32((1 << 24) - 1)).astype(jnp.int32) - (1 << 23)
    t = (m.astype(jnp.float32) * 2.0 ** -24
         + j.astype(jnp.float32) * (_G_LO * 2.0 ** -47))
    # the azimuth is 2*pi*(t + 1/2): cos and sin change sign
    return -s * jnp.cos(2.0 * np.pi * t), -s * jnp.sin(2.0 * np.pi * t), z


@jax.jit
def fibonacci_snap(u: jnp.ndarray, codebook: jnp.ndarray):
    """``nearest_code`` for ``codebook = fibonacci_sphere(n)``, without the
    scan: (codes int32 (...,), their codewords (..., 3)).

    Codeword i sits at z_i = 1 - (2i + 1)/n and azimuth 2*pi*i/PHI (after
    ``_AZIMUTH0``). In the (azimuth, z) plane these points, repeated every
    2*pi, form a lattice; at latitude z the lattice vectors of indices
    F_k and F_{k+1} (consecutive Fibonacci numbers, k from Keinert's zone
    formula) span a near-square cell on the sphere. Solving for the cell
    that holds u, in closed form, gives its corner (a, b), i.e. index
    a*F_k + b*F_{k+1}; the 4 x 4 block of lattice points around it are
    the 16 candidates. They are ranked by coordinates computed from their
    indices, and the best ``_VERIFY`` are gathered from the codebook and
    scored with ``_scores``' three f32 products, the lowest index winning
    a tie, as in the scan: the codes are the scan's. A zero vector ties
    every codeword and gets 0, as in the scan.

    A gathered row costs ~8 ns on a TPU v5e whether it holds 1 float or
    3, more than the rest of the work per candidate, so stored rows are
    gathered for 4 candidates, not 16, and the winner's row comes back
    with its code.

    u: (..., 3); codebook: (n, 3), the rows of ``fibonacci_sphere(n)``.
    """
    n = codebook.shape[0]
    lead = u.shape[:-1]
    x, y, z = (u.reshape(-1, 3)[:, d] for d in range(3))       # (V,)
    r2 = x * x + y * y + z * z
    cos_t = jnp.clip(z * jax.lax.rsqrt(jnp.maximum(r2, 1e-30)), -1.0, 1.0)
    turns = (jnp.arctan2(y, x) - _AZIMUTH0) / (2.0 * np.pi)
    # Keinert's zone: the k whose lattice vectors are shortest here
    k_max = max(2, int(np.log(n * np.pi * 5.0 ** 0.5) / np.log(_PHI ** 2)))
    zone = jnp.log(n * np.pi * 5.0 ** 0.5
                   * jnp.maximum(1.0 - cos_t * cos_t, 1e-30)) \
        / np.log(_PHI ** 2)
    k = jnp.clip(jnp.floor(zone), 2, k_max)
    # Binet: F_k = round(PHI**k / sqrt 5); the two basis vectors are
    # (2*pi*d_k, -2 F_k / n) with d_k = F_k/PHI - F_{k-1} = -(-1/PHI)**k,
    # and their determinant is (-1)**k 4 pi / n, so the inverse is exact
    # in these terms: a and b are the corner's lattice coordinates
    pk = jnp.exp(k * np.log(_PHI))
    f0 = jnp.round(pk / 5.0 ** 0.5).astype(jnp.int32)
    f1 = jnp.round(pk * (_PHI / 5.0 ** 0.5)).astype(jnp.int32)
    sign = 1.0 - 2.0 * jnp.mod(k, 2.0)
    dz = 0.5 * n * (cos_t - (1.0 - 1.0 / n))
    a = jnp.floor(-sign * f1 * turns - dz / (pk * _PHI)).astype(jnp.int32)
    b = jnp.floor(sign * f0 * turns - dz / pk).astype(jnp.int32)

    da, db = (jnp.asarray(o.reshape(-1, 1), jnp.int32)
              for o in np.meshgrid(_BLOCK, _BLOCK, indexing="ij"))
    cand = jnp.clip((a + da) * f0 + (b + db) * f1, 0, n - 1)   # (16, V)
    cx, cy, cz = _fibonacci_point(cand, n)
    approx = x * cx + y * cy + z * cz
    picks = []
    for _ in range(_VERIFY):
        top = jnp.max(approx, axis=0)
        pick = jnp.min(jnp.where(approx == top, cand, n), axis=0)
        picks.append(pick)
        approx = jnp.where(cand == pick, -jnp.inf, approx)
    picks = jnp.stack(picks)                                   # (4, V)
    rows = codebook[picks]                                     # (4, V, 3)
    scores = (x * rows[..., 0] + y * rows[..., 1]) + z * rows[..., 2]
    best = jnp.max(scores, axis=0)
    idx = jnp.min(jnp.where(scores == best, picks, n), axis=0)
    q = rows[-1]
    for r in range(_VERIFY - 1, -1, -1):
        q = jnp.where((picks[r] == idx)[:, None], rows[r], q)
    nonzero = r2 > 0.0
    idx = jnp.where(nonzero, idx, 0).astype(jnp.int32)
    q = jnp.where(nonzero[:, None], q, codebook[0])
    return idx.reshape(lead), q.reshape(lead + (3,))


def nearest_fibonacci_code(u: jnp.ndarray, codebook: jnp.ndarray
                           ) -> jnp.ndarray:
    """``nearest_code(u, codebook)`` for ``codebook = fibonacci_sphere(n)``
    in closed form (``fibonacci_snap``): the same int32 codes (...,)."""
    return fibonacci_snap(u, codebook)[0]


def quantize_direction(u: jnp.ndarray, codebook: jnp.ndarray) -> jnp.ndarray:
    """Q_d: snap unit vectors to their nearest codeword. Shape-preserving."""
    idx = nearest_code(u, codebook)
    return codebook[idx]


def covering_radius(codebook: jnp.ndarray, n_samples: int = 200_000,
                    seed: int = 0) -> float:
    """Monte-Carlo estimate of delta_d = sup_u min_c angle(u, c) (radians)."""
    key = jax.random.PRNGKey(seed)
    v = jax.random.normal(key, (n_samples, 3))
    u = v / jnp.linalg.norm(v, axis=-1, keepdims=True)
    cos = jnp.einsum("sd,nd->sn", u, codebook)
    best = jnp.max(cos, axis=-1)
    return float(jnp.max(jnp.arccos(jnp.clip(best, -1.0, 1.0))))
