"""Tests for MDDQ, spherical codebooks, geometric STE, LEE, attention norm."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from helpers.equivariance import assert_rotation_equivariant_bounded
from repro.core import (
    MDDQConfig,
    covering_radius,
    fibonacci_snap,
    fibonacci_sphere,
    geometric_ste_direction,
    lee,
    lee_regularizer,
    make_codebook,
    mddq_decode,
    mddq_encode,
    mddq_fake_quant,
    nearest_code,
    nearest_fibonacci_code,
    octahedral_sphere,
    quantize_direction,
    random_rotation,
    random_rotations,
    robust_attention_weights,
    cosine_attention_logits,
    snap_path,
)
from repro.core import mddq as mddq_mod


def _rand_vectors(key, shape):
    return jax.random.normal(key, shape + (3,))


class TestCodebook:
    def test_fibonacci_unit_norm(self):
        c = fibonacci_sphere(256)
        np.testing.assert_allclose(np.linalg.norm(c, axis=-1), 1.0, atol=1e-6)

    def test_octahedral_closed_under_group(self):
        c = octahedral_sphere(256)
        assert len(c) > 0
        # rotating the codebook by a group element permutes it
        R = np.array([[0, -1, 0], [1, 0, 0], [0, 0, 1]], dtype=np.float32)  # z 90deg
        rc = c @ R.T
        d = np.linalg.norm(rc[:, None, :] - c[None, :, :], axis=-1).min(axis=1)
        assert d.max() < 1e-4

    def test_covering_radius_decreases_with_bits(self):
        r4 = covering_radius(make_codebook(4), n_samples=20000)
        r8 = covering_radius(make_codebook(8), n_samples=20000)
        assert r8 < r4
        # 256 points: expected covering radius ~ sqrt(4/N) ~ 0.125 rad; be loose
        assert r8 < 0.25

    def test_nearest_code_exact_on_codewords(self):
        c = make_codebook(6)
        idx = nearest_code(c, c)
        np.testing.assert_array_equal(np.asarray(idx), np.arange(len(c)))


class TestMDDQ:
    def test_fake_quant_preserves_shape_and_bounded_angle(self):
        cfg = MDDQConfig(direction_bits=8)
        v = _rand_vectors(jax.random.PRNGKey(0), (128,))
        q = mddq_fake_quant(v, cfg)
        assert q.shape == v.shape
        cos = np.sum(np.asarray(q) * np.asarray(v), axis=-1) / (
            np.linalg.norm(q, axis=-1) * np.linalg.norm(v, axis=-1))
        delta = covering_radius(cfg.codebook(), n_samples=50000)
        assert np.arccos(np.clip(cos, -1, 1)).max() <= delta + 0.02

    def test_magnitude_relative_error_small(self):
        cfg = MDDQConfig()
        v = _rand_vectors(jax.random.PRNGKey(1), (256,)) * 10.0
        q = mddq_fake_quant(v, cfg)
        m_in = np.linalg.norm(np.asarray(v), axis=-1)
        m_out = np.linalg.norm(np.asarray(q), axis=-1)
        assert np.abs(m_out / m_in - 1).max() < 0.05

    def test_zero_vector_maps_to_zero(self):
        cfg = MDDQConfig()
        v = jnp.zeros((4, 3))
        np.testing.assert_allclose(np.asarray(mddq_fake_quant(v, cfg)), 0.0)

    def test_encode_decode_roundtrip(self):
        cfg = MDDQConfig()
        v = _rand_vectors(jax.random.PRNGKey(2), (64,))
        idx, mag = mddq_encode(v, cfg)
        assert idx.dtype == jnp.int32
        v2 = mddq_decode(idx, mag, cfg)
        # bounded error: angle <= covering radius, magnitude rel err < 5%
        cos = np.sum(np.asarray(v2) * np.asarray(v), axis=-1) / (
            np.linalg.norm(v2, axis=-1) * np.linalg.norm(v, axis=-1))
        assert cos.min() > np.cos(0.25)

    @given(st.integers(0, 50))
    @settings(max_examples=10, deadline=None)
    def test_approximate_equivariance_property(self, seed):
        """Q(Rv) ~ R Q(v) up to 2*covering-radius chordal error (paper Eq. 4)."""
        cfg = MDDQConfig(direction_bits=8)
        cb = cfg.codebook()
        key = jax.random.PRNGKey(seed)
        k1, k2 = jax.random.split(key)
        v = _rand_vectors(k1, (32,))
        u = v / jnp.linalg.norm(v, axis=-1, keepdims=True)
        # both sides land within delta of Ru -> within 2 delta (chordal)
        delta = 0.17  # measured covering radius of 256-pt fibonacci ~ 0.135
        assert_rotation_equivariant_bounded(
            lambda x: quantize_direction(jnp.asarray(x), cb), u,
            bound=2 * 2 * np.sin(delta / 2) + 1e-5,
            R=np.asarray(random_rotation(k2), np.float32))


def _unit(v):
    v = np.asarray(v, np.float64)
    return (v / np.linalg.norm(v, axis=-1, keepdims=True)).astype(np.float32)


def _snap_vectors(family: str, bits: int, seed: int = 0) -> np.ndarray:
    """Unit vectors where a closed-form snap can go wrong: anywhere, at
    the poles, on the equator, on the azimuth's +-pi seam, on codewords,
    and half-way between consecutive codewords. 2,000 of each (20,000
    random ones at 16 bits), so one compile serves every family."""
    n = 2 ** bits
    rng = np.random.default_rng(seed)
    cb = np.asarray(make_codebook(bits), np.float64)
    m = 2000
    if family == "random":
        return _unit(rng.normal(size=(20000 if bits == 16 else m, 3)))
    if family == "codewords":
        return cb[rng.choice(n, m, replace=n < m)].astype(np.float32)
    if family == "midpoints":
        j = rng.choice(n - 1, m, replace=n - 1 < m)
        return _unit(cb[j] + cb[j + 1])
    if family == "poles":
        z = rng.choice([-1.0, 1.0], m) * (1.0 - rng.uniform(0.0, 4.0 / n, m))
        az = rng.uniform(-np.pi, np.pi, m)
    elif family == "equator":
        z = rng.normal(0.0, 1e-4, m)
        az = rng.uniform(-np.pi, np.pi, m)
    else:                                   # the seam, from either side
        z = rng.uniform(-1.0, 1.0, m)
        az = np.pi + rng.normal(0.0, 1e-6, m)
    r = np.sqrt(np.maximum(1.0 - z * z, 0.0))
    return _unit(np.stack([r * np.cos(az), r * np.sin(az), z], -1))


class TestClosedFormSnap:
    """``nearest_fibonacci_code`` returns the scan's codes, ties included."""

    @pytest.mark.parametrize("family", ["random", "poles", "equator", "seam",
                                        "codewords", "midpoints"])
    @pytest.mark.parametrize("bits", [4, 6, 8, 10, 12, 16])
    def test_codes_equal_the_scan(self, bits, family):
        cb = make_codebook(bits)
        u = jnp.asarray(_snap_vectors(family, bits))
        want = np.asarray(jax.jit(nearest_code)(u, cb))
        got, rows = map(np.asarray, fibonacci_snap(u, cb))
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(rows, np.asarray(cb)[want])
        if family == "codewords":       # each codeword is its own code
            np.testing.assert_array_equal(
                np.asarray(cb)[got], np.asarray(u))

    @pytest.mark.parametrize("bits", [8, 16])
    def test_zero_vectors(self, bits):
        cb = make_codebook(bits)
        idx = np.asarray(nearest_fibonacci_code(jnp.zeros((5, 3)), cb))
        assert ((idx >= 0) & (idx < 2 ** bits)).all()
        cfg = MDDQConfig(direction_bits=bits)
        v = jnp.zeros((4, 3)).at[1].set(jnp.array([0.3, -1.0, 2.0]))
        out, grad = jax.jit(jax.value_and_grad(
            lambda v_: jnp.sum(mddq_fake_quant(v_, cfg, cb) ** 2)))(v)
        assert np.isfinite(np.asarray(out)) and np.isfinite(grad).all()
        q = np.asarray(jax.jit(lambda v_: mddq_fake_quant(v_, cfg, cb))(v))
        np.testing.assert_array_equal(q[[0, 2, 3]], 0.0)
        assert np.linalg.norm(q[1]) > 0

    def test_codes_do_not_depend_on_batch_shape(self):
        cb = make_codebook(16)
        u = jnp.asarray(_unit(np.random.default_rng(3).normal(
            size=(8, 16, 16, 3))))
        batched = np.asarray(nearest_fibonacci_code(u, cb))
        flat = np.asarray(nearest_fibonacci_code(u.reshape(-1, 3), cb))
        assert batched.shape == (8, 16, 16)
        np.testing.assert_array_equal(batched.reshape(-1), flat)

    @pytest.mark.parametrize("bits", [8, 16])
    def test_fake_quant_bit_identical_to_the_scan(self, bits, monkeypatch):
        cfg = MDDQConfig(direction_bits=bits)
        cb = make_codebook(bits)
        v = jax.random.normal(jax.random.PRNGKey(bits), (64, 16, 3)) * 2.0
        v = v.at[0, :4].set(0.0)
        w = jnp.arange(v.size, dtype=jnp.float32).reshape(v.shape) / v.size

        def out_and_grad():     # op by op, so both run the same kernels
            return jax.value_and_grad(
                lambda v_: jnp.sum(w * mddq_fake_quant(v_, cfg, cb)))(v), \
                mddq_fake_quant(v, cfg, cb)

        (l_cf, g_cf), q_cf = out_and_grad()
        monkeypatch.setattr(mddq_mod, "fibonacci_snap",
                            lambda u, c: (nearest_code(u, c),
                                          c[nearest_code(u, c)]))
        (l_sc, g_sc), q_sc = out_and_grad()
        np.testing.assert_array_equal(np.asarray(q_cf), np.asarray(q_sc))
        np.testing.assert_array_equal(np.asarray(g_cf), np.asarray(g_sc))
        assert float(l_cf) == float(l_sc)

    @pytest.mark.parametrize("kind,path", [("fibonacci", "closed_form"),
                                           ("octahedral", "scan")])
    def test_snap_path_follows_the_codebook_kind(self, kind, path):
        cfg = MDDQConfig(direction_bits=8, codebook_kind=kind)
        assert snap_path(cfg) == path
        v = jax.random.normal(jax.random.PRNGKey(4), (32, 3))
        idx, _ = mddq_encode(v, cfg)
        want = nearest_code(v / jnp.linalg.norm(v, axis=-1, keepdims=True),
                            cfg.codebook())
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(want))

    def test_wrong_codebook_size_is_refused(self):
        cfg = MDDQConfig(direction_bits=8)
        with pytest.raises(ValueError, match="8 bits"):
            mddq_fake_quant(jnp.ones((2, 3)), cfg, make_codebook(6))

    def test_engine_counts_closed_form_programs(self):
        from repro.models import so3krates as so3
        from repro.obs import REGISTRY
        from repro.serving import QuantizedEngine, ServeConfig
        serve = ServeConfig(mode="w8a8", bucket_sizes=(8,), max_batch=1)
        count = {p: REGISTRY.counter("mddq_snap_programs_total",
                                     mode="w8a8", path=p)
                 for p in ("closed_form", "scan")}
        before = {p: c.value for p, c in count.items()}
        engine = QuantizedEngine.from_config(
            so3.So3kratesConfig(feat=16, vec_feat=4, n_layers=1, n_rbf=4),
            serve=serve)
        engine.warmup()
        assert count["closed_form"].value - before["closed_form"] \
            == len(engine.compiled_shapes) > 0
        assert count["scan"].value == before["scan"]


class TestGeometricSTE:
    def test_gradient_is_tangent(self):
        key = jax.random.PRNGKey(0)
        v = _rand_vectors(key, (16,))
        u = v / jnp.linalg.norm(v, axis=-1, keepdims=True)
        q = quantize_direction(u, make_codebook(8))

        def loss(u_):
            out = geometric_ste_direction(u_, q)
            return jnp.sum(out * jnp.arange(48.0).reshape(16, 3))

        g = jax.grad(loss)(u)
        radial = np.abs(np.sum(np.asarray(g) * np.asarray(u), axis=-1))
        assert radial.max() < 1e-5  # Prop III.1: <u, dL/du> = 0

    def test_forward_returns_quantized(self):
        u = jnp.array([[1.0, 0.0, 0.0]])
        q = jnp.array([[0.0, 1.0, 0.0]])
        np.testing.assert_allclose(np.asarray(geometric_ste_direction(u, q)), np.asarray(q))


class TestLEE:
    def test_rotation_is_orthogonal(self):
        Rs = random_rotations(jax.random.PRNGKey(0), 8)
        eye = jnp.einsum("rij,rkj->rik", Rs, Rs)
        np.testing.assert_allclose(np.asarray(eye), np.tile(np.eye(3), (8, 1, 1)), atol=1e-5)
        det = np.linalg.det(np.asarray(Rs))
        np.testing.assert_allclose(det, 1.0, atol=1e-5)

    def test_lee_zero_for_equivariant_fn(self):
        # f(X) = X @ A with A = a I is equivariant: (XR^T) aI = (X aI) R^T
        f = lambda x: 2.5 * x
        coords = jax.random.normal(jax.random.PRNGKey(1), (10, 3))
        R = random_rotation(jax.random.PRNGKey(2))
        assert float(lee(f, coords, R)) < 1e-5

    def test_lee_positive_for_non_equivariant_fn(self):
        f = lambda x: x ** 2  # breaks equivariance
        coords = jax.random.normal(jax.random.PRNGKey(1), (10, 3))
        R = random_rotation(jax.random.PRNGKey(2))
        assert float(lee(f, coords, R)) > 0.1

    def test_regularizer_differentiable(self):
        coords = jax.random.normal(jax.random.PRNGKey(1), (6, 3))

        def model(w, x):
            return x * w  # equivariant iff scalar; grad flows through w

        g = jax.grad(lambda w: lee_regularizer(
            lambda x: model(w, x) + w * x ** 2, coords, jax.random.PRNGKey(0)))(1.0)
        assert np.isfinite(g)


class TestRobustAttention:
    def test_logits_bounded_by_tau(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 8)) * 100.0
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 8)) * 100.0
        logits = cosine_attention_logits(q, k, tau=10.0)
        assert float(jnp.max(jnp.abs(logits))) <= 10.0 + 1e-4

    def test_weights_sum_to_one_and_masked(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (3, 4, 8))
        k = jax.random.normal(jax.random.PRNGKey(1), (3, 6, 8))
        mask = jnp.ones((3, 4, 6), bool).at[:, :, -1].set(False)
        w = robust_attention_weights(q, k, mask=mask)
        np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
        assert float(w[..., -1].max()) < 1e-6

    def test_scale_invariance(self):
        """Attention depends only on directions (paper: scale carried by values)."""
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 8))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 8))
        w1 = robust_attention_weights(q, k)
        w2 = robust_attention_weights(q * 37.0, k * 0.01)
        np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-5)
