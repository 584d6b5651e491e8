"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracle,
with shape/dtype sweeps as required.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_codebook
from repro.core.quantizers import abs_max_scale, pack_int4, quantize
from repro.kernels import ops, ref
from repro.kernels.quant_matmul import w4a8_matmul, w8a8_matmul
from repro.kernels.mddq_kernel import mddq_encode_kernel
from repro.kernels.attention_int8kv import decode_attention_int8kv


def _mk_w8(key, m, k, n):
    k1, k2 = jax.random.split(key)
    a = jax.random.normal(k1, (m, k))
    w = jax.random.normal(k2, (k, n))
    a_scale = abs_max_scale(a, 8, channel_axis=0)
    a_q = quantize(a, a_scale, 8)
    w_scale = abs_max_scale(w, 8, channel_axis=1)
    w_q = quantize(w, w_scale, 8)
    return a_q, a_scale, w_q, w_scale


class TestQuantMatmul:
    @pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                       (128, 256, 512)])
    def test_w8a8_matches_ref(self, m, k, n):
        a_q, a_s, w_q, w_s = _mk_w8(jax.random.PRNGKey(0), m, k, n)
        out = w8a8_matmul(a_q, a_s, w_q, w_s, interpret=True)
        want = ref.w8a8_matmul_ref(a_q, a_s, w_q, w_s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("m,k,n", [(128, 128, 128), (128, 256, 256)])
    def test_w4a8_matches_ref(self, m, k, n):
        key = jax.random.PRNGKey(1)
        k1, k2 = jax.random.split(key)
        a = jax.random.normal(k1, (m, k))
        w = jax.random.normal(k2, (k, n))
        a_s = abs_max_scale(a, 8, channel_axis=0)
        a_q = quantize(a, a_s, 8)
        w_s = abs_max_scale(w, 4, channel_axis=1)
        w_p = pack_int4(quantize(w, w_s, 4))
        out = w4a8_matmul(a_q, a_s, w_p, w_s, interpret=True)
        want = ref.w4a8_matmul_ref(a_q, a_s, w_p, w_s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("bm,bn,bk", [(128, 128, 128), (128, 256, 128)])
    def test_block_shape_sweep(self, bm, bn, bk):
        a_q, a_s, w_q, w_s = _mk_w8(jax.random.PRNGKey(2), 256, 256, 256)
        out = w8a8_matmul(a_q, a_s, w_q, w_s, bm=bm, bn=bn, bk=bk,
                          interpret=True)
        want = ref.w8a8_matmul_ref(a_q, a_s, w_q, w_s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_ops_wrapper_end_to_end_close_to_fp32(self):
        """W8A8 wrapper approximates the fp32 matmul within quant noise."""
        key = jax.random.PRNGKey(3)
        x = jax.random.normal(key, (64, 200))
        w = jax.random.normal(jax.random.fold_in(key, 1), (200, 130))
        w_q, w_s = ops.prepare_w8(w)
        out = ops.matmul_w8a8(x, w_q, w_s)
        want = x @ w
        err = np.abs(np.asarray(out - want))
        assert err.mean() < 0.25  # ~1% of |x@w| rms (~14)
        assert out.shape == (64, 130)

    def test_ops_w4_wrapper_shapes(self):
        x = jax.random.normal(jax.random.PRNGKey(4), (32, 100))
        w = jax.random.normal(jax.random.PRNGKey(5), (100, 64))
        w_p, w_s = ops.prepare_w4(w)
        out = ops.matmul_w4a8(x, w_p, w_s)
        assert out.shape == (32, 64)
        rel = float(jnp.linalg.norm(out - x @ w) / jnp.linalg.norm(x @ w))
        # 4-bit abs-max per-column on N(0,1) weights: step ~ 3sigma/7 ->
        # ~11-12% relative error is the information-theoretic neighbourhood
        assert rel < 0.15


def _edge_problem(seed, B, cap, ec, F, W, cutoff=3.0):
    """Random padded batch + edge list + features for edge_softmax tests."""
    from repro.serving.bucketing import build_edge_list
    rng = np.random.default_rng(seed)
    side = (cap / 0.05) ** (1.0 / 3.0)   # constant density ~ degree 6
    coords = rng.uniform(0, side, size=(B, cap, 3)).astype(np.float32)
    mask = np.ones((B, cap), bool)
    mask[0, cap // 2:] = False
    el = build_edge_list(coords, mask, cutoff, ec)
    assert el is not None, "edge capacity too small for test problem"
    N, E = B * cap, B * ec
    q = jnp.asarray(rng.normal(size=(N, F)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(N, F)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(E,)).astype(np.float32))
    vals = jnp.asarray(rng.normal(size=(E, W)).astype(np.float32))
    return (q, k, bias, vals, jnp.asarray(el.senders),
            jnp.asarray(el.receivers), jnp.asarray(el.edge_mask))


class TestEdgeSoftmaxKernel:
    @pytest.mark.parametrize("B,cap,ec,F,W", [(2, 16, 256, 32, 56),
                                              (4, 32, 128, 64, 128),
                                              (1, 128, 512, 16, 80),
                                              (2, 21, 512, 64, 112)])
    def test_matches_ref(self, B, cap, ec, F, W):
        q, k, bias, vals, s, r, m = _edge_problem(B, B, cap, ec, F, W)
        out = ops.edge_softmax(q, k, bias, vals, s, r, m, cap=cap,
                               use_kernel=True)
        want = ref.edge_softmax_ref(q, k, bias, s, r, m, vals, B * cap)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_gradients_match_ref(self):
        """The fused kernel's custom VJP reproduces the oracle's
        gradients (forces differentiate through this path)."""
        q, k, bias, vals, s, r, m = _edge_problem(7, 2, 16, 256, 32, 40)

        def loss(fn):
            def f(q_, k_, b_, v_):
                return jnp.sum(fn(q_, k_, b_, v_) ** 2)
            return jax.grad(f, argnums=(0, 1, 2, 3))(q, k, bias, vals)

        g_ker = loss(lambda q_, k_, b_, v_: ops.edge_softmax(
            q_, k_, b_, v_, s, r, m, cap=16, use_kernel=True))
        g_ref = loss(lambda q_, k_, b_, v_: ref.edge_softmax_ref(
            q_, k_, b_, s, r, m, v_, 32))
        for a, b in zip(g_ker, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_no_edge_receivers_are_exact_zero(self):
        """Nodes no real edge points at (incl. all-masked molecules)
        produce exactly zero output, not softmax-of-mask noise."""
        q, k, bias, vals, s, r, m = _edge_problem(3, 2, 16, 128, 32, 24)
        out = np.asarray(ops.edge_softmax(q, k, bias, vals, s, r, m,
                                          cap=16, use_kernel=True))
        has_edge = np.zeros(32, bool)
        has_edge[np.asarray(r)[np.asarray(m)]] = True
        np.testing.assert_array_equal(out[~has_edge], 0.0)


class TestMDDQKernel:
    @pytest.mark.parametrize("n,bits", [(1024, 8), (2048, 6), (4096, 4)])
    def test_matches_ref(self, n, bits):
        cb = make_codebook(bits)
        cb_t = ops.pad_codebook(cb)
        v = jax.random.normal(jax.random.PRNGKey(0), (n, 3)) * 3.0
        idx, mag = mddq_encode_kernel(v[:, 0].copy(), v[:, 1].copy(),
                                      v[:, 2].copy(), cb_t, bn=1024,
                                      interpret=True)
        # reference works on the padded codebook too (pad = copies of cw 0,
        # ties resolve to the first occurrence = identical index)
        idx_ref, mag_ref = ref.mddq_encode_ref(v, jnp.asarray(cb_t.T))
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
        np.testing.assert_array_equal(np.asarray(mag), np.asarray(mag_ref))

    def test_ops_wrapper_arbitrary_shape(self):
        cb_t = ops.pad_codebook(make_codebook(8))
        v = jax.random.normal(jax.random.PRNGKey(1), (7, 13, 3))
        idx, mag = ops.mddq_encode(v, cb_t)
        assert idx.shape == (7, 13) and mag.shape == (7, 13)
        idx_ref, _ = ref.mddq_encode_ref(v.reshape(-1, 3), jnp.asarray(cb_t.T))
        np.testing.assert_array_equal(np.asarray(idx).ravel(),
                                      np.asarray(idx_ref))

    def test_padded_codebook_never_wins_argmax(self):
        """``pad_codebook`` 128-aligns with COPIES OF CODEWORD 0, so a
        padded column can at most tie codeword 0's score; argmax takes
        the first maximizing index — the real index 0 — and no encoded
        index ever points at a padding slot. Includes the exact-tie case
        (inputs colinear with codeword 0)."""
        cb = make_codebook(6)                    # 64 entries -> padded to 128
        cb_t = ops.pad_codebook(cb)
        assert cb_t.shape == (3, 128)
        v = jax.random.normal(jax.random.PRNGKey(9), (1024, 3)) * 2.0
        v = v.at[:64].set(jnp.tile(cb[:1] * 3.0, (64, 1)))  # ties with cw 0
        idx, _ = mddq_encode_kernel(v[:, 0].copy(), v[:, 1].copy(),
                                    v[:, 2].copy(), cb_t, bn=1024,
                                    interpret=True)
        idx = np.asarray(idx)
        assert idx.max() < 64, "argmax selected a padding slot"
        np.testing.assert_array_equal(idx[:64], 0)

    def test_qdq_kernel_matches_fake_quant(self):
        """Serve-time quantize-dequantize through the Pallas encode kernel
        (ops.mddq_qdq_kernel): identical values to the fake-quant
        reference, exact zero for zero vectors, identical Geometric-STE
        gradients."""
        from repro.core.mddq import MDDQConfig, mddq_fake_quant
        cfg = MDDQConfig(direction_bits=6, magnitude_bits=8)
        cb = make_codebook(6)
        v = jax.random.normal(jax.random.PRNGKey(11), (64, 8, 3)) * 2.0
        v = v.at[0, 0].set(0.0)
        out = ops.mddq_qdq_kernel(v, cfg, cb)
        want = mddq_fake_quant(v, cfg, cb)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-6)
        np.testing.assert_array_equal(np.asarray(out)[0, 0], 0.0)
        g_ker = jax.grad(lambda v_: jnp.sum(
            ops.mddq_qdq_kernel(v_, cfg, cb) ** 2))(v)
        g_ref = jax.grad(lambda v_: jnp.sum(
            mddq_fake_quant(v_, cfg, cb) ** 2))(v)
        np.testing.assert_allclose(np.asarray(g_ker), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-5)

    def test_qdq_kernel_respects_magnitude_config(self):
        """Regression: the encode kernel must use the config's magnitude
        grid (bits, m_min, m_max), not its 8-bit defaults — a 4-bit
        config decoded on the wrong grid overflows exp()."""
        from repro.core.mddq import MDDQConfig, mddq_fake_quant
        cfg = MDDQConfig(direction_bits=6, magnitude_bits=4,
                         m_min=1e-3, m_max=10.0)
        cb = make_codebook(6)
        v = jax.random.normal(jax.random.PRNGKey(12), (32, 4, 3))
        out = ops.mddq_qdq_kernel(v, cfg, cb)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(mddq_fake_quant(v, cfg, cb)),
                                   atol=1e-6)
        # linear-domain magnitudes are not kernel-supported: explicit error
        with pytest.raises(NotImplementedError):
            ops.mddq_qdq_kernel(
                v, MDDQConfig(direction_bits=6,
                              magnitude_domain="linear"), cb)


class TestInt8KVDecode:
    @pytest.mark.parametrize("bh,s,d,bs", [(4, 1024, 128, 512),
                                           (2, 512, 64, 256),
                                           (8, 2048, 128, 512)])
    def test_matches_ref(self, bh, s, d, bs):
        key = jax.random.PRNGKey(0)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (bh, d))
        k = jax.random.normal(ks[1], (bh, s, d))
        v = jax.random.normal(ks[2], (bh, s, d))
        k_q, k_s, v_q, v_s = ops.prepare_kv_int8(k, v)
        out = decode_attention_int8kv(q, k_q, k_s, v_q, v_s, bs=bs,
                                      interpret=True)
        want = ref.decode_attention_int8kv_ref(
            q, k_q, k_s, v_q, v_s, softmax_scale=1.0 / d ** 0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_close_to_fp32_attention(self):
        """int8 KV attention approximates fp32 attention."""
        key = jax.random.PRNGKey(7)
        ks = jax.random.split(key, 3)
        bh, s, d = 4, 512, 64
        q = jax.random.normal(ks[0], (bh, d))
        k = jax.random.normal(ks[1], (bh, s, d))
        v = jax.random.normal(ks[2], (bh, s, d))
        k_q, k_s, v_q, v_s = ops.prepare_kv_int8(k, v)
        out = ops.decode_attention_int8kv(q, k_q, k_s, v_q, v_s, bs=256)
        logits = jnp.einsum("bd,bsd->bs", q, k) / d ** 0.5
        want = jnp.einsum("bs,bsd->bd", jax.nn.softmax(logits, -1), v)
        rel = float(jnp.linalg.norm(out - want) / jnp.linalg.norm(want))
        assert rel < 0.02


class TestActQuantKernel:
    @pytest.mark.parametrize("m,k,bm", [(256, 512, 256), (512, 384, 128),
                                        (128, 1000, 64)])
    def test_matches_ref(self, m, k, bm):
        from repro.kernels.act_quant import act_quant
        x = jax.random.normal(jax.random.PRNGKey(0), (m, k)) * 3.0
        q, s = act_quant(x, bm=bm, interpret=True)
        s_ref = abs_max_scale(x, 8, channel_axis=0)
        q_ref = quantize(x, s_ref, 8)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                                   rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))

    def test_roundtrip_error_bounded(self):
        from repro.kernels.act_quant import act_quant
        x = jax.random.normal(jax.random.PRNGKey(1), (128, 256))
        q, s = act_quant(x, interpret=True)
        err = np.abs(np.asarray(q, np.float32) * np.asarray(s) - np.asarray(x))
        assert err.max() <= float(np.asarray(s).max()) / 2 + 1e-7
