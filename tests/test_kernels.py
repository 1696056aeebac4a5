"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp
oracle, plus gradient checks for the differentiable ones."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.mps_combine import kernel as mk, ops as mops, ref as mref
from repro.kernels.quant_matmul import kernel as qk, ops as qops, ref as qref
from repro.kernels.ssd_scan import kernel as sk, ops as sops, ref as sref

import proptest as pt


def _assert_quant_close(out, ref, w):
    """Compare two fake-quant implementations: identical math, but
    division vs reciprocal-multiply can flip round() at exact .5 grid
    boundaries. Allow <0.1% of elements to differ by at most one 2-bit
    grid step (the coarsest grid in the sweep)."""
    out, ref = np.asarray(out), np.asarray(ref)
    absmax = np.max(np.abs(np.asarray(w)), axis=1, keepdims=True)
    grid_step = absmax  # 2-bit grid: absmax / 1
    diff = np.abs(out - ref)
    bad = diff > 1e-5
    assert bad.mean() < 1e-3, f"{bad.mean():.2%} elements differ"
    assert np.all(diff <= grid_step + 1e-5)


class TestMpsCombine:
    @pytest.mark.parametrize("m,k", [(8, 128), (70, 300), (256, 512),
                                     (33, 1000), (128, 129)])
    @pytest.mark.parametrize("precisions", [(0, 2, 4, 8), (0, 8), (2, 4, 8)])
    def test_matches_oracle(self, m, k, precisions):
        kw = jax.random.key(m * k)
        w = jax.random.normal(kw, (m, k))
        probs = jax.nn.softmax(
            jax.random.normal(jax.random.key(1), (m, len(precisions))), -1)
        out = mops.mps_combine(w, probs, precisions)
        ref = mref.mps_combine_ref(w, probs, precisions)
        _assert_quant_close(out, ref, w)

    @pt.given(seed=pt.integers(0, 10**6))
    def test_property_random(self, seed):
        rng = np.random.default_rng(seed)
        m = int(rng.integers(4, 90))
        k = int(rng.integers(4, 400))
        w = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
        probs = jax.nn.softmax(jnp.asarray(
            rng.normal(size=(m, 4)).astype(np.float32)), -1)
        out = mops.mps_combine(w, probs, (0, 2, 4, 8))
        ref = mref.mps_combine_ref(w, probs, (0, 2, 4, 8))
        _assert_quant_close(out, ref, w)

    def test_custom_vjp_matches_ste_autodiff(self):
        """Kernel backward must match autodiff through the STE-correct
        pure-jnp path (core.mps.effective_weight). NOTE: ref.py is a
        forward-only oracle (plain round, no STE) -- differentiating it
        gives degenerate zero/absmax-leak gradients by design."""
        from repro.core import mps as mps_mod
        w = jax.random.normal(jax.random.key(0), (24, 96))
        gamma = jax.random.normal(jax.random.key(1), (24, 4))

        def loss(w, g, use_kernel):
            ctx = mps_mod.SearchCtx(use_kernel=use_kernel)
            return jnp.sum(jnp.tanh(mps_mod.effective_weight(
                w, g, (0, 2, 4, 8), ctx)))

        gk = jax.grad(loss, (0, 1))(w, gamma, True)
        gr = jax.grad(loss, (0, 1))(w, gamma, False)
        # each row's absmax element sits exactly on the clip boundary;
        # whether two float pipelines both see the tie is ULP luck, so
        # exclude near-boundary elements from the dW comparison
        wn = np.asarray(w)
        absmax = np.max(np.abs(wn), axis=1, keepdims=True)
        interior = np.abs(wn) < 0.999 * absmax
        dwk, dwr = np.asarray(gk[0]), np.asarray(gr[0])
        np.testing.assert_allclose(dwk[interior], dwr[interior],
                                   atol=5e-3, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(gk[1]), np.asarray(gr[1]),
                                   atol=5e-3, rtol=1e-3)


class TestQuantMatmul:
    @pytest.mark.parametrize("bits", [8, 4, 2])
    @pytest.mark.parametrize("m,n,k", [(8, 16, 64), (33, 50, 200),
                                       (128, 128, 512), (1, 256, 1024)])
    def test_matches_oracle(self, bits, m, n, k):
        rng = np.random.default_rng(bits * m + n)
        lim = 2 ** (bits - 1)
        wq = rng.integers(-lim + 1, lim, size=(n, k)).astype(np.int8)
        x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
        xq, sx = qref.quantize_activations(x)
        sw = jnp.asarray(np.abs(rng.normal(size=n)).astype(np.float32))
        packed = jnp.asarray(qref.pack_weights(wq, bits))
        out = qops.quant_matmul(xq, packed, sw, sx, w_bits=bits)
        ref = qref.quant_matmul_ref(xq, jnp.asarray(wq), sw, sx)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_pack_roundtrip(self):
        for bits in (2, 4, 8):
            lim = 2 ** (bits - 1)
            rng = np.random.default_rng(0)
            # the full signed range, -2^(b-1) included, exercises the
            # sign extension of every plane
            wq = rng.integers(-lim, lim, size=(5, 24)).astype(np.int8)
            packed = qref.pack_weights(wq, bits)
            assert packed.shape == (5, 24 * bits // 8)
            # planar layout: plane i holds columns [i*Kp, (i+1)*Kp)
            planes = qk._unpack(jnp.asarray(packed), bits)
            unpacked = np.concatenate([np.asarray(p) for p in planes], 1)
            np.testing.assert_array_equal(unpacked, wq)

    def test_quantized_linear_errors_bounded(self):
        """End-to-end w8a8 quantized linear stays close to float matmul."""
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(16, 128)).astype(np.float32))
        w = rng.normal(size=(32, 128)).astype(np.float32) * 0.1
        from repro.core import quantizers
        qi, scale = quantizers.integerize_weights(jnp.asarray(w), 8, 0)
        xq, sx = qref.quantize_activations(x)
        y = qops.quant_matmul(xq, jnp.asarray(np.asarray(qi)),
                              jnp.asarray(np.asarray(scale)[:, 0]), sx, 8)
        ref = x @ w.T
        rel = float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref))
        assert rel < 0.02


class TestSSDScan:
    @pytest.mark.parametrize("c,h,p,n", [(4, 8, 16, 16), (6, 16, 8, 16),
                                         (1, 8, 4, 4), (10, 24, 16, 32)])
    def test_matches_oracle(self, c, h, p, n):
        k = jax.random.key(c * h)
        dec = jax.random.uniform(k, (c, h), minval=0.3, maxval=1.0)
        s_in = jax.random.normal(jax.random.key(1), (c, h, p, n))
        s0 = jax.random.normal(jax.random.key(2), (h, p, n))
        pk_, fk = sk.ssd_scan_fwd(dec, s_in, s0, interpret=True)
        pr, fr = sref.ssd_scan_ref(dec, s_in, s0)
        np.testing.assert_allclose(np.asarray(pk_), np.asarray(pr),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(fk), np.asarray(fr),
                                   rtol=1e-5, atol=1e-5)

    def test_ops_dispatch_cpu_uses_ref(self):
        dec = jnp.ones((3, 8)) * 0.5
        s_in = jnp.ones((3, 8, 4, 4))
        s0 = jnp.zeros((8, 4, 4))
        prefix, final = sops.ssd_scan(dec, s_in, s0)
        # analytic: S_c = sum_{i<c} 0.5^(c-1-i); final = S_3
        np.testing.assert_allclose(float(final[0, 0, 0]),
                                   1 + 0.5 + 0.25, rtol=1e-6)

    def test_decay_zero_blocks_history(self):
        dec = jnp.zeros((2, 8))
        s_in = jax.random.normal(jax.random.key(0), (2, 8, 4, 4))
        s0 = 100 * jnp.ones((8, 4, 4))
        prefix, final = sk.ssd_scan_fwd(dec, s_in, s0, interpret=True)
        np.testing.assert_allclose(np.asarray(final), np.asarray(s_in[1]),
                                   atol=1e-5)
