"""Per-kernel allclose tests: shape/dtype sweeps against pure-jnp oracles.

Property tests use ``hypothesis`` when installed; otherwise the shim in
``tests/_hypothesis_compat.py`` degrades them to a fixed example grid so the
suite still collects and runs (see requirements-dev.txt).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.proximity import proximity, proximity_ref
from repro.kernels.tsgemm import tsgemm, tsgemm_ref

KEY = jax.random.PRNGKey(0)


class TestProximityKernel:
    @pytest.mark.parametrize("measure", ["eq3", "eq2"])
    @pytest.mark.parametrize("K,n,p", [(4, 64, 3), (8, 128, 5), (10, 100, 2),
                                       (17, 256, 4), (3, 32, 1)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_allclose(self, K, n, p, dtype, measure):
        U = jnp.stack([
            jnp.linalg.qr(jax.random.normal(jax.random.fold_in(KEY, i), (n, p)))[0]
            for i in range(K)
        ]).astype(dtype)
        got = np.asarray(proximity(U, measure=measure))
        want = np.asarray(proximity_ref(U, measure=measure))
        tol = 0.6 if dtype == jnp.bfloat16 else 1e-3
        np.testing.assert_allclose(got, want, atol=tol)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(2, 12), st.integers(1, 5), st.sampled_from(["eq3", "eq2"]))
    def test_property_sweep(self, K, p, measure):
        key = jax.random.PRNGKey(K * 7 + p)
        U = jnp.stack([
            jnp.linalg.qr(jax.random.normal(jax.random.fold_in(key, i), (48, p)))[0]
            for i in range(K)
        ])
        got = np.asarray(proximity(U, measure=measure))
        want = np.asarray(proximity_ref(U, measure=measure))
        np.testing.assert_allclose(got, want, atol=1e-2)


class TestProximityKernelLayout:
    """The chip layout: (p, K, n) slabs, 128-edge tiles, the kernel's arccos."""

    def test_arccos01_matches_jnp_arccos(self):
        from repro.core.measures import arccos01

        x = jnp.concatenate([
            jnp.linspace(0.0, 1.0, 200_001, dtype=jnp.float32),
            1.0 - jnp.logspace(-7, -1, 1000, dtype=jnp.float32),
        ])
        err = np.abs(np.asarray(arccos01(x)) - np.asarray(jnp.arccos(x)))
        assert err.max() <= 1e-6

    @pytest.mark.parametrize("measure", ["eq3", "eq2"])
    def test_default_tile_multi_cell_grid(self, measure):
        # K=260 pads to 384 = three 128-edge tiles per side
        from repro.kernels.proximity.proximity import BLOCK

        U = jax.vmap(lambda k: jnp.linalg.qr(jax.random.normal(k, (24, 3)))[0])(
            jax.random.split(KEY, 260)
        )
        assert BLOCK == 128
        got = np.asarray(proximity(U, measure=measure))
        want = np.asarray(proximity_ref(U, measure=measure))
        np.testing.assert_allclose(got, want, atol=1e-3)

    @pytest.mark.parametrize("measure", ["eq3", "eq2"])
    def test_measure_tile_slabs_match_measure_pair(self, measure):
        from repro.core.measures import measure_pair, measure_tile

        ka, kb = jax.random.split(KEY)
        Ui = jax.vmap(lambda k: jnp.linalg.qr(jax.random.normal(k, (20, 4)))[0])(
            jax.random.split(ka, 6)
        )
        Uj = jax.vmap(lambda k: jnp.linalg.qr(jax.random.normal(k, (20, 4)))[0])(
            jax.random.split(kb, 9)
        )
        tile = measure_tile(Ui.transpose(2, 0, 1), Uj.transpose(2, 0, 1), measure)
        np.testing.assert_allclose(
            np.asarray(tile), np.asarray(measure_pair(Ui, Uj, measure)), atol=1e-4
        )


class TestTsgemmKernel:
    @pytest.mark.parametrize("m,k,p", [(128, 128, 8), (512, 300, 10),
                                       (1000, 768, 13), (50, 40, 3)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_allclose(self, m, k, p, dtype):
        A = jax.random.normal(KEY, (m, k)).astype(dtype)
        B = jax.random.normal(jax.random.fold_in(KEY, 1), (k, p)).astype(dtype)
        got = np.asarray(tsgemm(A, B))
        want = np.asarray(tsgemm_ref(A, B))
        rtol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(got, want, rtol=rtol, atol=rtol * 10)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 300), st.integers(1, 200), st.integers(1, 16))
    def test_property_sweep(self, m, k, p):
        key = jax.random.PRNGKey(m * 31 + k * 7 + p)
        A = jax.random.normal(key, (m, k))
        B = jax.random.normal(jax.random.fold_in(key, 1), (k, p))
        np.testing.assert_allclose(
            np.asarray(tsgemm(A, B)), np.asarray(tsgemm_ref(A, B)),
            rtol=1e-4, atol=1e-3,
        )


class TestFlashAttentionKernel:
    @pytest.mark.parametrize(
        "B,Sq,Skv,Hq,Hkv,hd,causal,window,qoff",
        [
            (2, 64, 64, 4, 2, 32, True, None, 0),
            (1, 32, 128, 8, 8, 16, False, None, 0),
            (2, 64, 64, 4, 1, 32, True, 16, 0),
            (1, 16, 64, 4, 2, 32, True, None, 48),   # decode-suffix offset
            (1, 128, 128, 2, 2, 64, True, None, 0),
            (3, 32, 32, 6, 3, 32, True, 8, 0),
        ],
    )
    def test_allclose(self, B, Sq, Skv, Hq, Hkv, hd, causal, window, qoff):
        q = jax.random.normal(KEY, (B, Sq, Hq, hd))
        k = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Skv, Hkv, hd))
        v = jax.random.normal(jax.random.fold_in(KEY, 3), (B, Skv, Hkv, hd))
        got = np.asarray(flash_attention(q, k, v, causal=causal, window=window,
                                         q_offset=qoff, bq=16, bk=16))
        want = np.asarray(attention_ref(q, k, v, causal=causal, window=window,
                                        q_offset=qoff))
        np.testing.assert_allclose(got, want, atol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        q = jax.random.normal(KEY, (1, 32, 4, 32)).astype(dtype)
        k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 32, 2, 32)).astype(dtype)
        v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 32, 2, 32)).astype(dtype)
        got = np.asarray(flash_attention(q, k, v, bq=16, bk=16), dtype=np.float32)
        want = np.asarray(attention_ref(q, k, v), dtype=np.float32)
        tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(got, want, atol=tol)

    @settings(max_examples=8, deadline=None)
    @given(
        st.sampled_from([16, 32, 64]),      # Sq
        st.sampled_from([32, 64]),          # Skv
        st.sampled_from([(4, 2), (8, 4), (2, 2)]),
        st.booleans(),
    )
    def test_property_sweep(self, sq, skv, heads, causal):
        hq, hkv = heads
        key = jax.random.PRNGKey(sq * 7 + skv + hq)
        q = jax.random.normal(key, (1, sq, hq, 32))
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, skv, hkv, 32))
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, skv, hkv, 32))
        got = np.asarray(flash_attention(q, k, v, causal=causal, bq=16, bk=16))
        want = np.asarray(attention_ref(q, k, v, causal=causal))
        np.testing.assert_allclose(got, want, atol=3e-5)

    def test_matches_model_chunked_attention(self):
        """Kernel == the pure-JAX chunked_attention the models actually use."""
        from repro.models.attention import chunked_attention

        q = jax.random.normal(KEY, (2, 64, 8, 32))
        k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 64, 4, 32))
        v = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 64, 4, 32))
        pos = jnp.arange(64, dtype=jnp.int32)
        got = np.asarray(flash_attention(q, k, v, causal=True, bq=16, bk=16))
        want = np.asarray(chunked_attention(q, k, v, pos, pos, causal=True, chunk=16))
        np.testing.assert_allclose(got, want, atol=3e-2)  # bf16 model path


class TestWkvKernel:
    @pytest.mark.parametrize("B,S,H,hd", [(2, 16, 4, 16), (1, 40, 2, 32),
                                          (3, 7, 1, 16)])
    def test_allclose(self, B, S, H, hd):
        from repro.kernels.wkv import wkv, wkv_ref

        key = jax.random.PRNGKey(B * 100 + S)
        r, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, S, H, hd))
                   for i in range(3))
        w = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 3), (B, S, H, hd)))
        u = 0.1 * jax.random.normal(jax.random.fold_in(key, 4), (H, hd))
        o1, s1 = wkv(r, k, v, w, u)
        o2, s2 = wkv_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-5)

    def test_with_initial_state(self):
        from repro.kernels.wkv import wkv, wkv_ref

        key = jax.random.PRNGKey(7)
        B, S, H, hd = 2, 12, 2, 16
        r, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, S, H, hd))
                   for i in range(3))
        w = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 3), (B, S, H, hd)))
        u = 0.1 * jax.random.normal(jax.random.fold_in(key, 4), (H, hd))
        s0 = jax.random.normal(jax.random.fold_in(key, 5), (B, H, hd, hd))
        o1, s1 = wkv(r, k, v, w, u, s0)
        o2, s2 = wkv_ref(r, k, v, w, u, s0)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-5)
