"""Cross-backend parity for the proximity dispatch in ``repro.core.angles``.

The dense einsum path is the oracle; the blocked lax.map path and the Pallas
kernel (interpret mode on CPU) must agree with it for both paper measures,
including awkward shapes (K not divisible by the block size), non-orthonormal
inputs (clipping must keep arccos in-domain), and downstream hierarchical
clustering must be invariant to which backend produced A.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.angles import (
    PROXIMITY_BACKENDS,
    cross_proximity,
    proximity_matrix,
)
from repro.core.hc import hierarchical_clustering

KEY = jax.random.PRNGKey(0)

MEASURES = ["eq2", "eq3"]
NON_AUTO = [b for b in PROXIMITY_BACKENDS if b != "auto"]


def _signatures(K, n=40, p=3, key=KEY):
    return jnp.stack([
        jnp.linalg.qr(jax.random.normal(jax.random.fold_in(key, i), (n, p)))[0]
        for i in range(K)
    ])


class TestBackendParity:
    @pytest.mark.parametrize("measure", MEASURES)
    @pytest.mark.parametrize("backend", NON_AUTO)
    @pytest.mark.parametrize("K", [5, 13])  # both indivisible by block sizes
    def test_matches_dense_reference(self, K, backend, measure):
        U = _signatures(K)
        ref = np.asarray(proximity_matrix(U, measure, backend="jnp"))
        got = np.asarray(
            proximity_matrix(U, measure, backend=backend, block_size=4)
        )
        np.testing.assert_allclose(got, ref, atol=1e-3)

    @pytest.mark.parametrize("measure", MEASURES)
    def test_block_size_larger_than_k(self, measure):
        U = _signatures(3)
        ref = np.asarray(proximity_matrix(U, measure, backend="jnp"))
        got = np.asarray(
            proximity_matrix(U, measure, backend="jnp_blocked", block_size=64)
        )
        np.testing.assert_allclose(got, ref, atol=1e-3)

    @pytest.mark.parametrize("backend", NON_AUTO)
    def test_non_orthonormal_inputs_stay_in_domain(self, backend):
        """Slightly overscaled bases push |cos| past 1; every backend must
        clip before arccos instead of emitting NaNs."""
        U = _signatures(6) * 1.01
        for measure in MEASURES:
            A = np.asarray(
                proximity_matrix(U, measure, backend=backend, block_size=4)
            )
            assert np.isfinite(A).all(), (backend, measure)
            assert (A >= -1e-4).all()

    @pytest.mark.parametrize("measure", MEASURES)
    def test_pallas_default_tile_spans_two_cells(self, measure):
        """block_size=None takes the kernel's 128 edge: K=130 pads to a
        2x2 grid whose padded rows/cols must not leak into A."""
        U = _signatures(130, n=16)
        ref = np.asarray(proximity_matrix(U, measure, backend="jnp"))
        got = np.asarray(proximity_matrix(U, measure, backend="pallas"))
        assert got.shape == (130, 130)
        np.testing.assert_allclose(got, ref, atol=1e-3)

    def test_auto_resolves_and_matches(self):
        U = _signatures(9)
        ref = np.asarray(proximity_matrix(U, "eq3", backend="jnp"))
        got = np.asarray(proximity_matrix(U, "eq3", backend="auto"))
        np.testing.assert_allclose(got, ref, atol=1e-3)

    def test_unknown_backend_and_measure_raise(self):
        U = _signatures(4)
        with pytest.raises(ValueError):
            proximity_matrix(U, "eq3", backend="cuda")
        with pytest.raises(ValueError):
            proximity_matrix(U, "eq7")


class TestClusteringInvariance:
    @pytest.mark.parametrize("measure", MEASURES)
    def test_hc_labels_invariant_across_backends(self, measure):
        """Two well-separated subspace families: HC must produce the same
        partition regardless of which backend computed A."""
        k1, k2 = jax.random.split(KEY)
        B1, _ = jnp.linalg.qr(jax.random.normal(k1, (40, 3)))
        B2, _ = jnp.linalg.qr(jax.random.normal(k2, (40, 3)))

        def jitter(B, i):
            # small perturbation keeps columns aligned, so BOTH measures see
            # the family structure (eq3 is basis-alignment sensitive — an
            # in-subspace rotation would look far under eq3).
            noise = 0.01 * jax.random.normal(jax.random.fold_in(KEY, i), B.shape)
            return jnp.linalg.qr(B + noise)[0]

        U = jnp.stack([jitter(B1, 1), jitter(B1, 2), jitter(B1, 3),
                       jitter(B2, 4), jitter(B2, 5)])
        labels = {}
        for backend in NON_AUTO:
            A = np.asarray(
                proximity_matrix(U, measure, backend=backend, block_size=2)
            )
            labels[backend] = tuple(hierarchical_clustering(A, beta=45.0))
        assert len(set(labels.values())) == 1, labels
        assert labels["jnp"][0] == labels["jnp"][1] == labels["jnp"][2]
        assert labels["jnp"][3] == labels["jnp"][4]
        assert labels["jnp"][0] != labels["jnp"][3]


class TestCrossProximity:
    @pytest.mark.parametrize("measure", MEASURES)
    @pytest.mark.parametrize("backend", ["jnp", "jnp_blocked"])
    def test_matches_square_blocks(self, measure, backend):
        U = _signatures(11)
        A = np.asarray(proximity_matrix(U, measure, backend="jnp"))
        C = np.asarray(
            cross_proximity(U, U[7:], measure, backend=backend, block_size=4)
        )
        assert C.shape == (11, 4)
        np.testing.assert_allclose(C[:7], A[:7, 7:], atol=1e-3)

    def test_pallas_backend_falls_back_for_rectangles(self):
        U = _signatures(6)
        C = np.asarray(cross_proximity(U, U[:2], "eq3", backend="pallas"))
        A = np.asarray(proximity_matrix(U, "eq3", backend="jnp"))
        np.testing.assert_allclose(C[2:], A[2:, :2], atol=1e-3)

    def test_pallas_fallback_accepts_lapack_solvers(self):
        """The rectangle fallback executes on the blocked path, so explicit
        LAPACK eq2 solvers must be accepted — solver validation follows the
        actual executor, not the requested square-only kernel."""
        U = _signatures(6)
        C = np.asarray(
            cross_proximity(U, U[:2], "eq2", backend="pallas", eq2_solver="svd")
        )
        A = np.asarray(proximity_matrix(U, "eq2", backend="jnp"))
        np.testing.assert_allclose(C[2:], A[2:, :2], atol=1e-3)
