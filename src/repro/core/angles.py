"""Principal angles between client data subspaces and the proximity matrix.

Implements Eq. 1-3 of the paper.  Given orthonormal bases ``U in R^{n x p}``
and ``W in R^{n x q}`` the principal angles are ``arccos`` of the singular
values of ``U^T W``.  The paper's two proximity measures:

* Eq. 2 — smallest principal angle ``Theta_1`` (needs the largest singular
  value of ``U^T W``).
* Eq. 3 — ``tr(arccos(U^T W))`` over *identically ordered* singular-vector
  pairs (no inner SVD; the measure the paper calls the more rigorous one).

Angles are reported in **degrees** to match the paper's Tables 1 and 6.

Every backend reduces its Gram blocks through the shared measure core in
:mod:`repro.core.measures` — one implementation of the eq2/eq3 reductions,
with eq2 solved by a batched fixed-sweep Jacobi eigensolve by default
(``eq2_solver="jacobi"``; ``"eigh"``/``"svd"`` kept as parity fallbacks).

Backends
--------
:func:`proximity_matrix` is the single entry point for the (K, K) matrix and
dispatches across four implementations:

* ``"jnp"`` — the einsum reference.  Materializes the full (K, K, p, p) Gram
  tensor; simplest and fastest for small K, but O(K^2 p^2) peak memory
  (~10 GB of f32 at K=10k, p=5).  Its eq2 defaults to the LAPACK ``svd``
  solver so it stays the independent oracle the fast paths are tested
  against.
* ``"jnp_blocked"`` — tiles the computation into (bk, bk) client blocks with
  ``lax.scan``; peak intermediate memory is O(bk^2 p^2) plus the (K, K)
  output, so the server scales to K far beyond the dense path.
* ``"jnp_sharded"`` — the blocked computation with the i-block (row strip)
  axis sharded across all local devices via ``jax.make_mesh`` +
  ``shard_map``: each device owns K/ndev rows of the output and streams
  (bk, bk) Gram blocks against the replicated signature stack, so the
  (K, K) output and the O(K^2 n p^2) flops split across devices while each
  device's peak intermediate stays O(bk^2 p^2).  Reproducible on CPU with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
* ``"pallas"`` — the TPU kernel in ``repro.kernels.proximity`` (interpret
  mode off-TPU); supports both measures (eq2 via the same Jacobi core).

``"auto"`` picks pallas on TPU, else the dense path for small K and the
blocked path beyond ``_AUTO_BLOCKED_MIN_K`` clients; ``"jnp_sharded"`` is
opt-in (it is a wash on a single device).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.measures import EQ2_SOLVERS, measure_from_gram, measure_pair

PROXIMITY_BACKENDS = ("auto", "jnp", "jnp_blocked", "jnp_sharded", "pallas")

# "auto" switches from the dense einsum to the blocked path at this K: below
# it the (K, K, p, p) tensor is tens of MB and einsum wins on latency.
_AUTO_BLOCKED_MIN_K = 512


def principal_angles(U: jax.Array, W: jax.Array) -> jax.Array:
    """All principal angles (radians, ascending) between span(U), span(W)."""
    G = U.astype(jnp.float32).T @ W.astype(jnp.float32)
    s = jnp.linalg.svd(G, compute_uv=False)
    s = jnp.clip(s, -1.0, 1.0)
    return jnp.sort(jnp.arccos(s))


def smallest_principal_angle_deg(U: jax.Array, W: jax.Array) -> jax.Array:
    """Eq. 2 entry: smallest principal angle, in degrees."""
    return jnp.degrees(principal_angles(U, W)[0])


def trace_angle_deg(U: jax.Array, W: jax.Array) -> jax.Array:
    """Eq. 3 entry: sum of arccos of the diagonal of U^T W, in degrees."""
    G = U.astype(jnp.float32).T @ W.astype(jnp.float32)
    d = jnp.clip(jnp.diagonal(G), -1.0, 1.0)
    return jnp.degrees(jnp.sum(jnp.arccos(jnp.abs(d))))


def _hygiene(A: jax.Array) -> jax.Array:
    """Exact symmetry and exact zeros on the diagonal."""
    A = 0.5 * (A + A.T)
    return A * (1.0 - jnp.eye(A.shape[0], dtype=A.dtype))


@functools.partial(jax.jit, static_argnames=("measure", "eq2_solver"))
def _proximity_dense(U_stack: jax.Array, measure: str, eq2_solver: str) -> jax.Array:
    """Einsum reference.  eq2 materializes the full (K, K, p, p) Gram
    tensor; eq3 takes the diagonal-only route (K, K, p) in measure_pair."""
    U_stack = U_stack.astype(jnp.float32)
    return _hygiene(
        measure_pair(U_stack, U_stack, measure, eq2_solver=eq2_solver)
    )


@functools.partial(
    jax.jit, static_argnames=("measure", "block_size", "eq2_solver")
)
def _proximity_blocked(
    U_stack: jax.Array, measure: str, block_size: int, eq2_solver: str
) -> jax.Array:
    """Tiled path: (bk, bk) client blocks, upper-triangular tiles only.

    Peak intermediate memory is one (bk, bk, p, p) Gram block per step plus
    the (K, K) output — never the full (K, K, p, p) tensor.  A is symmetric,
    so only the nb*(nb+1)/2 upper tiles are computed and each is mirrored
    into the lower triangle, halving the dominant O(K^2 n p^2) cost.
    Zero-padded clients produce zero Gram blocks (90-degree angles) in
    rows/cols that are sliced off before the hygiene pass.
    """
    U_stack = U_stack.astype(jnp.float32)
    K, n, p = U_stack.shape
    bk = block_size
    pad = (-K) % bk
    Up = jnp.pad(U_stack, ((0, pad), (0, 0), (0, 0)))
    Kp = Up.shape[0]
    nb = Kp // bk
    blocks = Up.reshape(nb, bk, n, p)
    ii, jj = np.triu_indices(nb)

    def body(A, idx):
        i, j = idx
        Ui = jnp.take(blocks, i, axis=0)
        Uj = jnp.take(blocks, j, axis=0)
        # einsum Gram + shared reduction; eq3 only contracts the p Gram
        # diagonals (see measure_pair)
        tile = measure_pair(Ui, Uj, measure, eq2_solver=eq2_solver)  # (bk, bk)
        A = jax.lax.dynamic_update_slice(A, tile.T, (j * bk, i * bk))
        A = jax.lax.dynamic_update_slice(A, tile, (i * bk, j * bk))
        return A, None

    A0 = jnp.zeros((Kp, Kp), jnp.float32)
    idxs = jnp.stack([jnp.asarray(ii), jnp.asarray(jj)], axis=1)
    A, _ = jax.lax.scan(body, A0, idxs)
    return _hygiene(A[:K, :K])


# --- device-sharded engine ------------------------------------------------
#
# Row strips of the output are owned by devices: device d computes rows
# [d * Kp/ndev, (d+1) * Kp/ndev) of A against the replicated signature
# stack, streaming (bk, bk) Gram blocks through the shared measure core.
# Both triangles are computed (the transpose tile lives on another device),
# so the sharded path trades the 2x triangular saving for N-way parallelism
# and an N-fold smaller per-device output resident set.


def _strip_blocks(rows: jax.Array, full: jax.Array, measure, bk, eq2_solver):
    """(Kl, n, p) local rows x (Kp, n, p) replicated -> (Kl, Kp) angles."""
    Kl, n, p = rows.shape
    nbi = Kl // bk
    nbj = full.shape[0] // bk
    rb = rows.reshape(nbi, bk, n, p)
    fb = full.reshape(nbj, bk, n, p)

    def strip(Ui):
        def cell(Uj):
            return measure_pair(Ui, Uj, measure, eq2_solver=eq2_solver)

        s = jax.lax.map(cell, fb)  # (nbj, bk, bk)
        return s.transpose(1, 0, 2).reshape(bk, nbj * bk)

    return jax.lax.map(strip, rb).reshape(nbi * bk, nbj * bk)


@functools.lru_cache(maxsize=None)
def _sharded_cross_fn(ndev: int, measure: str, bk: int, eq2_solver: str):
    # Auto axes (make_mesh defaults to Explicit): the row-sharded strips are
    # then a plain GSPMD array, and the hygiene pass's A + A.T may reshard.
    mesh = jax.make_mesh((ndev,), ("i",), axis_types=(jax.sharding.AxisType.Auto,))

    def local(rows, full):
        return _strip_blocks(rows, full, measure, bk, eq2_solver)

    return jax.jit(
        jax.shard_map(local, mesh=mesh, in_specs=(P("i"), P()), out_specs=P("i"))
    )


@functools.lru_cache(maxsize=None)
def _sharded_square_fn(ndev: int, measure: str, bk: int, eq2_solver: str):
    # The square computation is the cross computation against itself: rows
    # sharded, the full stack replicated.
    mapped = _sharded_cross_fn(ndev, measure, bk, eq2_solver)
    return jax.jit(lambda Up: mapped(Up, Up))


def _pad_rows(U: jax.Array, multiple: int) -> jax.Array:
    pad = (-U.shape[0]) % multiple
    return jnp.pad(U, ((0, pad), (0, 0), (0, 0))) if pad else U


def _proximity_sharded(
    U_stack: jax.Array, measure: str, block_size: int, eq2_solver: str
) -> jax.Array:
    U_stack = U_stack.astype(jnp.float32)
    K = U_stack.shape[0]
    ndev = len(jax.devices())
    Up = _pad_rows(U_stack, block_size * ndev)
    A = _sharded_square_fn(ndev, measure, block_size, eq2_solver)(Up)
    return _hygiene(A[:K, :K])


def _cross_sharded(
    U_a: jax.Array, U_b: jax.Array, measure: str, block_size: int, eq2_solver: str
) -> jax.Array:
    U_a = U_a.astype(jnp.float32)
    U_b = U_b.astype(jnp.float32)
    Ka, Kb = U_a.shape[0], U_b.shape[0]
    ndev = len(jax.devices())
    Ua = _pad_rows(U_a, block_size * ndev)
    Ub = _pad_rows(U_b, block_size)
    C = _sharded_cross_fn(ndev, measure, block_size, eq2_solver)(Ua, Ub)
    return C[:Ka, :Kb]


def resolve_backend(backend: str, K: int) -> str:
    """The backend ``proximity_matrix`` runs for ``backend`` at K clients:
    ``"auto"`` is pallas on a TPU, else dense below 512 clients and blocked
    from there; any other name is returned as given."""
    if backend not in PROXIMITY_BACKENDS:
        raise ValueError(
            f"unknown proximity backend: {backend!r} (want one of {PROXIMITY_BACKENDS})"
        )
    if backend != "auto":
        return backend
    if jax.default_backend() == "tpu":
        return "pallas"
    return "jnp" if K < _AUTO_BLOCKED_MIN_K else "jnp_blocked"


# Per-backend tile defaults: the scan/map paths amortize best with big client
# tiles — and eq2's per-tile arithmetic (the packed Jacobi) is heavy enough
# that a larger tile wins again over the scan overhead, so the blocked
# default is measure-aware.  The sharded default stays at 64 so the row pad
# (a multiple of bk * ndev) stays small.  The Pallas kernel's edge is the
# smallest its (bk, bk) output block may have on a TPU (the (8, 128) tiling
# rule), not a tuned value.  An explicit block_size overrides all of these.
_DEFAULT_BLOCK = {
    "jnp_blocked": {"eq3": 64, "eq2": 96},
    "jnp_sharded": {"eq3": 64, "eq2": 64},
    "pallas": {"eq3": 128, "eq2": 128},  # = repro.kernels.proximity BLOCK
}

# Per-backend eq2 default: the dense reference keeps the LAPACK svd so it
# stays an independent oracle; the scalable paths use the batched Jacobi
# eigensolve (the pallas kernel lowers only jacobi on-chip).
_DEFAULT_EQ2_SOLVER = {
    "jnp": "svd",
    "jnp_blocked": "jacobi",
    "jnp_sharded": "jacobi",
    "pallas": "jacobi",
}


def _resolve_eq2_solver(eq2_solver: str, resolved_backend: str) -> str:
    if eq2_solver == "auto":
        return _DEFAULT_EQ2_SOLVER[resolved_backend]
    if eq2_solver not in EQ2_SOLVERS:
        raise ValueError(
            f"unknown eq2 solver: {eq2_solver!r} (want 'auto' or one of {EQ2_SOLVERS})"
        )
    if resolved_backend == "pallas" and eq2_solver != "jacobi":
        raise ValueError(
            "the pallas backend only lowers the 'jacobi' eq2 solver on-chip"
        )
    return eq2_solver


def proximity_matrix(
    U_stack: jax.Array,
    measure: str = "eq3",
    *,
    backend: str = "auto",
    block_size: int | None = None,
    eq2_solver: str = "auto",
) -> jax.Array:
    """Proximity matrix A (K x K, **degrees**) from stacked signatures.

    Parameters
    ----------
    U_stack: (K, n, p) stacked orthonormal client signatures.
    measure: "eq3" (default; trace of arccos over all p principal angles)
        or "eq2" (smallest principal angle).
    backend: "auto" (default) | "jnp" | "jnp_blocked" | "jnp_sharded" |
        "pallas" — see module docstring.  "auto" picks the dense einsum
        reference at small K and the blocked path beyond.
    block_size: client tile edge for the blocked/sharded/pallas paths; None
        (default) picks the backend's tuned default (blocked: 64 eq3 /
        96 eq2, sharded: 64, pallas: 128).
    eq2_solver: "auto" (default) | "jacobi" | "eigh" | "svd" —
        largest-singular-value solver for eq2 (see repro.core.measures).
        "auto" keeps the dense reference on svd and the scalable paths on
        the batched Jacobi.

    Parity guarantee: all backends and eq2 solvers agree with the dense
    einsum reference to <= 1e-3 degrees on orthonormal f32 inputs (the CI
    smoke gates this at K=128, ``benchmarks/proximity_scale.py --quick``),
    and downstream HC labels across backends are checked bitwise.  The
    result is symmetric with a zero diagonal.
    """
    if measure not in ("eq2", "eq3"):
        raise ValueError(f"unknown measure: {measure!r}")
    resolved = resolve_backend(backend, int(U_stack.shape[0]))
    solver = _resolve_eq2_solver(eq2_solver, resolved)
    if resolved == "jnp":
        return _proximity_dense(U_stack, measure, solver)
    bk = block_size if block_size is not None else _DEFAULT_BLOCK[resolved][measure]
    if resolved == "jnp_blocked":
        return _proximity_blocked(U_stack, measure, bk, solver)
    if resolved == "jnp_sharded":
        return _proximity_sharded(U_stack, measure, bk, solver)
    from repro.kernels.proximity import ops as pops

    # bk is honored as the kernel tile edge: K is padded to a multiple of it
    # and each grid cell holds two (bk, n, p) slabs in VMEM, so large values
    # trade padding waste + VMEM for fewer grid steps.
    return pops.proximity(U_stack, measure=measure, bk=bk)


@functools.partial(jax.jit, static_argnames=("measure", "eq2_solver"))
def _cross_dense(
    U_a: jax.Array, U_b: jax.Array, measure: str, eq2_solver: str
) -> jax.Array:
    return measure_pair(U_a, U_b, measure, eq2_solver=eq2_solver)


@functools.partial(
    jax.jit, static_argnames=("measure", "block_size", "eq2_solver")
)
def _cross_blocked(
    U_a: jax.Array, U_b: jax.Array, measure: str, block_size: int, eq2_solver: str
) -> jax.Array:
    """Both operands are tiled, so peak intermediate memory is one
    (bk, bk, p, p) Gram block regardless of which side is the huge one."""
    U_a = U_a.astype(jnp.float32)
    U_b = U_b.astype(jnp.float32)
    Ka, Kb = U_a.shape[0], U_b.shape[0]
    bk = block_size
    Ua = _pad_rows(U_a, bk)
    Ub = _pad_rows(U_b, bk)
    C = _strip_blocks(Ua, Ub, measure, bk, eq2_solver)
    return C[:Ka, :Kb]


def cross_proximity(
    U_a: jax.Array,
    U_b: jax.Array,
    measure: str = "eq3",
    *,
    backend: str = "auto",
    block_size: int | None = None,
    eq2_solver: str = "auto",
) -> jax.Array:
    """Rectangular angle block: (Ka, n, p) x (Kb, n, p) -> (Ka, Kb) degrees.

    The PME workhorse (Algorithm 2): newcomers need only the cross block
    against seen clients, never a fresh (Ka+Kb)^2 recomputation.  The
    ``jnp_sharded`` backend shards the U_a row-strip axis across local
    devices (U_b replicated).  The pallas backend is square-only, so it
    falls back to the blocked path here.

    Parity guarantee: entries are bitwise the matching off-diagonal block of
    :func:`proximity_matrix` over the concatenated stack (same measure and
    float32 Gram pipeline), independent of backend and block size.
    """
    if measure not in ("eq2", "eq3"):
        raise ValueError(f"unknown measure: {measure!r}")
    # auto must consider BOTH sides: the dense path materializes a
    # (Ka, Kb, p, p) tensor, so a small Ka with a huge Kb still blows up.
    resolved = resolve_backend(backend, max(int(U_a.shape[0]), int(U_b.shape[0])))
    if resolved == "pallas":
        # square-only kernel: the blocked path executes instead, so solver
        # validation and the block default must follow the actual executor
        resolved = "jnp_blocked"
    solver = _resolve_eq2_solver(eq2_solver, resolved)
    if resolved == "jnp":
        return _cross_dense(U_a, U_b, measure, solver)
    bk = block_size if block_size is not None else _DEFAULT_BLOCK[resolved][measure]
    if resolved == "jnp_sharded":
        return _cross_sharded(U_a, U_b, measure, bk, solver)
    return _cross_blocked(U_a, U_b, measure, bk, solver)


def proximity_matrix_pallas(U_stack: jax.Array, measure: str = "eq3") -> jax.Array:
    """Proximity matrix through the Pallas kernel (interpret mode off-TPU)."""
    return proximity_matrix(U_stack, measure, backend="pallas")
