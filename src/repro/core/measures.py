"""Shared measure core: Eq. 2 / Eq. 3 reductions over pairwise Gram blocks.

One implementation used by every proximity backend — the dense einsum
reference, the blocked ``lax.map`` path and the device-sharded engine in
``repro.core.angles`` reduce their ``(..., p, p)`` Gram blocks through
:func:`measure_from_gram`, and the Pallas TPU kernel in
``repro.kernels.proximity`` through :func:`measure_tile`, which forms the
same Gram entries and shares the Jacobi rotations, so backends cannot
drift apart numerically (the kernel's arccos is the polynomial
:func:`arccos01`).  Every Gram contraction runs at :data:`GRAM_PRECISION`.

Eq. 3 is a diagonal gather.  Eq. 2 needs the largest singular value of each
``p x p`` block ``G = U_i^T U_j`` and dispatches across three solvers:

* ``"jacobi"`` — fixed-sweep cyclic Jacobi on ``B = G^T G``, kept in a
  *packed symmetric* representation: the ``p (p + 1) / 2`` unique entries
  live as separate batch vectors, and each plane rotation touches only the
  ``O(p)`` entries it actually changes.  Pure vectorized arithmetic with
  static plane indices: no per-matrix LAPACK dispatch (the reason the old
  blocked eq2 path ran millions of tiny host SVDs and sat ~13x behind eq3)
  and no dynamic gather/scatter, so the same code lowers inside the Pallas
  TPU kernel.
* ``"eigh"`` — batched ``jnp.linalg.eigvalsh`` on ``G^T G`` (one LAPACK
  dispatch per block); parity fallback.
* ``"svd"`` — batched ``jnp.linalg.svd`` (one LAPACK dispatch per block);
  the historical path, kept as the parity oracle the fast solvers are
  tested against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EQ2_SOLVERS = ("jacobi", "eigh", "svd")

# Precision of every Gram contraction in the core.  On a TPU, XLA's default
# for an f32 dot is a single bf16 pass, whose ~3 significant digits put
# angles tenths of a degree off the float64 truth; HIGHEST keeps the
# products f32 there.  CPU dots are f32 either way.
GRAM_PRECISION = jax.lax.Precision.HIGHEST

# Abramowitz & Stegun 4.4.46: arccos(x) = sqrt(1 - x) * poly7(x) on [0, 1],
# |error| <= 2e-8 rad in exact arithmetic (about 2.5e-7 rad in f32).
_ACOS_COEFFS = (
    1.5707963050, -0.2145988016, 0.0889789874, -0.0501743046,
    0.0308918810, -0.0170881256, 0.0066700901, -0.0012624911,
)

# Cyclic Jacobi sweeps.  Convergence is quadratic; for the paper's p <= 5
# four sweeps already sit on the f32 roundoff floor (~2e-4 deg worst case on
# clustered subspaces, asserted at 1e-3 by the parity suite), while larger p
# gets two extra sweeps of margin.
_JACOBI_SWEEPS_SMALL_P = 4
_JACOBI_SWEEPS_LARGE_P = 6


def jacobi_sweeps(p: int) -> int:
    """Default sweep count for a ``p x p`` eigensolve."""
    return _JACOBI_SWEEPS_SMALL_P if p <= 5 else _JACOBI_SWEEPS_LARGE_P


def _key(a: int, b: int) -> tuple[int, int]:
    return (a, b) if a <= b else (b, a)


# Keeps the rotation-tangent denominator away from the 0/0 of an
# already-diagonal plane (d = e = 0 gives t = 0/TINY = 0, a no-op rotation)
# without a select; negligible against any physically meaningful entry of
# B = G^T G, whose scale is ~1 for orthonormal signatures.
_TINY = 1e-30


def _jacobi_rotate(b: dict, p: int, i: int, j: int) -> None:
    """One batched plane rotation zeroing ``B[i, j]``, in packed form.

    The rotation tangent is the small root of ``t^2 + 2 tau t - 1 = 0``
    with ``tau = (b_jj - b_ii) / (2 b_ij)``, computed in the
    cancellation-free form ``t = sign(d) * e / (|d| + sqrt(d^2 + e^2))``
    (``d = b_jj - b_ii``, ``e = 2 b_ij``) so no intermediate overflows and
    the already-diagonal plane ``d = e = 0`` degrades to a no-op via the
    ``_TINY`` denominator guard.
    """
    bii, bjj, bij = b[(i, i)], b[(j, j)], b[(i, j)]
    d = bjj - bii
    e = bij + bij
    den = jnp.abs(d) + jnp.sqrt(d * d + e * e) + _TINY
    sgn = jnp.where(d >= 0.0, 1.0, -1.0)
    t = sgn * e / den
    c = jax.lax.rsqrt(1.0 + t * t)
    s = t * c
    tb = t * bij
    b[(i, i)] = bii - tb
    b[(j, j)] = bjj + tb
    b[(i, j)] = jnp.zeros_like(bij)
    for k in range(p):
        if k == i or k == j:
            continue
        bik, bjk = b[_key(i, k)], b[_key(j, k)]
        b[_key(i, k)] = c * bik - s * bjk
        b[_key(j, k)] = s * bik + c * bjk


def jacobi_max_eig_packed(b: dict, p: int, sweeps: int | None = None) -> jax.Array:
    """Largest eigenvalue of packed symmetric PSD batches.

    ``b`` maps ``(i, j)`` with ``i <= j < p`` to the batch vector of that
    entry; it is consumed (mutated) by the sweeps.  All indices are static
    Python ints, so the loop unrolls into a fixed sequence of batched
    vector ops — Pallas-lowerable, no dynamic gather/scatter.
    """
    if p == 1:
        return b[(0, 0)]
    if sweeps is None:
        sweeps = jacobi_sweeps(p)
    for _ in range(sweeps):
        for i in range(p - 1):
            for j in range(i + 1, p):
                _jacobi_rotate(b, p, i, j)
    return functools.reduce(jnp.maximum, [b[(i, i)] for i in range(p)])


def jacobi_max_eig(B: jax.Array, p: int, sweeps: int | None = None) -> jax.Array:
    """Largest eigenvalue of symmetric PSD ``B`` with shape ``(..., p, p)``."""
    b = {(i, j): B[..., i, j] for i in range(p) for j in range(i, p)}
    return jacobi_max_eig_packed(b, p, sweeps)


def _eq2_jacobi(G: jax.Array) -> jax.Array:
    """Largest singular value of ``(..., p, p)`` blocks via packed Jacobi.

    ``B = G^T G`` is formed entry-wise as contiguous batched reductions —
    a batched ``(p, p) @ (p, p)`` matmul here would fall back to one tiny
    LAPACK/loop dispatch per block on CPU and dominate the whole measure.
    """
    p = G.shape[-1]
    cols = [G[..., :, q] for q in range(p)]
    b = {}
    for q in range(p):
        for r in range(q, p):
            b[(q, r)] = jnp.sum(cols[q] * cols[r], axis=-1)
    lam = jacobi_max_eig_packed(b, p)
    return jnp.sqrt(jnp.clip(lam, 0.0, None))


def arccos01(x: jax.Array) -> jax.Array:
    """``arccos`` on ``[0, 1]`` in radians, as multiply-adds and one sqrt.

    Pallas has no TPU lowering for ``acos`` (nor ``atan2``), so the
    kernel's tile uses this form; callers clip into the domain first.  The
    jnp backends keep ``jnp.arccos``: fused into a Gram contraction on
    XLA:CPU, this polynomial rounds differently with the array shape, so a
    pair's angle in an admission's (B, B) square block would less often
    match its bits in a later (M, B) cross block, and the store would then
    depend on how churn was batched.
    """
    poly = _ACOS_COEFFS[-1]
    for c in reversed(_ACOS_COEFFS[:-1]):
        poly = poly * x + c
    return jnp.sqrt(1.0 - x) * poly


def _angle_deg(cos: jax.Array, acos=jnp.arccos) -> jax.Array:
    """Degrees of ``acos(clip(cos, 0, 1))``."""
    return jnp.degrees(acos(jnp.clip(cos, 0.0, 1.0)))


def eq3_from_diag(d: jax.Array) -> jax.Array:
    """Eq. 3 reduction from ``(..., p)`` Gram *diagonal* entries, degrees."""
    return jnp.sum(_angle_deg(jnp.abs(d)), axis=-1)


def measure_pair(
    Ui: jax.Array, Uj: jax.Array, measure: str, *, eq2_solver: str = "jacobi"
) -> jax.Array:
    """Pairwise measure block straight from signature stacks:
    ``(a, n, p) x (b, n, p) -> (a, b)`` degrees.

    The jnp backends' tile: eq3 needs only the ``p`` Gram diagonal entries
    ``G_ab[r, r] = <Ui[a, :, r], Uj[b, :, r]>``, so it takes the
    ``einsum("anr,bnr->abr")`` route — p of the p^2 dot products, a ~p-fold
    flop cut over materializing the full ``(a, b, p, p)`` Gram block.  eq2
    genuinely needs every entry (largest singular value) and keeps the full
    Gram + :func:`measure_from_gram` reduction.

    Parity guarantee: bitwise-identical to the full-Gram
    :func:`measure_from_gram` route (the eq3 diagonal shortcut reorders no
    floating-point reductions), deterministic for fixed inputs.
    """
    Ui = Ui.astype(jnp.float32)
    Uj = Uj.astype(jnp.float32)
    if measure == "eq3":
        return eq3_from_diag(
            jnp.einsum("anr,bnr->abr", Ui, Uj, precision=GRAM_PRECISION)
        )
    G = jnp.einsum("anp,bnq->abpq", Ui, Uj, precision=GRAM_PRECISION)
    return measure_from_gram(G, measure, eq2_solver=eq2_solver)


def measure_from_gram(
    G: jax.Array, measure: str, *, eq2_solver: str = "jacobi"
) -> jax.Array:
    """(..., p, p) pairwise Gram blocks -> (...,) angles in degrees.

    ``measure`` is ``"eq2"`` (smallest principal angle) or ``"eq3"`` (trace
    of arccos over identically ordered pairs).  ``eq2_solver`` picks the
    largest-singular-value solver — see the module docstring; ``"jacobi"``
    is the only one that lowers inside the Pallas kernel.

    Parity guarantee: deterministic for fixed ``(G, measure, eq2_solver)``;
    every backend tile reduces through this exact function (or its bitwise
    eq3 diagonal shortcut), which is what makes cross-backend parity hold.
    """
    if measure == "eq3":
        return eq3_from_diag(jnp.diagonal(G, axis1=-2, axis2=-1))
    if measure != "eq2":
        raise ValueError(f"unknown measure: {measure!r}")
    if eq2_solver == "jacobi":
        smax = _eq2_jacobi(G)
    elif eq2_solver == "eigh":
        B = jnp.matmul(jnp.swapaxes(G, -1, -2), G, precision=GRAM_PRECISION)
        smax = jnp.sqrt(jnp.clip(jnp.linalg.eigvalsh(B)[..., -1], 0.0, None))
    elif eq2_solver == "svd":
        s = jnp.linalg.svd(G, compute_uv=False)
        smax = s[..., 0]
    else:
        raise ValueError(
            f"unknown eq2 solver: {eq2_solver!r} (want one of {EQ2_SOLVERS})"
        )
    return _angle_deg(smax)


def measure_tile(Ui, Uj, measure: str) -> jax.Array:
    """Pallas kernel tile: p column slabs per side -> ``(bi, bj)`` degrees.

    ``Ui[r]`` is the ``(bi, n)`` slab of column ``r`` of ``bi`` signatures
    (the kernel's ``(p, K, n)`` layout), likewise ``Uj[r]`` with ``bj``.
    Each Gram entry ``G_ab[r, q] = <Ui[r][a], Uj[q][b]>`` comes from one
    plain 2-D ``(bi, n) . (n, bj)`` MXU matmul: p of them for eq3, which
    needs only the diagonal, and p^2 for eq2.  The reductions are then
    elementwise over ``(bi, bj)`` planes: eq3 sums the p arccos terms, and
    eq2 builds the packed ``B = G^T G`` entries for the Jacobi eigensolve.
    No reshape, transpose or gather, so Mosaic lowers all of it.  The Jacobi
    rotations are the code every jnp backend reduces with; the arccos is
    :func:`arccos01`, within 2.5e-7 rad of theirs.
    """
    p = len(Ui)

    def gram(r, q):
        return jax.lax.dot_general(
            Ui[r], Uj[q], (((1,), (1,)), ((), ())),
            precision=GRAM_PRECISION, preferred_element_type=jnp.float32,
        )

    if measure == "eq3":
        total = _angle_deg(jnp.abs(gram(0, 0)), arccos01)
        for r in range(1, p):
            total = total + _angle_deg(jnp.abs(gram(r, r)), arccos01)
        return total
    if measure != "eq2":
        raise ValueError(f"unknown measure: {measure!r}")
    S = [[gram(k, q) for q in range(p)] for k in range(p)]
    b = {}
    for q in range(p):
        for r in range(q, p):
            acc = S[0][q] * S[0][r]
            for k in range(1, p):
                acc = acc + S[k][q] * S[k][r]
            b[(q, r)] = acc
    lam = jacobi_max_eig_packed(b, p)
    return _angle_deg(jnp.sqrt(jnp.clip(lam, 0.0, None)), arccos01)
