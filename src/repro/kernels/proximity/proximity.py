"""Pallas TPU kernel: blocked principal-angle proximity matrix (Eq. 2 + Eq. 3).

The PACFL server's hot spot: for K clients with signatures ``U in (K, n, p)``
compute either

* ``measure="eq3"`` — ``A[i, j] = sum_r arccos(|U_i[:, r] . U_j[:, r]|)``, or
* ``measure="eq2"`` — the smallest principal angle, ``arccos`` of the largest
  singular value of each per-pair ``p x p`` Gram block ``U_i^T U_j``.

Layout: the wrapper transposes the stack once, in XLA, to ``(p, K, n)``, so
column ``r`` of a block of ``bk`` signatures is a plain ``(bk, n)`` slab with
n on the lanes.  Tiling: a 2-D grid over (bi, bj) client-pair tiles; each
cell loads two ``(p, bk, n)`` blocks into VMEM and reduces them through the
shared measure core (``repro.core.measures.measure_tile``): every Gram entry
is one 2-D ``(bk, n) . (n, bk)`` MXU matmul (p for eq3, p^2 for eq2), and eq2
runs the fixed-sweep packed Jacobi eigensolve of the p x p matrices
``G^T G`` elementwise over the ``(bk, bk)`` plane (p is tiny — 2-5 in the
paper — and all plane indices are static, so no gather/scatter or reshape
reaches Mosaic).  The ``(bk, bk)`` output block obeys the TPU's (8, 128)
tiling rule when ``bk`` is a multiple of 128.
O(K^2 n p^2) flops, ``p * bk * n * 4`` bytes of VMEM per operand block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.measures import measure_tile

# Default tile edge: the smallest multiple of 128, so the (bk, bk) output
# block satisfies the (8, 128) rule and each Gram matmul fills the MXU.
BLOCK = 128
# eq2 at HIGHEST precision needs ~21 MB of scoped VMEM at bk=128 (n=784,
# p=5), over Mosaic's 16 MiB default.
VMEM_LIMIT = 32 * 1024 * 1024


def _proximity_kernel(ui_ref, uj_ref, a_ref, *, measure: str):
    # The whole cell is the shared tile reduction: the same Gram entries and
    # rotation code the jnp backends reduce with, so the kernel differs from
    # them only by float reduction order and its polynomial arccos.
    p = ui_ref.shape[0]
    ui = [ui_ref[r].astype(jnp.float32) for r in range(p)]   # p x (bk, n)
    uj = [uj_ref[r].astype(jnp.float32) for r in range(p)]
    a_ref[...] = measure_tile(ui, uj, measure)


@functools.partial(jax.jit, static_argnames=("measure", "bk", "interpret"))
def _proximity_pallas_jit(
    U: jax.Array, *, measure: str, bk: int, interpret: bool
) -> jax.Array:
    K, n, p = U.shape
    pad = (-K) % bk
    if pad:
        # jnp.pad writes ZERO signatures for the padded clients, so their
        # Gram blocks are zero and both measures read arccos(0) = 90 degrees
        # there.  That is only safe because the padded rows/cols are sliced
        # off below — never feed the padded matrix to clustering directly.
        U = jnp.pad(U, ((0, pad), (0, 0), (0, 0)))
    Kp = U.shape[0]
    Ut = U.transpose(2, 0, 1)                          # (p, Kp, n)
    grid = (Kp // bk, Kp // bk)
    A = pl.pallas_call(
        functools.partial(_proximity_kernel, measure=measure),
        grid=grid,
        in_specs=[
            pl.BlockSpec((p, bk, n), lambda i, j: (0, i, 0)),
            pl.BlockSpec((p, bk, n), lambda i, j: (0, j, 0)),
        ],
        out_specs=pl.BlockSpec((bk, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Kp, Kp), jnp.float32),
        interpret=interpret,
        name="proximity",
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
    )(Ut, Ut)
    A = A[:K, :K]
    A = 0.5 * (A + A.T)
    return A * (1.0 - jnp.eye(K, dtype=A.dtype))


def proximity_pallas(
    U: jax.Array,
    *,
    measure: str = "eq3",
    bk: int = BLOCK,
    interpret: bool | None = None,
) -> jax.Array:
    """U: (K, n, p) -> (K, K) proximity matrix in degrees.

    ``bk`` is the client tile edge; compiled on a TPU it must be a multiple
    of 128 (interpret mode takes any edge).  ``interpret=None`` (default)
    auto-detects the backend like ``ops.proximity`` does: compiled on TPU,
    interpret mode elsewhere.  Pass an explicit bool only to force one mode
    (e.g. interpret-on-TPU for debugging).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _proximity_pallas_jit(U, measure=measure, bk=bk, interpret=interpret)
