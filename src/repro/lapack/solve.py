"""Rpotrs / Rgetrs — solve A x = b from the posit factorizations, plus
binary32 counterparts (the paper's §5.1 protocol uses these to measure
relative backward error).

``quire=True`` switches both substitution sweeps to the quire-exact
variants (one rounding per solved component; lapack/blas.py) — the
building block of the iterative-refinement drivers in lapack/refine.py.
The sweeps of ``rgetrs``/``rpotrs`` run under the ``posit.sweep`` or
``posit.quire_sweep`` scope, ``rgetrs``' pivot scan under ``posit.swap``
(repro.obs.scopes).
``fmt`` selects the posit format of the factors/right-hand side (static,
default Posit(32,2)); the mixed-precision drivers run these in p16e1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.formats import P32E2, PositFormat
from repro.lapack.blas import (rtrsv_lower, rtrsv_lower_quire, rtrsv_upper,
                               rtrsv_upper_quire)
from repro.obs import scopes as _scopes


def _sweeps(quire: bool):
    if quire:
        return rtrsv_lower_quire, rtrsv_upper_quire
    return rtrsv_lower, rtrsv_upper


@functools.partial(jax.jit, static_argnames=("lower", "unit_diag", "quire",
                                             "fmt"))
def rtrtrs(t_p: jax.Array, b_p: jax.Array, lower: bool = False,
           unit_diag: bool = False, quire: bool = False,
           fmt: PositFormat = P32E2) -> jax.Array:
    """Solve T x = b for triangular T (vector b) — the dtrtrs driver over
    the blas substitution sweeps.  ``quire=True`` switches to the
    quire-exact rows (one rounding per solved component) — the
    least-squares solvers' R / R^T correction sweeps (lapack/qr.py).
    The opposite triangle of ``t_p`` is never referenced (zero words and
    not-yet-solved components contribute exact zeros), so QR-factored
    matrices can be passed without masking."""
    fwd, bwd = _sweeps(quire)
    fn = fwd if lower else bwd
    return fn(t_p, b_p, unit_diag=unit_diag, fmt=fmt)


@functools.partial(jax.jit, static_argnames=("quire", "fmt"))
def rpotrs(l_p: jax.Array, b_p: jax.Array, quire: bool = False,
           fmt: PositFormat = P32E2) -> jax.Array:
    """Solve (L L^T) x = b in posit: forward then backward substitution."""
    lower, upper = _sweeps(quire)
    with jax.named_scope(_scopes.sweep(quire)):
        y = lower(l_p, b_p, unit_diag=False, fmt=fmt)
        return upper(l_p.T, y, unit_diag=False, fmt=fmt)


@functools.partial(jax.jit, static_argnames=("quire", "fmt"))
def rgetrs(lu_p: jax.Array, ipiv: jax.Array, b_p: jax.Array,
           quire: bool = False, fmt: PositFormat = P32E2) -> jax.Array:
    """Solve (P L U) x = b in posit."""
    def one(b, kp):
        k, p = kp
        bk, bp_ = b[k], b[p]
        return b.at[k].set(bp_).at[p].set(bk), None

    with jax.named_scope(_scopes.SWAP):
        b, _ = jax.lax.scan(one, b_p, (jnp.arange(ipiv.shape[0]), ipiv))
    lower, upper = _sweeps(quire)
    with jax.named_scope(_scopes.sweep(quire)):
        y = lower(lu_p, b, unit_diag=True, fmt=fmt)
        return upper(lu_p, y, unit_diag=False, fmt=fmt)


def spotrs(l32: jax.Array, b32: jax.Array) -> jax.Array:
    return jax.scipy.linalg.cho_solve((l32, True), b32.astype(jnp.float32))


def sgetrs(lu32, piv, b32: jax.Array) -> jax.Array:
    return jax.scipy.linalg.lu_solve((lu32, piv), b32.astype(jnp.float32))
