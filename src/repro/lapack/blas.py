"""Posit BLAS-2/3 building blocks (triangular solves, rank-1 updates).

Every scalar operation is a rounded posit op (fast backend) in the
working format ``fmt`` (static, default Posit(32,2)), in the same
operation order as reference-BLAS dtrsm/dtrsv (rank-1 / axpy form) —
this is what "running LAPACK in posit" via MPLAPACK does on the host in
the paper, with only Rgemm offloaded to the accelerator.  One traced
program serves every registered format; the format's field constants
fold at trace time (DESIGN.md §8).

All matrices are int32 posit-word arrays of the ONE format ``fmt``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import posit
from repro.core.formats import P32E2, PositFormat
from repro.quire import quire_dot


def _div(a, b, fmt: PositFormat = P32E2):
    """Word-domain rounded divide — used where the operand is already a
    posit word (the quire substitutions' fused-dot results)."""
    return posit.div(a, b, fmt, backend="fast")


@functools.partial(jax.jit, static_argnames=("unit_diag", "fmt"))
def rtrsm_left_lower(l_p: jax.Array, b_p: jax.Array, unit_diag: bool = True,
                     fmt: PositFormat = P32E2) -> jax.Array:
    """Solve L X = B, L (n,n) lower-triangular posit, B (n, m) posit.

    Forward substitution in rank-1-update order: n steps, each a
    vectorized posit mul+sub over the remaining rows.  Fused-chain
    execution (core/posit.py): L and B decode once, each scalar op
    is still individually posit-rounded, words are packed once at exit —
    bit-identical to per-op fast-backend words.
    """
    n = l_p.shape[0]
    rows = jnp.arange(n)
    lv = posit.chain_decode(l_p, fmt)

    def step(b, k):
        xk = b[k, :] if unit_diag else posit.chain_div(b[k, :], lv[k, k],
                                                       fmt)
        upd = posit.chain_sub(b, posit.chain_mul(lv[:, k][:, None],
                                                 xk[None, :], fmt), fmt)
        mask = (rows > k)[:, None]
        b = jnp.where(mask, upd, b)
        b = b.at[k, :].set(xk)
        return b, None

    x, _ = jax.lax.scan(step, posit.chain_decode(b_p, fmt), jnp.arange(n))
    return posit.chain_encode(x, fmt)


@functools.partial(jax.jit, static_argnames=("unit_diag", "fmt"))
def rtrsm_left_upper(u_p: jax.Array, b_p: jax.Array, unit_diag: bool = False,
                     fmt: PositFormat = P32E2) -> jax.Array:
    """Solve U X = B, U (n,n) upper-triangular posit, B (n, m) posit.

    Backward substitution in rank-1-update order (the dtrsm mirror of
    ``rtrsm_left_lower``) — Rgels' final R x = Q^T b solve.  Fused-chain
    execution; the strict lower triangle of U is never referenced, so a
    QR-factored matrix (reflector tails below the diagonal) can be
    passed as-is.
    """
    n = u_p.shape[0]
    rows = jnp.arange(n)
    uv = posit.chain_decode(u_p, fmt)

    def step(b, k):
        xk = b[k, :] if unit_diag else posit.chain_div(b[k, :], uv[k, k],
                                                       fmt)
        upd = posit.chain_sub(b, posit.chain_mul(uv[:, k][:, None],
                                                 xk[None, :], fmt), fmt)
        mask = (rows < k)[:, None]
        b = jnp.where(mask, upd, b)
        b = b.at[k, :].set(xk)
        return b, None

    x, _ = jax.lax.scan(step, posit.chain_decode(b_p, fmt),
                        jnp.arange(n - 1, -1, -1))
    return posit.chain_encode(x, fmt)


@functools.partial(jax.jit, static_argnames=("fmt",))
def rtrsm_right_lowerT(b_p: jax.Array, l_p: jax.Array,
                       fmt: PositFormat = P32E2) -> jax.Array:
    """Solve X L^T = B  (right, lower-transpose, non-unit diag).

    Used by Cholesky's panel update A21 <- A21 * L11^{-T}.  Right-looking
    column order: X[:,k] = B[:,k] / L[k,k]; B[:,j>k] -= X[:,k] L[j,k].
    Fused-chain execution; bit-identical to the word-domain form.
    """
    n = l_p.shape[0]
    cols = jnp.arange(n)
    lv = posit.chain_decode(l_p, fmt)

    def step(b, k):
        xk = posit.chain_div(b[:, k], lv[k, k], fmt)
        upd = posit.chain_sub(b, posit.chain_mul(xk[:, None],
                                                 lv[:, k][None, :], fmt),
                              fmt)
        mask = (cols > k)[None, :]
        b = jnp.where(mask, upd, b)
        b = b.at[:, k].set(xk)
        return b, None

    x, _ = jax.lax.scan(step, posit.chain_decode(b_p, fmt), jnp.arange(n))
    return posit.chain_encode(x, fmt)


@functools.partial(jax.jit, static_argnames=("unit_diag", "fmt"))
def rtrsv_lower(l_p: jax.Array, b_p: jax.Array, unit_diag: bool = False,
                fmt: PositFormat = P32E2) -> jax.Array:
    """Solve L x = b (vector), forward substitution with posit axpy steps
    (fused-chain form, bit-identical to per-op words)."""
    n = l_p.shape[0]
    idx = jnp.arange(n)
    lv = posit.chain_decode(l_p, fmt)

    def step(b, k):
        xk = b[k] if unit_diag else posit.chain_div(b[k], lv[k, k], fmt)
        upd = posit.chain_sub(b, posit.chain_mul(lv[:, k], xk, fmt), fmt)
        b = jnp.where(idx > k, upd, b)
        b = b.at[k].set(xk)
        return b, None

    x, _ = jax.lax.scan(step, posit.chain_decode(b_p, fmt), jnp.arange(n))
    return posit.chain_encode(x, fmt)


@functools.partial(jax.jit, static_argnames=("unit_diag", "fmt"))
def rtrsv_upper(u_p: jax.Array, b_p: jax.Array, unit_diag: bool = False,
                fmt: PositFormat = P32E2) -> jax.Array:
    """Solve U x = b (vector), backward substitution with posit axpy steps
    (fused-chain form, bit-identical to per-op words)."""
    n = u_p.shape[0]
    idx = jnp.arange(n)
    uv = posit.chain_decode(u_p, fmt)

    def step(b, k):
        xk = b[k] if unit_diag else posit.chain_div(b[k], uv[k, k], fmt)
        upd = posit.chain_sub(b, posit.chain_mul(uv[:, k], xk, fmt), fmt)
        b = jnp.where(idx < k, upd, b)
        b = b.at[k].set(xk)
        return b, None

    x, _ = jax.lax.scan(step, posit.chain_decode(b_p, fmt),
                        jnp.arange(n - 1, -1, -1))
    return posit.chain_encode(x, fmt)


# --------------------------------------------------------------------------
# Householder reflector helper (the dlarfg kernel, fused-chain form) —
# the scalar engine of lapack/qr.py's panel factorization
# --------------------------------------------------------------------------

def rlarfg_chain(col: jax.Array, k, fmt: PositFormat = P32E2):
    """Generate the Householder reflector H = I - tau v v^T annihilating
    ``col`` below index ``k`` (dlarfg, every scalar op posit-rounded).

    ``col`` is a fused-chain (decoded) column; ``k`` the pivot index
    (traced).  Returns chain-domain ``(newcol, v, tau)``:

    * ``newcol`` — beta = -sign(alpha) * ||col[k:]|| at index k (no
      cancellation), the reflector tail v[k+1:] below it, rows < k
      untouched;
    * ``v``      — the full reflector: 0 above k, exactly 1 at k;
    * ``tau``    — (beta - alpha) / beta, or 0 for an already-zero tail
      (H = I, the dlarfg trivial case — also what a zero-height tail in
      the last panel column produces).
    """
    m = col.shape[0]
    rows = jnp.arange(m)

    def acc(s, i):
        upd = posit.chain_add(s, posit.chain_mul(col[i], col[i], fmt), fmt)
        return jnp.where(i > k, upd, s), None

    s2, _ = jax.lax.scan(acc, posit.chain_const(0.0), rows)
    alpha = col[k]
    norm = posit.chain_sqrt(
        posit.chain_add(posit.chain_mul(alpha, alpha, fmt), s2, fmt), fmt)
    # posit rounding saturates at minpos (never flushes to zero), so
    # s2 == 0 iff every tail element is exactly zero
    trivial = posit.chain_iszero(s2)
    beta = jnp.where(posit.chain_positive(alpha), posit.chain_neg(norm), norm)
    tau = jnp.where(trivial, posit.chain_const(0.0),
                    posit.chain_div(posit.chain_sub(beta, alpha, fmt), beta,
                                    fmt))
    denom = posit.chain_sub(alpha, beta, fmt)
    tail = posit.chain_div(col, denom, fmt)
    v = jnp.where(rows == k, posit.chain_const(1.0),
                  jnp.where((rows > k) & ~trivial, tail,
                            posit.chain_const(0.0)))
    newcol = jnp.where(
        rows == k, jnp.where(trivial, alpha, beta),
        jnp.where(rows > k, jnp.where(trivial, col, tail), col))
    return newcol, v, tau


# --------------------------------------------------------------------------
# quire-backed substitutions: the per-row inner product is an exact fused
# dot (repro.quire), so each solved component suffers exactly ONE rounding
# before the divide instead of n rounded axpy steps — the accuracy lever
# the iterative-refinement drivers (lapack/refine.py) are built on.
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("unit_diag", "fmt"))
def rtrsv_lower_quire(l_p: jax.Array, b_p: jax.Array, unit_diag: bool = False,
                      fmt: PositFormat = P32E2) -> jax.Array:
    """Solve L x = b with quire-exact rows:
    x_k = round(b_k - fdp(L[k, :k], x[:k])) / L_kk."""
    n = l_p.shape[0]
    x0 = jnp.zeros_like(jnp.asarray(b_p, jnp.int32))

    def step(x, k):
        # x[j] == 0 (posit zero word) for j >= k, so the full-row fused
        # dot only picks up the already-solved prefix — no masking needed.
        rk = quire_dot(l_p[k, :], x, fmt, init_p=b_p[k], negate=True)
        xk = rk if unit_diag else _div(rk, l_p[k, k], fmt)
        return x.at[k].set(xk), None

    x, _ = jax.lax.scan(step, x0, jnp.arange(n))
    return x


@functools.partial(jax.jit, static_argnames=("unit_diag", "fmt"))
def rtrsv_upper_quire(u_p: jax.Array, b_p: jax.Array, unit_diag: bool = False,
                      fmt: PositFormat = P32E2) -> jax.Array:
    """Solve U x = b, backward substitution with quire-exact rows."""
    n = u_p.shape[0]
    x0 = jnp.zeros_like(jnp.asarray(b_p, jnp.int32))

    def step(x, k):
        rk = quire_dot(u_p[k, :], x, fmt, init_p=b_p[k], negate=True)
        xk = rk if unit_diag else _div(rk, u_p[k, k], fmt)
        return x.at[k].set(xk), None

    x, _ = jax.lax.scan(step, x0, jnp.arange(n - 1, -1, -1))
    return x
