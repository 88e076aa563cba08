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


# --------------------------------------------------------------------------
# trapezoid trimming (DESIGN.md §6.2): a rank-1 scan whose step k changes
# only the rows past k walks its rows in tiles, and replays into each
# tile only the steps that reach it.  A TPU holds an array's second-last
# dimension on 8 sublanes, so a tile is a whole number of sublane tiles;
# how many is each scan's fastest on a v5e (DESIGN.md §6.2).
# --------------------------------------------------------------------------

SUBLANES = 8
TRSM_TILE = 2 * SUBLANES    # rows per tile of ``rtrsm_left_lower``
PANEL_TILE = 4 * SUBLANES   # panel columns per tile of ``decomp.getf2``


def _steps(n: int, t: int) -> int:
    """Row-steps of a scan over n rows in t-row tiles: tile i holds t
    rows and runs min((i + 1) t, n) steps."""
    return t * sum(min(i * t, n) for i in range(1, -(-n // t) + 1))


def row_tiles(n: int, tile: int) -> tuple[int, int]:
    """(rows per tile, tiles) of a trimmed scan over n rows: ``tile``-row
    tiles where they compute less than the masked scan, else one tile of
    all n rows, which is the masked scan."""
    t = tile if _steps(n, tile) < n * n else n
    return t, -(-n // t)


def tile_rows(i, t: int, n: int):
    """Tile i of a trimmed scan over n rows of t-row tiles: (first row
    it solves, first row it holds, steps it runs).  The last tile of an
    n that t does not divide is shifted up to end at row n, so every
    tile has one shape; the rows it holds above its first solved row
    are finished and stay as they are."""
    own0 = i * t
    return own0, jnp.minimum(own0, n - t), jnp.minimum(own0 + t, n)


def trimmed_steps(n: int, tile: int) -> int:
    """Row-steps a trimmed scan over n rows in ``tile``-row tiles
    computes (the masked scan computed n * n)."""
    return _steps(n, row_tiles(n, tile)[0])


@functools.partial(jax.jit, static_argnames=("unit_diag", "fmt"))
def rtrsm_left_lower(l_p: jax.Array, b_p: jax.Array, unit_diag: bool = True,
                     fmt: PositFormat = P32E2) -> jax.Array:
    """Solve L X = B, L (n,n) lower-triangular posit, B (n, m) posit.

    Forward substitution in rank-1-update order: step k solves row k,
    x_k = B[k] (/ L[k, k]), and every row i > k takes
    B[i] <- B[i] - L[i, k] * x_k.  Fused-chain execution (core/posit.py):
    L and B decode once, each scalar op is still individually
    posit-rounded, words are packed once at exit — bit-identical to
    per-op fast-backend words.

    Trapezoid-trimmed: the rows walk in ``TRSM_TILE``-row tiles, and tile
    [r0, r0 + t) runs steps k < r0 + t only, on its own t rows: k < r0
    replays a finished row x_k into it, k >= r0 solves its row k.  Every
    element still takes B[i] - L[i, k] * x_k for each k < i, in ascending
    k, with the same operands, and then is solved, so the words are the
    masked scan's; only the updates the mask threw away (rows <= k) are
    no longer computed.  For a non-unit L a replay step's divide is
    computed and not used.
    """
    n = l_p.shape[0]
    t, tiles = row_tiles(n, TRSM_TILE)
    tr = jnp.arange(t)
    lv = posit.chain_decode(l_p, fmt)

    def solve_tile(i, x):
        own0, top, end = tile_rows(i, t, n)
        g = top + tr
        lt = jax.lax.dynamic_slice_in_dim(lv, top, t)

        def step(k, b):
            xk = jax.lax.dynamic_index_in_dim(b, jnp.maximum(k - top, 0),
                                              keepdims=False)
            if not unit_diag:
                xk = posit.chain_div(xk, lv[k, k], fmt)
            xk = jnp.where(k >= own0, xk, x[k])
            lk = jax.lax.dynamic_index_in_dim(lt, k, axis=1)
            upd = posit.chain_sub(b, posit.chain_mul(lk, xk[None, :], fmt),
                                  fmt)
            live = ((g > k) & (g >= own0))[:, None]
            return jnp.where(live, upd, jnp.where((g == k)[:, None], xk, b))

        b = jax.lax.fori_loop(0, end, step,
                              jax.lax.dynamic_slice_in_dim(x, top, t))
        return jax.lax.dynamic_update_slice_in_dim(x, b, top, axis=0)

    x = jax.lax.fori_loop(0, tiles, solve_tile,
                          posit.chain_decode(b_p, fmt))
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
