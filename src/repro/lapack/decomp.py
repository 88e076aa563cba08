"""Blocked Cholesky (Rpotrf) and LU (Rgetrf) in posit arithmetic.

Right-looking LAPACK algorithms (dpotrf/dgetrf, Toledo [30]): unblocked
panel factorizations run fully in posit arithmetic (every scalar op
rounded), and the trailing-matrix update is a single Rgemm call — exactly
the paper's offload split ("Both Rpotrf and Rgetrf call Rgemm for updating
the trailing matrix", §5.2).  ``gemm_backend`` selects the accelerator
semantics: 'faithful' (paper's per-MAC-rounding PE), 'xla_quire'
(beyond-paper tile accumulation), 'quire_exact' (true posit-standard
quire — the alpha=-1/beta=1 trailing updates here are single-rounding
fused ops, see repro.quire), or 'pallas_split3[_comp]' (the TPU kernel
in interpret mode).

``fmt`` selects the posit format (static, default Posit(32,2)): the SAME
traced program factorizes in any registered format — this is what the
mixed-precision solvers (lapack/refine.py rgesv_mp/rposv_mp) build on,
factorizing cheap in p16e1 and refining exact in p32e2 (DESIGN.md §8).

Execution model (DESIGN.md §6.2): the block schedule is **static at trace
time**, so ``rpotrf``/``rgetrf`` are single-dispatch — the whole blocked
factorization (panels + triangular solves + trailing Rgemms) is ONE jitted
XLA program instead of ~n/nb Python-level dispatches with full-matrix
``at[].set`` copies between them.  The pre-PR-2 Python-loop drivers are
kept as ``rpotrf_loop``/``rgetrf_loop`` (bit-identical — same traced ops,
different dispatch granularity) as the measured baseline for
``benchmarks/bench_decomp.py``.  ``rpotrf_batched``/``rgetrf_batched``
vmap the same program over a leading matrix axis — the paper's §5.1
ensemble protocol (many matrices x many phi scales) as one batched
program.

Panel kernels run in fused-chain form (core/posit.py): operands decode to
binary64 patterns once at panel entry, every scalar op is still
individually rounded to the posit lattice, and words are encoded once at
panel exit — bit-identical to per-op fast-backend words, minus the
redundant decode/encode round-trips.  Integer ops only, so the panels
compile for a TPU.

binary32 baselines (spotrf/sgetrf) use the same XLA algorithms in f32,
standing in for LAPACK's spotrf/sgetrf as in the paper's comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import posit
from repro.core.formats import P32E2, PositFormat
from repro.kernels.ops import rgemm
from repro.lapack.blas import (PANEL_TILE, TRSM_TILE, row_tiles,
                               rtrsm_left_lower, rtrsm_right_lowerT,
                               tile_rows, trimmed_steps)
from repro.obs import metrics as _obs_metrics
from repro.obs import numerics as _obs_numerics
from repro.obs import scopes as _scopes
from repro.obs import trace as _obs_trace

_FMT = P32E2


# --------------------------------------------------------------------------
# unblocked panel kernels (all-posit, fused-chain form)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("fmt",))
def potf2(a_p: jax.Array, fmt: PositFormat = P32E2) -> jax.Array:
    """Unblocked lower Cholesky of an (n,n) posit matrix, dpotf2 op order.

    Decode-once / encode-once: the panel enters the chain domain once,
    every scalar op is posit-rounded in place, words are packed once at
    exit.
    """
    n = a_p.shape[0]
    rows = jnp.arange(n)
    a = posit.chain_decode(a_p, fmt)

    def outer(a, j):
        # col <- A[:, j] - A[:, :j] @ A[j, :j]   (chained over k < j)
        def inner(col, k):
            upd = posit.chain_sub(col, posit.chain_mul(a[:, k], a[j, k],
                                                       fmt), fmt)
            return jnp.where(k < j, upd, col), None

        col, _ = jax.lax.scan(inner, a[:, j], jnp.arange(n))
        ajj = posit.chain_sqrt(col[j], fmt)
        below = posit.chain_div(col, ajj, fmt)
        newcol = jnp.where(rows > j, below, jnp.where(rows == j, ajj, a[:, j]))
        return a.at[:, j].set(newcol), None

    a, _ = jax.lax.scan(outer, a, jnp.arange(n))
    return posit.chain_encode(a, fmt)


@functools.partial(jax.jit, static_argnames=("nb", "fmt"))
def getf2(a_p: jax.Array, nb: int, fmt: PositFormat = P32E2):
    """Unblocked partial-pivot LU of an (m, nb) posit panel (dgetf2 order).

    Returns (panel, ipiv) with L strictly-below-diagonal (unit diag) and U
    on/above.  Step k searches column k for its pivot, swaps rows k and
    piv across the panel, divides column k below the diagonal by the
    pivot, and updates A[i, c] <- A[i, c] - L[i, k] * U[k, c] for
    i, c > k.  Pivot search compares |value| — decoded posit values order
    exactly like the word patterns (posits are monotone), and so do the
    int64 patterns of nonnegative chain values, so the pattern comparison
    picks the same pivot the word comparison did.  Fused-chain execution:
    decode once, per-op rounding in fields, encode once.

    Trapezoid-trimmed, on the transposed panel (a panel column is a row,
    so the trimmed dimension lies on sublanes): the columns walk in
    ``blas.PANEL_TILE``-column tiles, and tile [c0, c0 + t) runs steps
    k < c0 + t only, on its own t columns: k < c0 replays step k's row
    swap and its update with the finished column k of L, k >= c0 factors
    its column k.  A column left of k takes step k's swap only at the
    end, after all steps, as nothing else touches it after its own step.
    So each column still sees, in ascending k, the same swaps, pivot
    search, divide and updates A[i, c] - L[i, k] * U[k, c] with the same
    operands: the words and pivots are the masked scan's, whatever the
    pivots, and only the updates its mask threw away (columns <= k) are
    no longer computed.
    """
    m, w = a_p.shape
    if nb != w or m < w:
        raise ValueError(f"getf2 factors an (m, nb) panel, m >= nb; got "
                         f"{a_p.shape} and nb={nb}")
    t, tiles = row_tiles(w, PANEL_TILE)
    tr, lanes = jnp.arange(t), jnp.arange(m)
    p0 = posit.chain_decode(a_p, fmt).T          # row c: panel column c

    def factor_tile(i, carry):
        p, ipiv = carry
        own0, top, end = tile_rows(i, t, w)
        g = top + tr

        def factor_column(k, r, tile, ipiv):
            col = tile[r]
            mag = jnp.where(lanes >= k, posit.chain_abs(col), -1)
            mag = jnp.where(posit.chain_isnan(col), -1, mag)  # NaR: never
            piv = jnp.argmax(mag).astype(jnp.int32)
            tile = _swap_columns(tile, k, piv, g >= k)
            col = tile[r]
            scaled = posit.chain_div(col, col[k], fmt)
            tile = tile.at[r].set(jnp.where(lanes > k, scaled, col))
            return tile, ipiv.at[k].set(piv)

        def replay_swap(k, tile, ipiv):
            return _swap_columns(tile, k, ipiv[k], g >= own0), ipiv

        def step(k, c):
            tile, ipiv = c
            r = jnp.maximum(k - top, 0)
            tile, ipiv = jax.lax.cond(
                k >= own0, functools.partial(factor_column, k, r),
                functools.partial(replay_swap, k), tile, ipiv)
            lk = jnp.where(k >= own0, tile[r], p[k])
            uk = jax.lax.dynamic_index_in_dim(tile, k, axis=1)
            upd = posit.chain_sub(tile, posit.chain_mul(lk[None, :], uk,
                                                        fmt), fmt)
            live = ((g > k) & (g >= own0))[:, None] & (lanes > k)[None, :]
            return jnp.where(live, upd, tile), ipiv

        tile, ipiv = jax.lax.fori_loop(
            0, end, step, (jax.lax.dynamic_slice_in_dim(p, top, t), ipiv))
        return jax.lax.dynamic_update_slice_in_dim(p, tile, top, 0), ipiv

    p, ipiv = jax.lax.fori_loop(0, tiles, factor_tile,
                                (p0, jnp.zeros((w,), jnp.int32)))
    cols = jnp.arange(w)
    p = jax.lax.fori_loop(
        0, w, lambda k, p: _swap_columns(p, k, ipiv[k], cols < k), p)
    return posit.chain_encode(p.T, fmt), ipiv


def _swap_columns(x, k, p, rows):
    """x with columns k and p exchanged in the given rows (panel rows k
    and p, in the given panel columns)."""
    xk, xp = x[:, k], x[:, p]
    return (x.at[:, k].set(jnp.where(rows, xp, xk))
            .at[:, p].set(jnp.where(rows, xk, xp)))


# --------------------------------------------------------------------------
# legacy word-domain panels — the pre-PR-2 implementations, kept as the
# measured baseline for the loop drivers (bit-identical to the chain
# panels; every intermediate round-trips through a posit word)
# --------------------------------------------------------------------------

def _mul(a, b, fmt=_FMT):
    return posit.mul(a, b, fmt, backend="fast")


def _sub(a, b, fmt=_FMT):
    return posit.sub(a, b, fmt, backend="fast")


def _div(a, b, fmt=_FMT):
    return posit.div(a, b, fmt, backend="fast")


@functools.partial(jax.jit, static_argnames=("fmt",))
def _potf2_words(a_p: jax.Array, fmt: PositFormat = P32E2) -> jax.Array:
    """Pre-PR-2 potf2: per-op decode/encode through posit words."""
    n = a_p.shape[0]
    rows = jnp.arange(n)

    def outer(a, j):
        def inner(col, k):
            upd = _sub(col, _mul(a[:, k], a[j, k], fmt), fmt)
            return jnp.where(k < j, upd, col), None

        col, _ = jax.lax.scan(inner, a[:, j], jnp.arange(n))
        ajj = posit.sqrt(col[j], fmt, backend="fast")
        below = _div(col, ajj, fmt)
        newcol = jnp.where(rows > j, below, jnp.where(rows == j, ajj, a[:, j]))
        return a.at[:, j].set(newcol), None

    a, _ = jax.lax.scan(outer, a_p, jnp.arange(n))
    return a


@functools.partial(jax.jit, static_argnames=("nb", "fmt"))
def _getf2_words(a_p: jax.Array, nb: int, fmt: PositFormat = P32E2):
    """Pre-PR-2 getf2: per-op decode/encode, word-pattern pivot compare."""
    m = a_p.shape[0]
    rows = jnp.arange(m)

    def step(a, k):
        col = jnp.where(rows >= k, jnp.abs(a[:, k]), -1)
        piv = jnp.argmax(col).astype(jnp.int32)
        rk, rp = a[k, :], a[piv, :]
        a = a.at[k, :].set(rp).at[piv, :].set(rk)
        scaled = _div(a[:, k], a[k, k], fmt)
        a = a.at[:, k].set(jnp.where(rows > k, scaled, a[:, k]))
        upd = _sub(a, _mul(a[:, k][:, None], a[k, :][None, :], fmt), fmt)
        mask = (rows > k)[:, None] & (jnp.arange(a.shape[1]) > k)[None, :]
        a = jnp.where(mask, upd, a)
        return a, piv

    a, ipiv = jax.lax.scan(step, a_p, jnp.arange(nb))
    return a, ipiv


# --------------------------------------------------------------------------
# blocked drivers — one traced body, three dispatch shapes
# --------------------------------------------------------------------------

def _rpotrf_body(a_p: jax.Array, nb: int, gemm_backend: str,
                 panel=potf2, fmt: PositFormat = P32E2,
                 collect: bool = False):
    """Right-looking blocked Cholesky; block schedule unrolled at trace.

    ``collect=True`` (the obs-variant program, a SEPARATE jit cache entry
    — see ``rpotrf``) additionally returns a per-block-step telemetry
    list: golden-zone occupancy / regime stats of each factored panel and
    trailing update (repro.obs.numerics.step_stats)."""
    n = a_p.shape[0]
    a = jnp.asarray(a_p, jnp.int32)
    tel = []
    for j in range(0, n, nb):
        w = min(nb, n - j)
        with jax.named_scope(_scopes.PANEL):
            l11 = panel(a[j:j + w, j:j + w], fmt=fmt)
            a = a.at[j:j + w, j:j + w].set(l11)
        step = {"panel": _obs_numerics.step_stats(l11, fmt)} if collect \
            else None
        if j + w < n:
            with jax.named_scope(_scopes.TRSM):
                a21 = rtrsm_right_lowerT(a[j + w:, j:j + w], l11, fmt=fmt)
                a = a.at[j + w:, j:j + w].set(a21)
            with jax.named_scope(_scopes.UPDATE):
                upd = rgemm(a21, a21, a[j + w:, j + w:], alpha=-1.0,
                            beta=1.0, trans_b=True, backend=gemm_backend,
                            fmt=fmt)
                a = a.at[j + w:, j + w:].set(upd)
            if collect:
                step["update"] = _obs_numerics.step_stats(upd, fmt)
        if collect:
            tel.append(step)
    # zero strict upper triangle (posit word 0 == value 0)
    tri = jnp.tril(jnp.ones((n, n), bool))
    out = jnp.where(tri, a, 0)
    return (out, tel) if collect else out


def _rgetrf_body(a_p: jax.Array, nb: int, gemm_backend: str,
                 panel_fn=getf2, fmt: PositFormat = P32E2,
                 collect: bool = False):
    """Right-looking blocked partial-pivot LU; schedule unrolled at trace.
    ``collect=True`` adds the per-step telemetry list (see
    ``_rpotrf_body``)."""
    n = a_p.shape[1]
    m = a_p.shape[0]
    a = jnp.asarray(a_p, jnp.int32)
    ipiv = jnp.zeros((min(m, n),), jnp.int32)
    tel = []
    for j in range(0, min(m, n), nb):
        w = min(nb, min(m, n) - j)
        with jax.named_scope(_scopes.PANEL):
            panel, piv_loc = panel_fn(a[j:, j:j + w], w, fmt=fmt)
        if collect:
            tel.append({"panel": _obs_numerics.step_stats(panel, fmt)})

        def apply_swaps(blk):
            def one(b, kp):
                k, p = kp
                rk, rp = b[k, :], b[p, :]
                return b.at[k, :].set(rp).at[p, :].set(rk), None
            blk, _ = jax.lax.scan(one, blk, (jnp.arange(w), piv_loc))
            return blk

        with jax.named_scope(_scopes.SWAP):
            # apply the panel's row swaps to the rest of the matrix
            left = a[j:, :j]
            right = a[j:, j + w:]
            if j > 0:
                left = apply_swaps(left)
                a = a.at[j:, :j].set(left)
            if j + w < n:
                right = apply_swaps(right)
        with jax.named_scope(_scopes.PANEL):
            a = a.at[j:, j:j + w].set(panel)
            ipiv = ipiv.at[j:j + w].set(piv_loc + j)
        if j + w < n:
            with jax.named_scope(_scopes.TRSM):
                u12 = rtrsm_left_lower(panel[:w, :], right[:w, :],
                                       unit_diag=True, fmt=fmt)
                a = a.at[j:j + w, j + w:].set(u12)
            if j + w < m:
                with jax.named_scope(_scopes.UPDATE):
                    l21 = panel[w:, :]
                    upd = rgemm(l21, u12, right[w:, :], alpha=-1.0,
                                beta=1.0, backend=gemm_backend, fmt=fmt)
                    a = a.at[j + w:, j + w:].set(upd)
                if collect:
                    tel[-1]["update"] = _obs_numerics.step_stats(upd, fmt)
    return (a, ipiv, tel) if collect else (a, ipiv)


@functools.partial(jax.jit, static_argnames=("nb", "gemm_backend", "fmt"))
def _rpotrf_jit(a_p: jax.Array, nb: int = 64,
                gemm_backend: str = "xla_quire",
                fmt: PositFormat = P32E2) -> jax.Array:
    return _rpotrf_body(a_p, nb, gemm_backend, fmt=fmt)


@functools.partial(jax.jit, static_argnames=("nb", "gemm_backend", "fmt"))
def _rgetrf_jit(a_p: jax.Array, nb: int = 64,
                gemm_backend: str = "xla_quire",
                fmt: PositFormat = P32E2):
    return _rgetrf_body(a_p, nb, gemm_backend, fmt=fmt)


@functools.partial(jax.jit, static_argnames=("nb", "gemm_backend", "fmt"))
def _rpotrf_collect(a_p: jax.Array, nb: int, gemm_backend: str,
                    fmt: PositFormat):
    return _rpotrf_body(a_p, nb, gemm_backend, fmt=fmt, collect=True)


@functools.partial(jax.jit, static_argnames=("nb", "gemm_backend", "fmt"))
def _rgetrf_collect(a_p: jax.Array, nb: int, gemm_backend: str,
                    fmt: PositFormat):
    return _rgetrf_body(a_p, nb, gemm_backend, fmt=fmt, collect=True)


def rpotrf(a_p: jax.Array, nb: int = 64, gemm_backend: str = "xla_quire",
           fmt: PositFormat = P32E2) -> jax.Array:
    """Blocked lower Cholesky, ONE XLA dispatch; returns L (lower).

    With an ``obs.scoped()`` collector open (and a concrete ``a_p``),
    runs the collect-variant program instead — same factorization ops
    plus per-block-step golden-zone/regime telemetry (bit-identical L,
    separate jit cache entry); otherwise dispatches the exact program
    this function has always been.  Either way the call runs under the
    host span ``posit.rpotrf`` (repro.obs.scopes).
    """
    with _obs_trace.span("posit.rpotrf", n=int(a_p.shape[0]), nb=nb,
                         backend=gemm_backend, fmt=fmt.name):
        if not _obs_numerics.active(a_p):
            return _rpotrf_jit(a_p, nb=nb, gemm_backend=gemm_backend,
                               fmt=fmt)
        out, tel = _rpotrf_collect(a_p, nb=nb, gemm_backend=gemm_backend,
                                   fmt=fmt)
    _obs_numerics.emit_factor_steps("rpotrf", tel)
    return out


def rank1_work(m: int, n: int, nb: int) -> dict:
    """Element-steps of the rank-1 scans of ``rgetrf`` on an (m, n)
    matrix at block nb: {"panel": (trimmed, masked), "trsm": (trimmed,
    masked)}.  An element-step is one element's update computed in one
    scan step; the masked scans computed every element of the block at
    every step, the trimmed ones (``getf2``, ``rtrsm_left_lower``) the
    tiles of ``blas.trimmed_steps``."""
    work = {"panel": [0, 0], "trsm": [0, 0]}
    mn = min(m, n)
    for j in range(0, mn, nb):
        w = min(nb, mn - j)
        for part, width, tile in (("panel", m - j, PANEL_TILE),
                                  ("trsm", n - j - w, TRSM_TILE)):
            work[part][0] += trimmed_steps(w, tile) * width
            work[part][1] += w * w * width
    return {k: tuple(v) for k, v in work.items()}


def rgetrf(a_p: jax.Array, nb: int = 64, gemm_backend: str = "xla_quire",
           fmt: PositFormat = P32E2):
    """Blocked partial-pivot LU, ONE XLA dispatch; returns (LU, ipiv).
    Observability contract as in ``rpotrf``; host span
    ``posit.rgetrf``.  With a collector open it also counts the rank-1
    scans' element-steps (``rank1_work``) on the host:
    ``posit.panel.work``/``posit.panel.work_masked`` and
    ``posit.trsm.work``/``posit.trsm.work_masked``."""
    with _obs_trace.span("posit.rgetrf", m=int(a_p.shape[0]),
                         n=int(a_p.shape[1]), nb=nb, backend=gemm_backend,
                         fmt=fmt.name):
        if not _obs_numerics.active(a_p):
            return _rgetrf_jit(a_p, nb=nb, gemm_backend=gemm_backend,
                               fmt=fmt)
        for part, (work, masked) in rank1_work(*a_p.shape, nb).items():
            _obs_metrics.inc(f"posit.{part}.work", work)
            _obs_metrics.inc(f"posit.{part}.work_masked", masked)
        lu, ipiv, tel = _rgetrf_collect(a_p, nb=nb,
                                        gemm_backend=gemm_backend, fmt=fmt)
    _obs_numerics.emit_factor_steps("rgetrf", tel)
    return lu, ipiv


def rpotrf_loop(a_p: jax.Array, nb: int = 64,
                gemm_backend: str = "xla_quire",
                fmt: PositFormat = P32E2) -> jax.Array:
    """The pre-PR-2 dispatch shape: dispatch-per-block Python driver over
    the word-domain panels.  The trsm sweeps are the shared (chain-form)
    implementations — the original word-domain trsm was not kept — so
    this baseline is slightly FASTER than the true pre-PR-2 code and the
    benchmark's reported speedups are conservative.  Bit-identical to
    ``rpotrf`` (no schedule change alters rounding); the measured
    baseline in benchmarks/bench_decomp.py."""
    return _rpotrf_body(a_p, nb, gemm_backend, panel=_potf2_words, fmt=fmt)


def rgetrf_loop(a_p: jax.Array, nb: int = 64,
                gemm_backend: str = "xla_quire",
                fmt: PositFormat = P32E2):
    """Pre-PR-2 dispatch-per-block driver (bit-identical to ``rgetrf``;
    same conservative-baseline caveat as ``rpotrf_loop``)."""
    return _rgetrf_body(a_p, nb, gemm_backend, panel_fn=_getf2_words, fmt=fmt)


@functools.partial(jax.jit, static_argnames=("nb", "gemm_backend", "fmt"))
def rpotrf_batched(a_p: jax.Array, nb: int = 64,
                   gemm_backend: str = "xla_quire",
                   fmt: PositFormat = P32E2) -> jax.Array:
    """vmapped ``rpotrf`` over a leading (batch, n, n) axis — the §5.1
    ensemble / multi-scenario serving shape as one batched program."""
    fn = functools.partial(_rpotrf_body, nb=nb, gemm_backend=gemm_backend,
                           fmt=fmt)
    return jax.vmap(fn)(jnp.asarray(a_p, jnp.int32))


@functools.partial(jax.jit, static_argnames=("nb", "gemm_backend", "fmt"))
def rgetrf_batched(a_p: jax.Array, nb: int = 64,
                   gemm_backend: str = "xla_quire",
                   fmt: PositFormat = P32E2):
    """vmapped ``rgetrf`` over a leading (batch, m, n) axis; returns
    (LU (batch, m, n), ipiv (batch, min(m, n)))."""
    fn = functools.partial(_rgetrf_body, nb=nb, gemm_backend=gemm_backend,
                           fmt=fmt)
    return jax.vmap(fn)(jnp.asarray(a_p, jnp.int32))


# --------------------------------------------------------------------------
# binary32 baselines
# --------------------------------------------------------------------------

def spotrf(a32: jax.Array) -> jax.Array:
    return jax.scipy.linalg.cholesky(a32.astype(jnp.float32), lower=True)


def sgetrf(a32: jax.Array):
    lu, piv = jax.scipy.linalg.lu_factor(a32.astype(jnp.float32))
    return lu, piv


# --------------------------------------------------------------------------
# checksum-protected drivers (exact ABFT, repro.ft — DESIGN.md §11)
# --------------------------------------------------------------------------
#
# The _ft drivers re-state the SAME per-block-step ops as
# _rpotrf_body/_rgetrf_body — duplicated, not refactored, so the frozen
# _rpotrf_jit/_rgetrf_jit programs (and their lowered HLO) are untouched
# — but host-stepped: each block step is one jitted dispatch that ends
# with full-matrix checksum production, the fault-injection window, and
# verification.  A mismatch means some stored word changed between this
# step's production and its verification; the host retries the step from
# its verified predecessor state (the arrays are functional values, so
# recomputation fully repairs any corruption), bounded by max_retries.
# Fault-free, the words are bit-identical to the unprotected drivers:
# same ops, same order, same backends, and the checksum legs only read.

def _ft():
    # deferred import: keeps repro.lapack importable without pulling the
    # ft package into modules that never use protection
    from repro import ft as _pkg
    return _pkg


@functools.partial(jax.jit, static_argnames=("j", "nb", "gemm_backend",
                                             "fmt"))
def _rpotrf_ft_step(a, *, j, nb, gemm_backend, fmt):
    """One rpotrf block step (the _rpotrf_body per-j ops) + checksum
    production, one dispatch.  The injection window and verify leg run
    on the host so the compiled step is fault-plan-independent."""
    from repro.ft import abft
    n = a.shape[0]
    w = min(nb, n - j)
    l11 = potf2(a[j:j + w, j:j + w], fmt=fmt)
    a = a.at[j:j + w, j:j + w].set(l11)
    if j + w < n:
        a21 = rtrsm_right_lowerT(a[j + w:, j:j + w], l11, fmt=fmt)
        a = a.at[j + w:, j:j + w].set(a21)
        upd = rgemm(a21, a21, a[j + w:, j + w:], alpha=-1.0, beta=1.0,
                    trans_b=True, backend=gemm_backend, fmt=fmt)
        a = a.at[j + w:, j + w:].set(upd)
    return a, abft.checksum(a, fmt)


def rpotrf_ft(a_p: jax.Array, nb: int = 64, gemm_backend: str = "xla_quire",
              fmt: PositFormat = P32E2, plan=None, max_retries: int = 2):
    """Checksum-protected blocked Cholesky: returns (L, FtReport).

    Detection is total and threshold-free (exact quire-limb checksums —
    see repro.ft.abft); a corrupted step recomputes from its verified
    predecessor, so the recovered L is bit-identical to the fault-free
    ``rpotrf``.  Exhausting ``max_retries`` on one step raises
    ``AbftError``.  Injection site: ``"rpotrf.step"`` (step = j // nb),
    applied on the first attempt only (transient-fault model)."""
    ft = _ft()
    n = a_p.shape[0]
    a = jnp.asarray(a_p, jnp.int32)
    report = ft.FtReport()
    for j in range(0, n, nb):
        a_prev = a
        for attempt in range(max_retries + 1):
            a, cks = _rpotrf_ft_step(a_prev, j=j, nb=nb,
                                     gemm_backend=gemm_backend, fmt=fmt)
            if attempt == 0 and plan is not None:
                a = plan.words("rpotrf.step", j // nb, a, fmt)
            ok, bad_row, bad_col = ft.abft._verify_jit(a, cks, fmt=fmt)
            if bool(ok):
                report.retries += attempt
                break
            report.detections += 1
            report.sites.append(("rpotrf.step", j // nb,
                                 ft.locate(bad_row, bad_col, nb)))
            _obs_metrics.inc("ft.detections")
            _obs_metrics.inc("ft.retries")
        else:
            report.failed = True
            raise ft.abft.AbftError(
                f"rpotrf_ft: step {j // nb} mismatch persisted across "
                f"{max_retries + 1} attempts at {report.sites}")
    tri = jnp.tril(jnp.ones((n, n), bool))
    return jnp.where(tri, a, 0), report


@functools.partial(jax.jit, static_argnames=("j", "nb", "gemm_backend",
                                             "fmt"))
def _rgetrf_ft_step(a, ipiv, *, j, nb, gemm_backend, fmt):
    """One rgetrf block step (the _rgetrf_body per-j ops) + checksum
    production (fault-plan-independent program; injection and verify run
    on the host, see _rpotrf_ft_step)."""
    from repro.ft import abft
    m, n = a.shape
    w = min(nb, min(m, n) - j)
    panel, piv_loc = getf2(a[j:, j:j + w], w, fmt=fmt)
    left = a[j:, :j]
    right = a[j:, j + w:]

    def apply_swaps(blk):
        def one(b, kp):
            k, p = kp
            rk, rp = b[k, :], b[p, :]
            return b.at[k, :].set(rp).at[p, :].set(rk), None
        blk, _ = jax.lax.scan(one, blk, (jnp.arange(w), piv_loc))
        return blk

    if j > 0:
        left = apply_swaps(left)
        a = a.at[j:, :j].set(left)
    if j + w < n:
        right = apply_swaps(right)
    a = a.at[j:, j:j + w].set(panel)
    ipiv = ipiv.at[j:j + w].set(piv_loc + j)
    if j + w < n:
        u12 = rtrsm_left_lower(panel[:w, :], right[:w, :], unit_diag=True,
                               fmt=fmt)
        a = a.at[j:j + w, j + w:].set(u12)
        if j + w < m:
            l21 = panel[w:, :]
            upd = rgemm(l21, u12, right[w:, :], alpha=-1.0, beta=1.0,
                        backend=gemm_backend, fmt=fmt)
            a = a.at[j + w:, j + w:].set(upd)
    return a, ipiv, abft.checksum(a, fmt)


def rgetrf_ft(a_p: jax.Array, nb: int = 64, gemm_backend: str = "xla_quire",
              fmt: PositFormat = P32E2, plan=None, max_retries: int = 2):
    """Checksum-protected blocked partial-pivot LU: returns
    (LU, ipiv, FtReport) — (LU, ipiv) bit-identical to ``rgetrf`` both
    fault-free and after recovery.  Contract and injection model as in
    ``rpotrf_ft``; site ``"rgetrf.step"``."""
    ft = _ft()
    m, n = a_p.shape
    a = jnp.asarray(a_p, jnp.int32)
    ipiv = jnp.zeros((min(m, n),), jnp.int32)
    report = ft.FtReport()
    for j in range(0, min(m, n), nb):
        a_prev, ipiv_prev = a, ipiv
        for attempt in range(max_retries + 1):
            a, ipiv, cks = _rgetrf_ft_step(
                a_prev, ipiv_prev, j=j, nb=nb, gemm_backend=gemm_backend,
                fmt=fmt)
            if attempt == 0 and plan is not None:
                a = plan.words("rgetrf.step", j // nb, a, fmt)
            ok, bad_row, bad_col = ft.abft._verify_jit(a, cks, fmt=fmt)
            if bool(ok):
                report.retries += attempt
                break
            report.detections += 1
            report.sites.append(("rgetrf.step", j // nb,
                                 ft.locate(bad_row, bad_col, nb)))
            _obs_metrics.inc("ft.detections")
            _obs_metrics.inc("ft.retries")
        else:
            report.failed = True
            raise ft.abft.AbftError(
                f"rgetrf_ft: step {j // nb} mismatch persisted across "
                f"{max_retries + 1} attempts at {report.sites}")
    return a, ipiv, report
