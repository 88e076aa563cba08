"""Rgeqrf / Rormqr / Rorgqr / Rgels — blocked Householder QR and
quire-exact least-squares solvers in posit arithmetic (MPLAPACK naming).

The over-determined-system scenario on top of the existing stack: the
paper evaluates Posit(32,2) on Cholesky/LU (§5); least squares is the
dense workload where the golden-zone accuracy story matters most, since
forming the normal equations squares the backward error.  Householder QR
avoids A^T A entirely, and the quire turns the remaining error sources
(triangular solves, residuals) into single-rounding fused ops.

Algorithms (right-looking LAPACK, compact-WY):

* ``geqr2``  — unblocked panel (dgeqr2/dlarfg op order): every scalar op
  is a rounded posit op in fused-chain form (decode once, ``chain_round``
  each op, encode once — bit-identical to per-op word arithmetic).
* ``larft``  — forward columnwise T factor of the block reflector
  H_0 ... H_{w-1} = I - V T V^T, rounded-chain in dlarft's op order.
* ``rgeqrf`` — blocked driver: panel + three ``ops.rgemm`` calls per
  block (larfb: W = V^T C; W = T^T W; C -= V W) — the same offload split
  as ``rpotrf``/``rgetrf``, so the trailing-update flops ride whichever
  accelerator backend ``gemm_backend`` selects (quire_exact, xla_quire,
  the fused-encode Pallas kernel, faithful).  The block schedule is
  static at trace time: ``rgeqrf`` is ONE jitted XLA dispatch;
  ``rgeqrf_loop`` keeps the dispatch-per-block Python driver as the
  bit-identical measured baseline (benchmarks/bench_qr.py), and
  ``rgeqrf_batched`` vmaps the same program over a leading matrix axis.
* ``rormqr`` / ``rorgqr`` — apply Q/Q^T from the stored reflectors /
  materialize Q explicitly.  They rebuild V and T from the factored
  words, and chain values round-trip the word encode exactly, so the
  T each block applies is bit-identical to the one ``rgeqrf`` used.
* ``rgels``  — over-determined solve (m >= n): x = R^{-1} (Q^T b)[:n].
* ``rgels_ir`` / ``rgels_mp`` — quire-exact iterative refinement of the
  least-squares solution through ``refine.refine_pair``'s
  ``solve_fn``/``residual_fn`` extension points.  The residual
  r = b - A(x_hi + x_lo) is exact per component (one rounding); the
  correction solves min ||A d - r|| by the semi-normal equations
  R^T R d = A^T r with a quire-exact A^T r (``quire_gemv``) and
  quire-backed triangular sweeps — refinement makes semi-normal
  equations backward-stable (Björck's CSNE), and the PR-4 power-of-two
  equilibrations (matrix before factorization, residual per sweep) make
  the contraction sigma-invariant.  ``rgels_mp`` factorizes in a cheap
  narrow format (default Posit(16,1)) and refines with working-format
  quire residuals — the HPL-AI trade on the LS scenario.  See
  DESIGN.md §9.

All matrices are int32 posit words of the static format ``fmt``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import posit
from repro.core.formats import P16E1, P32E2, PositFormat
from repro.kernels.ops import rgemm
from repro.lapack import refine
from repro.lapack import solve
from repro.lapack.blas import rlarfg_chain, rtrsm_left_upper
from repro.obs import metrics as _obs_metrics
from repro.obs import numerics as _obs_numerics
from repro.obs import trace as _obs_trace
from repro.quire import quire_gemv


# --------------------------------------------------------------------------
# unblocked panel (all-posit, fused-chain form)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("fmt",))
def geqr2(a_p: jax.Array, fmt: PositFormat = P32E2):
    """Unblocked Householder QR of an (m, w) posit panel, dgeqr2 op order.

    Returns (panel, tau): R on/above the diagonal, the reflectors' tails
    below it (v_k = 1 implicit), and the (w,) tau posit words.  Fused-
    chain execution: the panel decodes once, every scalar op is
    posit-rounded in place, words are packed once at exit.
    """
    m, w = a_p.shape
    rows = jnp.arange(m)
    cols = jnp.arange(w)
    a0 = posit.chain_decode(a_p, fmt)

    def step(carry, k):
        a, taus = carry
        newcol, v, tau = rlarfg_chain(a[:, k], k, fmt)
        # apply H = I - tau v v^T to the remaining columns (> k):
        # wvec = v^T A (row-ascending chained adds; v_k = 1 contributes
        # A[k, :] exactly), then A -= v (tau * wvec)  (rank-1, rounded)
        def accw(s, i):
            upd = posit.chain_add(s, posit.chain_mul(v[i], a[i, :], fmt),
                                  fmt)
            return jnp.where(i > k, upd, s), None

        wvec, _ = jax.lax.scan(accw, a[k, :], rows)
        t = posit.chain_mul(tau, wvec, fmt)
        upd = posit.chain_sub(a, posit.chain_mul(v[:, None], t[None, :],
                                                 fmt), fmt)
        mask = (rows >= k)[:, None] & (cols > k)[None, :]
        a = jnp.where(mask, upd, a)
        a = a.at[:, k].set(newcol)
        return (a, taus.at[k].set(tau)), None

    (a, taus), _ = jax.lax.scan(step, (a0, jnp.zeros((w,), jnp.int64)),
                                cols)
    return posit.chain_encode(a, fmt), posit.chain_encode(taus, fmt)


@functools.partial(jax.jit, static_argnames=("fmt",))
def larft(v_p: jax.Array, tau_p: jax.Array,
          fmt: PositFormat = P32E2) -> jax.Array:
    """Forward columnwise T of the block reflector (dlarft):
    H_0 ... H_{w-1} = I - V T V^T with T (w, w) upper-triangular.

    Rounded-chain evaluation in dlarft's op order: G = V^T V column dots
    (row-ascending chained adds; the unit-trapezoid zeros contribute
    exactly nothing), then per column j: T[:j, j] = T[:j, :j] @
    (-tau_j G[:j, j]) (chained trmv), T[j, j] = tau_j.
    """
    m, w = v_p.shape
    v = posit.chain_decode(v_p, fmt)
    tau = posit.chain_decode(tau_p, fmt)
    cols = jnp.arange(w)

    def accg(g, r):
        g = posit.chain_add(g, posit.chain_mul(v[r, :][:, None],
                                               v[r, :][None, :], fmt), fmt)
        return g, None

    g, _ = jax.lax.scan(accg, jnp.zeros((w, w), jnp.int64), jnp.arange(m))

    def tcol(t, j):
        h = posit.chain_mul(posit.chain_neg(tau[j]), g[:, j], fmt)

        def acct(s, el):
            upd = posit.chain_add(s, posit.chain_mul(t[:, el], h[el], fmt),
                                  fmt)
            return jnp.where(el < j, upd, s), None

        h2, _ = jax.lax.scan(acct, jnp.zeros((w,), jnp.int64), cols)
        newcol = jnp.where(cols < j, h2, jnp.where(cols == j, tau[j], 0))
        return t.at[:, j].set(newcol), None

    t, _ = jax.lax.scan(tcol, jnp.zeros((w, w), jnp.int64), cols)
    return posit.chain_encode(t, fmt)


def _v_words(panel_p: jax.Array, fmt: PositFormat) -> jax.Array:
    """Unit-lower-trapezoid reflector words V from a factored panel: the
    below-diagonal tails, an exact 1 on the diagonal, exact 0 above."""
    mj, w = panel_p.shape
    rows = jnp.arange(mj)[:, None]
    cols = jnp.arange(w)[None, :]
    one = posit.word_of(1.0, fmt)
    return jnp.where(rows > cols, panel_p,
                     jnp.where(rows == cols, one, 0))


def _r_words(qr_p: jax.Array, n: int) -> jax.Array:
    """Upper-triangular R words from a factored matrix (reflector tails
    below the diagonal zeroed; posit word 0 == value 0)."""
    tri = jnp.triu(jnp.ones((n, n), bool))
    return jnp.where(tri, qr_p[:n, :n], 0)


def _apply_block(c_p: jax.Array, v_w: jax.Array, t_w: jax.Array,
                 trans: bool, gemm_backend: str,
                 fmt: PositFormat) -> jax.Array:
    """larfb: C <- (I - V T V^T) C  (or the transpose, trans=True) as
    three Rgemm calls on the selected accelerator backend."""
    w1 = rgemm(v_w, c_p, trans_a=True, backend=gemm_backend, fmt=fmt)
    w2 = rgemm(t_w, w1, trans_a=trans, backend=gemm_backend, fmt=fmt)
    return rgemm(v_w, w2, c_p, alpha=-1.0, beta=1.0, backend=gemm_backend,
                 fmt=fmt)


# --------------------------------------------------------------------------
# blocked drivers — one traced body, three dispatch shapes (decomp.py idiom)
# --------------------------------------------------------------------------

def _rgeqrf_body(a_p: jax.Array, nb: int, gemm_backend: str,
                 fmt: PositFormat = P32E2, collect: bool = False):
    """Right-looking blocked Householder QR; schedule unrolled at trace.
    ``collect=True`` (the obs-variant program, see ``rgeqrf``) adds the
    per-block-step telemetry list (decomp.py convention)."""
    m, n = a_p.shape
    kk = min(m, n)
    a = jnp.asarray(a_p, jnp.int32)
    taus = jnp.zeros((kk,), jnp.int32)
    tel = []
    for j in range(0, kk, nb):
        w = min(nb, kk - j)
        panel, tau = geqr2(a[j:, j:j + w], fmt=fmt)
        a = a.at[j:, j:j + w].set(panel)
        taus = taus.at[j:j + w].set(tau)
        if collect:
            tel.append({"panel": _obs_numerics.step_stats(panel, fmt)})
        if j + w < n:
            v_w = _v_words(panel, fmt)
            t_w = larft(v_w, tau, fmt=fmt)
            c2 = _apply_block(a[j:, j + w:], v_w, t_w, True, gemm_backend,
                              fmt)
            a = a.at[j:, j + w:].set(c2)
            if collect:
                tel[-1]["update"] = _obs_numerics.step_stats(c2, fmt)
    return (a, taus, tel) if collect else (a, taus)


def _rormqr_body(a_qr: jax.Array, tau_p: jax.Array, c_p: jax.Array,
                 trans: bool, nb: int, gemm_backend: str,
                 fmt: PositFormat = P32E2):
    """Apply Q (trans=False) or Q^T (trans=True) from the left.

    Q = B_0 B_1 ... B_L with B_j = I - V_j T_j V_j^T, so Q^T C applies
    the transposed blocks in forward order and Q C the blocks in reverse
    (dormqr).  V and T are rebuilt from the stored words — chain values
    round-trip the encode exactly, so each block's T is bit-identical to
    the one the factorization used.
    """
    kk = tau_p.shape[0]
    c = jnp.asarray(c_p, jnp.int32)
    vec = c.ndim == 1
    if vec:
        c = c[:, None]
    starts = list(range(0, kk, nb))
    if not trans:
        starts = starts[::-1]
    for j in starts:
        w = min(nb, kk - j)
        panel = a_qr[j:, j:j + w]
        v_w = _v_words(panel, fmt)
        t_w = larft(v_w, tau_p[j:j + w], fmt=fmt)
        c2 = _apply_block(c[j:, :], v_w, t_w, trans, gemm_backend, fmt)
        c = c.at[j:, :].set(c2)
    return c[:, 0] if vec else c


@functools.partial(jax.jit, static_argnames=("nb", "gemm_backend", "fmt"))
def _rgeqrf_jit(a_p: jax.Array, nb: int = 32,
                gemm_backend: str = "xla_quire",
                fmt: PositFormat = P32E2):
    return _rgeqrf_body(a_p, nb, gemm_backend, fmt=fmt)


@functools.partial(jax.jit, static_argnames=("nb", "gemm_backend", "fmt"))
def _rgeqrf_collect(a_p: jax.Array, nb: int, gemm_backend: str,
                    fmt: PositFormat):
    return _rgeqrf_body(a_p, nb, gemm_backend, fmt=fmt, collect=True)


def rgeqrf(a_p: jax.Array, nb: int = 32, gemm_backend: str = "xla_quire",
           fmt: PositFormat = P32E2):
    """Blocked Householder QR, ONE XLA dispatch; returns (QR, tau) with R
    on/above the diagonal and the reflector tails below it.

    With an ``obs.scoped()`` collector open and a concrete ``a_p``, the
    collect-variant program runs instead (bit-identical factors plus
    per-block-step golden-zone/regime telemetry — decomp.py contract);
    disabled or traced calls dispatch the unchanged jitted program.
    """
    if _obs_numerics.active(a_p):
        with _obs_trace.span("rgeqrf", m=int(a_p.shape[0]),
                             n=int(a_p.shape[1]), nb=nb,
                             backend=gemm_backend, fmt=fmt.name):
            qr_p, tau, tel = _rgeqrf_collect(a_p, nb=nb,
                                             gemm_backend=gemm_backend,
                                             fmt=fmt)
        _obs_numerics.emit_factor_steps("rgeqrf", tel)
        return qr_p, tau
    return _rgeqrf_jit(a_p, nb=nb, gemm_backend=gemm_backend, fmt=fmt)


def rgeqrf_loop(a_p: jax.Array, nb: int = 32,
                gemm_backend: str = "xla_quire",
                fmt: PositFormat = P32E2):
    """Dispatch-per-block Python driver over the same traced blocks —
    bit-identical to ``rgeqrf`` (the schedule changes no rounding); the
    measured baseline in benchmarks/bench_qr.py."""
    return _rgeqrf_body(jnp.asarray(a_p, jnp.int32), nb, gemm_backend,
                        fmt=fmt)


@functools.partial(jax.jit, static_argnames=("nb", "gemm_backend", "fmt"))
def rgeqrf_batched(a_p: jax.Array, nb: int = 32,
                   gemm_backend: str = "xla_quire",
                   fmt: PositFormat = P32E2):
    """vmapped ``rgeqrf`` over a leading (batch, m, n) axis; returns
    (QR (batch, m, n), tau (batch, min(m, n)))."""
    fn = functools.partial(_rgeqrf_body, nb=nb, gemm_backend=gemm_backend,
                           fmt=fmt)
    return jax.vmap(fn)(jnp.asarray(a_p, jnp.int32))


@functools.partial(jax.jit, static_argnames=("trans", "nb", "gemm_backend",
                                             "fmt"))
def rormqr(a_qr: jax.Array, tau_p: jax.Array, c_p: jax.Array,
           trans: bool = False, nb: int = 32,
           gemm_backend: str = "xla_quire",
           fmt: PositFormat = P32E2) -> jax.Array:
    """C <- Q C (trans=False) or Q^T C (trans=True); C may be (m,) or
    (m, nc)."""
    return _rormqr_body(jnp.asarray(a_qr, jnp.int32),
                        jnp.asarray(tau_p, jnp.int32), c_p, trans, nb,
                        gemm_backend, fmt)


@functools.partial(jax.jit, static_argnames=("ncols", "nb", "gemm_backend",
                                             "fmt"))
def rorgqr(a_qr: jax.Array, tau_p: jax.Array, ncols: int | None = None,
           nb: int = 32, gemm_backend: str = "xla_quire",
           fmt: PositFormat = P32E2) -> jax.Array:
    """Materialize the first ``ncols`` (default: all k) columns of Q by
    applying the stored reflectors to the identity (exact posit words)."""
    m = a_qr.shape[0]
    nc = tau_p.shape[0] if ncols is None else ncols
    eye = posit.from_float64(jnp.eye(m, nc, dtype=jnp.float64), fmt)
    return _rormqr_body(jnp.asarray(a_qr, jnp.int32),
                        jnp.asarray(tau_p, jnp.int32), eye, False, nb,
                        gemm_backend, fmt)


# --------------------------------------------------------------------------
# least squares
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("nb", "gemm_backend", "fmt"))
def rgels(a_p: jax.Array, b_p: jax.Array, nb: int = 32,
          gemm_backend: str = "xla_quire", fmt: PositFormat = P32E2):
    """Over-determined least-squares solve min ||A x - b||_2 (m >= n) via
    Householder QR: x = R^{-1} (Q^T b)[:n].

    b may be (m,) or (m, nrhs).  Returns (x, (qr, tau)) — reuse the
    factors with ``rormqr`` / ``rgels_ir``'s machinery for more RHS.
    """
    a_p = jnp.asarray(a_p, jnp.int32)
    b_p = jnp.asarray(b_p, jnp.int32)
    m, n = a_p.shape
    assert m >= n, f"rgels requires m >= n, got {a_p.shape}"
    qr_p, tau = _rgeqrf_body(a_p, nb, gemm_backend, fmt=fmt)
    c = _rormqr_body(qr_p, tau, b_p, True, nb, gemm_backend, fmt)
    r_w = _r_words(qr_p, n)
    if b_p.ndim == 1:
        x = rtrsm_left_upper(r_w, c[:n, None], unit_diag=False,
                             fmt=fmt)[:, 0]
    else:
        x = rtrsm_left_upper(r_w, c[:n, :], unit_diag=False, fmt=fmt)
    return x, (qr_p, tau)


def _snes_solve_fn(a_eq_t: jax.Array, r_w: jax.Array, inv_scale,
                   solve_fmt: PositFormat, fmt: PositFormat):
    """Correction solve for LS refinement: d = argmin ||A d - f|| by the
    semi-normal equations R^T R d = A^T f, all quire-backed:

        f_s = f / t              (power-of-two residual equilibration —
                                  exact in the f64 carrier, keeps every
                                  sweep's shrinking residual in the
                                  format's golden zone, PR-4 trick)
        w   = quire_gemv(A_eq^T, f_s)      (exact fused dot, ONE rounding)
        y   = R^T y = w;  d = R d = y      (quire-backed sweeps)
        d  <- d * t * inv_scale            (undo both equilibrations)

    ``solve_fmt`` is the factor format (== ``fmt`` for ``rgels_ir``, the
    narrow format for ``rgels_mp``); ``inv_scale`` folds the matrix
    equilibration A = s * A_eq back in (d_A = d_eq / s).
    """
    def solve_fn(f):
        fv = posit.to_float64(f, fmt)
        t = refine.pow2_scale(fv)
        f_s = posit.from_float64(fv / t, solve_fmt)
        w = quire_gemv(a_eq_t, f_s, fmt=solve_fmt)
        y = solve.rtrtrs(r_w.T, w, lower=True, quire=True, fmt=solve_fmt)
        d = solve.rtrtrs(r_w, y, lower=False, quire=True, fmt=solve_fmt)
        dv = posit.to_float64(d, solve_fmt)
        return posit.from_float64(dv * (t * inv_scale), fmt)
    return solve_fn


def _ls_driver(a_p, b_p, solve_fn, iters, fmt: PositFormat):
    """refine._driver with a rectangular residual: r = b - A (hi + lo) is
    the quire-exact LS residual (per-component fused dot, one rounding)."""
    b_p = jnp.asarray(b_p, jnp.int32)
    residual_fn = lambda hi, lo, b: refine.residual_quire(a_p, hi, b, lo,
                                                          fmt=fmt)
    one = functools.partial(refine.refine_pair, solve_fn, residual_fn,
                            iters=iters, fmt=fmt)
    if b_p.ndim == 1:
        return one(b_p)
    return jax.vmap(one, in_axes=1, out_axes=1)(b_p)


def rgels_ir(a_p: jax.Array, b_p: jax.Array, iters: int = 3, nb: int = 32,
             gemm_backend: str = "xla_quire", fmt: PositFormat = P32E2):
    """QR least squares with quire-exact iterative refinement (corrected
    semi-normal equations, Björck): factorize the power-of-two
    equilibrated A once, then Wilkinson-refine the posit-pair iterate
    with exact residuals b - A(hi+lo) and semi-normal correction solves.

    Returns ((x_hi, x_lo), (qr, tau)); the factors are of A / s.  b may
    be (m,) or (m, nrhs) (vmapped over columns); a batched (batch, m, n)
    A vmaps the whole driver.  Backward error lands on the same
    posit-pair floor as ``rgesv_ir`` (digits_lost ~ 0 across the §5.1
    sigma grid — gated in tests and benchmarks/bench_qr.py).
    """
    a_p = jnp.asarray(a_p, jnp.int32)
    if a_p.ndim == 3:
        return jax.vmap(lambda a, b: rgels_ir(a, b, iters, nb, gemm_backend,
                                              fmt)
                        )(a_p, jnp.asarray(b_p, jnp.int32))
    m, n = a_p.shape
    assert m >= n, f"rgels_ir requires m >= n, got {a_p.shape}"
    av = posit.to_float64(a_p, fmt)
    s = refine.pow2_scale(av)
    a_eq = posit.from_float64(av / s, fmt)     # exact: s is a power of two
    qr_p, tau = rgeqrf(a_eq, nb=nb, gemm_backend=gemm_backend, fmt=fmt)
    solve_fn = _snes_solve_fn(a_eq.T, _r_words(qr_p, n), 1.0 / s, fmt, fmt)
    return _ls_driver(a_p, b_p, solve_fn, iters, fmt), (qr_p, tau)


def rgels_mp(a_p: jax.Array, b_p: jax.Array, iters: int = 10, nb: int = 32,
             gemm_backend: str = "xla_quire",
             factor_fmt: PositFormat = P16E1, fmt: PositFormat = P32E2):
    """Mixed-precision LS solve: Householder QR of the equilibrated A in
    ``factor_fmt`` (default Posit(16,1)), then working-format quire-exact
    refinement to the posit-pair floor.

    The narrow factorization's win here is accuracy-per-bit and (on real
    hardware) halved memory traffic; in THIS emulation QR wall-clock is
    panel-dominated and format-independent (~1.0x at dispatch-per-block
    granularity, benchmarks/bench_qr.py — unlike LU's 1.2-1.3x, whose
    trailing updates dominate).

    A, b and the returned pair are ``fmt`` words; the factors (qr, tau)
    are ``factor_fmt`` words of A / s.  Convergence: the semi-normal
    correction squares the condition number, so the contraction is
    rho ~ cond(A)^2 * eps_factor per sweep — fine for the well-
    conditioned rectangular §5.1 ensemble (cond of an (m, n) Gaussian
    ~ (sqrt(m)+sqrt(n))/(sqrt(m)-sqrt(n))), and the reason the default
    sweep count is higher than ``rgesv_mp``'s.  Same conventions
    (multi-RHS, batched A) as ``rgels_ir``.
    """
    a_p = jnp.asarray(a_p, jnp.int32)
    if a_p.ndim == 3:
        return jax.vmap(lambda a, b: rgels_mp(a, b, iters, nb, gemm_backend,
                                              factor_fmt, fmt)
                        )(a_p, jnp.asarray(b_p, jnp.int32))
    m, n = a_p.shape
    assert m >= n, f"rgels_mp requires m >= n, got {a_p.shape}"
    a_lo, s = refine.mp_narrow_matrix(a_p, factor_fmt, fmt)
    qr_p, tau = rgeqrf(a_lo, nb=nb, gemm_backend=gemm_backend,
                       fmt=factor_fmt)
    solve_fn = _snes_solve_fn(a_lo.T, _r_words(qr_p, n), 1.0 / s,
                              factor_fmt, fmt)
    return _ls_driver(a_p, b_p, solve_fn, iters, fmt), (qr_p, tau)


@functools.partial(jax.jit, static_argnames=("nb", "gemm_backend", "fmt"))
def rgels_batched(a_p: jax.Array, b_p: jax.Array, nb: int = 32,
                  gemm_backend: str = "xla_quire",
                  fmt: PositFormat = P32E2):
    """vmapped ``rgels`` over leading (batch, m, n) / (batch, m[, nrhs])
    axes — the §5.1 ensemble / multi-scenario serving shape."""
    fn = functools.partial(rgels, nb=nb, gemm_backend=gemm_backend, fmt=fmt)
    return jax.vmap(fn)(jnp.asarray(a_p, jnp.int32),
                        jnp.asarray(b_p, jnp.int32))


# --------------------------------------------------------------------------
# binary32 baseline (the §5.1 comparison column)
# --------------------------------------------------------------------------

def sgels(a32: jax.Array, b32: jax.Array) -> jax.Array:
    """binary32 least squares via XLA QR — the S-prefixed baseline."""
    q, r = jnp.linalg.qr(a32.astype(jnp.float32))
    return jax.scipy.linalg.solve_triangular(r, q.T @ b32.astype(jnp.float32),
                                             lower=False)


# --------------------------------------------------------------------------
# checksum-protected driver (exact ABFT, repro.ft — DESIGN.md §11)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("j", "nb", "gemm_backend",
                                             "fmt"))
def _rgeqrf_ft_step(a, taus, *, j, nb, gemm_backend, fmt):
    """One rgeqrf block step (the _rgeqrf_body per-j ops) + checksum
    production over both the matrix and the tau vector, one dispatch.
    Injection and verification run on the host, so the compiled step is
    fault-plan-independent (decomp.py _ft convention)."""
    from repro.ft import abft
    m, n = a.shape
    kk = min(m, n)
    w = min(nb, kk - j)
    panel, tau = geqr2(a[j:, j:j + w], fmt=fmt)
    a = a.at[j:, j:j + w].set(panel)
    taus = taus.at[j:j + w].set(tau)
    if j + w < n:
        v_w = _v_words(panel, fmt)
        t_w = larft(v_w, tau, fmt=fmt)
        c2 = _apply_block(a[j:, j + w:], v_w, t_w, True, gemm_backend, fmt)
        a = a.at[j:, j + w:].set(c2)
    return a, taus, abft.checksum(a, fmt), abft.checksum(taus[None, :], fmt)


def rgeqrf_ft(a_p: jax.Array, nb: int = 32, gemm_backend: str = "xla_quire",
              fmt: PositFormat = P32E2, plan=None, max_retries: int = 2):
    """Checksum-protected blocked Householder QR: returns
    (QR, tau, FtReport) — bit-identical to ``rgeqrf`` fault-free and
    after recovery (repro.ft exact-ABFT contract: total threshold-free
    detection, retry from the verified predecessor state, ``AbftError``
    past ``max_retries``).  Injection sites: ``"rgeqrf.step"`` (matrix
    words) and ``"rgeqrf.tau"`` (reflector scalars), step = j // nb,
    first attempt only."""
    from repro import ft
    m, n = a_p.shape
    kk = min(m, n)
    a = jnp.asarray(a_p, jnp.int32)
    taus = jnp.zeros((kk,), jnp.int32)
    report = ft.FtReport()
    for j in range(0, kk, nb):
        a_prev, taus_prev = a, taus
        for attempt in range(max_retries + 1):
            a, taus, cks, cks_t = _rgeqrf_ft_step(
                a_prev, taus_prev, j=j, nb=nb, gemm_backend=gemm_backend,
                fmt=fmt)
            if attempt == 0 and plan is not None:
                a = plan.words("rgeqrf.step", j // nb, a, fmt)
                taus = plan.words("rgeqrf.tau", j // nb, taus, fmt)
            ok_a, bad_row, bad_col = ft.abft._verify_jit(a, cks, fmt=fmt)
            ok_t, _, _ = ft.abft._verify_jit(taus[None, :], cks_t, fmt=fmt)
            ok = ok_a & ok_t
            if bool(ok):
                report.retries += attempt
                break
            report.detections += 1
            report.sites.append(("rgeqrf.step", j // nb,
                                 ft.locate(bad_row, bad_col, nb)))
            _obs_metrics.inc("ft.detections")
            _obs_metrics.inc("ft.retries")
        else:
            report.failed = True
            raise ft.abft.AbftError(
                f"rgeqrf_ft: step {j // nb} mismatch persisted across "
                f"{max_retries + 1} attempts at {report.sites}")
    return a, taus, report
