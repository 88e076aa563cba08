"""Rgesv_ir / Rposv_ir — quire-exact iterative refinement — and
Rgesv_mp / Rposv_mp — mixed-precision IR (factorize cheap, refine exact).

Beyond the paper's accuracy tables: the factorization runs in a working
posit format (Rgetrf/Rpotrf, any rgemm backend), and the refinement loop
recovers the digits the factorization rounds away using the quire:

    x_0 = solve(A ~= LU, b)             (quire-exact substitutions)
    repeat: r_i = b - A x_i             (EXACT fused dot per row, ONE
                                         rounding — repro.quire)
            d_i = solve(LU, r_i)
            x_{i+1} = x_i + d_i         (EXACT compensated update)

The iterate is carried as an unevaluated **posit pair** x = hi + lo (the
double-word analogue of LAPACK dsgesv's f64 carrier, in posit-native
form): a single posit32 x floors the backward error at its own storage
rounding (~2^-28 — measured, see tests/test_quire.py), while the pair
pushes the floor to ~eps^2.  Both the residual b - A*(hi+lo) and the
renormalization (hi', lo') = twosum(hi + lo + d) are EXACT in the quire
— no FastTwoSum branch games, the fixed-point accumulator just holds all
three addends.  Classic Wilkinson refinement then contracts the backward
error 4-6 decimal digits below a plain Rgetrs/Rpotrs solve on the
paper's §5.1 protocol (n=256, phi=0 ensemble; see
benchmarks/paper_tables.py::bench_refinement).

**Mixed precision** (``rgesv_mp``/``rposv_mp``, DESIGN.md §8): the
HPL-AI play on the same loop.  The O(n^3) factorization runs in a cheap
narrow format (default Posit(16,1) — ~1.2-1.3x faster end-to-end rgetrf
at n=512 in this emulation, where only the quire limb count is
format-dependent and the isolated quire update gains ~2x;
benchmarks/bench_formats.py), while the O(n^2) residual stays
quire-exact in the working format (default Posit(32,2)).  Convergence:
each sweep contracts the error by rho ~ cond(A) * eps_factor; with
eps_p16e1 ~ 2^-12 (golden zone) the contraction is ~1.7 decimal digits
per sweep for cond ~ 1e2, so the pair floor is reached in more (default
8) but cheaper iterations than ``rgesv_ir``'s 2-3 — the classic trade.
The correction solve runs entirely in the factor format; only the
residual and the compensated pair update see the working format,
bridged by one correctly-rounded narrowing each way with a power-of-two
equilibration folded in (``mp_narrow_matrix`` / ``_mp_solve_fn`` —
``posit.pconvert`` minus the scale; the narrow r -> r16 rounding is
harmless: the correction only needs the residual's leading digits).
When cond(A) * eps_factor >~ 1 the loop stalls — use ``rgesv_ir``
(full-width factorization) there; the §5.1 sigma grid in
``error_eval.mixed_precision_study`` measures exactly this envelope.

Both drivers accept b of shape (n,) or (n, nrhs); the multi-RHS form is
vmapped over columns — one factorization amortized across many scenario
solves (the serving-shaped use: one model, many right-hand sides).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import posit
from repro.core.formats import P16E1, P32E2, PositFormat
from repro.lapack import decomp, solve
from repro.obs import metrics as _obs_metrics
from repro.obs import numerics as _obs_numerics
from repro.obs import scopes as _scopes
from repro.obs import trace as _obs_trace
from repro.quire import (q_to_posit, qadd_posit, quire_dot, quire_from_posit)


@functools.partial(jax.jit, static_argnames=("fmt",))
def residual_quire(a_p: jax.Array, x_p: jax.Array, b_p: jax.Array,
                   x_lo_p: jax.Array | None = None,
                   fmt: PositFormat = P32E2) -> jax.Array:
    """r = b - A (x + x_lo) with each component an exact fused dot product
    rounded once to posit (the quire residual at the heart of the
    refinement).  ``x_lo_p`` extends x to an unevaluated posit pair.
    Runs under the ``posit.quire_residual`` scope."""
    with jax.named_scope(_scopes.QUIRE_RESIDUAL):
        if x_lo_p is None:
            aa, xx = a_p, x_p
        else:
            aa = jnp.concatenate([a_p, a_p], axis=1)
            xx = jnp.concatenate([x_p, x_lo_p])
        return quire_dot(aa, xx[None, :], fmt, init_p=b_p, negate=True)


@functools.partial(jax.jit, static_argnames=("fmt",))
def pair_to_float64(x_p: jax.Array, x_lo_p: jax.Array,
                    fmt: PositFormat = P32E2) -> jax.Array:
    """Evaluate an unevaluated posit pair in binary64 (|lo| <~ ulp(hi), so
    the f64 sum is exact to f64 precision)."""
    return posit.to_float64(x_p, fmt) + posit.to_float64(x_lo_p, fmt)


def _pair_update(hi, lo, d, fmt: PositFormat):
    """The exact compensated update of the pair: q = hi + lo + d held
    exactly in the quire; hi' = round(q); lo' = round(q - hi') (q - hi'
    is exact).  Returns (hi', lo', q), under the ``posit.pair_update``
    scope."""
    with jax.named_scope(_scopes.PAIR_UPDATE):
        q = quire_from_posit(hi, fmt)
        q = qadd_posit(q, lo, fmt)
        q = qadd_posit(q, d, fmt)
        hi2 = q_to_posit(q, fmt)
        lo2 = q_to_posit(qadd_posit(q, hi2, fmt, negate=True), fmt)
    return hi2, lo2, q


def refine_pair(solve_fn, residual_fn, b_col: jax.Array, iters: int,
                fmt: PositFormat = P32E2):
    """The Wilkinson loop over an abstract solver/residual pair:

        x = solve_fn(b); repeat iters times:
            r = residual_fn(hi, lo, b)      # must be quire-exact
            d = solve_fn(r)
            (hi, lo) = exact twosum(hi + lo + d)

    ``residual_fn(x_hi, x_lo, b) -> r`` is the extension point the
    DISTRIBUTED solvers plug into (repro.dist.pdecomp wires
    ``pblas.p_residual_quire`` here — same exact fused-dot semantics,
    limb-plane psum across the grid); the single-device drivers pass a
    ``residual_quire`` closure.  ``solve_fn`` is the second extension
    point: the MIXED-PRECISION drivers wrap a narrow-format correction
    solve (factor format in, working format out) while the loop's pair
    carrier and quire updates stay in ``fmt``, and the LEAST-SQUARES
    drivers (lapack/qr.py rgels_ir/rgels_mp) plug in a rectangular
    residual b - A(hi+lo) with a semi-normal-equations correction
    solve — the loop itself never assumes the system is square.
    Returns the posit pair (x_hi, x_lo), both in ``fmt``.

    With an ``obs.scoped()`` collector open (and concrete inputs) the
    loop runs as ``_refine_pair_obs`` — the same op sequence unrolled in
    Python so each sweep can be observed: residual norm, digits gained,
    golden-zone occupancy of r, and quire limb-carry counts land in the
    ``ir.sweep`` series.
    """
    if _obs_numerics.active(b_col):
        return _refine_pair_obs(solve_fn, residual_fn, b_col, iters, fmt)
    x_hi = solve_fn(b_col)
    x_lo = jnp.zeros_like(x_hi)

    def body(carry, _):
        hi, lo = carry
        r = residual_fn(hi, lo, b_col)
        d = solve_fn(r)
        hi2, lo2, _ = _pair_update(hi, lo, d, fmt)
        return (hi2, lo2), None

    (x_hi, x_lo), _ = jax.lax.scan(body, (x_hi, x_lo), None, length=iters)
    return x_hi, x_lo


def _refine_pair_obs(solve_fn, residual_fn, b_col: jax.Array, iters: int,
                     fmt: PositFormat = P32E2):
    """Observed Wilkinson loop: the SAME op sequence as ``refine_pair``'s
    scan body, unrolled in Python (scan-vs-unroll is bit-identical — the
    body is pure), with one ``ir.sweep`` series row per iteration:

        {sweep, r_norm, digits_gained, golden_frac, limb_carries}

    ``digits_gained`` is log10(||r_0|| / ||r_i||) — the per-sweep digit
    trajectory ``error_eval.golden_zone_study`` correlates with
    golden-zone occupancy.  ``limb_carries`` counts nonzero carries the
    pair-update quire releases on read-out (repro.obs.numerics).
    """
    x_hi = solve_fn(b_col)
    x_lo = jnp.zeros_like(x_hi)
    r0_norm = None
    for i in range(iters):
        with _obs_trace.span("ir.sweep", sweep=i):
            r = residual_fn(x_hi, x_lo, b_col)
            d = solve_fn(r)
            hi2, lo2, q = _pair_update(x_hi, x_lo, d, fmt)

            r_norm = float(jnp.max(jnp.abs(posit.to_float64(r, fmt))))
            if r0_norm is None:
                r0_norm = r_norm if r_norm > 0 else 1.0
            digits = float(jnp.log10(r0_norm / max(r_norm, 1e-300)))
            st = _obs_numerics.step_stats(r, fmt)
            carries = _obs_numerics.quire_carry_stats(q.limbs)
            _obs_metrics.record("ir.sweep", sweep=i, r_norm=r_norm,
                                digits_gained=digits,
                                golden_frac=float(st["golden_frac"]),
                                limb_carries=int(carries["total"]))
        x_hi, x_lo = hi2, lo2
    _obs_metrics.inc("ir.sweeps", iters)
    return x_hi, x_lo


def _driver(a_p, b_p, solve_fn, iters, fmt: PositFormat = P32E2):
    b_p = jnp.asarray(b_p, jnp.int32)
    residual_fn = lambda hi, lo, b: residual_quire(a_p, hi, b, lo, fmt=fmt)
    one = functools.partial(refine_pair, solve_fn, residual_fn, iters=iters,
                            fmt=fmt)
    if b_p.ndim == 1:
        return one(b_p)
    if _obs_numerics.active(a_p, b_p):
        # Observed path: loop the columns (vmap-vs-loop bit-identity is
        # pinned by the repo's refinement tests) so each column's sweeps
        # land in the ir.sweep series.
        cols = [one(b_p[:, j]) for j in range(b_p.shape[1])]
        return (jnp.stack([hi for hi, _ in cols], axis=1),
                jnp.stack([lo for _, lo in cols], axis=1))
    return jax.vmap(one, in_axes=1, out_axes=1)(b_p)


def rgesv_ir(a_p: jax.Array, b_p: jax.Array, iters: int = 3, nb: int = 32,
             gemm_backend: str = "xla_quire", fmt: PositFormat = P32E2):
    """LU-based solve of A x = b with quire-exact iterative refinement.

    Returns ((x_hi, x_lo), (lu, ipiv)): the solution is the unevaluated
    posit pair x_hi + x_lo (use x_hi alone for a plain posit32 result, or
    ``pair_to_float64`` for the full refined value).  b may be (n,) or
    (n, nrhs) (vmapped over columns).  A batched a_p of shape
    (batch, n, n) (with matching leading axis on b) vmaps the whole
    driver — factorizations and refinement sweeps run as one batched
    program on top of the single-dispatch ``rgetrf``.  Runs under the
    host span ``posit.rgesv_ir``.
    """
    with _obs_trace.span("posit.rgesv_ir", iters=iters, nb=nb,
                         backend=gemm_backend, fmt=fmt.name):
        a_p = jnp.asarray(a_p, jnp.int32)
        if a_p.ndim == 3:
            return jax.vmap(lambda a, b: rgesv_ir(a, b, iters, nb,
                                                  gemm_backend, fmt)
                            )(a_p, jnp.asarray(b_p, jnp.int32))
        lu, ipiv = decomp.rgetrf(a_p, nb=nb, gemm_backend=gemm_backend,
                                 fmt=fmt)
        solve_fn = lambda r: solve.rgetrs(lu, ipiv, r, quire=True, fmt=fmt)
        return _driver(a_p, b_p, solve_fn, iters, fmt), (lu, ipiv)


def rposv_ir(a_p: jax.Array, b_p: jax.Array, iters: int = 3, nb: int = 32,
             gemm_backend: str = "xla_quire", fmt: PositFormat = P32E2):
    """Cholesky-based SPD solve with quire-exact iterative refinement.

    Returns ((x_hi, x_lo), l); same conventions (including batched a_p)
    as ``rgesv_ir``; host span ``posit.rposv_ir``.
    """
    with _obs_trace.span("posit.rposv_ir", iters=iters, nb=nb,
                         backend=gemm_backend, fmt=fmt.name):
        a_p = jnp.asarray(a_p, jnp.int32)
        if a_p.ndim == 3:
            return jax.vmap(lambda a, b: rposv_ir(a, b, iters, nb,
                                                  gemm_backend, fmt)
                            )(a_p, jnp.asarray(b_p, jnp.int32))
        l_p = decomp.rpotrf(a_p, nb=nb, gemm_backend=gemm_backend, fmt=fmt)
        solve_fn = lambda r: solve.rpotrs(l_p, r, quire=True, fmt=fmt)
        return _driver(a_p, b_p, solve_fn, iters, fmt), l_p


# --------------------------------------------------------------------------
# mixed-precision IR: narrow-format factorization, working-format residual
# --------------------------------------------------------------------------

def pow2_scale(x64):
    """2^floor(log2(max|x|)) — the exact-in-f64 equilibration scale
    bringing max|x| into [1, 2) (NaN lanes ignored; 1.0 for all-zero)."""
    mx = jnp.max(jnp.abs(jnp.where(jnp.isnan(x64), 0.0, x64)))
    safe = jnp.where(mx > 0, mx, 1.0)
    return jnp.exp2(jnp.floor(jnp.log2(safe)))


def mp_narrow_matrix(a_p, factor_fmt: PositFormat, fmt: PositFormat):
    """A -> (A/s rounded to factor_fmt, s) with s a power of two placing
    max|A| in [1, 2) — posit-aware matrix equilibration.  The narrow
    format's fraction bits peak in the golden zone around 1, so scaling A
    there makes the factorization's relative error (and hence the IR
    contraction rate) independent of the problem's sigma/phi scale; the
    paper's "accuracy depends on operand scale" effect, turned around
    and used.  s is folded back in the correction solve: A = s * A'
    => A^{-1} r = (1/s) * A'^{-1} r.  Exact: s is a power of two applied
    in the f64 carrier."""
    av = posit.to_float64(a_p, fmt)
    s = pow2_scale(av)
    return posit.from_float64(av / s, factor_fmt), s


def _mp_solve_fn(base_solve, a_scale, factor_fmt: PositFormat,
                 fmt: PositFormat):
    """Wrap a factor-format solve as a working-format correction solve:
    round r down (the correction only needs r's leading digits), solve in
    the cheap format, lift d back up.

    The residual is **equilibrated** too (the HPL-AI/dsgesv trick, in
    posit terms): as refinement converges, ||r|| shrinks toward — and
    past — the narrow format's golden zone, where p16e1 keeps almost no
    fraction bits (and underflows entirely at minpos = 2^-28), stalling
    the contraction at ~1e-8 backward error.  Scaling by the power of two
    that brings max|r| to [1, 2) puts every component at the format's
    maximum-precision regime; the solve is scale-invariant, and the
    power-of-two scale/unscale is exact in the f64 carrier (posit values
    are exactly f64-representable), so the only roundings are the r -> r16
    narrowing and the final d encode — the same two any narrow solve has.
    ``a_scale`` is the matrix equilibration scale from
    ``mp_narrow_matrix`` (the factors are of A/a_scale, so the
    correction gains a 1/a_scale).
    """
    def solve_fn(r):
        rv = posit.to_float64(r, fmt)
        s = pow2_scale(rv)
        r_lo = posit.from_float64(rv / s, factor_fmt)
        d_lo = posit.to_float64(base_solve(r_lo), factor_fmt)
        return posit.from_float64(d_lo * (s / a_scale), fmt)
    return solve_fn


def rgesv_mp(a_p: jax.Array, b_p: jax.Array, iters: int = 8, nb: int = 32,
             gemm_backend: str = "xla_quire",
             factor_fmt: PositFormat = P16E1, fmt: PositFormat = P32E2):
    """Mixed-precision LU solve: factorize A in ``factor_fmt`` (default
    Posit(16,1) — the cheap O(n^3) step), refine with ``fmt`` (default
    Posit(32,2)) quire-exact residuals until the pair floor.

    A, b, and the returned pair (x_hi, x_lo) are ``fmt`` words; the
    returned factors (lu, ipiv) are ``factor_fmt`` words.  Same (n,) /
    (n, nrhs) / batched-A conventions as ``rgesv_ir``.  Reaches the same
    backward-error digits as ``rgesv_ir`` wherever
    cond(A) * eps_factor < 1 (the §5.1 sigma grid in
    ``error_eval.mixed_precision_study``), in more but much cheaper
    iterations — see the module docstring for the convergence argument.
    """
    a_p = jnp.asarray(a_p, jnp.int32)
    if a_p.ndim == 3:
        return jax.vmap(lambda a, b: rgesv_mp(a, b, iters, nb, gemm_backend,
                                              factor_fmt, fmt)
                        )(a_p, jnp.asarray(b_p, jnp.int32))
    a_lo, a_scale = mp_narrow_matrix(a_p, factor_fmt, fmt)
    lu, ipiv = decomp.rgetrf(a_lo, nb=nb, gemm_backend=gemm_backend,
                             fmt=factor_fmt)
    base = lambda r16: solve.rgetrs(lu, ipiv, r16, quire=True,
                                    fmt=factor_fmt)
    solve_fn = _mp_solve_fn(base, a_scale, factor_fmt, fmt)
    return _driver(a_p, b_p, solve_fn, iters, fmt), (lu, ipiv)


def rposv_mp(a_p: jax.Array, b_p: jax.Array, iters: int = 16, nb: int = 32,
             gemm_backend: str = "xla_quire",
             factor_fmt: PositFormat = P16E1, fmt: PositFormat = P32E2):
    """Mixed-precision SPD solve: Cholesky in ``factor_fmt``, quire-exact
    ``fmt`` refinement.  Returns ((x_hi, x_lo), l) with l in
    ``factor_fmt``; same conventions as ``rgesv_mp``.  The default sweep
    count is higher than ``rgesv_mp``'s: the §5.1 SPD ensemble is
    A = X^T X, whose condition number is cond(X)^2, and the contraction
    rho ~ cond(A) * eps_p16e1 is correspondingly slower.  The narrow
    rounding of A must preserve positive-definiteness (a diagonally
    dominant or well-conditioned SPD A survives p16e1's ~2^-12 relative
    perturbation; a barely-SPD A may not — NaR from sqrt poisons the
    factor, and the returned pair will be NaR too, which is the correct
    failure signal).
    """
    a_p = jnp.asarray(a_p, jnp.int32)
    if a_p.ndim == 3:
        return jax.vmap(lambda a, b: rposv_mp(a, b, iters, nb, gemm_backend,
                                              factor_fmt, fmt)
                        )(a_p, jnp.asarray(b_p, jnp.int32))
    a_lo, a_scale = mp_narrow_matrix(a_p, factor_fmt, fmt)
    l_p = decomp.rpotrf(a_lo, nb=nb, gemm_backend=gemm_backend,
                        fmt=factor_fmt)
    base = lambda r16: solve.rpotrs(l_p, r16, quire=True, fmt=factor_fmt)
    solve_fn = _mp_solve_fn(base, a_scale, factor_fmt, fmt)
    return _driver(a_p, b_p, solve_fn, iters, fmt), l_p


# --------------------------------------------------------------------------
# graceful degradation: convergence monitor + escalation ladder (repro.ft,
# DESIGN.md §11)
# --------------------------------------------------------------------------

def refine_pair_monitored(solve_fn, residual_fn, b_col: jax.Array,
                          max_sweeps: int, fmt: PositFormat = P32E2,
                          target: float = 1e-10, patience: int = 2,
                          growth: float = 4.0):
    """``refine_pair`` with a host-level convergence monitor.

    The SAME per-sweep op sequence as ``refine_pair``'s scan body (so a
    run that converges in k sweeps yields the pair bit-identical to
    ``refine_pair(..., iters=k)``), unrolled in Python like
    ``_refine_pair_obs`` so each sweep's residual norm is a concrete
    host value the monitor can act on:

    * ``nar``       — NaR appeared in the residual or the iterate (a
      poisoned narrow factorization, an injected NaR, a singular
      correction solve): stop immediately, the pair cannot recover.
    * ``diverged``  — ||r|| grew by more than ``growth`` over a sweep
      and exceeds ||r_0||: the correction solve is amplifying, not
      contracting (cond * eps_factor >> 1).
    * ``stalled``   — ``patience`` consecutive sweeps without halving
      the best ||r|| seen, while still above target: contraction has
      flattened out (the classic mixed-precision stall,
      cond * eps_factor >~ 1).
    * ``converged`` — ||r||_inf <= ``target`` * ||b||_inf (backward-
      error-style test; exact zero converges trivially).

    Returns ((x_hi, x_lo), info dict) with info carrying outcome, the
    number of correction updates applied (``sweeps`` — so
    ``refine_pair(..., iters=sweeps)`` reproduces the pair exactly), and
    the first/last residual norms — ``rgesv_guarded`` folds these into
    its ``SolveReport``.
    """
    b_norm = float(jnp.max(jnp.abs(posit.to_float64(b_col, fmt))))
    tol = target * (b_norm if b_norm > 0 else 1.0)
    x_hi = solve_fn(b_col)
    x_lo = jnp.zeros_like(x_hi)
    outcome = "stalled"                    # if the sweep budget runs out
    r0_norm = r_norm = float("inf")
    best = float("inf")
    flat = 0
    sweeps = 0
    for i in range(max_sweeps):
        r = residual_fn(x_hi, x_lo, b_col)
        if bool(jnp.any(posit.is_nar(r, fmt))
                | jnp.any(posit.is_nar(x_hi, fmt))):
            outcome = "nar"
            break
        prev = r_norm
        r_norm = float(jnp.max(jnp.abs(posit.to_float64(r, fmt))))
        if i == 0:
            r0_norm = r_norm
        if r_norm <= tol:
            outcome = "converged"
            break
        if r_norm > growth * prev and r_norm > r0_norm:
            outcome = "diverged"
            break
        if r_norm > 0.5 * best:
            flat += 1
            if flat >= patience:
                outcome = "stalled"
                break
        else:
            flat = 0
        best = min(best, r_norm)
        d = solve_fn(r)
        x_hi, x_lo, _ = _pair_update(x_hi, x_lo, d, fmt)
        sweeps = i + 1
    info = {"outcome": outcome, "sweeps": sweeps, "r_norm": r_norm,
            "r_norm0": r0_norm}
    _obs_metrics.inc(f"ir.monitor.{outcome}")
    return (x_hi, x_lo), info


def _guarded_cols(a_p, b_p, solve_fn, max_sweeps, fmt, target):
    """Run the monitored loop per RHS column; merge to the WORST info
    (a ladder rung only counts as converged if every column converged)."""
    b_p = jnp.asarray(b_p, jnp.int32)
    residual_fn = lambda hi, lo, b: residual_quire(a_p, hi, b, lo, fmt=fmt)
    if b_p.ndim == 1:
        return refine_pair_monitored(solve_fn, residual_fn, b_p, max_sweeps,
                                     fmt, target=target)
    rank = {"converged": 0, "stalled": 1, "diverged": 2, "nar": 3}
    cols, worst = [], None
    for j in range(b_p.shape[1]):
        pair, info = refine_pair_monitored(solve_fn, residual_fn, b_p[:, j],
                                           max_sweeps, fmt, target=target)
        cols.append(pair)
        if worst is None or rank[info["outcome"]] > rank[worst["outcome"]]:
            worst = info
    return (jnp.stack([hi for hi, _ in cols], axis=1),
            jnp.stack([lo for _, lo in cols], axis=1)), worst


def rgesv_guarded(a_p: jax.Array, b_p: jax.Array, iters: int = 8,
                  nb: int = 32, gemm_backend: str = "xla_quire",
                  factor_fmt: PositFormat = P16E1,
                  fmt: PositFormat = P32E2, target: float = 1e-10,
                  plan=None, max_retries: int = 2):
    """Gracefully-degrading LU solve: the full robustness ladder.

        rgesv_mp (cheap narrow factorization, monitored refinement)
          -> stalls / diverges / NaRs ->
        rgesv_ir (full-width factorization, monitored refinement)
          -> still won't meet target ->
        plain rgetrs backsolve on the protected full-width factors
        (best-effort answer, reported as outcome="plain")

    Every factorization in the ladder is the checksum-PROTECTED
    ``rgetrf_ft`` (repro.ft exact ABFT): storage faults injected via
    ``plan`` are detected and repaired before the refinement loop ever
    sees them, and the detection/retry counts land in the returned
    ``SolveReport`` alongside the monitor outcome.  Returns
    ((x_hi, x_lo), SolveReport).  b may be (n,) or (n, nrhs); with
    multiple RHS the report reflects the worst column.
    """
    from repro.ft.report import SolveReport
    a_p = jnp.asarray(a_p, jnp.int32)
    detections = retries = 0
    fallbacks = []

    # rung 1: mixed precision
    a_lo, a_scale = mp_narrow_matrix(a_p, factor_fmt, fmt)
    lu16, piv16, ft_rep = decomp.rgetrf_ft(a_lo, nb=nb,
                                           gemm_backend=gemm_backend,
                                           fmt=factor_fmt, plan=plan,
                                           max_retries=max_retries)
    detections += ft_rep.detections
    retries += ft_rep.retries
    base = lambda r16: solve.rgetrs(lu16, piv16, r16, quire=True,
                                    fmt=factor_fmt)
    pair, info = _guarded_cols(a_p, b_p,
                               _mp_solve_fn(base, a_scale, factor_fmt, fmt),
                               iters, fmt, target)
    if info["outcome"] == "converged":
        return pair, SolveReport(outcome="converged", solver="rgesv_mp",
                                 sweeps=info["sweeps"],
                                 r_norm=info["r_norm"],
                                 r_norm0=info["r_norm0"],
                                 detections=detections, retries=retries)
    fallbacks.append(("rgesv_mp", info["outcome"]))
    _obs_metrics.inc("ft.fallbacks")

    # rung 2: full-width iterative refinement
    lu, ipiv, ft_rep = decomp.rgetrf_ft(a_p, nb=nb,
                                        gemm_backend=gemm_backend, fmt=fmt,
                                        plan=plan, max_retries=max_retries)
    detections += ft_rep.detections
    retries += ft_rep.retries
    solve_fn = lambda r: solve.rgetrs(lu, ipiv, r, quire=True, fmt=fmt)
    pair, info = _guarded_cols(a_p, b_p, solve_fn, iters, fmt, target)
    if info["outcome"] == "converged":
        return pair, SolveReport(outcome="converged", solver="rgesv_ir",
                                 sweeps=info["sweeps"],
                                 r_norm=info["r_norm"],
                                 r_norm0=info["r_norm0"],
                                 detections=detections, retries=retries,
                                 fallbacks=tuple(fallbacks))
    fallbacks.append(("rgesv_ir", info["outcome"]))
    _obs_metrics.inc("ft.fallbacks")

    # rung 3: plain backsolve on the (already protected) full factors —
    # best effort, no refinement claims
    b_w = jnp.asarray(b_p, jnp.int32)
    x = solve.rgetrs(lu, ipiv, b_w, quire=True, fmt=fmt)
    return (x, jnp.zeros_like(x)), SolveReport(
        outcome="plain", solver="rgetrs", sweeps=info["sweeps"],
        r_norm=info["r_norm"], r_norm0=info["r_norm0"],
        detections=detections, retries=retries, fallbacks=tuple(fallbacks))
