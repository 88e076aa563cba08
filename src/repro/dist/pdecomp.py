"""Distributed right-looking Rpotrf / Rgetrf + IR solvers over the grid.

ScaLAPACK's pdpotrf/pdgetrf schedule, expressed as ONE shard_map-jitted
XLA program per factorization (the dist analogue of PR 2's single-
dispatch drivers — the block schedule is static at trace time, the
device coordinate is the only traced index):

per block step j (width w = min(nb, n - j)):
  1. **panel broadcast** — the owning grid column's (lm, w) slice is
     psum-selected across "col" (non-owners contribute zero words), then
     all_gather'd + unpermuted along "row": every device holds the
     replicated (m, w) panel column.
  2. **panel factorization, replicated** — ``potf2`` / ``getf2`` run
     identically on every device (same words in, same words out; XLA CPU
     is bitwise deterministic), standing in for ScaLAPACK's column-team
     factor-then-broadcast with zero extra schedule states.
  3. (LU) **pivot application** — ``getf2``'s w swaps compose into one
     net row permutation (computed on the replicated ipiv); each device
     re-gathers its rows from the "row"-axis all_gather of its column
     strip through that permutation — one collective for the whole
     panel's swaps.
  4. **trailing update, distributed** — each device updates its OWN
     block-cyclic tiles with one local ``rgemm`` (any backend): the
     replicated panel is gathered per-device into (lm, w) / (ln, w)
     operand rows/cols by traced global index, and the masked write
     keeps only trailing-region elements.  Per-element this is the SAME
     backend reduction over the same K = w operands as the single-device
     trailing rgemm, so words match bit-for-bit (quire backends by limb
     associativity; f32/f64 backends by elementwise determinism of the
     fixed-K reduction — both pinned in tests/test_dist.py).

The masked update computes a full (lm, ln) tile product each step
(Σ_j lm*ln*w ≈ n³/(PQ) MACs vs the single-device Σ (n-j)²w ≈ n³/3) —
the uniform-SPMD trade: no data-dependent shapes, every device does
identical work, and the 3x constant is recovered once P*Q >= 3.

``p_rgesv_ir`` / ``p_rposv_ir`` wire the distributed pieces into
``lapack.refine.refine_pair``: distributed factorization, replicated
quire substitution sweeps on the gathered factors (O(n²) — not worth
distributing), and **distributed residuals** (``pblas.p_residual_quire``,
limb-plane psum) — bit-identical end to end to ``rgesv_ir``/``rposv_ir``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.core.formats import P32E2
from repro.kernels.ops import rgemm
from repro.lapack import solve
from repro.lapack.blas import rtrsm_left_lower, rtrsm_right_lowerT
from repro.lapack.decomp import getf2, potf2
from repro.lapack.refine import refine_pair
from repro.obs import metrics as _obs_metrics
from repro.obs import numerics as _obs_numerics
from repro.obs import trace as _obs_trace
from repro.dist.layout import (BlockCyclic, DistMatrix, grid_coords,
                               local_gidx, select_block_col, unshuffle)
from repro.dist.pblas import _record_collectives, p_residual_quire

_FMT = P32E2
_SPEC = jax.sharding.PartitionSpec("row", "col")
_REP = jax.sharding.PartitionSpec()


def _replicate_panel(a_loc, lay: BlockCyclic, c, j: int, w: int):
    """Steps 1 of the schedule: the (m, w) global column panel [*, j:j+w)
    replicated on every device (psum-select across "col", gather along
    "row", unpermute)."""
    mine = select_block_col(a_loc, lay, c, j, w)          # (lm, w) or 0
    rows = jax.lax.psum(mine, "col")                      # (lm, w)
    full = unshuffle(jax.lax.all_gather(rows, "row", tiled=False),
                     lay.p, lay.nb)
    return full[:lay.m]                                   # (m, w)


def _write_panel(a_loc, lay: BlockCyclic, r, c, j: int, w: int, col_new,
                 row_lo: int):
    """Masked write of replicated (m, w) ``col_new`` into the owner grid
    column's local tiles, rows [row_lo, m)."""
    c_star, _, off = lay.col_block_home(j)
    gidx = local_gidx(lay, 0, r)
    mine = col_new[jnp.clip(gidx, 0, lay.m - 1)]          # (lm, w)
    mask = ((c == c_star) & (gidx >= row_lo) & (gidx < lay.m))[:, None]
    cur = jax.lax.slice_in_dim(a_loc, off, off + w, axis=1)
    return jax.lax.dynamic_update_slice_in_dim(
        a_loc, jnp.where(mask, mine, cur), off, axis=1)


def _rpotrf_local(a_loc, lay: BlockCyclic, gemm_backend: str):
    n, nb = lay.n, lay.nb
    r, c = grid_coords()
    gr = local_gidx(lay, 0, r)                            # (lm,)
    gc = local_gidx(lay, 1, c)                            # (ln,)
    for j in range(0, n, nb):
        w = min(nb, n - j)
        colpan = _replicate_panel(a_loc, lay, c, j, w)    # (m, w)
        l11 = potf2(colpan[j:j + w])
        if j + w < n:
            a21 = rtrsm_right_lowerT(colpan[j + w:], l11)
            lcol = jnp.concatenate([colpan[:j], l11, a21])
        else:
            lcol = jnp.concatenate([colpan[:j], l11])
        a_loc = _write_panel(a_loc, lay, r, c, j, w, lcol, row_lo=j)
        if j + w < n:
            ar = lcol[jnp.clip(gr, 0, n - 1)]             # (lm, w)
            ac = lcol[jnp.clip(gc, 0, n - 1)]             # (ln, w)
            upd = rgemm(ar, ac, a_loc, alpha=-1.0, beta=1.0, trans_b=True,
                        backend=gemm_backend)
            tmask = (((gr >= j + w) & (gr < n))[:, None]
                     & ((gc >= j + w) & (gc < n))[None, :])
            a_loc = jnp.where(tmask, upd, a_loc)
    # zero the strict upper triangle and the padding region (word 0 == 0)
    keep = (gr[:, None] >= gc[None, :]) & (gr < n)[:, None] & (gc < n)[None, :]
    return jnp.where(keep, a_loc, 0)


def _rgetrf_local(a_loc, lay: BlockCyclic, gemm_backend: str):
    m, n, nb = lay.m, lay.n, lay.nb
    mn = min(m, n)
    r, c = grid_coords()
    gr = local_gidx(lay, 0, r)
    gc = local_gidx(lay, 1, c)
    ipiv = jnp.zeros((mn,), jnp.int32)
    for j in range(0, mn, nb):
        w = min(nb, mn - j)
        colpan = _replicate_panel(a_loc, lay, c, j, w)    # (m, w)
        pan, piv_loc = getf2(colpan[j:], w)               # replicated
        ipiv = jax.lax.dynamic_update_slice_in_dim(
            ipiv, piv_loc + j, j, axis=0)
        # net permutation of the w swaps (rows j..m), applied to every
        # column strip through ONE "row"-axis gather
        idx = jnp.arange(m, dtype=jnp.int32)
        for k in range(w):
            rk = j + k
            rp = j + piv_loc[k]
            vk, vp = idx[rk], idx[rp]
            idx = idx.at[rk].set(vp).at[rp].set(vk)
        strip = unshuffle(jax.lax.all_gather(a_loc, "row", tiled=False),
                          lay.p, lay.nb)[:m]              # (m, ln)
        strip = strip[idx]
        swapped = strip[jnp.clip(gr, 0, m - 1)]           # (lm, ln)
        a_loc = jnp.where(((gr >= j) & (gr < m))[:, None], swapped, a_loc)
        # factored panel (already internally swapped) overwrites its column
        pcol = jnp.concatenate([colpan[:j], pan]) if j else pan
        a_loc = _write_panel(a_loc, lay, r, c, j, w, pcol, row_lo=j)
        if j + w < n:
            # U12 row block: per-column unit-lower solve on MY columns of
            # the post-swap rows [j, j+w)
            u12 = rtrsm_left_lower(pan[:w], strip[j:j + w], unit_diag=True)
            u12_mine = u12[jnp.clip(gr - j, 0, w - 1)]    # (lm, ln)
            rmask = ((gr >= j) & (gr < j + w))[:, None]
            cmask = ((gc >= j + w) & (gc < n))[None, :]
            a_loc = jnp.where(rmask & cmask, u12_mine, a_loc)
            if j + w < m:
                l21 = pan[jnp.clip(gr - j, 0, m - j - 1)]  # (lm, w)
                upd = rgemm(l21, u12, a_loc, alpha=-1.0, beta=1.0,
                            backend=gemm_backend)
                tmask = (((gr >= j + w) & (gr < m))[:, None]
                         & ((gc >= j + w) & (gc < n))[None, :])
                a_loc = jnp.where(tmask, upd, a_loc)
    keep = (gr < m)[:, None] & (gc < n)[None, :]
    return jnp.where(keep, a_loc, 0), ipiv


@functools.partial(jax.jit, static_argnames=("lay", "mesh", "gemm_backend"))
def _p_rpotrf_sharded(a, *, lay, mesh, gemm_backend):
    fn = functools.partial(_rpotrf_local, lay=lay, gemm_backend=gemm_backend)
    return shard_map(fn, mesh=mesh, in_specs=(_SPEC,), out_specs=_SPEC,
                     check_vma=False)(a)


@functools.partial(jax.jit, static_argnames=("lay", "mesh", "gemm_backend"))
def _p_rgetrf_sharded(a, *, lay, mesh, gemm_backend):
    fn = functools.partial(_rgetrf_local, lay=lay, gemm_backend=gemm_backend)
    return shard_map(fn, mesh=mesh, in_specs=(_SPEC,),
                     out_specs=(_SPEC, _REP), check_vma=False)(a)


def pfactor_collective_plan(lay: BlockCyclic,
                            algo: str = "getrf") -> dict[str, int]:
    """Static PER-DEVICE collective byte plan of one distributed blocked
    factorization (``pblas.pdgemm_collective_plan`` convention).  Per
    block step: the (lm, w) i32 panel psum-select (all-reduce) and its
    (P, lm, w) i32 row gather; LU adds the per-step (P, lm, ln) i32
    column-strip gather the net pivot permutation reads through."""
    if algo not in ("getrf", "potrf"):
        raise ValueError(f"unknown algo {algo!r}")
    mn = min(lay.m, lay.n) if algo == "getrf" else lay.n
    ar = ag = 0
    for j in range(0, mn, lay.nb):
        w = min(lay.nb, mn - j)
        ar += 4 * lay.lm * w
        ag += 4 * lay.p * lay.lm * w
        if algo == "getrf":
            ag += 4 * lay.p * lay.lm * lay.ln
    return {"all-reduce": ar, "all-gather": ag}


def p_rpotrf(a: DistMatrix, gemm_backend: str = "xla_quire",
             checkpoint_dir=None, resume: bool = False) -> DistMatrix:
    """Distributed blocked lower Cholesky; bit-identical words to
    ``lapack.rpotrf(gather(a), nb=a.layout.nb, gemm_backend=...)``.  The
    block size IS the layout block size (the ScaLAPACK coupling: the
    algorithmic and distribution blockings coincide).

    With ``checkpoint_dir`` set, the factorization runs host-stepped
    through ``p_rpotrf_ft`` (same words — pinned in tests/test_dist.py)
    saving per-panel checkpoints, and ``resume=True`` restarts from the
    newest saved step bit-identically.  Default (no checkpointing)
    dispatches the unchanged single-program path."""
    lay = a.layout
    if lay.m != lay.n:
        raise ValueError(f"Cholesky needs square A, got {a.shape}")
    if checkpoint_dir is not None:
        out, _ = p_rpotrf_ft(a, gemm_backend=gemm_backend,
                             checkpoint_dir=checkpoint_dir, resume=resume)
        return out
    if _obs_numerics.active(a.data):
        with _obs_trace.span("p_rpotrf", n=lay.n, nb=lay.nb,
                             grid=f"{lay.p}x{lay.q}", backend=gemm_backend):
            out = _p_rpotrf_sharded(a.data, lay=lay, mesh=a.mesh,
                                    gemm_backend=gemm_backend)
        _record_collectives("dist.rpotrf",
                            pfactor_collective_plan(lay, algo="potrf"))
        _obs_numerics.record_numerics("dist.rpotrf.out", out, _FMT)
    else:
        out = _p_rpotrf_sharded(a.data, lay=lay, mesh=a.mesh,
                                gemm_backend=gemm_backend)
    return a.with_data(out)


def p_rgetrf(a: DistMatrix, gemm_backend: str = "xla_quire",
             checkpoint_dir=None, resume: bool = False):
    """Distributed blocked partial-pivot LU; returns (LU DistMatrix,
    replicated ipiv) bit-identical to ``lapack.rgetrf`` at nb =
    a.layout.nb.  ``checkpoint_dir``/``resume`` select the host-stepped
    per-panel checkpointing path (see ``p_rpotrf``)."""
    lay = a.layout
    if checkpoint_dir is not None:
        lu, ipiv, _ = p_rgetrf_ft(a, gemm_backend=gemm_backend,
                                  checkpoint_dir=checkpoint_dir,
                                  resume=resume)
        return lu, ipiv
    if _obs_numerics.active(a.data):
        with _obs_trace.span("p_rgetrf", m=lay.m, n=lay.n, nb=lay.nb,
                             grid=f"{lay.p}x{lay.q}", backend=gemm_backend):
            lu, ipiv = _p_rgetrf_sharded(a.data, lay=lay, mesh=a.mesh,
                                         gemm_backend=gemm_backend)
        _record_collectives("dist.rgetrf",
                            pfactor_collective_plan(lay, algo="getrf"))
        _obs_numerics.record_numerics("dist.rgetrf.out", lu, _FMT)
    else:
        lu, ipiv = _p_rgetrf_sharded(a.data, lay=lay, mesh=a.mesh,
                                     gemm_backend=gemm_backend)
    return a.with_data(lu), ipiv


# --------------------------------------------------------------------------
# distributed iterative-refinement drivers
# --------------------------------------------------------------------------

def _p_driver(a: DistMatrix, b_p, solve_fn, iters: int):
    """refine_pair over columns with DISTRIBUTED residuals.  RHS columns
    loop in Python (nrhs is small and the factorization — the O(n³)
    part — is already amortized across them)."""
    b_p = jnp.asarray(b_p, jnp.int32)
    residual_fn = lambda hi, lo, b: p_residual_quire(a, hi, b, lo)
    if b_p.ndim == 1:
        return refine_pair(solve_fn, residual_fn, b_p, iters)
    cols = [refine_pair(solve_fn, residual_fn, b_p[:, i], iters)
            for i in range(b_p.shape[1])]
    return (jnp.stack([h for h, _ in cols], axis=1),
            jnp.stack([l for _, l in cols], axis=1))


def p_rgesv_ir(a: DistMatrix, b_p, iters: int = 3,
               gemm_backend: str = "xla_quire"):
    """Distributed LU solve of A x = b with quire-exact iterative
    refinement: ``p_rgetrf`` factorization, replicated quire substitution
    sweeps on the gathered LU, and distributed limb-psum residuals.
    Returns ((x_hi, x_lo), (lu DistMatrix, ipiv)) with the pair words
    bit-identical to ``lapack.rgesv_ir`` at nb = a.layout.nb."""
    lu, ipiv = p_rgetrf(a, gemm_backend=gemm_backend)
    lu_rep = lu.gather()
    solve_fn = lambda r: solve.rgetrs(lu_rep, ipiv, r, quire=True)
    return _p_driver(a, b_p, solve_fn, iters), (lu, ipiv)


def p_rposv_ir(a: DistMatrix, b_p, iters: int = 3,
               gemm_backend: str = "xla_quire"):
    """Distributed Cholesky SPD solve with quire-exact iterative
    refinement; same conventions as ``p_rgesv_ir``.  Returns
    ((x_hi, x_lo), l DistMatrix)."""
    l_d = p_rpotrf(a, gemm_backend=gemm_backend)
    l_rep = l_d.gather()
    solve_fn = lambda r: solve.rpotrs(l_rep, r, quire=True)
    return _p_driver(a, b_p, solve_fn, iters), l_d


# --------------------------------------------------------------------------
# checksum-protected distributed drivers + per-panel checkpoint/restart
# (exact ABFT, repro.ft — DESIGN.md §11)
# --------------------------------------------------------------------------
#
# Host-stepped analogues of _rpotrf_local/_rgetrf_local: one shard_map
# dispatch per block step, where the panel BROADCAST carries its checksum
# strip.  The strip is computed from the pre-broadcast owner slices —
# each device deposits its local words into quire limbs and the strips
# psum across BOTH grid axes, so by limb-add associativity the strip is
# the exact column checksum of the panel no matter how it is sharded.
# After the broadcast every device recomputes the checksum of the
# replicated panel it actually RECEIVED and compares exactly; the
# conjunction psums across the grid, so one corrupted replica anywhere
# fails the step on every device, and the host retries it — panel
# re-broadcast + local recompute from the verified pre-step state, not a
# full restart.  Injection site "dist.panel" (device-gated via the
# linear id r*Q + c) corrupts one device's received replica, which is
# the broadcast-fault model: the wire is fine, a receiver's buffer
# flipped.

def _strip_cks(mine, fmt=_FMT):
    """Exact per-column checksums of a broadcast panel from its
    PRE-broadcast owner slices ``mine`` ((lm, w), zero off-owner):
    (canonical (w, L) value-sum limbs, (w,) nar, (w,) raw word sums),
    psum-reduced over both grid axes."""
    from repro.ft import abft
    from repro.quire.quire import Quire, q_renorm
    limbs, nar = abft._word_limbs(mine, fmt)
    lsum = jax.lax.psum(jax.lax.psum(jnp.sum(limbs, axis=0), "col"), "row")
    nsum = jax.lax.psum(jax.lax.psum(
        jnp.sum(nar.astype(jnp.int32), axis=0), "col"), "row") > 0
    wsum = jax.lax.psum(jax.lax.psum(
        jnp.sum(mine.astype(jnp.int64), axis=0), "col"), "row")
    q = q_renorm(Quire(limbs=lsum, nar=nsum))
    return q.limbs, q.nar, wsum


def _strip_verify(colpan, srow, snar, swsum, fmt=_FMT):
    """Per-device exact recompute-and-compare of the received replica
    against the strip; returns the grid-wide count of agreeing devices
    (== P*Q iff every replica verified)."""
    from repro.ft import abft
    grow, gnar = abft.word_sums(colpan, fmt, axis=0)
    gw = jnp.sum(colpan.astype(jnp.int64), axis=0)
    ok = (jnp.all(grow == srow) & jnp.all(gnar == snar)
          & jnp.all(gw == swsum))
    return jax.lax.psum(jax.lax.psum(ok.astype(jnp.int32), "col"), "row")


def _replicate_panel_ft(a_loc, lay: BlockCyclic, r, c, j: int, w: int,
                        plan, active: bool):
    """_replicate_panel with the checksum strip riding the broadcast and
    the 'dist.panel' injection window on the received replica."""
    mine = select_block_col(a_loc, lay, c, j, w)
    srow, snar, swsum = _strip_cks(mine)
    rows = jax.lax.psum(mine, "col")
    full = unshuffle(jax.lax.all_gather(rows, "row", tiled=False),
                     lay.p, lay.nb)
    colpan = full[:lay.m]
    if active and plan is not None:
        colpan = plan.words("dist.panel", j // lay.nb, colpan, _FMT,
                            dev=r * lay.q + c)
    okc = _strip_verify(colpan, srow, snar, swsum)
    return colpan, okc


def _rpotrf_ft_step_local(a_loc, *, lay: BlockCyclic, j: int,
                          gemm_backend: str, plan, active: bool):
    """One _rpotrf_local block step (same per-j ops) with the verified
    broadcast; returns (a_loc', agreeing-device count)."""
    n, nb = lay.n, lay.nb
    r, c = grid_coords()
    gr = local_gidx(lay, 0, r)
    gc = local_gidx(lay, 1, c)
    w = min(nb, n - j)
    colpan, okc = _replicate_panel_ft(a_loc, lay, r, c, j, w, plan, active)
    l11 = potf2(colpan[j:j + w])
    if j + w < n:
        a21 = rtrsm_right_lowerT(colpan[j + w:], l11)
        lcol = jnp.concatenate([colpan[:j], l11, a21])
    else:
        lcol = jnp.concatenate([colpan[:j], l11])
    a_loc = _write_panel(a_loc, lay, r, c, j, w, lcol, row_lo=j)
    if j + w < n:
        ar = lcol[jnp.clip(gr, 0, n - 1)]
        ac = lcol[jnp.clip(gc, 0, n - 1)]
        upd = rgemm(ar, ac, a_loc, alpha=-1.0, beta=1.0, trans_b=True,
                    backend=gemm_backend)
        tmask = (((gr >= j + w) & (gr < n))[:, None]
                 & ((gc >= j + w) & (gc < n))[None, :])
        a_loc = jnp.where(tmask, upd, a_loc)
    return a_loc, okc


def _rgetrf_ft_step_local(a_loc, ipiv, *, lay: BlockCyclic, j: int,
                          gemm_backend: str, plan, active: bool):
    """One _rgetrf_local block step (same per-j ops) with the verified
    broadcast; returns (a_loc', ipiv', agreeing-device count)."""
    m, n, nb = lay.m, lay.n, lay.nb
    mn = min(m, n)
    r, c = grid_coords()
    gr = local_gidx(lay, 0, r)
    gc = local_gidx(lay, 1, c)
    w = min(nb, mn - j)
    colpan, okc = _replicate_panel_ft(a_loc, lay, r, c, j, w, plan, active)
    pan, piv_loc = getf2(colpan[j:], w)
    ipiv = jax.lax.dynamic_update_slice_in_dim(ipiv, piv_loc + j, j, axis=0)
    idx = jnp.arange(m, dtype=jnp.int32)
    for k in range(w):
        rk = j + k
        rp = j + piv_loc[k]
        vk, vp = idx[rk], idx[rp]
        idx = idx.at[rk].set(vp).at[rp].set(vk)
    strip = unshuffle(jax.lax.all_gather(a_loc, "row", tiled=False),
                      lay.p, lay.nb)[:m]
    strip = strip[idx]
    swapped = strip[jnp.clip(gr, 0, m - 1)]
    a_loc = jnp.where(((gr >= j) & (gr < m))[:, None], swapped, a_loc)
    pcol = jnp.concatenate([colpan[:j], pan]) if j else pan
    a_loc = _write_panel(a_loc, lay, r, c, j, w, pcol, row_lo=j)
    if j + w < n:
        u12 = rtrsm_left_lower(pan[:w], strip[j:j + w], unit_diag=True)
        u12_mine = u12[jnp.clip(gr - j, 0, w - 1)]
        rmask = ((gr >= j) & (gr < j + w))[:, None]
        cmask = ((gc >= j + w) & (gc < n))[None, :]
        a_loc = jnp.where(rmask & cmask, u12_mine, a_loc)
        if j + w < m:
            l21 = pan[jnp.clip(gr - j, 0, m - j - 1)]
            upd = rgemm(l21, u12, a_loc, alpha=-1.0, beta=1.0,
                        backend=gemm_backend)
            tmask = (((gr >= j + w) & (gr < m))[:, None]
                     & ((gc >= j + w) & (gc < n))[None, :])
            a_loc = jnp.where(tmask, upd, a_loc)
    return a_loc, ipiv, okc


@functools.partial(jax.jit, static_argnames=("lay", "mesh", "j",
                                             "gemm_backend", "plan",
                                             "active"))
def _p_rpotrf_ft_step(a, *, lay, mesh, j, gemm_backend, plan, active):
    fn = functools.partial(_rpotrf_ft_step_local, lay=lay, j=j,
                           gemm_backend=gemm_backend, plan=plan,
                           active=active)
    return shard_map(fn, mesh=mesh, in_specs=(_SPEC,),
                     out_specs=(_SPEC, _REP), check_vma=False)(a)


@functools.partial(jax.jit, static_argnames=("lay", "mesh", "j",
                                             "gemm_backend", "plan",
                                             "active"))
def _p_rgetrf_ft_step(a, ipiv, *, lay, mesh, j, gemm_backend, plan, active):
    fn = functools.partial(_rgetrf_ft_step_local, lay=lay, j=j,
                           gemm_backend=gemm_backend, plan=plan,
                           active=active)
    return shard_map(fn, mesh=mesh, in_specs=(_SPEC, _REP),
                     out_specs=(_SPEC, _REP, _REP), check_vma=False)(a, ipiv)


def _potrf_keep_local(a_loc, *, lay: BlockCyclic):
    r, c = grid_coords()
    gr = local_gidx(lay, 0, r)
    gc = local_gidx(lay, 1, c)
    n = lay.n
    keep = ((gr[:, None] >= gc[None, :]) & (gr < n)[:, None]
            & (gc < n)[None, :])
    return jnp.where(keep, a_loc, 0)


def _getrf_keep_local(a_loc, *, lay: BlockCyclic):
    r, c = grid_coords()
    gr = local_gidx(lay, 0, r)
    gc = local_gidx(lay, 1, c)
    keep = (gr < lay.m)[:, None] & (gc < lay.n)[None, :]
    return jnp.where(keep, a_loc, 0)


@functools.partial(jax.jit, static_argnames=("lay", "mesh", "algo"))
def _p_keep_mask(a, *, lay, mesh, algo):
    fn = functools.partial(_potrf_keep_local if algo == "potrf"
                           else _getrf_keep_local, lay=lay)
    return shard_map(fn, mesh=mesh, in_specs=(_SPEC,), out_specs=_SPEC,
                     check_vma=False)(a)


def _ckpt_save(checkpoint_dir, step, tree, keep_last):
    import numpy as np
    from repro.checkpoint.store import save_checkpoint
    save_checkpoint(checkpoint_dir, step,
                    {k: np.asarray(v) for k, v in tree.items()},
                    keep_last=keep_last)


def _ckpt_restore(checkpoint_dir, like):
    """(step, {name: np array}) of the newest checkpoint restored into
    the structure of ``like``, or (0, None) when none exist."""
    from repro.checkpoint.store import latest_step, restore_checkpoint
    step = latest_step(checkpoint_dir)
    if step is None:
        return 0, None
    tree, step, _ = restore_checkpoint(checkpoint_dir, like, step)
    return step, tree


def p_rpotrf_ft(a: DistMatrix, gemm_backend: str = "xla_quire", plan=None,
                max_retries: int = 2, checkpoint_dir=None,
                resume: bool = False, keep_last: int = 2,
                _stop_after=None):
    """Checksum-protected distributed Cholesky: returns
    (L DistMatrix, FtReport), bit-identical to ``p_rpotrf`` (and hence to
    single-device ``rpotrf``) fault-free and after recovery.

    Host-stepped: every panel broadcast carries its exact checksum strip
    and a failed verify retries just that step (re-broadcast + local
    recompute).  With ``checkpoint_dir`` set, the sharded state is saved
    per block step (repro.checkpoint.store: posit words as int32 npy,
    sha256-verified) and ``resume=True`` restarts from the newest step,
    resuming bit-identically — posit words are exact integer state, so a
    resumed run produces the same factor word-for-word.  ``_stop_after``
    (test hook) simulates a mid-factorization kill: the driver returns
    (None, report) after that many steps.
    """
    from repro import ft
    lay = a.layout
    if lay.m != lay.n:
        raise ValueError(f"Cholesky needs square A, got {a.shape}")
    data = a.data
    report = ft.FtReport()
    start = 0
    if checkpoint_dir is not None and resume:
        start, state = _ckpt_restore(checkpoint_dir, {"a": data})
        if state is not None:
            data = jax.device_put(state["a"], data.sharding)
    steps = list(range(0, lay.n, lay.nb))
    for s, j in enumerate(steps):
        if s < start:
            continue
        prev = data
        for attempt in range(max_retries + 1):
            data, okc = _p_rpotrf_ft_step(prev, lay=lay, mesh=a.mesh, j=j,
                                          gemm_backend=gemm_backend,
                                          plan=plan, active=(attempt == 0))
            if int(okc) == lay.p * lay.q:
                report.retries += attempt
                break
            report.detections += 1
            report.sites.append(("dist.panel", s))
            _obs_metrics.inc("ft.detections")
            _obs_metrics.inc("ft.retries")
        else:
            report.failed = True
            from repro.ft.abft import AbftError
            raise AbftError(f"p_rpotrf_ft: step {s} broadcast mismatch "
                            f"persisted across {max_retries + 1} attempts")
        if checkpoint_dir is not None:
            _ckpt_save(checkpoint_dir, s + 1, {"a": data},
                       keep_last=keep_last)
        if _stop_after is not None and s + 1 >= _stop_after \
                and s + 1 < len(steps):
            return None, report
    data = _p_keep_mask(data, lay=lay, mesh=a.mesh, algo="potrf")
    return a.with_data(data), report


def p_rgetrf_ft(a: DistMatrix, gemm_backend: str = "xla_quire", plan=None,
                max_retries: int = 2, checkpoint_dir=None,
                resume: bool = False, keep_last: int = 2,
                _stop_after=None):
    """Checksum-protected distributed partial-pivot LU: returns
    (LU DistMatrix, ipiv, FtReport) — contract, checkpointing, and the
    ``_stop_after`` kill hook as in ``p_rpotrf_ft`` (which see);
    returns (None, None, report) when the kill hook fires."""
    from repro import ft
    lay = a.layout
    mn = min(lay.m, lay.n)
    data = a.data
    ipiv = jnp.zeros((mn,), jnp.int32)
    report = ft.FtReport()
    start = 0
    if checkpoint_dir is not None and resume:
        start, state = _ckpt_restore(checkpoint_dir,
                                     {"a": data, "ipiv": ipiv})
        if state is not None:
            data = jax.device_put(state["a"], data.sharding)
            ipiv = jnp.asarray(state["ipiv"], jnp.int32)
    steps = list(range(0, mn, lay.nb))
    for s, j in enumerate(steps):
        if s < start:
            continue
        prev, ipiv_prev = data, ipiv
        for attempt in range(max_retries + 1):
            data, ipiv, okc = _p_rgetrf_ft_step(
                prev, ipiv_prev, lay=lay, mesh=a.mesh, j=j,
                gemm_backend=gemm_backend, plan=plan,
                active=(attempt == 0))
            if int(okc) == lay.p * lay.q:
                report.retries += attempt
                break
            report.detections += 1
            report.sites.append(("dist.panel", s))
            _obs_metrics.inc("ft.detections")
            _obs_metrics.inc("ft.retries")
        else:
            report.failed = True
            from repro.ft.abft import AbftError
            raise AbftError(f"p_rgetrf_ft: step {s} broadcast mismatch "
                            f"persisted across {max_retries + 1} attempts")
        if checkpoint_dir is not None:
            _ckpt_save(checkpoint_dir, s + 1, {"a": data, "ipiv": ipiv},
                       keep_last=keep_last)
        if _stop_after is not None and s + 1 >= _stop_after \
                and s + 1 < len(steps):
            return None, None, report
    data = _p_keep_mask(data, lay=lay, mesh=a.mesh, algo="getrf")
    return a.with_data(data), ipiv, report
