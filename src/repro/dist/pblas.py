"""PBLAS over posit words: SUMMA-style distributed Rgemm (+ the quire
matrix-vector residual the IR solvers reduce across devices).

``pdgemm`` computes C = alpha * A @ B + beta * C with A (M, K), B (K, N),
C (M, N) block-cyclic over the P x Q grid (dist/layout.py), per-device
products running through the ordinary ``kernels.ops.rgemm`` backends.
Two schedules, both **bit-identical to single-device rgemm** (the
acceptance contract, pinned in tests/test_dist.py and asserted by
benchmarks/bench_dist.py before any speedup is reported):

* **owner-computes** (default): one all_gather of A's row strip along
  "col" and of B's column strip along "row" (the batched form of SUMMA's
  per-panel broadcasts), then ONE local ``rgemm`` over the full K on the
  C-tile owner.  Every output element is produced by the same backend
  from the same full-K row/column vectors as on a single device, so the
  result is elementwise identical for EVERY backend — including the f32-
  and f64-accumulating ones whose partial sums would not re-associate.
  Compute per device is (M/P)(N/Q)K — perfect O(PQ) scaling of the
  multiply work; memory is the ScaLAPACK panel bound O((M/P + N/Q) K).

* **k_split** (quire backend only): each device deposits its LOCAL K
  slab into int64 quire limb planes (``quire.quire_gemm_limbs``, the
  pre-rounding hook) for all N output columns in dist column order; the
  cross-device reduction is a ``psum_scatter`` of those integer planes
  across "col", sent as int32 pieces (``limb_psum_scatter``; a TPU
  cannot reduce-scatter int64) — each device receives exactly its own
  tile's limbs —
  and the single posit rounding happens after it.  Bit-identical to
  single-device ``quire_gemm`` *by construction* (integer limb adds are
  associative; no float partial-sum scheme can say this).  This is the
  deep-K schedule: A never moves (each device consumes its own K slab —
  owner-computes gathers O(lm * K) A words per device), B moves by
  slab-exchange all_to_all (O(K * N / Q), not replication), and the
  price is the O(lm * Q*ln * L) limb-plane scatter-reduce — worth it
  when K >> N * L, i.e. deep reductions with narrow outputs.  The IR
  residual (N = nrhs, x already replicated so NOTHING is gathered) is
  exactly that shape; it uses the plain-psum form
  (``launch.collectives.limb_psum``) since its output has no column
  partition.

``p_residual_quire`` is the K-split path specialized to the refinement
residual r = b - A (x + x_lo): one exact fused dot per row, deposited
across the grid's column axis, psum-reduced in limb space, rounded once —
the distributed drop-in for ``lapack.refine.residual_quire``.

``fmt`` (static, default Posit(32,2)) selects the posit format of every
word on the grid: the owner-computes schedule threads it straight into
the local ``rgemm`` (so any format any backend), and the k_split limb
planes take their limb count from the format's quire (4 limbs for
p16e1/p8e2 vs 16 for p32e2 — the psum payload shrinks 4x, same
bit-identity argument).  One format per call; mixed-format distributed
GEMM converts at the boundary like the single-device path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.core.formats import P32E2, PositFormat
from repro.core import posit
from repro.kernels.ops import rgemm
from repro.launch.collectives import limb_psum, limb_psum_scatter
from repro.obs import metrics as _obs_metrics
from repro.obs import numerics as _obs_numerics
from repro.obs import trace as _obs_trace
from repro.quire import (Quire, q_to_posit, qadd_posit, quire_gemm_limbs,
                         quire_limbs)
from repro.dist.layout import (BlockCyclic, DistMatrix, grid_coords,
                               local_gidx, unshuffle)

_SPEC = jax.sharding.PartitionSpec("row", "col")
_REP = jax.sharding.PartitionSpec()


def _gather_rows_fullK(a_loc, lay_a: BlockCyclic):
    """(lm, lk) local tile of A -> (lm, K) full-K rows for this device's
    block-cyclic rows: all_gather A's column strip along "col" and
    unpermute the cyclic column order."""
    g = jax.lax.all_gather(a_loc.T, "col", tiled=False)   # (Q, lk, lm)
    return unshuffle(g, lay_a.q, lay_a.nb).T[:, :lay_a.n]


def _gather_cols_fullK(b_loc, lay_b: BlockCyclic):
    """(lk, ln) local tile of B -> (K, ln) full-K columns."""
    g = jax.lax.all_gather(b_loc, "row", tiled=False)     # (P, lk, ln)
    return unshuffle(g, lay_b.p, lay_b.nb)[:lay_b.m]


def _dist_col_order(lay: BlockCyclic):
    """Static global-column index for every dist-order column position
    (c', t, v) -> (c' + Q*t)*nb + v; padding positions map past n."""
    idx = []
    for cp in range(lay.q):
        for t in range(lay.lnb):
            base = (cp + lay.q * t) * lay.nb
            idx.extend(range(base, base + lay.nb))
    return jnp.asarray(idx, jnp.int32)


def _k_slab_limbs(a_loc, b_loc, lay_a: BlockCyclic, lay_b: BlockCyclic,
                  negate: bool, fmt: PositFormat = P32E2):
    """Split-K deposit: this device's K slab (A's local columns, global
    k ≡ this grid column mod Q) against ALL N output columns, arranged
    in dist column order.  The (lm, Q*ln, L) limb planes reduce across
    "col" with ONE psum_scatter — integer limb adds, so the merged state
    is bit-identical to a single-device full-K deposit — and the scatter
    hands each device back exactly its own (lm, ln, L) tile.

    B movement is slab-exchange, not replication: gather my columns'
    full K along "row" (O(K * ln) words), regroup the K rows into the Q
    cyclic slabs (static permutation), then ONE all_to_all along "col" —
    each device ends holding only its (lk, N) slab, O(K * N / Q) words.
    """
    _, c = grid_coords()
    b_full = _gather_cols_fullK(b_loc, lay_b)             # (K, ln)
    # pad + permute K rows into dist-slab order (slab c' = rows k ≡ c'
    # mod Q, each of length lk = lay_a.ln); padding rows masked to the
    # zero word so they deposit nothing and can't poison nar.
    kslab = _dist_col_order(lay_a)                        # (Q*lk,) static
    b_slabs = jnp.where((kslab < lay_a.n)[:, None],
                        b_full[jnp.clip(kslab, 0, lay_b.m - 1)], 0)
    # slab exchange: send slab c' of my columns to device c'; receive my
    # slab from every column peer -> (lk, Q*ln), columns grouped by
    # source = exactly dist column order.
    b_dist = jax.lax.all_to_all(b_slabs, "col", split_axis=0, concat_axis=1,
                                tiled=True)
    limbs, nar = quire_gemm_limbs(a_loc, b_dist, fmt, negate=negate)
    return limb_psum_scatter(limbs, nar, "col", 1)        # (lm, ln, L)


def _pdgemm_local(a_loc, b_loc, c_loc, lay_a, lay_b, alpha, beta,
                  backend, k_split, fmt: PositFormat = P32E2):
    if k_split:
        if backend != "quire_exact":
            raise ValueError("k_split pdgemm is the quire limb-plane "
                             "schedule; use backend='quire_exact'")
        a_in = a_loc
        if alpha not in (1.0, -1.0, 1, -1):
            alpha_p = posit.word_of(alpha, fmt)
            a_in = posit.mul(alpha_p, a_loc, fmt, backend="fast")
        limbs, nar = _k_slab_limbs(a_in, b_loc, lay_a, lay_b,
                                   negate=alpha in (-1.0, -1), fmt=fmt)
        q = Quire(limbs=limbs, nar=nar)
        if beta in (1.0, 1):
            q = qadd_posit(q, c_loc, fmt)
        elif beta not in (0.0, 0):
            beta_p = posit.word_of(beta, fmt)
            q = qadd_posit(q, posit.mul(beta_p, c_loc, fmt, backend="fast"),
                           fmt)
        return q_to_posit(q, fmt)
    a_full = _gather_rows_fullK(a_loc, lay_a)             # (lm, K)
    b_full = _gather_cols_fullK(b_loc, lay_b)             # (K, ln)
    return rgemm(a_full, b_full, c_loc, alpha=alpha, beta=beta,
                 backend=backend, fmt=fmt)


@functools.partial(jax.jit, static_argnames=("lay_a", "lay_b", "mesh",
                                             "alpha", "beta", "backend",
                                             "k_split", "fmt"))
def _pdgemm_sharded(a, b, c, *, lay_a, lay_b, mesh, alpha, beta,
                    backend, k_split, fmt):
    fn = functools.partial(_pdgemm_local, lay_a=lay_a, lay_b=lay_b,
                           alpha=alpha, beta=beta,
                           backend=backend, k_split=k_split, fmt=fmt)
    return shard_map(fn, mesh=mesh, in_specs=(_SPEC, _SPEC, _SPEC),
                     out_specs=_SPEC, check_vma=False)(a, b, c)


def pdgemm_collective_plan(lay_a: BlockCyclic, lay_b: BlockCyclic,
                           k_split: bool = False,
                           fmt: PositFormat = P32E2) -> dict[str, int]:
    """Static PER-DEVICE collective byte plan of one ``pdgemm`` dispatch:
    {collective kind -> result bytes}, derived purely from the layouts.
    Same accounting convention as ``launch.hlo_analysis.collective_bytes``
    (sum of per-device collective RESULT shapes in the SPMD module), so
    the two are directly comparable — ``benchmarks/roofline.py
    --check-pdgemm`` asserts they and the runtime obs counters agree.

    owner-computes: A row strip gathered along "col" ((Q, lk, lm) i32)
    + B column strip along "row" ((P, lk, ln) i32).  k_split: B strip
    gather, the (lk, Q*ln) i32 slab-exchange all_to_all, and the
    limb-plane reduce-scatter pair: (lm, ln, 3L) i32 wire pieces of the
    i64 limbs (``limb_psum_scatter``) + (lm, ln) i32 NaR flags.
    """
    if not k_split:
        return {"all-gather": 4 * (lay_a.q * lay_a.ln * lay_a.lm
                                   + lay_b.p * lay_b.lm * lay_b.ln)}
    lay_c = BlockCyclic(m=lay_a.m, n=lay_b.n, nb=lay_a.nb,
                        p=lay_a.p, q=lay_a.q)
    L = quire_limbs(fmt)
    return {
        "all-gather": 4 * lay_b.p * lay_b.lm * lay_b.ln,
        "all-to-all": 4 * lay_a.ln * lay_a.q * lay_b.ln,
        "reduce-scatter": lay_c.lm * lay_c.ln * (12 * L + 4),
    }


def p_residual_plan(lay: BlockCyclic, nrhs: int = 1,
                    fmt: PositFormat = P32E2) -> dict[str, int]:
    """Static PER-DEVICE collective byte plan of one ``p_residual_quire``
    dispatch (same convention as ``pdgemm_collective_plan``): the
    (lm, nrhs, L) i64 + (lm, nrhs) i32 limb psum (all-reduce) and the
    (P, lm, nrhs) i32 row gather of the rounded residual."""
    L = quire_limbs(fmt)
    return {
        "all-reduce": lay.lm * nrhs * (8 * L + 4),
        "all-gather": 4 * lay.p * lay.lm * nrhs,
    }


def _record_collectives(name: str, plan: dict[str, int]) -> None:
    """Counter per collective kind: ``name.<kind>.bytes`` (per-device)."""
    for kind, nbytes in plan.items():
        _obs_metrics.inc(f"{name}.{kind}.bytes", nbytes)
    _obs_metrics.inc(f"{name}.calls")


def pdgemm(a: DistMatrix, b: DistMatrix, c: DistMatrix | None = None,
           alpha=1.0, beta=0.0, backend: str = "xla_quire",
           k_split: bool = False, fmt: PositFormat = P32E2) -> DistMatrix:
    """Distributed C = alpha * A @ B + beta * C, one jitted dispatch.

    ``backend`` is any ``rgemm`` backend; ``k_split=True`` selects the
    quire limb-plane psum schedule (quire_exact only).  ``fmt`` is the
    posit format of every word (both schedules; the owner-computes
    schedule simply hands it to the local ``rgemm``).  The result is
    bit-identical to single-device ``rgemm`` with the same ``fmt`` on
    the gathered operands in either schedule.
    """
    la, lb = a.layout, b.layout
    if (la.n, la.nb, la.p, la.q) != (lb.m, lb.nb, lb.p, lb.q):
        raise ValueError(f"incompatible layouts {la} @ {lb}")
    lay_c = BlockCyclic(m=la.m, n=lb.n, nb=la.nb, p=la.p, q=la.q)
    if c is None:
        sharding = jax.sharding.NamedSharding(a.mesh, _SPEC)
        c_data = jnp.zeros((lay_c.p * lay_c.lm, lay_c.q * lay_c.ln),
                           jnp.int32)
        c_data = jax.device_put(c_data, sharding)
    else:
        if c.layout != lay_c:
            raise ValueError(f"C layout {c.layout} != {lay_c}")
        c_data = c.data
    if _obs_numerics.active(a.data, b.data, c_data):
        with _obs_trace.span("pdgemm", m=la.m, k=la.n, n=lb.n,
                             grid=f"{la.p}x{la.q}", backend=backend,
                             k_split=k_split, fmt=fmt.name):
            out = _pdgemm_sharded(a.data, b.data, c_data, lay_a=la, lay_b=lb,
                                  mesh=a.mesh, alpha=alpha, beta=beta,
                                  backend=backend, k_split=k_split, fmt=fmt)
        _record_collectives("dist.pdgemm",
                            pdgemm_collective_plan(la, lb, k_split=k_split,
                                                   fmt=fmt))
        _obs_numerics.record_numerics("dist.pdgemm.out", out, fmt)
    else:
        out = _pdgemm_sharded(a.data, b.data, c_data, lay_a=la, lay_b=lb,
                              mesh=a.mesh, alpha=alpha, beta=beta,
                              backend=backend, k_split=k_split, fmt=fmt)
    return DistMatrix(data=out, layout=lay_c, mesh=a.mesh)


# --------------------------------------------------------------------------
# distributed quire residual (matrix-vector / multi-RHS K-split)
# --------------------------------------------------------------------------

def _residual_local(a_loc, x, b, x_lo, lay: BlockCyclic,
                    fmt: PositFormat = P32E2):
    """r = b - A (x + x_lo), one exact fused dot per row, K split across
    the grid columns and reduced in limb space; output replicated."""
    r_, c = grid_coords()
    kidx = local_gidx(lay, 1, c)                          # (lk,)
    valid = (kidx < lay.n)[:, None]
    kc = jnp.clip(kidx, 0, lay.n - 1)
    x_sel = jnp.where(valid, x[kc], 0)                    # (lk, nrhs)
    if x_lo is None:
        a2, x2 = a_loc, x_sel
    else:
        # the pair residual b - A*hi - A*lo as ONE fused reduction: the
        # same [A | A] @ [hi; lo] concatenation as residual_quire, with
        # the K halves living on the same device slab.
        lo_sel = jnp.where(valid, x_lo[kc], 0)
        a2 = jnp.concatenate([a_loc, a_loc], axis=1)
        x2 = jnp.concatenate([x_sel, lo_sel], axis=0)
    limbs, nar = quire_gemm_limbs(a2, x2, fmt, negate=True)
    limbs, nar = limb_psum(limbs, nar, "col")
    gidx = local_gidx(lay, 0, r_)                         # (lm,)
    rvalid = (gidx < lay.m)[:, None]
    b_my = jnp.where(rvalid, b[jnp.clip(gidx, 0, lay.m - 1)], 0)
    q = Quire(limbs=limbs, nar=nar & rvalid)
    q = qadd_posit(q, b_my, fmt)
    r_rows = q_to_posit(q, fmt)                           # (lm, nrhs)
    full = unshuffle(jax.lax.all_gather(r_rows, "row", tiled=False),
                     lay.p, lay.nb)                       # (P*lm, nrhs)
    return full[:lay.m]


@functools.partial(jax.jit, static_argnames=("lay", "mesh", "pair", "fmt"))
def _residual_sharded(a, x, b, x_lo, *, lay, mesh, pair, fmt):
    fn = lambda ad, xd, bd, ld: _residual_local(ad, xd, bd,
                                                ld if pair else None, lay,
                                                fmt)
    return shard_map(fn, mesh=mesh, in_specs=(_SPEC, _REP, _REP, _REP),
                     out_specs=_REP, check_vma=False)(a, x, b, x_lo)


def p_residual_quire(a: DistMatrix, x_p: jax.Array, b_p: jax.Array,
                     x_lo_p: jax.Array | None = None,
                     fmt: PositFormat = P32E2) -> jax.Array:
    """Distributed drop-in for ``lapack.refine.residual_quire``: each
    component of r = b - A (x + x_lo) is an exact fused dot product
    rounded ONCE, with the K reduction psum-ed across the grid in int64
    limb planes — bit-identical to the single-device quire residual by
    limb-add associativity.  x/b replicated (n,) or (n, nrhs); returns
    the replicated residual of the same shape."""
    lay = a.layout
    x_p = jnp.asarray(x_p, jnp.int32)
    b_p = jnp.asarray(b_p, jnp.int32)
    vec = x_p.ndim == 1
    x2 = x_p[:, None] if vec else x_p
    b2 = b_p[:, None] if vec else b_p
    pair = x_lo_p is not None
    lo2 = (jnp.asarray(x_lo_p, jnp.int32)[:, None] if vec
           else jnp.asarray(x_lo_p, jnp.int32)) if pair else jnp.zeros_like(x2)
    if _obs_numerics.active(a.data, x2, b2, lo2):
        with _obs_trace.span("p_residual", n=lay.n, nrhs=int(x2.shape[1]),
                             grid=f"{lay.p}x{lay.q}", fmt=fmt.name):
            r = _residual_sharded(a.data, x2, b2, lo2, lay=lay, mesh=a.mesh,
                                  pair=pair, fmt=fmt)
        _record_collectives("dist.p_residual",
                            p_residual_plan(lay, nrhs=int(x2.shape[1]),
                                            fmt=fmt))
    else:
        r = _residual_sharded(a.data, x2, b2, lo2, lay=lay, mesh=a.mesh,
                              pair=pair, fmt=fmt)
    return r[:, 0] if vec else r


# --------------------------------------------------------------------------
# checksum-protected distributed GEMM (exact ABFT, repro.ft — DESIGN.md §11)
# --------------------------------------------------------------------------

def _pdgemm_ft_local(a_loc, b_loc, c_loc, *, lay_a, lay_b, alpha, beta,
                     backend, fmt, plan, active):
    """Owner-computes pdgemm with both operand gathers carrying exact
    checksum strips: A's per-row and B's per-column value sums are
    deposited from the LOCAL tiles (zero words in the padding deposit
    nothing) and psum-reduced across the axis the gather spans — limb
    adds are associative, so the strip equals the checksum of the
    gathered full-K operand exactly.  Every device then recomputes the
    checksums of the operands it actually received and compares exactly;
    the conjunction psums grid-wide.  Injection sites 'pdgemm.a' /
    'pdgemm.b' corrupt one device's gathered copy (dev = r*Q + c)."""
    from repro.ft import abft
    from repro.quire.quire import Quire, q_renorm
    r, c = grid_coords()
    dev = r * lay_a.q + c
    al, anar = abft._word_limbs(a_loc, fmt)               # (lm, lk, L)
    arow = jax.lax.psum(jnp.sum(al, axis=1), "col")       # (lm, L)
    arow_nar = jax.lax.psum(jnp.sum(anar.astype(jnp.int32), axis=1),
                            "col") > 0
    arow_w = jax.lax.psum(jnp.sum(a_loc.astype(jnp.int64), axis=1), "col")
    qa = q_renorm(Quire(limbs=arow, nar=arow_nar))
    bl, bnar = abft._word_limbs(b_loc, fmt)               # (lk, ln, L)
    bcol = jax.lax.psum(jnp.sum(bl, axis=0), "row")       # (ln, L)
    bcol_nar = jax.lax.psum(jnp.sum(bnar.astype(jnp.int32), axis=0),
                            "row") > 0
    bcol_w = jax.lax.psum(jnp.sum(b_loc.astype(jnp.int64), axis=0), "row")
    qb = q_renorm(Quire(limbs=bcol, nar=bcol_nar))

    a_full = _gather_rows_fullK(a_loc, lay_a)             # (lm, K)
    b_full = _gather_cols_fullK(b_loc, lay_b)             # (K, ln)
    if active and plan is not None:
        a_full = plan.words("pdgemm.a", 0, a_full, fmt, dev=dev)
        b_full = plan.words("pdgemm.b", 0, b_full, fmt, dev=dev)
    ga, ga_nar = abft.word_sums(a_full, fmt, axis=1)
    gb, gb_nar = abft.word_sums(b_full, fmt, axis=0)
    ok = (jnp.all(ga == qa.limbs) & jnp.all(ga_nar == qa.nar)
          & jnp.all(jnp.sum(a_full.astype(jnp.int64), axis=1) == arow_w)
          & jnp.all(gb == qb.limbs) & jnp.all(gb_nar == qb.nar)
          & jnp.all(jnp.sum(b_full.astype(jnp.int64), axis=0) == bcol_w))
    okc = jax.lax.psum(jax.lax.psum(ok.astype(jnp.int32), "col"), "row")
    out = rgemm(a_full, b_full, c_loc, alpha=alpha, beta=beta,
                backend=backend, fmt=fmt)
    return out, okc


@functools.partial(jax.jit, static_argnames=("lay_a", "lay_b", "mesh",
                                             "alpha", "beta", "backend",
                                             "fmt", "plan", "active"))
def _pdgemm_ft_sharded(a, b, c, *, lay_a, lay_b, mesh, alpha, beta,
                       backend, fmt, plan, active):
    fn = functools.partial(_pdgemm_ft_local, lay_a=lay_a, lay_b=lay_b,
                           alpha=alpha, beta=beta, backend=backend, fmt=fmt,
                           plan=plan, active=active)
    return shard_map(fn, mesh=mesh, in_specs=(_SPEC, _SPEC, _SPEC),
                     out_specs=(_SPEC, _REP), check_vma=False)(a, b, c)


def pdgemm_ft(a: DistMatrix, b: DistMatrix, c: DistMatrix | None = None,
              alpha=1.0, beta=0.0, backend: str = "xla_quire",
              fmt: PositFormat = P32E2, plan=None, max_retries: int = 2):
    """Checksum-protected owner-computes ``pdgemm``: returns
    (C DistMatrix, FtReport), C bit-identical to ``pdgemm`` fault-free
    and after recovery.  A failed grid-wide verify re-dispatches the
    whole GEMM (gathers are the unit of recovery here — the k_split
    limb-plane schedule is already integrity-checked end to end by the
    repo's bit-identity contract and has no gathered replica to
    corrupt, so it has no _ft variant).  Exhaustion raises
    ``AbftError`` (repro.ft.abft)."""
    from repro import ft
    la, lb = a.layout, b.layout
    if (la.n, la.nb, la.p, la.q) != (lb.m, lb.nb, lb.p, lb.q):
        raise ValueError(f"incompatible layouts {la} @ {lb}")
    lay_c = BlockCyclic(m=la.m, n=lb.n, nb=la.nb, p=la.p, q=la.q)
    if c is None:
        sharding = jax.sharding.NamedSharding(a.mesh, _SPEC)
        c_data = jnp.zeros((lay_c.p * lay_c.lm, lay_c.q * lay_c.ln),
                           jnp.int32)
        c_data = jax.device_put(c_data, sharding)
    else:
        if c.layout != lay_c:
            raise ValueError(f"C layout {c.layout} != {lay_c}")
        c_data = c.data
    report = ft.FtReport()
    for attempt in range(max_retries + 1):
        out, okc = _pdgemm_ft_sharded(a.data, b.data, c_data, lay_a=la,
                                      lay_b=lb, mesh=a.mesh, alpha=alpha,
                                      beta=beta, backend=backend, fmt=fmt,
                                      plan=plan, active=(attempt == 0))
        if int(okc) == la.p * la.q:
            report.retries = attempt
            return DistMatrix(data=out, layout=lay_c, mesh=a.mesh), report
        report.detections += 1
        report.sites.append(("pdgemm", 0))
        _obs_metrics.inc("ft.detections")
        _obs_metrics.inc("ft.retries")
    report.failed = True
    from repro.ft.abft import AbftError
    raise AbftError(f"pdgemm_ft: gather mismatch persisted across "
                    f"{max_retries + 1} attempts")
