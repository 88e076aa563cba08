"""PR-2 fast paths vs their pre-refactor baselines.

Every optimization in this PR is a *schedule* change (chunked integer
limb adds, fused single-dispatch drivers, decode-once chains, in-kernel
encode), so the contract everywhere is BIT-IDENTITY, not tolerance —
except the cross-backend rgemm parity block, where f32 accumulation is
compared against the exact quire with the kernel's analytic error bound.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import posit as P
from repro.core.formats import P16E1, P32E2
from repro import quire as Q
from repro.kernels.ops import rgemm
from repro.kernels.posit_gemm import (encode_p32_f32, posit_gemm,
                                      posit_gemm_f32)
from repro.lapack import blas, decomp
from repro.lapack.blas import rtrsm_left_lower, rtrsm_right_lowerT


def _posits(rng, shape, lo=-8, hi=8, fmt=P32E2):
    x = rng.standard_normal(shape) * np.exp2(rng.uniform(lo, hi, shape))
    return P.from_float64(jnp.asarray(x), fmt)


# --------------------------------------------------------------------------
# K-chunked quire GEMM / quire_dot: any schedule is bit-identical
# --------------------------------------------------------------------------

def test_quire_gemm_chunking_bit_identical():
    rng = np.random.default_rng(0)
    for (m, k, n) in ((7, 33, 5), (16, 64, 16), (3, 100, 9)):
        ap = _posits(rng, (m, k), -30, 30)
        bp = _posits(rng, (k, n), -30, 30)
        cp = _posits(rng, (m, n))
        ref = np.asarray(Q.quire_gemm(ap, bp, cp, negate=True,
                                      kc=1, unroll=1))
        for kc, ur in ((4, 1), (8, 4), (16, 2), (64, 1)):
            got = np.asarray(Q.quire_gemm(ap, bp, cp, negate=True,
                                          kc=kc, unroll=ur))
            assert np.array_equal(ref, got), (m, k, n, kc, ur)


def test_quire_dot_chunking_bit_identical():
    rng = np.random.default_rng(1)
    for fmt in (P32E2, P16E1):
        ap = _posits(rng, (4, 300), -20, 20, fmt)
        bp = _posits(rng, (4, 300), -20, 20, fmt)
        ip = _posits(rng, (4,), fmt=fmt)
        ref = np.asarray(Q.quire_dot(ap, bp, fmt, init_p=ip, negate=True,
                                     kc=300))
        for kc in (7, 64, 128, None):
            got = np.asarray(Q.quire_dot(ap, bp, fmt, init_p=ip,
                                         negate=True, kc=kc))
            assert np.array_equal(ref, got), (fmt.name, kc)


# --------------------------------------------------------------------------
# rgemm backend parity: non-square, non-block-multiple shapes
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["pallas_split3", "pallas_split3_comp",
                                     "xla_quire"])
@pytest.mark.parametrize("shape", [(65, 17, 130), (33, 65, 9)])
def test_rgemm_backend_parity_odd_shapes(backend, shape):
    """Every accumulation backend agrees with the exact quire to the f32
    kernel's analytic bound on shapes that exercise padding/slicing."""
    m, k, n = shape
    rng = np.random.default_rng(2)
    ap = _posits(rng, (m, k), -4, 4)
    bp = _posits(rng, (k, n), -4, 4)
    exact = P.to_float64(rgemm(ap, bp, backend="quire_exact"))
    got = P.to_float64(rgemm(ap, bp, backend=backend, block=64))
    av = np.asarray(P.to_float64(ap))
    bv = np.asarray(P.to_float64(bp))
    scale = np.outer(np.linalg.norm(av, axis=1), np.linalg.norm(bv, axis=0))
    err = np.abs(np.asarray(got) - np.asarray(exact)) / np.maximum(scale,
                                                                   1e-300)
    assert err.max() < np.sqrt(k) * 8e-8, (backend, shape, err.max())


@pytest.mark.parametrize("backend", ["pallas_split3", "pallas_split3_comp",
                                     "xla_quire"])
def test_rgemm_backend_parity_trailing_update(backend):
    """alpha=-1/beta=1 — the factorizations' trailing-update form."""
    m, k, n = 65, 130, 17
    rng = np.random.default_rng(3)
    ap = _posits(rng, (m, k), -2, 2)
    bp = _posits(rng, (k, n), -2, 2)
    cp = _posits(rng, (m, n), -2, 2)
    exact = np.asarray(P.to_float64(rgemm(ap, bp, cp, alpha=-1.0, beta=1.0,
                                          backend="quire_exact")))
    got = np.asarray(P.to_float64(rgemm(ap, bp, cp, alpha=-1.0, beta=1.0,
                                        backend=backend, block=64)))
    av = np.asarray(P.to_float64(ap))
    bv = np.asarray(P.to_float64(bp))
    cv = np.asarray(P.to_float64(cp))
    scale = (np.outer(np.linalg.norm(av, axis=1),
                      np.linalg.norm(bv, axis=0)) + np.abs(cv))
    err = np.abs(got - exact) / np.maximum(scale, 1e-300)
    assert err.max() < np.sqrt(k) * 8e-8, (backend, err.max())


# --------------------------------------------------------------------------
# fused in-kernel posit encode
# --------------------------------------------------------------------------

def test_encode_p32_f32_matches_from_float32_bits():
    rng = np.random.default_rng(4)
    x = (rng.standard_normal(100000)
         * np.exp2(rng.uniform(-148, 130, 100000))).astype(np.float32)
    specials = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 1e-45,
                         -1e-45, 2.0 ** -126, 2.0 ** 119, 2.0 ** -120,
                         1.5 * 2.0 ** 119, 3.4e38], np.float32)
    # every f32 exponent x mantissa corners, both signs
    exps = np.arange(0, 256, dtype=np.int64)
    mans = np.array([0, 1, 0x400000, 0x7FFFFF, 0x2AAAAA], np.int64)
    bits = ((exps[:, None] << 23) | mans[None, :]).reshape(-1)
    bits = bits.astype(np.uint32)
    corners = np.concatenate([bits, bits | np.uint32(1 << 31)]
                             ).view(np.float32)
    x = np.concatenate([x, specials, corners])
    got = np.asarray(encode_p32_f32(jnp.asarray(x)))
    want = np.asarray(P.from_float32_bits(jnp.asarray(x)))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("mode", ["split3", "split3_comp"])
def test_posit_gemm_fused_encode_bit_identical(mode):
    rng = np.random.default_rng(5)
    ap = _posits(rng, (128, 128), -6, 6)
    bp = _posits(rng, (128, 128), -6, 6)
    acc = posit_gemm_f32(ap, bp, mode=mode)
    for neg in (False, True):
        fused = np.asarray(posit_gemm(ap, bp, mode=mode, negate=neg))
        host = np.asarray(P.from_float32_bits(-acc if neg else acc))
        assert np.array_equal(fused, host), (mode, neg)


def test_rgemm_pallas_fused_matches_legacy_epilogue():
    """The fused path must equal the pre-refactor f32->f64->encode chain."""
    rng = np.random.default_rng(6)
    ap = _posits(rng, (40, 50), -4, 4)
    bp = _posits(rng, (50, 30), -4, 4)
    new = np.asarray(rgemm(ap, bp, backend="pallas_split3", block=64))
    ap_pad = jnp.pad(ap, ((0, 24), (0, 14)))
    bp_pad = jnp.pad(bp, ((0, 14), (0, 34)))
    acc = np.asarray(posit_gemm_f32(ap_pad, bp_pad, bm=64, bn=64, bk=64),
                     np.float64)[:40, :30]
    old = np.asarray(P.from_float64(jnp.asarray(acc)))
    assert np.array_equal(new, old)


# --------------------------------------------------------------------------
# fused-chain scalar ops and panels
# --------------------------------------------------------------------------

def test_chain_round_matches_word_roundtrip():
    rng = np.random.default_rng(7)
    for fmt in (P32E2, P16E1):
        x = rng.standard_normal(50000) * np.exp2(rng.uniform(-140, 140,
                                                             50000))
        x = np.concatenate([x, [0.0, -0.0, np.inf, -np.inf, np.nan,
                                5e-324, 2.0 ** 120, 2.0 ** -120,
                                1.5 * 2.0 ** 113, 2.0 ** 113]])
        got = np.asarray(P.chain_round(jnp.asarray(x), fmt))
        want = np.asarray(P.to_float64(P.from_float64(jnp.asarray(x), fmt),
                                       fmt))
        ok = (got == want) | (np.isnan(got) & np.isnan(want))
        assert ok.all(), (fmt.name, x[~ok][:5], got[~ok][:5], want[~ok][:5])


def test_chain_potf2_matches_legacy_panel():
    rng = np.random.default_rng(8)
    x = rng.standard_normal((48, 48))
    sp = P.from_float64(jnp.asarray(x.T @ x))
    assert np.array_equal(np.asarray(decomp.potf2(sp)),
                          np.asarray(decomp._potf2_words(sp)))


# jitted: an eager encode of a new shape compiles op by op, for seconds
_encode = jax.jit(P.from_float64)


@pytest.mark.parametrize("m, w, nar", [(64, 24, None), (200, 136, None),
                                       (80, 80, 5)])
def test_chain_panels_match_legacy_panels(m, w, nar):
    """getf2 against the word-domain masked panel.  At w = 136 and 80
    the trimmed scan walks five and three column tiles (the last
    shifted), and the pivots of the later tiles swap rows across the
    finished ones.  A NaR column leaves no pivot candidate, so its step
    swaps with row 0, above the diagonal; later tiles replay that swap."""
    rng = np.random.default_rng(8)
    g = rng.standard_normal((m, w)) * np.exp2(rng.uniform(-6, 6, (m, w)))
    gp = _encode(jnp.asarray(g))
    if nar is not None:
        gp = gp.at[:, nar].set(P32E2.nar_pattern)
    pn, ivn = decomp.getf2(gp, w)
    po, ivo = decomp._getf2_words(gp, w)
    assert np.array_equal(np.asarray(pn), np.asarray(po))
    assert np.array_equal(np.asarray(ivn), np.asarray(ivo))
    if w > 24:
        t = blas.PANEL_TILE
        assert blas.row_tiles(w, t) == (t, -(-w // t))
    if nar is not None:
        assert int(ivn[nar]) == 0
    elif w > 24:
        late = np.arange(blas.PANEL_TILE, w)
        assert (np.asarray(ivn)[late] > late).sum() > len(late) // 2


# per-op word-domain mul, sub and div, each jitted alone
_WORD_OPS = tuple(jax.jit(functools.partial(op, fmt=P32E2, backend="fast"))
                  for op in (P.mul, P.sub, P.div))


@pytest.mark.parametrize("n, unit_diag", [(24, False), (72, False),
                                          (72, True)])
def test_chain_trsm_matches_word_domain(n, unit_diag):
    """Pin the chain-form rtrsm_left_lower against a per-op word-domain
    reference (the pre-PR-2 semantics, reconstructed inline).  At n = 72
    the trimmed scan walks four 16-row tiles and a last one, shifted,
    that solves 8 new rows."""
    mul, sub, div = _WORD_OPS
    rng = np.random.default_rng(9)
    m = 8
    l64 = np.tril(rng.standard_normal((n, n))) + 4 * np.eye(n)
    b64 = rng.standard_normal((n, m))
    lp = _encode(jnp.asarray(l64))
    bp = _encode(jnp.asarray(b64))

    bw = np.asarray(bp).copy()
    lw = np.asarray(lp)
    for k in range(n):
        xk = bw[k] if unit_diag else np.asarray(
            div(jnp.asarray(bw[k]), jnp.asarray(lw[k, k])))
        upd = np.asarray(sub(jnp.asarray(bw),
                             mul(jnp.asarray(lw[:, k][:, None]),
                                 jnp.asarray(xk[None, :]))))
        bw[k + 1:, :] = upd[k + 1:, :]
        bw[k, :] = xk
    got = np.asarray(rtrsm_left_lower(lp, bp, unit_diag=unit_diag))
    assert np.array_equal(got, bw)
    assert blas.row_tiles(n, blas.TRSM_TILE)[1] == (1 if n == 24 else 5)


def test_chain_trsm_right_matches_word_domain():
    """rtrsm_right_lowerT against its word-domain reference."""
    mul, sub, div = _WORD_OPS
    rng = np.random.default_rng(9)
    n, m = 24, 8
    l11 = _encode(jnp.asarray(
        np.tril(rng.standard_normal((m, m))) + 4 * np.eye(m)))
    b2 = _encode(jnp.asarray(rng.standard_normal((n, m))))
    bw = np.asarray(b2).copy()
    lw = np.asarray(l11)
    for k in range(m):
        xk = np.asarray(div(jnp.asarray(bw[:, k]), jnp.asarray(lw[k, k])))
        upd = np.asarray(sub(jnp.asarray(bw),
                             mul(jnp.asarray(xk[:, None]),
                                 jnp.asarray(lw[:, k][None, :]))))
        bw[:, k + 1:] = upd[:, k + 1:]
        bw[:, k] = xk
    got = np.asarray(rtrsm_right_lowerT(b2, l11))
    assert np.array_equal(got, bw)


def test_rank1_work_shares():
    """The trimmed scans' element-steps against the masked ones: at
    n = 4096, nb = 256 the panel computes 36 of every 64 (32-column
    tiles), the trsm 34 of every 64 (16-row tiles); a block of one tile
    computes what the masked scan did."""
    work = decomp.rank1_work(4096, 4096, 256)
    assert work["panel"][0] / work["panel"][1] == 0.5625
    assert work["trsm"][0] / work["trsm"][1] == 0.53125
    assert work["trsm"][1] == 256 * 256 * sum(range(256, 4096, 256))
    small = decomp.rank1_work(96, 96, 24)
    assert small == {"panel": (24 * 24 * 240,) * 2,
                     "trsm": (24 * 24 * 144,) * 2}


# --------------------------------------------------------------------------
# beta = 0 never references C (BLAS convention) on non-faithful backends
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["pallas_split3", "xla_quire",
                                     "quire_exact"])
def test_rgemm_beta_zero_ignores_nar_in_c(backend):
    rng = np.random.default_rng(12)
    ap = _posits(rng, (8, 8))
    bp = _posits(rng, (8, 8))
    c_nar = jnp.full((8, 8), P32E2.nar_pattern, jnp.int32)
    got = np.asarray(rgemm(ap, bp, c_nar, beta=0.0, backend=backend,
                           block=64))
    ref = np.asarray(rgemm(ap, bp, backend=backend, block=64))
    assert np.array_equal(got, ref), backend
    assert not (got == P32E2.nar_pattern).any()


# --------------------------------------------------------------------------
# single-dispatch + batched drivers
# --------------------------------------------------------------------------

def test_single_dispatch_matches_loop_drivers():
    rng = np.random.default_rng(10)
    n = 96
    a64 = rng.standard_normal((n, n))
    ap = P.from_float64(jnp.asarray(a64))
    sp = P.from_float64(jnp.asarray(a64.T @ a64))
    lu_j, iv_j = decomp.rgetrf(ap, nb=32)
    lu_l, iv_l = decomp.rgetrf_loop(ap, nb=32)
    assert np.array_equal(np.asarray(lu_j), np.asarray(lu_l))
    assert np.array_equal(np.asarray(iv_j), np.asarray(iv_l))
    assert np.array_equal(np.asarray(decomp.rpotrf(sp, nb=32)),
                          np.asarray(decomp.rpotrf_loop(sp, nb=32)))


def test_ensemble_matches_study_same_backend():
    """backward_error_ensemble's POSIT cells == backward_error_study with
    the same gemm_backend (vmapping the posit programs changes no
    rounding).  The binary32 baseline is only compared loosely: XLA's
    batched f32 LU/Cholesky kernels round differently than the
    single-matrix forms."""
    from repro.lapack.error_eval import (backward_error_ensemble,
                                         backward_error_study)
    for algo in ("lu", "cholesky"):
        cells = backward_error_ensemble(32, [1.0, 100.0], algo=algo,
                                        seeds=(0,), nb=16,
                                        gemm_backend="xla_quire")
        for cell in cells:
            single = backward_error_study(32, cell.sigma, algo, seed=0,
                                          nb=16, gemm_backend="xla_quire")
            assert cell.e_posit == single.e_posit, (algo, cell.sigma)
            assert np.isclose(np.log10(cell.e_binary32),
                              np.log10(single.e_binary32), atol=1.0)


def test_batched_matches_single_bit_for_bit():
    rng = np.random.default_rng(11)
    n, batch = 48, 3
    mats = [rng.standard_normal((n, n)) for _ in range(batch)]
    gen = jnp.stack([P.from_float64(jnp.asarray(m)) for m in mats])
    spd = jnp.stack([P.from_float64(jnp.asarray(m.T @ m)) for m in mats])

    lub, ivb = decomp.rgetrf_batched(gen, nb=16)
    lb = decomp.rpotrf_batched(spd, nb=16)
    for i in range(batch):
        lu_i, iv_i = decomp.rgetrf(gen[i], nb=16)
        assert np.array_equal(np.asarray(lub[i]), np.asarray(lu_i)), i
        assert np.array_equal(np.asarray(ivb[i]), np.asarray(iv_i)), i
        assert np.array_equal(np.asarray(lb[i]),
                              np.asarray(decomp.rpotrf(spd[i], nb=16))), i
