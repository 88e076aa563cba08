"""Format-parametric stack: per-format oracle pinning, p32e2 bit-identity
vs PR 3, and the mixed-precision IR acceptance.

Three layers of guarantees:

1. **PR-3 golden pins** — sha256 of the posit words every p32e2 path
   produced BEFORE the format parameterization (captured from the PR-3
   tree on fixed seeds).  The refactor threads a static ``fmt`` whose
   constants fold at trace time, so every p32e2 word must be bit-identical
   — any hash change is a silent numerics change, not noise.
2. **Per-format oracle** — p16e1 and p8e2 encode/decode/round round-trips
   and the ``chain_round`` identity against the exact rational oracle
   (tests/posit_oracle.py), property-tested with hypothesis when
   installed and a deterministic fixed-seed sweep otherwise (same
   convention as test_posit_core.py).
3. **Mixed-precision acceptance** — ``rgesv_mp`` (p16e1 factor + p32e2
   quire refinement) reaches the same backward-error digits as
   ``rgesv_ir`` on the §5.1 sigma grid.
"""
import hashlib
from fractions import Fraction

import numpy as np
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                       # pragma: no cover
    HAVE_HYPOTHESIS = False

import posit_oracle as oracle
from repro.core import posit as P
from repro.core.formats import P16E1, P32E2, P8E2, get_format
from repro.kernels.ops import rgemm
from repro.kernels.posit_gemm import (decode_split_f32, encode_p16_f32,
                                      encode_p32_f32, encode_posit_f32)
from repro.lapack import decomp, error_eval, refine, solve


# --------------------------------------------------------------------------
# 1. PR-3 golden pins: every p32e2 path bit-identical to the pre-refactor
#    tree (hashes captured from commit 59ee04b on these exact seeds)
# --------------------------------------------------------------------------

GOLDEN_P32 = {
    "rgemm_xla_quire": "7c1a480e5c9a7d8c",
    "rgemm_quire_exact": "7c1a480e5c9a7d8c",
    "rgemm_faithful": "7a55e20adb994b6a",
    "rgemm_pallas_split3": "3fd3e072ff75b648",
    "rgemm_ab1": "e0d80ac10820c8d9",
    "rpotrf": "7e9165ec6ef12151",
    "rgetrf": "07c2e4fd338ae084",
    "rgetrs_q": "895d2a22713a1d75",
    "rgesv_ir": "d16b0c99d17ea97f",
    "rposv_ir": "42dd7e9cbf36c6c2",
    "residual": "36651c97a763a809",
}


def _h(*arrs):
    m = hashlib.sha256()
    for a in arrs:
        m.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return m.hexdigest()[:16]


def golden_words():
    """The pins' inputs (a general matrix A, the SPD matrix A^T A and a
    right-hand side b, as p32e2 words), converted with integer ops only so
    a TPU makes the same words: chip_smoke.py reproduces the pins from
    this recipe and the hashes above."""
    rng = np.random.default_rng(42)
    a64 = rng.standard_normal((48, 48))
    s64 = a64.T @ a64
    b64 = rng.standard_normal(48)
    import jax
    to_words = jax.jit(P.from_float64_bits)
    return tuple(to_words(x.view(np.int64)) for x in (a64, s64, b64))


@pytest.fixture(scope="module")
def golden_inputs():
    return golden_words()


def test_golden_rgemm_backends(golden_inputs):
    ap, sp, _ = golden_inputs
    for bk in ("xla_quire", "quire_exact", "faithful", "pallas_split3"):
        assert _h(rgemm(ap, ap, backend=bk)) == GOLDEN_P32[f"rgemm_{bk}"], bk
    got = rgemm(ap, ap, sp, alpha=-1.0, beta=1.0, backend="quire_exact")
    assert _h(got) == GOLDEN_P32["rgemm_ab1"]


def test_golden_factorizations_and_solves(golden_inputs):
    ap, sp, bp = golden_inputs
    assert _h(decomp.rpotrf(sp, nb=16)) == GOLDEN_P32["rpotrf"]
    lu, piv = decomp.rgetrf(ap, nb=16)
    assert _h(lu, piv) == GOLDEN_P32["rgetrf"]
    assert _h(solve.rgetrs(lu, piv, bp, quire=True)) == GOLDEN_P32["rgetrs_q"]


def test_golden_refinement(golden_inputs):
    ap, sp, bp = golden_inputs
    (xh, xl), _ = refine.rgesv_ir(ap, bp, iters=2, nb=16)
    assert _h(xh, xl) == GOLDEN_P32["rgesv_ir"]
    (yh, yl), _ = refine.rposv_ir(sp, bp, iters=2, nb=16)
    assert _h(yh, yl) == GOLDEN_P32["rposv_ir"]
    assert _h(refine.residual_quire(ap, xh, bp, xl)) == GOLDEN_P32["residual"]


# --------------------------------------------------------------------------
# 2. per-format oracle: encode/decode/round round-trip + chain_round
#    identity (p16e1, p8e2), hypothesis-or-deterministic
# --------------------------------------------------------------------------

def test_p8e2_exhaustive_decode_and_roundtrip():
    all_p = np.arange(-127, 128, dtype=np.int32)
    got = np.asarray(P.to_float64(all_p, P8E2))
    want = np.array([float(oracle.decode(int(p), 8, 2)) for p in all_p])
    assert np.array_equal(got, want)
    # decode -> encode round-trip is the identity on every pattern
    back = np.asarray(P.from_float64(got, P8E2))
    assert np.array_equal(back, all_p)


def test_p16e1_sampled_roundtrip():
    rng = np.random.default_rng(11)
    ps = rng.integers(-(1 << 15) + 1, 1 << 15, size=4000).astype(np.int32)
    vals = np.asarray(P.to_float64(ps, P16E1))
    want = np.array([float(oracle.decode(int(p), 16, 1)) for p in ps[:400]])
    assert np.array_equal(vals[:400], want)
    back = np.asarray(P.from_float64(vals, P16E1))
    assert np.array_equal(back, ps)


def _check_round_nearest(x: float, fmt):
    """from_float64 must equal the oracle's round-to-nearest pattern."""
    got = int(np.asarray(P.from_float64(np.array([x], np.float64), fmt))[0])
    want = oracle.encode(Fraction(x) if x else Fraction(0), fmt.nbits,
                         fmt.es)
    assert got == want, (fmt.name, x)


def _check_chain_round_identity(x: float, fmt):
    """chain_round == to_float64(from_float64(x)) — the fused-chain
    contract the panel kernels rely on, per format."""
    via_word = float(np.asarray(
        P.to_float64(P.from_float64(np.array([x], np.float64), fmt), fmt))[0])
    direct = float(np.asarray(P.chain_round(np.array([x], np.float64),
                                            fmt))[0])
    assert via_word == direct or (np.isnan(via_word) and np.isnan(direct)), (
        fmt.name, x, via_word, direct)


_FMTS = (P16E1, P8E2)

if HAVE_HYPOTHESIS:
    finite = st.floats(min_value=-1e12, max_value=1e12, allow_nan=False,
                       allow_infinity=False, allow_subnormal=False)

    @settings(max_examples=120, deadline=None)
    @given(finite, st.sampled_from(_FMTS))
    def test_round_nearest_matches_oracle(x, fmt):
        _check_round_nearest(x, fmt)

    @settings(max_examples=120, deadline=None)
    @given(finite, st.sampled_from(_FMTS))
    def test_chain_round_identity(x, fmt):
        _check_chain_round_identity(x, fmt)

else:
    # deterministic fallback: fixed-seed magnitudes + hand-picked edges so
    # the per-format pinning still runs where hypothesis isn't installed
    _RNG = np.random.default_rng(20260727)
    _XS = list(_RNG.standard_normal(80) * np.exp2(_RNG.uniform(-30, 30, 80)))
    _XS += [0.0, 1.0, -1.0, 0.75, 1.5, 2.0 ** 24, 2.0 ** -24, 1e12, -1e12,
            2.0 ** -28, 3.0, -3.0]

    def test_round_nearest_matches_oracle():
        for fmt in _FMTS:
            for x in _XS:
                _check_round_nearest(float(x), fmt)

    def test_chain_round_identity():
        for fmt in _FMTS:
            for x in _XS:
                _check_chain_round_identity(float(x), fmt)


def test_chain_round_fixpoint_on_lattice():
    """chain_round is the identity on every posit value (p8e2 exhaustive,
    p16e1 sampled) — no double rounding in the fused-chain panels."""
    vals8 = np.asarray(P.to_float64(np.arange(-127, 128, dtype=np.int32),
                                    P8E2))
    assert np.array_equal(np.asarray(P.chain_round(vals8, P8E2)), vals8)
    rng = np.random.default_rng(13)
    p16 = rng.integers(-(1 << 15) + 1, 1 << 15, size=3000).astype(np.int32)
    vals16 = np.asarray(P.to_float64(p16, P16E1))
    assert np.array_equal(np.asarray(P.chain_round(vals16, P16E1)), vals16)


def test_pconvert_round_trips_and_rounds():
    """Widening p16e1 -> p32e2 is exact (round-trips); narrowing is the
    correctly-rounded oracle encode of the exact wide value."""
    rng = np.random.default_rng(17)
    p16 = rng.integers(-(1 << 15) + 1, 1 << 15, size=2000).astype(np.int32)
    wide = P.pconvert(p16, P16E1, P32E2)
    back = np.asarray(P.pconvert(wide, P32E2, P16E1))
    assert np.array_equal(back, p16)
    p32 = rng.integers(-(1 << 31) + 1, 1 << 31, size=300).astype(np.int32)
    narrow = np.asarray(P.pconvert(p32, P32E2, P16E1))
    for p, g in zip(p32, narrow):
        want = oracle.encode(oracle.decode(int(p), 32, 2), 16, 1)
        assert int(g) == want, int(p)


def test_get_format_registry():
    assert get_format("p8e2") is P8E2
    assert get_format("p16e1") is P16E1
    with pytest.raises(KeyError):
        get_format("p64e3")


# --------------------------------------------------------------------------
# kernel codecs per format: in-kernel encode == from_float32_bits; decode
# split is exact
# --------------------------------------------------------------------------

def test_encode_pXX_f32_matches_bit_codec():
    rng = np.random.default_rng(19)
    x = (rng.standard_normal(30000) * np.exp2(rng.uniform(-40, 40, 30000))
         ).astype(np.float32)
    x = np.concatenate([x, np.array([0.0, 1.0, -1.0, np.inf, -np.inf,
                                     np.nan, 3.3e38, 1e-45], np.float32)])
    for fmt, enc in ((P32E2, encode_p32_f32), (P16E1, encode_p16_f32),
                     (P8E2, lambda v: encode_posit_f32(v, P8E2))):
        got = np.asarray(enc(jnp.asarray(x)))
        want = np.asarray(P.from_float32_bits(x, fmt))
        assert np.array_equal(got, want), fmt.name


def test_decode_split_f32_exact_per_format():
    rng = np.random.default_rng(23)
    for fmt in (P32E2, P16E1, P8E2):
        half = 1 << (fmt.nbits - 1)
        ps = rng.integers(-half + 1, half, 8000).astype(np.int32)
        hi, lo = decode_split_f32(jnp.asarray(ps), fmt)
        got = np.asarray(hi, np.float64) + np.asarray(lo, np.float64)
        want = np.asarray(P.to_float64(ps, fmt))
        big = np.abs(want) >= 2.0 ** -99
        assert np.array_equal(got[big], want[big]), fmt.name
        assert np.isnan(got[np.isnan(want)]).all(), fmt.name


# --------------------------------------------------------------------------
# format-parametric LAPACK: backends agree per format; p16e1 factorization
# reconstructs
# --------------------------------------------------------------------------

def test_rgemm_backends_agree_p16e1():
    """quire_exact == xla_quire words in p16e1 (both are exact-sum, one
    rounding); pallas fused epilogue agrees too (13-bit significands are
    f32-exact, so the f32 accumulator path is also a single rounding of
    an exact sum for small K)."""
    rng = np.random.default_rng(29)
    a = P.from_float64(jnp.asarray(rng.standard_normal((24, 24))), P16E1)
    b = P.from_float64(jnp.asarray(rng.standard_normal((24, 24))), P16E1)
    ref = np.asarray(rgemm(a, b, backend="quire_exact", fmt=P16E1))
    xla = np.asarray(rgemm(a, b, backend="xla_quire", fmt=P16E1))
    assert np.array_equal(ref, xla)
    pal = np.asarray(rgemm(a, b, backend="pallas_split3", block=8,
                           fmt=P16E1))
    truth = (np.asarray(P.to_float64(a, P16E1))
             @ np.asarray(P.to_float64(b, P16E1)))
    err = np.abs(np.asarray(P.to_float64(pal, P16E1)) - truth).max()
    assert err < 1e-2 * np.abs(truth).max()


def test_rpotrf_rgetrf_p16e1_reconstruct():
    rng = np.random.default_rng(31)
    n = 32
    x = rng.standard_normal((n, n))
    a64 = x.T @ x + n * np.eye(n)
    ap = P.from_float64(jnp.asarray(a64), P16E1)
    lp = decomp.rpotrf(ap, nb=16, fmt=P16E1)
    lv = np.asarray(P.to_float64(lp, P16E1))
    rec = lv @ lv.T
    a16 = np.asarray(P.to_float64(ap, P16E1))
    assert np.linalg.norm(rec - a16) / np.linalg.norm(a16) < 1e-2

    g64 = rng.standard_normal((n, n))
    gp = P.from_float64(jnp.asarray(g64), P16E1)
    lup, ipiv = decomp.rgetrf(gp, nb=16, fmt=P16E1)
    luv = np.asarray(P.to_float64(lup, P16E1))
    lm = np.tril(luv, -1) + np.eye(n)
    um = np.triu(luv)
    g16 = np.asarray(P.to_float64(gp, P16E1))
    pa = g16.copy()
    for kk, pv in enumerate(np.asarray(ipiv)):
        pa[[kk, pv], :] = pa[[pv, kk], :]
    assert np.linalg.norm(lm @ um - pa) / np.linalg.norm(pa) < 1e-2


def test_backward_error_study_runs_per_format():
    """The §5.1 protocol runs end-to-end in narrower formats; p16e1 loses
    digits to binary32 (expected — 12-bit fractions), and the p32e2 cell
    matches the default-format cell exactly."""
    r16 = error_eval.backward_error_study(32, 1.0, "lu", nb=16,
                                          gemm_backend="xla_quire",
                                          fmt=P16E1)
    assert r16.fmt == "p16e1" and r16.e_posit > r16.e_binary32
    r32 = error_eval.backward_error_study(32, 1.0, "lu", nb=16,
                                          gemm_backend="xla_quire")
    r32b = error_eval.backward_error_study(32, 1.0, "lu", nb=16,
                                           gemm_backend="xla_quire",
                                           fmt=P32E2)
    assert r32.e_posit == r32b.e_posit


# --------------------------------------------------------------------------
# 3. mixed-precision IR acceptance: rgesv_mp digits == rgesv_ir digits on
#    the §5.1 sigma grid
# --------------------------------------------------------------------------

@pytest.mark.parametrize("sigma", [1e-2, 1.0, 1e2])
def test_rgesv_mp_matches_ir_digits(sigma):
    r = error_eval.mixed_precision_study(48, sigma, "lu", nb=16)
    # same floor: within half a decimal digit of the full-width IR solve
    assert r.digits_lost < 0.5, r


def test_rposv_mp_matches_ir_digits():
    r = error_eval.mixed_precision_study(48, 1.0, "cholesky", nb=16)
    assert r.digits_lost < 0.5, r


def test_rgesv_mp_multi_rhs_and_factor_format():
    """Multi-RHS vmap convention + the returned factors really are p16e1
    words (the narrow factorization is what the speedup is made of)."""
    rng = np.random.default_rng(37)
    n = 32
    a64 = rng.standard_normal((n, n))
    b64 = rng.standard_normal((n, 3))
    ap = P.from_float64(jnp.asarray(a64))
    bp = P.from_float64(jnp.asarray(b64))
    (xh, xl), (lu, ipiv) = refine.rgesv_mp(ap, bp, iters=8, nb=16)
    assert xh.shape == (n, 3) and lu.shape == (n, n)
    # p16e1 words live in [-2^15, 2^15): narrow patterns, wide int32 would
    # exceed this range almost surely for a 32x32 factor
    assert np.abs(np.asarray(lu)).max() < (1 << 15)
    x = np.asarray(refine.pair_to_float64(xh, xl))
    want = np.linalg.solve(np.asarray(P.to_float64(ap)),
                           np.asarray(P.to_float64(bp)))
    assert np.abs(x - want).max() / np.abs(want).max() < 1e-10
