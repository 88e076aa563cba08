"""Pallas posit GEMM kernel vs the pure-jnp oracles (interpret mode).

Sweeps shapes / block sizes / magnitude regimes (the paper's sigma axis)
and asserts against kernels/ref.py.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import posit as P
from repro.core.formats import P16E1, P32E2
from repro.kernels.ops import rgemm
from repro.kernels.posit_gemm import decode_split_f32, posit_gemm_f32


def make_inputs(m, k, n, sigma, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((m, k)) * sigma
    b = rng.standard_normal((k, n)) * sigma
    return (jnp.asarray(P.from_float64(a)), jnp.asarray(P.from_float64(b)))


def test_decode_split_exact():
    rng = np.random.default_rng(0)
    x = rng.standard_normal(20000) * np.exp(rng.uniform(-18, 18, 20000))
    p = P.from_float64(x)
    v = np.asarray(P.to_float64(p))
    hi, lo = decode_split_f32(jnp.asarray(p))
    rec = np.asarray(hi, np.float64) + np.asarray(lo, np.float64)
    assert np.array_equal(rec, v)


def test_decode_split_specials():
    pats = np.array([0, P.P32E2.nar_pattern if hasattr(P, "P32E2") else
                     -(1 << 31)], np.int32)
    hi, lo = decode_split_f32(jnp.asarray(pats))
    assert float(hi[0]) == 0.0 and float(lo[0]) == 0.0
    assert np.isnan(np.asarray(hi)[1])


@pytest.mark.parametrize("shape", [(128, 128, 128), (128, 256, 128),
                                   (256, 128, 384)])
@pytest.mark.parametrize("sigma", [1.0, 1e-2, 1e4])
def test_kernel_matches_quire_semantics(shape, sigma):
    m, k, n = shape
    ap, bp = make_inputs(m, k, n, sigma)
    av = np.asarray(P.to_float64(ap))
    bv = np.asarray(P.to_float64(bp))
    truth = av @ bv
    out = np.asarray(posit_gemm_f32(ap, bp), np.float64)
    scale = np.outer(np.linalg.norm(av, axis=1), np.linalg.norm(bv, axis=0))
    err = np.abs(out - truth) / np.maximum(scale, 1e-300)
    # f32 accumulation with exact 28-bit inputs: error ~ sqrt(K) * 2^-24
    assert err.max() < np.sqrt(k) * 8e-8, err.max()


@pytest.mark.parametrize("mode", ["split3", "split3_comp"])
def test_kernel_block_sweep(mode):
    ap, bp = make_inputs(64, 192, 64, 1.0)
    ref_out = np.asarray(posit_gemm_f32(ap, bp, bm=64, bn=64, bk=64,
                                        mode=mode))
    for bm, bn, bk in [(32, 32, 96), (64, 64, 192), (32, 64, 64)]:
        out = np.asarray(posit_gemm_f32(ap, bp, bm=bm, bn=bn, bk=bk,
                                        mode=mode))
        av = np.asarray(P.to_float64(ap))
        bv = np.asarray(P.to_float64(bp))
        sc = np.outer(np.linalg.norm(av, axis=1),
                      np.linalg.norm(bv, axis=0))
        assert (np.abs(out - ref_out) / np.maximum(sc, 1e-300)).max() < 1e-6


def test_compensated_beats_plain_on_long_k():
    ap, bp = make_inputs(8, 4096, 8, 1.0, seed=1)
    av = np.asarray(P.to_float64(ap))
    bv = np.asarray(P.to_float64(bp))
    truth = av @ bv
    plain = np.asarray(posit_gemm_f32(ap, bp, bm=8, bn=8, bk=128,
                                      mode="split3"), np.float64)
    comp = np.asarray(posit_gemm_f32(ap, bp, bm=8, bn=8, bk=128,
                                     mode="split3_comp"), np.float64)
    e_plain = np.abs(plain - truth).max()
    e_comp = np.abs(comp - truth).max()
    assert e_comp <= e_plain * 1.01


def test_rgemm_faithful_chain_is_bit_exact():
    ap, bp = make_inputs(8, 8, 8, 1.0)
    got = np.asarray(rgemm(ap, bp, backend="faithful"))
    acc = np.zeros((8, 8), np.int32)
    for kk in range(8):
        prod = np.asarray(P.mul(np.asarray(ap)[:, kk][:, None],
                                np.asarray(bp)[kk, :][None, :]))
        acc = np.asarray(P.add(acc, prod))
    assert np.array_equal(got, acc)


def test_rgemm_alpha_beta_and_transposes():
    rng = np.random.default_rng(2)
    a = rng.standard_normal((16, 24))
    b = rng.standard_normal((24, 16))
    c = rng.standard_normal((16, 16))
    ap, bp = P.from_float64(a), P.from_float64(b)
    cp = P.from_float64(c)
    out = rgemm(ap, bp, cp, alpha=2.0, beta=-0.5, backend="xla_quire")
    got = np.asarray(P.to_float64(out))
    av = np.asarray(P.to_float64(ap))
    bv = np.asarray(P.to_float64(bp))
    cv = np.asarray(P.to_float64(cp))
    want = 2.0 * av @ bv - 0.5 * cv
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-6
    # transposes reduce to the plain product
    t1 = np.asarray(rgemm(ap.T, bp, trans_a=True, backend="xla_quire"))
    t2 = np.asarray(rgemm(ap, bp.T, trans_b=True, backend="xla_quire"))
    base = np.asarray(rgemm(ap, bp, backend="xla_quire"))
    assert np.array_equal(t1, base) and np.array_equal(t2, base)


def test_quire_vs_faithful_accuracy():
    """Beyond-paper claim: single-rounding (quire) GEMM is at least as
    accurate as the paper's per-MAC-rounding chain."""
    ap, bp = make_inputs(32, 256, 32, 1.0, seed=3)
    av = np.asarray(P.to_float64(ap))
    bv = np.asarray(P.to_float64(bp))
    truth = av @ bv
    q = np.asarray(P.to_float64(rgemm(ap, bp, backend="xla_quire")))
    f = np.asarray(P.to_float64(rgemm(ap, bp, backend="faithful")))
    sc = np.abs(truth) + 1e-300
    assert np.median(np.abs(q - truth) / sc) <= \
        np.median(np.abs(f - truth) / sc)


def test_xla_quire_refuses_tpu(monkeypatch):
    """A TPU has no exact f64 dot: on one, the xla_quire backend raises
    and names the backends that run there instead of compiling an f64
    emulation of unknown exactness."""
    import jax
    ap = P.from_float64(jnp.asarray(np.eye(8) * 1.5))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="quire_exact.*pallas_split3"):
        rgemm(ap, ap, backend="xla_quire", alpha=0.5)


# --------------------------------------------------------------------------
# the compiled kernel's dot: FMA chains on the VPU, same words as XLA's dot
# --------------------------------------------------------------------------

def _round_f32(q):
    """A Fraction correctly rounded to f32 (ties to even)."""
    from fractions import Fraction
    f = np.float32(float(q))
    cands = (np.nextafter(f, np.float32(-np.inf)), f,
             np.nextafter(f, np.float32(np.inf)))
    return min(cands, key=lambda c: (abs(Fraction(float(c)) - q),
                                     int(np.float32(c).view(np.int32)) & 1))


def test_fma_f32_is_correctly_rounded():
    """_fma_f32 == RN(s + x*y) on ties, cancellations and wide exponent
    gaps, against exact rational arithmetic."""
    from fractions import Fraction
    from repro.kernels.posit_gemm import _fma_f32, _split12
    rng = np.random.default_rng(11)
    n = 3000
    x = (rng.standard_normal(n) * np.exp2(rng.integers(-30, 31, n))
         ).astype(np.float32)
    y = (rng.standard_normal(n) * np.exp2(rng.integers(-30, 31, n))
         ).astype(np.float32)
    s = (rng.standard_normal(n) * np.exp2(rng.integers(-60, 61, n))
         ).astype(np.float32)
    third = n // 3                       # s close to -x*y: cancellation
    s[:third] = (-(x[:third].astype(np.float64) * y[:third])
                 ).astype(np.float32)
    ties = slice(third, third + 200)     # s + x*y on a tie of s's grid
    y[ties] = np.float32(1.0)
    s[ties] = np.float32(1.0)
    x[ties] = np.float32(2.0 ** -24) * rng.choice([1, -1, 3, -3], 200
                                                  ).astype(np.float32)
    # just off a tie by a product bit below ph: only the sticky bit of the
    # round-to-odd step breaks it the right way
    odd = np.float32(1 + 2.0 ** -23)
    for i, (sg_s, sg_x) in enumerate(((1, 1), (-1, -1), (1, -1), (-1, 1))):
        s[third + 200 + i] = sg_s * odd
        x[third + 200 + i] = sg_x * odd
        y[third + 200 + i] = np.float32((1 - 2.0 ** -23) * 2.0 ** -24)
    got = np.asarray(_fma_f32(jnp.asarray(s), jnp.asarray(x),
                              jnp.asarray(y), _split12(jnp.asarray(x)),
                              _split12(jnp.asarray(y))))
    want = np.array([_round_f32(Fraction(float(a)) + Fraction(float(b))
                                * Fraction(float(c)))
                     for a, b, c in zip(s, x, y)], np.float32)
    assert np.array_equal(got.view(np.int32), want.view(np.int32))


@pytest.mark.parametrize("fmt", [P32E2, P16E1], ids=lambda f: f.name)
@pytest.mark.parametrize("mode", ["split3", "split3_comp"])
@pytest.mark.parametrize("spread", [0, 20])
def test_chain_kernel_matches_dot_kernel(fmt, mode, spread):
    """The compiled kernel forms its dots as FMA chains (a TPU's MXU
    rounds differently); run in the interpreter here, the chain gives
    the words and f32 accumulators of the XLA-dot kernel bit for bit."""
    import jax
    from repro.kernels.posit_gemm import _posit_gemm_call
    rng = np.random.default_rng(spread)
    x = rng.standard_normal((128, 256)) * np.exp2(
        rng.integers(-spread, spread + 1, (128, 256)))
    y = rng.standard_normal((256, 256)) * np.exp2(
        rng.integers(-spread, spread + 1, (256, 256)))
    y[128:192] = -y[64:128] * (1 + 2.0 ** -20)        # cancellation
    ap, bp = (jax.jit(lambda v: P.from_float64_bits(v, fmt))(v.view(np.int64))
              for v in (x, y))
    for emit_posit, negate in ((False, False), (True, True)):
        kw = dict(bm=128, bn=128, bk=128, mode=mode, interpret=True,
                  emit_posit=emit_posit, negate=negate, fmt=fmt)
        dot = np.asarray(_posit_gemm_call(ap, bp, chain=False, **kw))
        chain = np.asarray(_posit_gemm_call(ap, bp, chain=True, **kw))
        assert np.array_equal(dot.view(np.int32), chain.view(np.int32))
