"""Branch-free, vectorized Posit(n, es) arithmetic in pure JAX.

This is the paper's core mechanism (SoftPosit [19] ported to an accelerator),
adapted to the TPU execution model:

* The paper's GPU port keeps SoftPosit's *data-dependent loops* for the regime
  decode, which costs 2.1x extra instructions + branch divergence outside the
  golden zone (paper Tables 2-3).  TPU vector units are lockstep SIMD with no
  per-lane control flow at all, so here every op is a **fixed-length,
  branch-free integer dataflow** (priority-encoder arithmetic instead of
  while-loops) — the software analogue of the paper's FPGA combinational
  decode, which makes op cost magnitude-independent *by construction*.
* All ops are exact (bit-for-bit round-to-nearest-even on the variable-width
  fraction boundary, saturation at +-maxpos, single NaR), matching SoftPosit
  semantics.  The working integer width is int64; the Pallas kernels use a
  narrower int32/f32 dataflow (see ``repro.kernels``).

Two backends share one public API:
  * ``backend="exact"`` — int64 significand arithmetic, the ground truth.
  * ``backend="fast"``  — binary64 semantics: the operation is rounded to
    binary64 (RNE, 53 bits) and then to the posit lattice, as decoding to
    f64, operating and re-rounding would.  A p32e2 product needs up to 56
    bits, so mul, like add and div, admits a double-rounding corner with
    probability ~2^-26 per op, which is immaterial for the accuracy
    *benchmarks* (they measure digits of backward error).  The property
    tests pin the exact backend against a pure-Python rational-arithmetic
    oracle, and the fast backend against real f64 arithmetic.

The fast backend and the fused-chain helpers run binary64 **in integer
fields** (section "binary64 in integer fields"): a TPU has no f64 unit,
its compiler refuses f64 bit casts, and its f64 emulation is not IEEE
exact.  ``to_float64`` / ``from_float64`` / ``chain_round`` keep the real
f64 implementations as the reference the tests compare against.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import P32E2, PositFormat, get_format

jax.config.update("jax_enable_x64", True)

# Working significand layout: 1.f normalized to [2^F, 2^{F+1}).
# F must hold the widest posit fraction (27 bits for p32e2) exactly.
_F = 27
# Guard bits appended for alignment/rounding inside add/div/sqrt.
_G = 3
_I64 = jnp.int64
_MASK63 = (1 << 63) - 1


def _i64(x):
    return jnp.asarray(x, dtype=_I64)


# --------------------------------------------------------------------------
# bit utilities (fixed-depth, vectorized)
# --------------------------------------------------------------------------

def floor_log2(x):
    """floor(log2(x)) for x > 0 (int64), 6 fixed binary-search steps."""
    x = _i64(x)
    r = jnp.zeros_like(x)
    for s in (32, 16, 8, 4, 2, 1):
        t = x >> s
        big = t > 0
        x = jnp.where(big, t, x)
        r = r + jnp.where(big, s, 0)
    return r


def _lsr64(x, n):
    """Logical shift right on int64 with all operands guaranteed bit63==0."""
    return x >> n  # arithmetic == logical because x >= 0 by construction


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------

def decode(p, fmt: PositFormat = P32E2):
    """Decode sign-extended int32 patterns into (is_zero, is_nar, sign,
    scale, sig) with sig in [2^F, 2^{F+1}) — exact for every posit <= 32 bits.
    """
    p = jnp.asarray(p, dtype=jnp.int32)
    nbits = fmt.nbits
    is_zero = p == 0
    is_nar = p == fmt.nar_pattern
    sign = p < 0
    a = _i64(jnp.where(sign, -p.astype(_I64), p.astype(_I64)))

    # Align pattern body (bits nbits-2 .. 0) with its MSB at bit 62.
    body = (a << (64 - nbits)) & _MASK63
    r0 = (body >> 62) & 1
    y = jnp.where(r0 == 1, (~body) & _MASK63, body)
    # Run length of identical leading bits within bits 62..0.
    # y has bit63 == 0 so clz64(y) = 63 - floor_log2(y); guard y == 0
    # (cannot happen for valid nonzero patterns, but keep it total).
    safe_y = jnp.where(y == 0, 1, y)
    m = jnp.where(y == 0, 62, 62 - floor_log2(safe_y))  # clamped: zero lane is
    k = jnp.where(r0 == 1, m - 1, -m)                   # overridden by is_zero

    # Strip regime + terminator; the remainder is [e | f] left-aligned at 62.
    u = (body << (m + 1)) & _MASK63
    es = fmt.es
    if es > 0:
        e = _lsr64(u, 63 - es)
        f_al = (u << es) & _MASK63
    else:
        e = jnp.zeros_like(u)
        f_al = u
    scale = (k << es) + e
    sig = (_i64(1) << _F) | _lsr64(f_al, 63 - _F)
    return is_zero, is_nar, sign, scale, sig


# --------------------------------------------------------------------------
# encode (pack-and-round; carry through the regime boundary is exact because
# posit patterns are monotone in value — see DESIGN.md §3.1)
# --------------------------------------------------------------------------

def encode(sign, scale, sig, sticky, is_zero, is_nar, fmt: PositFormat = P32E2,
           width: int = _F):
    """Round-to-nearest-even encode of (-1)^sign * sig * 2^(scale - width),
    with sig in [2^width, 2^{width+1}) and ``sticky`` = dropped-bits-nonzero.

    Saturates at +-maxpos (posits never overflow to NaR) and never rounds a
    nonzero value to zero (underflow clamps at minpos).
    """
    nbits, es = fmt.nbits, fmt.es
    scale = _i64(scale)
    sig = _i64(sig)
    sticky = jnp.asarray(sticky, dtype=bool)

    over = scale > fmt.max_scale
    under = scale < -fmt.max_scale
    # Clamp so the shift arithmetic below stays in range even for the
    # saturated lanes (their value is overridden at the end).
    scale_c = jnp.clip(scale, -fmt.max_scale, fmt.max_scale)

    k = scale_c >> es
    e = scale_c - (k << es)
    reg_len = jnp.where(k >= 0, k + 2, 1 - k)          # field width w/ terminator
    regime_val = jnp.where(k >= 0, ((_i64(1) << (k + 1)) - 1) << 1, _i64(1))

    frac = sig & ((_i64(1) << width) - 1)
    # Pre-drop low fraction bits into sticky so the packed field fits int64
    # even at the longest regime (reg_len + es + width can reach 64 bits).
    L = reg_len + es + width
    pre = jnp.maximum(L - 59, 0)
    sticky = sticky | ((frac & ((_i64(1) << pre) - 1)) != 0)
    frac = frac >> pre
    w2 = width - pre
    # One always-zero guard bit at the bottom keeps shift >= 1 below.
    body = ((((regime_val << es | e) << w2) | frac) << 1)
    shift = (L - pre) - (nbits - 1) + 1                 # >= 1 for all formats
    kept = body >> shift
    rem = body & ((_i64(1) << shift) - 1)
    half = _i64(1) << (shift - 1)
    rnd = (rem > half) | ((rem == half) & (sticky | ((kept & 1) == 1)))
    pat = kept + rnd.astype(_I64)

    pat = jnp.minimum(pat, fmt.maxpos_pattern)
    pat = jnp.where(over, fmt.maxpos_pattern, pat)
    pat = jnp.where(under, fmt.minpos_pattern, pat)
    out = jnp.where(jnp.asarray(sign, bool), -pat, pat)
    out = jnp.where(is_zero, 0, out)
    out = jnp.where(is_nar, fmt.nar_pattern, out)
    return out.astype(jnp.int32)


# --------------------------------------------------------------------------
# arithmetic — exact backend
# --------------------------------------------------------------------------

def _normalize(mag, sticky):
    """Normalize mag > 0 to [2^(F+G), 2^(F+G+1)) tracking sticky; returns
    (sig, sticky) at width F+G.  mag == 0 handled by caller."""
    W = _F + _G
    safe = jnp.where(mag == 0, 1, mag)
    msb = floor_log2(safe)
    dl = W - msb                       # left shift if positive
    left = jnp.maximum(dl, 0)
    right = jnp.maximum(-dl, 0)        # right shift at most a few bits
    lost = mag & ((_i64(1) << right) - 1)
    sig = jnp.where(dl >= 0, mag << left, mag >> right)
    sticky = sticky | (lost != 0)
    return sig, sticky, msb


def add_(a, b, fmt: PositFormat = P32E2):
    za, na, sa, ca, fa = decode(a, fmt)
    zb, nb, sb, cb, fb = decode(b, fmt)

    # order |a| >= |b|
    swap = (cb > ca) | ((cb == ca) & (fb > fa))
    sa_, sb_ = jnp.where(swap, sb, sa), jnp.where(swap, sa, sb)
    ca_, cb_ = jnp.where(swap, cb, ca), jnp.where(swap, ca, cb)
    fa_, fb_ = jnp.where(swap, fb, fa), jnp.where(swap, fa, fb)

    d = jnp.clip(ca_ - cb_, 0, _F + _G + 2)
    A = fa_ << _G
    Bs = fb_ << _G
    lost = Bs & ((_i64(1) << d) - 1)
    Bj = (Bs >> d) | (lost != 0).astype(_I64)          # jam sticky into bit 0
    eff_sub = sa_ != sb_
    mag = jnp.where(eff_sub, A - Bj, A + Bj)

    res_zero = mag == 0
    sig, sticky, _ = _normalize(mag, jnp.zeros_like(mag, dtype=bool))
    scale = ca_ + floor_log2(jnp.where(res_zero, 1, mag)) - (_F + _G)

    is_nar = na | nb
    is_zero = (za & zb) | (res_zero & ~is_nar)
    # exact-cancel sign: posit standard gives +0
    sign = jnp.where(za, sb_ & ~zb, jnp.where(zb, sa_, sa_))
    # if a is zero result is b, if b is zero result is a — fold via select:
    out = encode(sign, scale, sig, sticky, is_zero, is_nar, fmt, width=_F + _G)
    out = jnp.where(za & ~zb & ~is_nar, jnp.asarray(b, jnp.int32), out)
    out = jnp.where(zb & ~za & ~is_nar, jnp.asarray(a, jnp.int32), out)
    return out


def mul_(a, b, fmt: PositFormat = P32E2):
    za, na, sa, ca, fa = decode(a, fmt)
    zb, nb, sb, cb, fb = decode(b, fmt)
    sign = sa ^ sb
    scale = ca + cb
    prod = fa * fb                                      # < 2^56, exact
    ge2 = (prod >> (2 * _F + 1)) > 0
    scale = scale + ge2.astype(_I64)
    shift = (_F - _G) + ge2.astype(_I64)                # renormalize to F+G bits
    lost = prod & ((_i64(1) << shift) - 1)
    sig = prod >> shift
    sticky = lost != 0
    is_nar = na | nb
    is_zero = (za | zb) & ~is_nar
    return encode(sign, scale, sig, sticky, is_zero, is_nar, fmt, width=_F + _G)


def div_(a, b, fmt: PositFormat = P32E2):
    za, na, sa, ca, fa = decode(a, fmt)
    zb, nb, sb, cb, fb = decode(b, fmt)
    sign = sa ^ sb
    num = fa << (_F + _G + 1)                           # <= 2^59
    q = num // fb
    r = num - q * fb
    # q in (2^(F+G), 2^(F+G+2)): normalize to [2^(F+G), 2^(F+G+1)).
    # value = q * 2^(ca - cb - (F+G+1)), so scale = ca - cb - 1 (+1 if q >= 2).
    ge2 = (q >> (_F + _G + 1)) > 0
    scale = ca - cb - 1 + ge2.astype(_I64)
    lost = jnp.where(ge2, q & 1, 0)
    sig = jnp.where(ge2, q >> 1, q)
    sticky = (r != 0) | (lost != 0)
    is_nar = na | nb | zb                               # x/0 = NaR
    is_zero = za & ~is_nar
    return encode(sign, scale, sig, sticky, is_zero, is_nar, fmt, width=_F + _G)


def sqrt_(a, fmt: PositFormat = P32E2):
    za, na, sa, ca, fa = decode(a, fmt)
    is_nar = na | (sa & ~za)                            # sqrt(neg) = NaR
    half = ca >> 1                                      # floor(scale / 2)
    r = ca - (half << 1)                                # 0 or 1
    # a = fa * 2^(ca - F) = X * 2^(2*half - F - 33) with X = fa << (r + 33),
    # X in [2^60, 2^62) and F + 33 = 60 even => sqrt(a) = isqrt(X) * 2^(half-30)
    X = fa << (r + 33)
    s0 = jnp.floor(jnp.sqrt(X.astype(jnp.float64))).astype(_I64)
    # f64 estimate is within +-1 of the true integer sqrt; two correction
    # rounds make it exact.
    for _ in range(2):
        s0 = jnp.where((s0 + 1) * (s0 + 1) <= X, s0 + 1, s0)
        s0 = jnp.where(s0 * s0 > X, s0 - 1, s0)
    sticky = s0 * s0 != X
    # s0 in [2^30, 2^31) == [2^(F+G), 2^(F+G+1)) — already normalized.
    is_zero = za
    return encode(jnp.zeros_like(sa), half, s0, sticky, is_zero, is_nar, fmt,
                  width=_F + _G)


def neg_(a, fmt: PositFormat = P32E2):
    a = jnp.asarray(a, jnp.int32)
    return jnp.where(a == fmt.nar_pattern, a, -a)


def abs_(a, fmt: PositFormat = P32E2):
    a = jnp.asarray(a, jnp.int32)
    return jnp.where(a == fmt.nar_pattern, a, jnp.abs(a))


def is_nar(p, fmt: PositFormat = P32E2):
    """Elementwise NaR predicate on sign-extended posit words.

    NaR is the single pattern 10...0 (sign-extended: int32 -2^(nbits-1)
    for nbits=32, or its sign-extension for narrower formats), so the
    test is one word compare — no decode.  This is the check every NaR
    gate in the stack uses (``decode``, ``neg_``/``abs_``, the quire
    deposit); exposed so monitors (lapack.refine) and fault-tolerance
    verifiers (repro.ft) can ask "is this lane poisoned?" without
    reimplementing the pattern."""
    return jnp.asarray(p, jnp.int32) == fmt.nar_pattern


# --------------------------------------------------------------------------
# conversions (exact / correctly rounded)
# --------------------------------------------------------------------------

def to_float64(p, fmt: PositFormat = P32E2):
    is_zero, is_nar, sign, scale, sig = decode(p, fmt)
    mag = jnp.ldexp(sig.astype(jnp.float64), (scale - _F).astype(jnp.int32))
    out = jnp.where(sign, -mag, mag)
    out = jnp.where(is_zero, 0.0, out)
    out = jnp.where(is_nar, jnp.nan, out)
    return out


def from_float64(x, fmt: PositFormat = P32E2):
    x = jnp.asarray(x, jnp.float64)
    is_nar = jnp.isnan(x) | jnp.isinf(x)
    is_zero = (x == 0.0) & ~is_nar
    sign = x < 0
    # f64 subnormals (XLA frexp mishandles them) are far below every
    # format's minpos: clamp straight to minpos via the tiny flag.
    tiny = ~is_nar & ~is_zero & (jnp.abs(x) < np.float64(2.0 ** -1022))
    ax = jnp.abs(jnp.where(is_nar | is_zero | tiny, 1.0, x))
    mant, ex = jnp.frexp(ax)                            # mant in [0.5, 1)
    scale = ex.astype(_I64) - 1
    # One bit wider than the widest posit fraction (width F+1 = 28 > fs_max)
    # so encode's round position always sits strictly above sig's LSB —
    # with width == fs_max the round bit would be lost to truncation.
    R = mant * np.float64(1 << (_F + 2))                # in [2^{F+1}, 2^{F+2})
    sig = jnp.floor(R).astype(_I64)
    sticky = R != sig.astype(jnp.float64)
    scale = jnp.where(tiny, -(fmt.max_scale + 8), scale)
    return encode(sign, scale, sig, sticky, is_zero, is_nar, fmt, width=_F + 1)


def to_float32(p, fmt: PositFormat = P32E2):
    return to_float64(p, fmt).astype(jnp.float32)


def pconvert(p, src: PositFormat, dst: PositFormat):
    """Posit -> posit format conversion, correctly rounded (RNE on the
    destination pattern boundary).  Exact decode (every supported posit
    fits the 53-bit field significand) followed by one correctly-rounded
    encode, so widening (e.g. p16e1 -> p32e2) is exact and narrowing
    rounds once.  NaR maps to NaR, zero to zero.  Integer fields only.
    The mixed-precision IR solvers (lapack/refine.py rgesv_mp) perform
    this same decode-scale-encode dance with a power-of-two equilibration
    folded between the two halves — see refine.mp_narrow_matrix."""
    if src is dst:
        return jnp.asarray(p, jnp.int32)
    return _soft_encode(_soft_from_posit(p, src), dst)


def from_float32(x, fmt: PositFormat = P32E2):
    return from_float64(jnp.asarray(x, jnp.float32).astype(jnp.float64), fmt)


# --------------------------------------------------------------------------
# binary64 in integer fields — the device path
#
# A binary64 value is carried as its IEEE-754 bit pattern in int64 (the
# ``*_bits`` conversions and the fused-chain values) or unpacked into a
# ``_Soft`` tuple of fields.  + - * / sqrt round to nearest-even at 53
# bits exactly as IEEE f64 hardware does, using int64 integer ops only,
# so a TPU (no f64 unit; its compiler refuses f64 bit casts and its f64
# emulation is not IEEE-exact) computes the same words as a CPU's f64.
# Operands of * / sqrt must carry <= 28 significant bits (posit words and
# f32 values do); + takes any 53-bit operands.  Every value this module
# produces lies in [2^-250, 2^250], far inside binary64's normal range,
# so no subnormal, overflow or infinity handling is needed; NaN covers
# NaR, x/0 and sqrt of a negative.
# --------------------------------------------------------------------------

_B = 52                                   # binary64 fraction bits
_FRAC_MASK = (1 << _B) - 1
_NAN_BITS = 0x7FF8000000000000            # jnp.nan's pattern
_INF_BITS = 0x7FF0000000000000
_SIGN_BITS = -(1 << 63)                   # int64 with only bit 63 set


class _Soft(NamedTuple):
    """Unpacked binary64: value = (-1)^sign * sig * 2^(scale - 52) with
    sig in [2^52, 2^53); ``sig`` is 2^52 on zero/NaN lanes so shifts of
    dead lanes stay in range."""
    nan: jax.Array
    zero: jax.Array
    sign: jax.Array
    scale: jax.Array
    sig: jax.Array


def _soft_where(c, x: _Soft, y: _Soft) -> _Soft:
    return _Soft(*(jnp.where(c, a, b) for a, b in zip(x, y)))


def _soft_from_posit(p, fmt: PositFormat) -> _Soft:
    is_zero, nar, sign, scale, sig = decode(p, fmt)
    return _Soft(nar, is_zero, sign & ~is_zero, scale, sig << (_B - _F))


def _soft_from_f32(x) -> _Soft:
    """f32 values -> fields, exactly; subnormals read as zero, as XLA's
    f32 -> f64 conversion reads them."""
    bits = jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.float32),
                                        jnp.int32)
    expf = ((bits >> 23) & 0xFF).astype(_I64)
    zero = expf == 0
    sig = ((bits & 0x7FFFFF).astype(_I64) | (1 << 23)) << (_B - 23)
    return _Soft(expf == 0xFF, zero, (bits < 0) & ~zero, expf - 127, sig)


def _soft_from_bits(x) -> _Soft:
    """binary64 patterns (int64) -> fields.  Subnormals read as zero, as
    XLA's flush-to-zero f64 arithmetic reads them."""
    x = _i64(x)
    ex = (x >> _B) & 0x7FF
    zero = ex == 0
    return _Soft(ex == 0x7FF, zero, (x < 0) & ~zero, ex - 1023,
                 (x & _FRAC_MASK) | (_i64(1) << _B))


def _soft_to_bits(v: _Soft):
    bits = ((v.scale + 1023) << _B) | (v.sig & _FRAC_MASK)
    bits = jnp.where(v.sign, bits | _SIGN_BITS, bits)
    bits = jnp.where(v.zero, _i64(0), bits)
    return jnp.where(v.nan, _i64(_NAN_BITS), bits)


def _soft_round(nan, zero, sign, mag, e0) -> _Soft:
    """Round mag * 2^e0 (mag > 0 on live lanes) to 53 bits, RNE.  A caller
    whose mag is inexact jams the sticky bit into bit 0 and guarantees at
    least two dropped bits (mag >= 2^54), which makes the jam exact."""
    live = ~(nan | zero)
    mag = jnp.where(live, mag, _i64(1) << _B)
    msb = floor_log2(mag)
    r = jnp.maximum(msb - _B, 0)
    sig = jnp.where(msb > _B, mag >> r, mag << jnp.maximum(_B - msb, 0))
    rem = mag & ((_i64(1) << r) - 1)
    half = (_i64(1) << r) >> 1
    up = (r > 0) & ((rem > half) | ((rem == half) & ((sig & 1) == 1)))
    sig = sig + up.astype(_I64)
    carry = sig >> (_B + 1)                               # 0, or 1 at 2^53
    return _Soft(nan, zero & ~nan, sign, msb + e0 + carry, sig >> carry)


def _soft_neg(v: _Soft) -> _Soft:
    return v._replace(sign=~v.sign & ~v.zero)


def _soft_add(a: _Soft, b: _Soft) -> _Soft:
    swap = (b.scale > a.scale) | ((b.scale == a.scale) & (b.sig > a.sig))
    hi = _soft_where(swap, b, a)
    lo = _soft_where(swap, a, b)
    sh = jnp.minimum(hi.scale - lo.scale, 63)
    big = hi.sig << 9                                     # [2^61, 2^62)
    small = lo.sig << 9
    lost = (small & ((_i64(1) << sh) - 1)) != 0           # only if sh > 9
    small = (small >> sh) | lost.astype(_I64)
    mag = jnp.where(hi.sign != lo.sign, big - small, big + small)
    nan = a.nan | b.nan
    out = _soft_round(nan, mag == 0, hi.sign & (mag != 0), mag,
                      hi.scale - _B - 9)
    out = _soft_where(a.zero & ~nan, b, out)              # 0 + y == y
    return _soft_where(b.zero & ~a.zero & ~nan, a, out)


def _soft_mul(a: _Soft, b: _Soft) -> _Soft:
    nan = a.nan | b.nan
    mag = (a.sig >> (_B - _F)) * (b.sig >> (_B - _F))    # [2^54, 2^56)
    return _soft_round(nan, a.zero | b.zero, a.sign ^ b.sign, mag,
                       a.scale + b.scale - 2 * _F)


def _soft_div(a: _Soft, b: _Soft) -> _Soft:
    """Quotient by restoring long division: 63 quotient bits plus a jammed
    sticky bit.  The remainder stays below 2^29, so the loop is int32 (a
    TPU has no integer divide, and its emulated int64 one is slow)."""
    den = (b.sig >> (_B - _F)).astype(jnp.int32)          # [2^27, 2^28)
    rem = (a.sig >> (_B - _F)).astype(jnp.int32)
    halves = []
    for width in (31, 32):                                # Q bits 62..0
        q = jnp.zeros_like(rem)
        for _ in range(width):
            bit = rem >= den
            rem = jnp.where(bit, rem - den, rem)
            q = (q << 1) | bit.astype(jnp.int32)
            rem = rem << 1
        halves.append(q)
    hi, lo = halves
    mag = ((hi.astype(_I64) << 32) | (lo.astype(_I64) & 0xFFFFFFFF)
           | (rem != 0).astype(_I64))                     # (2^61, 2^63)
    nan = a.nan | b.nan | b.zero                          # x/0, 0/0: NaN
    return _soft_round(nan, a.zero, a.sign ^ b.sign, mag,
                       a.scale - b.scale - 62)


def _soft_sqrt(a: _Soft) -> _Soft:
    """Digit-by-digit integer square root of sig * 2^82: 55-56 root bits
    plus a jammed sticky bit."""
    odd = (a.scale - _F) & 1                              # make exp even
    x = (a.sig >> (_B - _F)) << odd                       # <= 29 bits
    root = jnp.zeros_like(x)
    rem = jnp.zeros_like(x)
    for i in range(55, -1, -1):                           # X = x << 82
        pos = 2 * i - 82
        pair = (x >> pos) & 3 if pos >= 0 else jnp.zeros_like(x)
        rem = (rem << 2) | pair
        trial = (root << 2) | 1
        ge = rem >= trial
        rem = jnp.where(ge, rem - trial, rem)
        root = (root << 1) | ge.astype(_I64)
    nan = a.nan | (a.sign & ~a.zero)
    return _soft_round(nan, a.zero, jnp.zeros_like(a.sign),
                       root | (rem != 0).astype(_I64),
                       (a.scale - _F - odd - 82) >> 1)


def _soft_encode(v: _Soft, fmt: PositFormat):
    """Fields -> posit words, correctly rounded (the value is exact)."""
    return encode(v.sign, v.scale, v.sig, False, v.zero, v.nan, fmt,
                  width=_B)


def _soft_posit_round(v: _Soft, fmt: PositFormat) -> _Soft:
    """Round fields to the nearest posit *value*, staying in fields — the
    integer twin of ``chain_round`` (see there for the rounding rule)."""
    nbits, es = fmt.nbits, fmt.es
    q = v.sig >> (_B - 28)                                # [2^28, 2^29)
    sticky = (v.sig & ((_i64(1) << (_B - 28)) - 1)) != 0
    frac = q & ((_i64(1) << 28) - 1)
    scale = v.scale
    k = scale >> es
    e = scale - (k << es)
    reg_len = jnp.where(k >= 0, k + 2, 1 - k)
    ef = (_i64(1) << (es + 28)) | (e << 28) | frac      # [1|e|frac28]
    d = jnp.clip(29 + es + reg_len - nbits, 1, es + 28)
    dropped = ef & ((_i64(1) << d) - 1)
    half = _i64(1) << (d - 1)
    kept = ef >> d
    pat_lsb = jnp.where(d == es + 28,
                        jnp.where(k < 0, _i64(1), _i64(0)), kept & 1)
    rnd = (dropped > half) | ((dropped == half) & (sticky | (pat_lsb == 1)))
    q2 = (kept + rnd.astype(_I64)) << d
    carry = q2 >> (es + 29)
    k2 = k + carry
    e2 = jnp.where(carry == 1, 0, (q2 >> 28) & ((_i64(1) << es) - 1))
    frac2 = jnp.where(carry == 1, 0, q2 & ((_i64(1) << 28) - 1))
    scale2 = (k2 << es) + e2
    sig2 = (frac2 + (_i64(1) << 28)) << (_B - 28)
    over = scale >= fmt.max_scale
    under = scale < -fmt.max_scale
    scale2 = jnp.where(over, fmt.max_scale,
                       jnp.where(under, -fmt.max_scale, scale2))
    sig2 = jnp.where(over | under, _i64(1) << _B, sig2)
    return v._replace(scale=scale2, sig=sig2)


def to_float64_bits(p, fmt: PositFormat = P32E2):
    """posit words -> binary64 bit patterns (int64), exact; NaR -> NaN.
    Bit-identical to ``bitcast(to_float64(p))`` with integer ops only."""
    return _soft_to_bits(_soft_from_posit(p, fmt))


def from_float64_bits(x, fmt: PositFormat = P32E2):
    """binary64 bit patterns (int64) -> posit words, correctly rounded;
    the integer-only twin of ``from_float64`` (NaN/inf -> NaR, f64
    subnormals -> 0 as XLA flushes them).  The host side of a device path
    passes ``np.asarray(x, np.float64).view(np.int64)``."""
    return _soft_encode(_soft_from_bits(x), fmt)


def word_of(x: float, fmt: PositFormat = P32E2):
    """A static Python scalar -> its posit word, as an int32 constant
    computed with integer ops (legal inside any device program)."""
    return from_float64_bits(np.asarray(x, np.float64).view(np.int64), fmt)


def f32_epilogue(acc, alpha_p, beta_p=None, c_p=None,
                 fmt: PositFormat = P32E2):
    """Posit words of ``alpha * acc (+ beta * c)`` for an f32 accumulator
    ``acc`` and posit words alpha, beta, c: each product and the sum are
    rounded to binary64, then the result once to the posit lattice — the
    f64 epilogue's words, in integer fields."""
    out = _soft_mul(_soft_from_posit(alpha_p, fmt), _soft_from_f32(acc))
    if c_p is not None:
        out = _soft_add(out, _soft_mul(_soft_from_posit(beta_p, fmt),
                                       _soft_from_posit(c_p, fmt)))
    return _soft_encode(out, fmt)


# --------------------------------------------------------------------------
# public dispatch (fast backend = binary64 semantics in integer fields)
# --------------------------------------------------------------------------

def _fast_binop(op):
    def f(a, b, fmt: PositFormat = P32E2):
        return _soft_encode(op(_soft_from_posit(a, fmt),
                               _soft_from_posit(b, fmt)), fmt)
    return f


_FAST = {
    "add": _fast_binop(_soft_add),
    "sub": _fast_binop(lambda x, y: _soft_add(x, _soft_neg(y))),
    "mul": _fast_binop(_soft_mul),
    "div": _fast_binop(_soft_div),
    "sqrt": lambda a, fmt=P32E2: _soft_encode(
        _soft_sqrt(_soft_from_posit(a, fmt)), fmt),
}

_EXACT = {
    "add": add_,
    "sub": lambda a, b, fmt=P32E2: add_(a, neg_(b, fmt), fmt),
    "mul": mul_,
    "div": div_,
    "sqrt": sqrt_,
}


def _dispatch(name, backend):
    table = {"exact": _EXACT, "fast": _FAST}[backend]
    return table[name]


def add(a, b, fmt: PositFormat = P32E2, backend: str = "exact"):
    return _dispatch("add", backend)(a, b, fmt)


def sub(a, b, fmt: PositFormat = P32E2, backend: str = "exact"):
    return _dispatch("sub", backend)(a, b, fmt)


def mul(a, b, fmt: PositFormat = P32E2, backend: str = "exact"):
    return _dispatch("mul", backend)(a, b, fmt)


def div(a, b, fmt: PositFormat = P32E2, backend: str = "exact"):
    return _dispatch("div", backend)(a, b, fmt)


def sqrt(a, fmt: PositFormat = P32E2, backend: str = "exact"):
    return _dispatch("sqrt", backend)(a, fmt)


# --------------------------------------------------------------------------
# fused_chain helpers — decode-once / encode-once op chains
#
# The fast backend's binop decodes BOTH operands and encodes the result on
# EVERY call, so a chained update like  col - a*b  (the panel kernels'
# inner loop) decodes the same entries once per scalar op and round-trips
# the intermediate product through a posit word it immediately decodes
# again.  The chain form carries binary64 bit patterns (int64) between
# ops and rounds each result to the posit lattice in fields: decode once
# on entry (``chain_decode``), encode once on exit (``chain_encode``).
# Every chain value is exactly the f64 value the fast backend's words
# decode to, so a chain of {round(op(...))} steps produces bit-for-bit the
# words of the corresponding fast-backend word ops.  Chain values are
# int64 patterns, not numbers: use ``chain_abs``/``chain_neg``/
# ``chain_isnan``/``chain_positive``/``chain_iszero``/``chain_const``
# for anything but the arithmetic below.
# --------------------------------------------------------------------------

def chain_decode(p, fmt: PositFormat = P32E2):
    """Posit words -> exact chain values (decode once, at chain entry)."""
    return to_float64_bits(p, fmt)


def chain_encode(x, fmt: PositFormat = P32E2):
    """Chain values -> posit words (encode once, at chain exit).  Exact
    (no extra rounding) for the output of a chain_* op."""
    return from_float64_bits(x, fmt)


def _chain_out(v: _Soft, fmt: PositFormat):
    return _soft_to_bits(_soft_posit_round(v, fmt))


def chain_add(a, b, fmt: PositFormat = P32E2):
    return _chain_out(_soft_add(_soft_from_bits(a), _soft_from_bits(b)), fmt)


def chain_sub(a, b, fmt: PositFormat = P32E2):
    return _chain_out(_soft_add(_soft_from_bits(a),
                                _soft_neg(_soft_from_bits(b))), fmt)


def chain_mul(a, b, fmt: PositFormat = P32E2):
    return _chain_out(_soft_mul(_soft_from_bits(a), _soft_from_bits(b)), fmt)


def chain_div(a, b, fmt: PositFormat = P32E2):
    return _chain_out(_soft_div(_soft_from_bits(a), _soft_from_bits(b)), fmt)


def chain_sqrt(a, fmt: PositFormat = P32E2):
    return _chain_out(_soft_sqrt(_soft_from_bits(a)), fmt)


def chain_neg(x):
    return _i64(x) ^ _SIGN_BITS


def chain_abs(x):
    return _i64(x) & _MASK63


def chain_isnan(x):
    return (_i64(x) & _MASK63) > _INF_BITS


def chain_iszero(x):
    return (_i64(x) & _MASK63) == 0


def chain_positive(x):
    """x > 0 (False for NaN), as the f64 comparison would say."""
    x = _i64(x)
    return (x > 0) & (x <= _INF_BITS)


def chain_const(x: float):
    """A Python scalar as a chain value (it must lie on the lattice)."""
    return _i64(int(np.asarray(x, np.float64).view(np.int64)))


def chain_round(x, fmt: PositFormat = P32E2):
    """The f64 reference of the chain rounding: round an f64 value to the
    nearest posit *value* (RNE on the pattern boundary, saturating,
    NaN -> NaN), staying in f64.  Bit-equivalent to
    ``to_float64(from_float64(x))`` (pinned by tests), computed directly
    on (scale, significand) fields; ``_soft_posit_round`` is its integer
    twin on the device path.

    The rounding position is the posit pattern boundary: with
    ``reg_len``-bit regime the pattern keeps ``fs = nbits-1-reg_len-es``
    fraction bits, i.e. drops ``d = 29+es+reg_len-nbits`` low bits of the
    30-bit ``[e|frac]`` field (28 fraction bits + hidden bit above).  Ties
    go to the even *pattern*: the pattern LSB is an ``[e|frac]`` bit while
    ``d < es+28``, but degenerates to the regime terminator (0 for k >= 0,
    1 for k < 0) when the whole ``[e|frac]`` field is dropped — the
    near-maxpos/minpos fringe where value-space "even" and pattern-space
    "even" disagree.
    """
    x = jnp.asarray(x, jnp.float64)
    nbits, es = fmt.nbits, fmt.es
    is_nan = jnp.isnan(x) | jnp.isinf(x)
    is_zero = (x == 0.0) & ~is_nan
    sign = x < 0
    # f64 subnormals sit far below every format's minpos: clamp via `tiny`
    # (same rule as from_float64).
    tiny = ~is_nan & ~is_zero & (jnp.abs(x) < np.float64(2.0 ** -1022))
    ax = jnp.abs(jnp.where(is_nan | is_zero | tiny, 1.0, x))
    mant, ex = jnp.frexp(ax)                            # mant in [0.5, 1)
    scale = ex.astype(_I64) - 1
    R = mant * np.float64(1 << 29)                      # [2^28, 2^29)
    q = jnp.floor(R)
    sticky = R != q
    frac = q.astype(_I64) & ((_i64(1) << 28) - 1)

    k = scale >> es
    e = scale - (k << es)
    reg_len = jnp.where(k >= 0, k + 2, 1 - k)
    ef = (_i64(1) << (es + 28)) | (e << 28) | frac      # [1|e|frac28]
    d = jnp.clip(29 + es + reg_len - nbits, 1, es + 28)
    dropped = ef & ((_i64(1) << d) - 1)
    half = _i64(1) << (d - 1)
    kept = ef >> d
    pat_lsb = jnp.where(d == es + 28,
                        jnp.where(k < 0, _i64(1), _i64(0)), kept & 1)
    rnd = (dropped > half) | ((dropped == half) & (sticky | (pat_lsb == 1)))

    q2 = (kept + rnd.astype(_I64)) << d                 # back at [1|e|frac]
    carry = q2 >> (es + 29)                             # regime carry: 2^(es(k+1))
    k2 = k + carry
    e2 = jnp.where(carry == 1, 0, (q2 >> 28) & ((_i64(1) << es) - 1))
    frac2 = jnp.where(carry == 1, 0, q2 & ((_i64(1) << 28) - 1))
    scale2 = (k2 << es) + e2
    mag = jnp.ldexp((frac2 + (_i64(1) << 28)).astype(jnp.float64),
                    (scale2 - 28).astype(jnp.int32))

    # saturation: every value with scale >= max_scale rounds to maxpos
    # (the k = k_max regime has no e/frac room), mirroring encode's
    # over-clamp + pattern minimum; under mirrors the minpos clamp.
    over = scale >= fmt.max_scale
    under = (scale < -fmt.max_scale) | tiny
    mag = jnp.where(over, np.float64(2.0) ** fmt.max_scale, mag)
    mag = jnp.where(under, np.float64(2.0) ** (-fmt.max_scale), mag)
    out = jnp.where(sign, -mag, mag)
    out = jnp.where(is_zero, 0.0, out)
    return jnp.where(is_nan, jnp.float64(jnp.nan), out)



# --------------------------------------------------------------------------
# epsilon model (paper §2: golden zone)
# --------------------------------------------------------------------------

def rounding_eps(x, fmt: PositFormat = P32E2):
    """Relative rounding ulp of |x| in this format (the paper's epsilon_posit,
    which beats binary32's 6e-8 only inside the golden zone)."""
    x = jnp.abs(jnp.asarray(x, jnp.float64))
    safe = jnp.where(x == 0, 1.0, x)
    _, ex = jnp.frexp(safe)
    scale = ex - 1
    k = scale >> fmt.es
    reg_len = jnp.where(k >= 0, k + 2, 1 - k)
    fs = jnp.clip(fmt.nbits - 1 - reg_len - fmt.es, 0, None)
    return jnp.where(x == 0, 0.0, 2.0 ** (-fs.astype(jnp.float64)))


def from_float32_bits(x, fmt: PositFormat = P32E2):
    """f32 -> posit via int32 bit extraction — no f64 anywhere, so this is
    the TPU-legal path (used by the posit16 optimizer/collective codecs and
    the QAT quantizer).  Correctly rounds the f32 value to the posit
    lattice (f32 carries 24 significand bits; encode's round position needs
    width > fs_max, satisfied for every supported format)."""
    x = jnp.asarray(x, jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    sign = bits < 0
    exp_f = (bits >> 23) & 0xFF
    man = bits & 0x7FFFFF
    is_zero = (exp_f == 0) & (man == 0)
    is_nar = exp_f == 255
    # subnormals (< 2^-126) are far below every supported format's minpos:
    # give them an under-range scale so encode clamps to minpos.
    scale = jnp.where(exp_f == 0, -150, exp_f.astype(jnp.int32) - 127)
    # zero-pad the 24-bit f32 significand to width F+1: encode requires the
    # round position strictly above the significand LSB (width > fs_max).
    sig = (((jnp.int32(1) << 23) | man).astype(_I64)) << (_F + 1 - 23)
    return encode(sign, _i64(scale), sig, False, is_zero, is_nar, fmt,
                  width=_F + 1)


def to_float32_bits(p, fmt: PositFormat = P32E2):
    """posit -> f32 without f64: exact for <= 24-bit significands (all of
    p16e1/p8e0; p32e2 rounds RNE to f32 via the astype)."""
    is_zero, is_nar, sign, scale, sig = decode(p, fmt)
    mag = jnp.ldexp(sig.astype(jnp.float32), (scale - _F).astype(jnp.int32))
    out = jnp.where(sign, -mag, mag)
    out = jnp.where(is_zero, jnp.float32(0.0), out)
    return jnp.where(is_nar, jnp.float32(jnp.nan), out)


@functools.lru_cache(maxsize=None)
def jitted(name: str, fmt_name: str = "p32e2", backend: str = "exact"):
    """jit-compiled op handle, cached per (op, format, backend)."""
    fmt = get_format(fmt_name)
    fn = {"add": add, "sub": sub, "mul": mul, "div": div}.get(name)
    if fn is not None:
        return jax.jit(lambda a, b: fn(a, b, fmt, backend))
    if name == "sqrt":
        return jax.jit(lambda a: sqrt(a, fmt, backend))
    if name == "to_f64":
        return jax.jit(lambda a: to_float64(a, fmt))
    if name == "from_f64":
        return jax.jit(lambda x: from_float64(x, fmt))
    raise KeyError(name)
