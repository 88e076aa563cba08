"""Pallas TPU kernel: format-parametric posit GEMM via a hi/lo split.

TPU adaptation of the paper's accelerators (DESIGN.md §2):

* The FPGA design surrounds each systolic MAC with combinational posit
  decode/encode.  Here the dataflow is *decode once per VMEM tile -> f32
  dot -> encode once per output tile*.
* A decoded Posit(32,2) significand has 28 bits; float32 carries 24.  We
  split each decoded value exactly as ``x = hi + lo`` (hi: top 24 bits,
  lo: bottom 4 bits) and compute ``A@B = Ah@Bh + (Ah@Bl + Al@Bh)`` as three
  f32 dots — the same splitting the paper discusses
  for tensor cores (Ootomo & Yokota [28], cited in §6.3), adapted to posit
  decode.  The ``Al@Bl`` term is < 2^-48 relative and is dropped.
* ``mode="split3_comp"`` adds tile-level Knuth TwoSum compensation of the
  K-loop accumulation (error ~ one f32 rounding per *tile* instead of per
  K step), at ~6 VPU flops per output element per K tile.

**Which f32 dot.**  The words are fixed by how each plane product of a K
tile rounds: as the sequential fused multiply-add chain in k order, one
rounding per k — what XLA's CPU dot computes, and what the golden pins
(tests/test_formats.py) hold.  A TPU's MXU accumulates the same dot in
another order and precision, so its words differ.  The compiled kernel
therefore forms each chain on the VPU (``_chain_split3``: f32 FMA from
Dekker products, TwoSum and a round-to-odd sticky bit, ``_fma_f32``),
which gives the CPU's words on a TPU; the interpreter uses XLA's dot
(``_matmul_f32``).  The tests pin the two to each other bit for bit.

``posit_gemm`` fuses the single posit rounding (quire-lite semantics, see
kernels/ref.py) into the final-k grid step: the last ``@pl.when`` block
encodes the f32 accumulator to Posit(32,2) words in-kernel
(``encode_p32_f32`` — pure int32/f32 ops, the mirror of
``decode_split_f32``) and writes an int32 ``o_ref``, so the posit result
never round-trips through HBM as f32 and ops.py consumes words directly.
``posit_gemm_f32`` keeps the raw-accumulator output for general
alpha/beta epilogues and accuracy studies.

Exactness domain: the hi/lo split is exact for |x| >= 2^-99 (lo's exponent
reaches f32's normal floor at scale-27 = -126); below that lo flushes to 0
— matching TPU subnormal-flush semantics — with relative error < 2^-24,
far outside the paper's golden zone and below binary32's own epsilon.
The chains match the CPU's words while products and partial sums stay in
f32's normal range (a TPU flushes subnormals; a CPU keeps them).

**Format parameterization** (DESIGN.md §8): decode and encode are one
field-space implementation over ``PositFormat`` — every per-format number
(regime alignment shift, es field width, maxpos/NaR patterns) is a static
Python constant folded at trace time, so the traced kernel for p32e2 is
op-for-op the pre-parametric kernel (pinned by the golden tests) and
narrower formats get the same branch-free dataflow for free.  For
p16e1/p8e2 the decoded significand carries <= 13 bits, so the hi plane
alone is exact: the interpreter's lo-plane dots multiply zeros, and the
compiled kernel skips its two lo chains.  ``encode_p16_f32`` /
``encode_p32_f32`` are the named per-format epilogue entry points
(bit-identical to ``posit.from_float32_bits`` per format, pinned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.formats import P16E1, P32E2, PositFormat

from jax.experimental.pallas import tpu as pltpu

_NAN = np.float32(np.nan)


# --------------------------------------------------------------------------
# in-kernel int32 posit decode -> (hi, lo) f32 split
# --------------------------------------------------------------------------

def _floor_log2_i32(x):
    """floor(log2(x)) for x > 0, int32, 5 fixed binary-search steps."""
    r = jnp.zeros_like(x)
    for s in (16, 8, 4, 2, 1):
        t = x >> s
        big = t > 0
        x = jnp.where(big, t, x)
        r = r + jnp.where(big, np.int32(s), np.int32(0))
    return r


def _pow2_f32(e):
    """2.0**e as f32 via exponent-field construction; caller masks e < -126."""
    bits = jnp.clip(e + 127, np.int32(1), np.int32(254)) << 23
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def decode_split_f32(p, fmt: PositFormat = P32E2):
    """int32 posit words -> (hi, lo) f32 with hi+lo == value exactly
    (for |value| >= 2^-99; see module docstring).  Pure int32/f32 ops —
    legal inside a Pallas TPU kernel body.  Format-parametric: alignment
    shifts and field widths are static per-format constants; the decoded
    significand is normalized to the shared 28-bit working width (bits
    below the format's fraction field are zero), so the hi/lo split and
    every downstream op are format-independent."""
    nbits, es = fmt.nbits, fmt.es
    is_zero = p == 0
    is_nar = p == np.int32(fmt.nar_pattern)
    signbit = p < 0
    a = jnp.where(signbit, jnp.int32(0) - p, p)          # 2's-complement abs
    body = a << (33 - nbits)                             # regime MSB at bit31
    r0 = body < 0
    y = jnp.where(r0, ~body, body)                       # bit31 == 0 now
    y_safe = jnp.where(y == 0, np.int32(1), y)
    m = 31 - _floor_log2_i32(y_safe)                     # regime run length
    k = jnp.where(r0, m - 1, -m)
    u = (body << m) << 1                                 # strip regime+term
    e = (u >> (32 - es)) & ((1 << es) - 1) if es else jnp.zeros_like(u)
    frac = u << es                                       # frac MSB at bit31
    sig = (1 << 27) | ((frac >> 5) & ((1 << 27) - 1))    # 28-bit significand
    scale = (k << es) + e

    sgn = jnp.where(signbit, jnp.float32(-1.0), jnp.float32(1.0))
    dead = is_zero | is_nar
    zero = np.float32(0.0)
    ph = jnp.where((scale - 23 >= -126) & ~dead, _pow2_f32(scale - 23), zero)
    plo = jnp.where((scale - 27 >= -126) & ~dead, _pow2_f32(scale - 27), zero)
    hi = (sig >> 4).astype(jnp.float32) * ph * sgn
    lo = (sig & 15).astype(jnp.float32) * plo * sgn
    hi = jnp.where(is_nar, _NAN, hi)
    return hi, lo


# --------------------------------------------------------------------------
# in-kernel f32 -> posit encode (the epilogue mirror of decode_split_f32)
# --------------------------------------------------------------------------

def encode_posit_f32(x, fmt: PositFormat = P32E2):
    """f32 values -> int32 posit words, pure int32 ops — legal inside a
    Pallas TPU kernel body.  Bit-identical to ``posit.from_float32_bits``
    for every registered format (pinned by tests): correctly rounds the
    f32 value to the posit lattice with RNE ties to the even *pattern*.

    The pattern is assembled directly — ``regime << avail | [e|frac]`` —
    so the tie check reads the true pattern LSB (an [e|frac] bit normally,
    the regime terminator in the long-regime fringe) and a round-up that
    crosses a regime boundary is plain integer +1 on the monotone pattern.
    All field widths (``es + 23``-bit [e|frac], ``nbits - 1`` pattern
    bits, max_scale clamps) are static per-format constants.
    """
    nbits, es = fmt.nbits, fmt.es
    ms = fmt.max_scale
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    sign = bits < 0
    expf = (bits >> 23) & 0xFF
    man = bits & 0x7FFFFF
    is_zero = (expf == 0) & (man == 0)
    is_nar = expf == 255                                 # inf/NaN -> NaR
    # f32 subnormals (< 2^-126) sit below every format's minpos.
    scale = jnp.where(expf == 0, jnp.int32(-150), expf - 127)
    over = scale >= ms                                   # k_max regime: maxpos
    under = (scale < -ms) & ~is_zero
    sc = jnp.clip(scale, np.int32(-ms), np.int32(ms - 1))                    # shift-safe lanes

    k = sc >> es                                         # floor(scale / 2^es)
    e = sc & ((1 << es) - 1)
    reg_len = jnp.where(k >= 0, k + 2, 1 - k)            # field w/ terminator
    avail = (nbits - 1) - reg_len                        # room for [e|frac]
    regime = jnp.where(k >= 0,
                       ((jnp.int32(1) << (k + 1)) - 1) << 1, jnp.int32(1))
    ef = (jnp.int32(1) << (es + 23)) | (e << 23) | man   # [1|e|frac23]
    d = jnp.maximum((es + 23) - avail, np.int32(0))                # [e|frac] bits dropped
    shl = jnp.maximum(avail - (es + 23), np.int32(0))              # or left-padded
    kf = (ef >> d) - (jnp.int32(1) << ((es + 23) - d))   # strip hidden bit
    pat0 = (regime << avail) | (kf << shl)
    dropped = ef & ((jnp.int32(1) << d) - 1)
    half = (jnp.int32(1) << d) >> 1
    rnd = (dropped > half) | ((dropped == half) & (dropped != 0)
                             & ((pat0 & 1) == 1))
    pat = pat0 + rnd.astype(jnp.int32)

    pat = jnp.where(over, jnp.int32(fmt.maxpos_pattern), pat)  # never NaR
    pat = jnp.where(under, jnp.int32(1), pat)            # clamp at minpos
    out = jnp.where(sign, jnp.int32(0) - pat, pat)       # 2's-complement neg
    out = jnp.where(is_zero, np.int32(0), out)
    return jnp.where(is_nar, np.int32(fmt.nar_pattern), out)


def encode_p32_f32(x):
    """f32 -> Posit(32,2) words (the PR-2 epilogue, now a specialization)."""
    return encode_posit_f32(x, P32E2)


def encode_p16_f32(x):
    """f32 -> Posit(16,1) words — the mixed-precision factorization
    format's in-kernel epilogue (p16e1 significands carry <= 13 bits, so
    the f32 accumulator holds them exactly and this rounding is the only
    one)."""
    return encode_posit_f32(x, P16E1)


# --------------------------------------------------------------------------
# kernel body
# --------------------------------------------------------------------------

def _matmul_f32(x, y):
    # The interpreter's dot: on a CPU, XLA computes it as the FMA chain the
    # compiled kernel reproduces; HIGHEST keeps any backend from dropping
    # to bf16 passes.
    return jax.lax.dot_general(
        x, y, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _i32(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _f32(b):
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _split12(x):
    """x == hi + lo exactly, each with at most 12 significant bits."""
    hi = _f32(_i32(x) & np.int32(-4096))
    return hi, x - hi


def _two_sum(a, b):
    """a + b == s + e exactly (Knuth), f32 round-to-nearest."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _fma_f32(s, x, y, xs, ys):
    """RN(s + x*y) in f32 from f32 adds and multiplies only.

    ``xs``/``ys`` are ``_split12`` halves, or None for an operand with at
    most 12 significant bits.  x*y == ph + pl exactly (Dekker), and
    s + ph == u + e (TwoSum); rounding e + pl to odd keeps a sticky bit
    far below u's last place, so one final round-to-nearest of u + v is
    the correctly rounded s + x*y (double rounding is harmless after a
    round-to-odd with two spare bits).  Exact for finite operands whose
    products and sums stay in f32's normal range.
    """
    xh, xl = xs if xs is not None else (x, None)
    yh, yl = ys if ys is not None else (y, None)
    ph = x * y
    pl_ = xh * yh - ph
    if yl is not None:
        pl_ = pl_ + xh * yl
    if xl is not None:
        pl_ = pl_ + xl * yh
    if xl is not None and yl is not None:
        pl_ = pl_ + xl * yl
    u, e = _two_sum(s, ph)
    v, w = _two_sum(e, pl_)
    vb = _i32(v)
    toward = jnp.where((vb < 0) == (w < 0), np.int32(1), np.int32(-1))
    vb = jnp.where((w != 0) & ((vb & 1) == 0), vb + toward, vb)
    return u + _f32(vb)


def _chain_split3(ah, al, b_ref, fmt, lo):
    """The three plane products of one K tile, each an f32 dot computed
    as the sequential fused multiply-add chain in k order, combined as
    ``hh + (hl + lh)``: the words XLA's CPU dot gives (it accumulates
    each output with one FMA per k, in order), computed on the VPU.
    Column t of the A planes is taken with an exact masked lane sum; row
    t of B is decoded where it is read.  ``lo=False`` (formats whose
    significand fits the hi plane) skips the two zero lo chains."""
    bm, bk = ah.shape
    bn = b_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 1)

    def col(x, t):
        return jnp.sum(jnp.where(lane == t, x, np.float32(0)), axis=1,
                       keepdims=True)

    def body(carry):
        t, hh, hl, lh = carry
        bh, bl = decode_split_f32(b_ref[pl.ds(t, 1), :], fmt)
        a_h = col(ah, t)
        a_hs, bhs = _split12(a_h), _split12(bh)
        hh = _fma_f32(hh, a_h, bh, a_hs, bhs)
        if lo:
            hl = _fma_f32(hl, a_h, bl, a_hs, None)
            lh = _fma_f32(lh, col(al, t), bh, None, bhs)
        return t + np.int32(1), hh, hl, lh

    # a while loop keeps the counter int32 (fori_loop's would be int64
    # under x64, which Mosaic cannot lower)
    zero = jnp.zeros((bm, bn), jnp.float32)
    _, hh, hl, lh = jax.lax.while_loop(lambda c: c[0] < bk, body,
                                       (jnp.int32(0), zero, zero, zero))
    return hh + (hl + lh) if lo else hh


def _kernel(a_ref, b_ref, o_ref, acc_ref, err_ref, *, n_k, compensated,
            emit_posit, negate, fmt, chain):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if compensated:
            err_ref[...] = jnp.zeros_like(err_ref)

    ah, al = decode_split_f32(a_ref[...], fmt)
    if chain:
        partial = _chain_split3(ah, al, b_ref, fmt,
                                lo=fmt.nbits - 3 - fmt.es > 23)
    else:
        bh, bl = decode_split_f32(b_ref[...], fmt)
        partial = (_matmul_f32(ah, bh)
                   + (_matmul_f32(ah, bl) + _matmul_f32(al, bh)))

    if compensated:
        acc = acc_ref[...]
        s = acc + partial
        bp = s - acc                                   # Knuth TwoSum
        err_ref[...] += (acc - (s - bp)) + (partial - bp)
        acc_ref[...] = s
    else:
        acc_ref[...] += partial

    @pl.when(k_idx == n_k - 1)
    def _done():
        val = acc_ref[...] + err_ref[...] if compensated else acc_ref[...]
        if negate:
            val = -val                                 # exact f32 sign flip
        if emit_posit:
            o_ref[...] = encode_posit_f32(val, fmt)    # fused epilogue
        else:
            o_ref[...] = val


# --------------------------------------------------------------------------
# pallas_call wrappers
# --------------------------------------------------------------------------

def _posit_gemm_call(a_p, b_p, *, bm, bn, bk, mode, interpret, emit_posit,
                     negate, fmt, chain=None):
    m, k = a_p.shape
    k2, n = b_p.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0, (
        (m, k, n), (bm, bn, bk))
    compensated = {"split3": False, "split3_comp": True}[mode]
    n_k = k // bk
    out_dtype = jnp.int32 if emit_posit else jnp.float32

    def call(a, b, interpret):
        # the compiled kernel forms its dots as FMA chains on the VPU, the
        # interpreter with XLA's dot, which gives the same words on a CPU
        kernel = functools.partial(
            _kernel, n_k=n_k, compensated=compensated, emit_posit=emit_posit,
            negate=negate, fmt=fmt,
            chain=not interpret if chain is None else chain)
        kwargs = {} if interpret else {
            "compiler_params": pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"))}
        return pl.pallas_call(
            kernel,
            grid=(m // bm, n // bn, n_k),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                            pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
            **kwargs,
        )(a, b)

    if interpret is not None:
        return call(a_p, b_p, interpret)
    # Compiled for a TPU, the Pallas interpreter on any other platform:
    # chosen when the program is lowered for its platform, so a program
    # lowered for a TPU always holds the compiled kernel.
    return jax.lax.platform_dependent(
        a_p, b_p, tpu=functools.partial(call, interpret=False),
        default=functools.partial(call, interpret=True))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "mode",
                                             "interpret", "fmt"))
def posit_gemm_f32(a_p: jax.Array, b_p: jax.Array, *, bm: int = 128,
                   bn: int = 128, bk: int = 128, mode: str = "split3",
                   interpret: bool | None = None,
                   fmt: PositFormat = P32E2) -> jax.Array:
    """(M,K) @ (K,N) over int32 posit words -> f32 accumulator.

    M, N, K must be multiples of the (MXU-aligned) block sizes; ops.py pads.
    ``interpret=None`` compiles the kernel in a program lowered for a TPU
    and interprets it on any other platform; pass True/False to force.  ``fmt`` selects the posit
    format of the input words (static; constants fold at trace).
    """
    return _posit_gemm_call(a_p, b_p, bm=bm, bn=bn, bk=bk, mode=mode,
                            interpret=interpret, emit_posit=False,
                            negate=False, fmt=fmt)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "mode",
                                             "negate", "interpret", "fmt"))
def posit_gemm(a_p: jax.Array, b_p: jax.Array, *, bm: int = 128,
               bn: int = 128, bk: int = 128, mode: str = "split3",
               negate: bool = False, interpret: bool | None = None,
               fmt: PositFormat = P32E2) -> jax.Array:
    """(M,K) @ (K,N) posit words -> posit words, encode fused in-kernel.

    The final-k ``@pl.when`` block rounds the f32 accumulator to the posit
    format inside the kernel (one rounding, quire-lite semantics) and
    emits int32 words — no f32 HBM round-trip, no host epilogue.
    ``negate`` flips the sign before the encode (exact), serving the BLAS
    alpha=-1 form.  Bit-identical to
    ``from_float32_bits(±posit_gemm_f32(...), fmt)`` for every format.
    """
    return _posit_gemm_call(a_p, b_p, bm=bm, bn=bn, bk=bk, mode=mode,
                            interpret=interpret, emit_posit=True,
                            negate=negate, fmt=fmt)
