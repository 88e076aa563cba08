"""The plain reference: the paper's data, a posit codec and the checks.

Everything here is numpy on the host and imports nothing of the program
under test, so a change to the program cannot move it.

* Data (Kobayashi et al., arXiv 2401.14117, section 5.1): a general matrix
  A ~ N(0, sigma^2) for LU; the exact solution is x = 1/sqrt(n) and
  b = A x in binary64.
* A posit(nbits, es) codec: binary64 to words (round to nearest, ties to
  even on the bit pattern, saturating at minpos and maxpos) and back
  (exact), on sign-extended int32 words.
* The numbers that decide ``correct``: the backward error of a solution,
  the backward error of a factorization and the componentwise error of a
  GEMM, all in binary64.
"""
from __future__ import annotations

import numpy as np

# --------------------------------------------------------------------------
# data
# --------------------------------------------------------------------------

def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream): any whole seed."""
    return np.random.default_rng([int(seed) % 2**64, stream])


def make_matrix(kind: str, n: int, sigma: float, seed: int, stream: int
                ) -> np.ndarray:
    """One n x n binary64 input matrix of the given kind."""
    rng = rng_for(seed, stream)
    if kind == "general":
        return rng.standard_normal((n, n)) * sigma
    raise ValueError(f"unknown matrix kind {kind!r}")


def exact_solution(n: int) -> np.ndarray:
    return np.full((n,), 1.0 / np.sqrt(n))


# --------------------------------------------------------------------------
# posit codec (int64 field arithmetic, vectorized; chunks run in threads)
# --------------------------------------------------------------------------

def _chunked(fn, x: np.ndarray, out_dtype, threads: int = 4) -> np.ndarray:
    """Apply an elementwise ``fn`` to ``x`` in row chunks on a few threads
    (numpy releases the interpreter lock inside its loops)."""
    from concurrent.futures import ThreadPoolExecutor
    flat = np.ascontiguousarray(x).reshape(-1)
    out = np.empty(flat.shape, out_dtype)
    bounds = np.linspace(0, flat.size, threads + 1).astype(int)

    def one(i):
        lo, hi = bounds[i], bounds[i + 1]
        out[lo:hi] = fn(flat[lo:hi])

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(one, range(threads)))
    return out.reshape(np.shape(x))


def _bit_length(y: np.ndarray) -> np.ndarray:
    """Bit length of 0 <= y < 2**53 (exact through binary64)."""
    return np.frexp(y.astype(np.float64))[1].astype(np.int64)


def _decode(w: np.ndarray, nbits: int, es: int) -> np.ndarray:
    nb = nbits - 1
    mask = (1 << nbits) - 1
    w = w.astype(np.int64) & mask
    neg = w >> nb == 1
    a = np.where(neg, (-w) & mask, w)
    body = a & ((1 << nb) - 1)
    r0 = body >> (nb - 1) == 1
    y = np.where(r0, ~body & ((1 << nb) - 1), body)
    run = nb - _bit_length(y)                     # regime run length
    k = np.where(r0, run - 1, -run)
    rest = np.maximum(nb - run - 1, 0)            # bits after the regime
    u = body & ((1 << rest) - 1)
    flen = np.maximum(rest - es, 0)               # fraction bits
    e = np.where(rest >= es, u >> flen, u << (es - rest)) if es else 0
    frac = u & ((1 << flen) - 1)
    val = np.ldexp(((1 << flen) + frac).astype(np.float64),
                   k * (1 << es) + e - flen)
    val = np.where(neg, -val, val)
    val = np.where(w == 0, 0.0, val)
    return np.where(w == 1 << nb, np.nan, val)


def decode(words: np.ndarray, nbits: int, es: int) -> np.ndarray:
    """Posit words (sign-extended int32) -> binary64 values, exactly.
    NaR decodes to NaN."""
    return _chunked(lambda w: _decode(w, nbits, es), np.asarray(words),
                    np.float64)


def _encode(x: np.ndarray, nbits: int, es: int) -> np.ndarray:
    nb = nbits - 1
    bits = x.view(np.int64)
    expo = (bits >> 52) & 0x7FF
    f52 = bits & ((1 << 52) - 1)
    scale = np.where(expo == 0, -(1 << 20), expo - 1023)  # tiny -> minpos
    k = scale >> es                               # floor division
    e = scale & ((1 << es) - 1)
    pos = k >= 0
    reg_len = np.where(pos, k + 2, 1 - k)
    sat = reg_len > nb                            # beyond maxpos / minpos
    reg_len = np.where(sat, nb, reg_len)
    kk = np.where(sat | ~pos, 0, k)
    regime = np.where(pos, ((1 << (kk + 1)) - 1) << 1, 1)
    avail = nb - reg_len                          # bits left for the tail
    drop = es + 52 - avail                        # > 0 for nbits <= 32
    tail = (e << 52) | f52
    keep = tail >> drop
    rem = tail & ((1 << drop) - 1)
    half = 1 << (drop - 1)
    pat = (regime << avail) | keep
    pat = pat + ((rem > half) | ((rem == half) & (pat & 1 == 1)))
    maxpos = (1 << nb) - 1
    pat = np.clip(pat, 1, maxpos)
    pat = np.where(sat, np.where(pos, maxpos, 1), pat)
    word = np.where(x < 0, -pat, pat)
    word = np.where(x == 0, 0, word)
    return np.where(np.isfinite(x), word, -(1 << nb)).astype(np.int32)


def encode(x: np.ndarray, nbits: int, es: int) -> np.ndarray:
    """binary64 -> posit words (sign-extended int32): round to nearest,
    ties to even on the bit pattern; never rounds to 0 or to NaR.  NaN
    and infinities encode to NaR."""
    return _chunked(lambda v: _encode(v, nbits, es),
                    np.asarray(x, np.float64), np.int32)


# --------------------------------------------------------------------------
# the numbers compared
# --------------------------------------------------------------------------

def solve_backward_error(a: np.ndarray, x: np.ndarray, b: np.ndarray
                         ) -> float:
    """|b - A x|_2 / |b|_2 in binary64 (NaN where x holds NaR)."""
    r = b - a @ x
    return float(np.linalg.norm(r) / np.linalg.norm(b))


def lu_backward_error(a: np.ndarray, lu: np.ndarray, ipiv: np.ndarray
                      ) -> float:
    """|P A - L U|_F / |A|_F for LAPACK-style ipiv (row k swapped with
    row ipiv[k], in order) and packed unit-lower L / upper U."""
    n = a.shape[0]
    perm = np.arange(n)
    for k, p in enumerate(np.asarray(ipiv, np.int64)):
        perm[k], perm[p] = perm[p], perm[k]
    low = np.tril(lu, -1) + np.eye(n)
    up = np.triu(lu)
    return float(np.linalg.norm(a[perm] - low @ up) / np.linalg.norm(a))


def gemm_error(out: np.ndarray, a: np.ndarray, b: np.ndarray,
               c: np.ndarray, alpha: float, beta: float) -> float:
    """max_ij |out - (alpha A B + beta C)|_ij / (|alpha| |A| |B| + |beta|
    |C|)_ij: the componentwise error of C <- alpha A B + beta C."""
    ref = alpha * (a @ b) + beta * c
    den = abs(alpha) * (np.abs(a) @ np.abs(b)) + abs(beta) * np.abs(c)
    err = np.abs(out - ref) / np.where(den > 0, den, 1.0)
    return float(np.max(np.where(np.isnan(out), np.inf, err)))
