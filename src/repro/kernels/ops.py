"""Rgemm — BLAS-3 GEMM interface over posit words (MPLAPACK naming).

    C = alpha * op(A) @ op(B) + beta * C,   op in {identity, transpose}

Transposes are applied at the op level before the kernel, mirroring the
paper's FPGA flow ("we transpose input matrices on a host CPU before
sending them to the FPGA").  Backends:

* ``pallas_split3`` / ``pallas_split3_comp`` — the TPU kernel
  (kernels/posit_gemm.py), f32 accumulators, single posit rounding
  (quire-lite semantics).  For alpha in {1, -1} and beta = 0 the rounding
  is fused into the kernel's final-k step (int32 posit words come
  straight off the kernel — DESIGN.md §2.1); other alpha/beta use the
  f32-accumulator output with a binary64-semantics epilogue computed in
  integer fields (``posit.f32_epilogue``).  Interpret mode on CPU,
  compiled on TPU (auto-detected).
* ``xla_quire``   — decode->f64 dot->encode (same semantics, no Pallas);
  the fast CPU path used by the decomposition benchmarks.  A TPU has no
  exact f64 dot, so on a TPU this backend raises and names the two that
  run there.
* ``quire_exact`` — true posit-standard quire (repro.quire): exact
  fixed-point accumulation, ONE rounding per output element.  For
  alpha in {1, -1} and beta in {0, 1} the whole update is a single fused
  op (products negated exactly, beta*C added into the quire exactly) —
  exactly the trailing-update shape Rpotrf/Rgetrf issue.  Other
  alpha/beta are folded in with one pre-rounded posit scaling.
* ``faithful``    — per-MAC posit rounding in BLAS chain order (the
  paper's PE behaviour): C(:,j) starts at beta*C, accumulates
  alpha*B(l,j)*A(:,l) with every op rounded.  Ground truth for accuracy
  studies.

Beta semantics: beta == 0 means C is NOT referenced (BLAS convention —
C may hold garbage or NaR) on every backend except ``faithful``, whose
literal per-op chain computes 0 * C first (the paper's PE op order, so
NaR in C poisons the output there).

``fmt`` selects the posit format (static, default Posit(32,2)): every
backend — including the Pallas kernel's in-kernel decode/encode — runs
the same dataflow with the format's field constants folded at trace time
(DESIGN.md §8).  All operands and the result are words of that ONE
format; mixed-format GEMM is done by converting at the boundary
(``posit.pconvert``), never inside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import posit
from repro.core.formats import P32E2, PositFormat
from repro.kernels import ref
from repro.kernels.posit_gemm import posit_gemm, posit_gemm_f32
from repro.obs import metrics as _obs_metrics
from repro.obs import numerics as _obs_numerics
from repro.obs import scopes as _scopes
from repro.obs import trace as _obs_trace
from repro.quire import quire_gemm

_ZERO = jnp.int32(0)


def _pad_to(x, mult, axes):
    pads = [(0, 0)] * x.ndim
    needs = False
    for ax in axes:
        r = (-x.shape[ax]) % mult
        if r:
            pads[ax] = (0, r)
            needs = True
    return jnp.pad(x, pads) if needs else x


def _scalar_posit(x, fmt: PositFormat):
    """alpha/beta are static Python scalars -> posit words at trace time."""
    assert isinstance(x, (int, float)), (
        "alpha/beta must be static Python scalars")
    return posit.word_of(x, fmt)


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "trans_a",
                                             "trans_b", "backend", "block",
                                             "fmt"))
def _rgemm_jit(a_p: jax.Array, b_p: jax.Array, c_p: jax.Array | None = None,
               alpha=1.0, beta=0.0, *, trans_a: bool = False,
               trans_b: bool = False, backend: str = "xla_quire",
               block: int = 128, fmt: PositFormat = P32E2) -> jax.Array:
    """The jitted GEMM program (see ``rgemm``, the public entry point),
    all of it under the ``posit.update`` scope."""
    with jax.named_scope(_scopes.UPDATE):
        return _rgemm_body(a_p, b_p, c_p, alpha, beta, trans_a, trans_b,
                           backend, block, fmt)


def _rgemm_body(a_p, b_p, c_p, alpha, beta, trans_a, trans_b, backend,
                block, fmt):
    a_p = jnp.asarray(a_p, jnp.int32)
    b_p = jnp.asarray(b_p, jnp.int32)
    if trans_a:
        a_p = a_p.T
    if trans_b:
        b_p = b_p.T
    m, k = a_p.shape
    _, n = b_p.shape
    alpha_p = _scalar_posit(alpha, fmt)
    beta_p = _scalar_posit(beta, fmt)
    if c_p is None:
        c_p = jnp.zeros((m, n), jnp.int32)

    if backend == "quire_exact":
        # Fold alpha/beta so the common BLAS-3 updates stay single-rounding:
        # |alpha| == 1 -> exact product negation; beta == 1 -> exact quire
        # add of C; anything else costs one pre-rounded posit scaling.
        a_in = a_p
        if alpha not in (1.0, -1.0, 1, -1):
            a_in = posit.mul(alpha_p, a_p, fmt, backend="fast")
        if beta in (0.0, 0):
            c_in = None
        elif beta in (1.0, 1):
            c_in = c_p
        else:
            c_in = posit.mul(beta_p, c_p, fmt, backend="fast")
        return quire_gemm(a_in, b_p, c_in, fmt,
                          negate=alpha in (-1.0, -1))

    if backend == "faithful":
        # BLAS chain order: C0 = beta*C; accumulate alpha*B(l,j) * A(:,l).
        b_scaled = posit.mul(alpha_p, b_p, fmt, backend="fast")
        c0 = posit.mul(beta_p, c_p, fmt, backend="fast")
        return ref.rgemm_faithful_chain(a_p, b_scaled, c0, fmt)

    if backend in ("pallas_split3", "pallas_split3_comp"):
        mode = backend.removeprefix("pallas_")
        ap = _pad_to(a_p, block, (0, 1))
        bp = _pad_to(b_p, block, (0, 1))
        if alpha in (1.0, 1, -1.0, -1) and beta in (0.0, 0):
            # Fused epilogue: the kernel's final-k step encodes the f32
            # accumulator to posit words in-VMEM (alpha=-1 as an exact
            # in-kernel sign flip), so rgemm consumes int32 words straight
            # off the kernel — no O(M*N) f32 HBM round-trip + host encode.
            return posit_gemm(ap, bp, bm=block, bn=block, bk=block,
                              mode=mode, fmt=fmt,
                              negate=alpha in (-1.0, -1))[:m, :n]
        acc = posit_gemm_f32(ap, bp, bm=block, bn=block, bk=block,
                             mode=mode, fmt=fmt)[:m, :n]
        # beta == 0: C is not referenced (BLAS convention, see below)
        if beta in (0.0, 0):
            return posit.f32_epilogue(acc, alpha_p, fmt=fmt)
        return posit.f32_epilogue(acc, alpha_p, beta_p, c_p, fmt)

    if backend != "xla_quire":
        raise ValueError(f"unknown backend {backend!r}")
    if jax.default_backend() == "tpu":
        raise ValueError(
            "rgemm backend 'xla_quire' accumulates in f64, which a TPU does "
            "not compute exactly; use backend='quire_exact' (exact) or "
            "'pallas_split3' (the TPU kernel)")
    ab = jnp.dot(posit.to_float64(a_p, fmt), posit.to_float64(b_p, fmt),
                 precision=jax.lax.Precision.HIGHEST)
    if beta in (0.0, 0):
        # BLAS convention: beta == 0 means C is NOT referenced (it may
        # hold garbage/NaR), matching the quire_exact and fused-pallas
        # paths.  'faithful' keeps its literal per-op chain (0 * NaR =
        # NaR) since it models the paper's PE op-for-op.
        out = posit.to_float64(alpha_p, fmt) * ab
    else:
        out = (posit.to_float64(alpha_p, fmt) * ab
               + posit.to_float64(beta_p, fmt) * posit.to_float64(c_p, fmt))
    return posit.from_float64(out, fmt)


def rgemm(a_p: jax.Array, b_p: jax.Array, c_p: jax.Array | None = None,
          alpha=1.0, beta=0.0, *, trans_a: bool = False, trans_b: bool = False,
          backend: str = "xla_quire", block: int = 128,
          fmt: PositFormat = P32E2) -> jax.Array:
    """Posit GEMM returning posit words (int32) in format ``fmt``.

    Observability (repro.obs): the call runs under the host span
    ``posit.rgemm``.  With a collector open and CONCRETE operands, the
    span is timed and the operand/result words are summarized
    (golden-zone occupancy, regime widths).  With no collector — or when
    this call is being traced into an outer jitted program
    (decomp/qr/pblas bodies), where the operands are tracers — the gate
    is resolved at the Python level and the exact same jitted program as
    before dispatches, so lowered programs are unchanged.
    """
    m = a_p.shape[1] if trans_a else a_p.shape[0]
    k = a_p.shape[0] if trans_a else a_p.shape[1]
    n = b_p.shape[0] if trans_b else b_p.shape[1]
    with _obs_trace.span("posit.rgemm", m=int(m), k=int(k), n=int(n),
                         backend=backend, fmt=fmt.name):
        out = _rgemm_jit(a_p, b_p, c_p, alpha, beta, trans_a=trans_a,
                         trans_b=trans_b, backend=backend, block=block,
                         fmt=fmt)
        if not _obs_numerics.active(a_p, b_p,
                                    c_p if c_p is not None else a_p):
            return out
        _obs_metrics.inc("rgemm.calls")
        _obs_metrics.inc("rgemm.macs", float(m) * float(k) * float(n))
        _obs_numerics.record_numerics("rgemm.a", a_p, fmt)
        _obs_numerics.record_numerics("rgemm.out", out, fmt)
    return out


def rgemm_f32(a_p, b_p, fmt: PositFormat = P32E2, **kw):
    """Convenience: decoded-f32 result (no final posit rounding)."""
    return posit.to_float64(rgemm(a_p, b_p, fmt=fmt, **kw),
                            fmt).astype(jnp.float32)
