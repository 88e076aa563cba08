"""Layer scopes: the names the main path's layers carry in op metadata.

Each layer of the posit LAPACK main path runs under one
``jax.named_scope`` from this table.  The scope becomes a component of
the metadata name of every op the layer emits
(``jit(_rgetrf_jit)/posit.swap/while/body/...``), so a profiler trace of
a plain call names the layer of each device op, and the name stays when
the jitted helpers inside a layer are merged or renamed.  Scopes are op
metadata only: with or without them the compiled programs are the same.

=========================  ==============================================
scope                      what runs under it
=========================  ==============================================
``posit.panel``            the panel factorization (``getf2``, ``potf2``)
                           and the write of the panel and its pivots
``posit.swap``             the panel's row swaps applied to the blocks
                           left and right of it, and their write;
                           ``rgetrs``' pivot scan on b
``posit.trsm``             the block row / column triangular solve
                           (``rtrsm_left_lower``, ``rtrsm_right_lowerT``)
                           and its write
``posit.update``           the trailing update: the ``rgemm`` call and
                           its write in the factorizations, and the whole
                           body of the jitted GEMM (kernel and epilogue)
``posit.sweep``            the substitution sweeps of ``rgetrs`` /
                           ``rpotrs`` with ``quire=False``
``posit.quire_sweep``      the same sweeps with ``quire=True``
``posit.quire_residual``   the quire residual ``b - A (x_hi + x_lo)``
``posit.pair_update``      the exact update of the refined pair
=========================  ==============================================

The public entry points (``rgetrf``, ``rpotrf``, ``rgemm``, ``rgesv_ir``,
``rposv_ir``) also open a host span of the same prefix, ``posit.<entry>``
(``obs.span``), which a profiler trace shows on the host's line.
"""
from __future__ import annotations

PANEL = "posit.panel"
SWAP = "posit.swap"
TRSM = "posit.trsm"
UPDATE = "posit.update"
SWEEP = "posit.sweep"
QUIRE_SWEEP = "posit.quire_sweep"
QUIRE_RESIDUAL = "posit.quire_residual"
PAIR_UPDATE = "posit.pair_update"

ALL = (PANEL, SWAP, TRSM, UPDATE, SWEEP, QUIRE_SWEEP, QUIRE_RESIDUAL,
       PAIR_UPDATE)


def sweep(quire: bool) -> str:
    """The scope of a substitution sweep."""
    return QUIRE_SWEEP if quire else SWEEP
