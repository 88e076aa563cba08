"""Cell entry: quire-refined LU solve, ``refine.rgesv_ir``.

``rgesv_ir`` runs its refinement loop outside any jitted program, so
called bare it traces and compiles that loop again on every call; the
entry calls it under one ``jax.jit``, as a caller that solves repeatedly
would, so the measured window holds no compilation.

Checked: the backward error of the refined pair x_hi + x_lo on every
call, and the factorization's |P A - L U| / |A| on a sample of calls.
"""
from __future__ import annotations

import reference as ref
from entries.lu_solve import operand_sets, to_device  # noqa: F401


def make_call(cfg: dict, fmt, traffic: dict):
    import jax
    from repro.lapack import refine

    @jax.jit
    def rgesv_ir(a, b):
        return refine.rgesv_ir(a, b, iters=traffic["iters"], nb=cfg["nb"],
                               gemm_backend=cfg["gemm_backend"], fmt=fmt)

    def call(d):
        (x_hi, x_lo), (lu, ipiv) = rgesv_ir(d["a"], d["b"])
        return {"x_hi": x_hi, "x_lo": x_lo, "lu": lu, "ipiv": ipiv}
    return call


def check(host: dict, out: dict, fmt, full: bool, traffic: dict) -> dict:
    x = (ref.decode(out["x_hi"], fmt.nbits, fmt.es)
         + ref.decode(out["x_lo"], fmt.nbits, fmt.es))
    nums = {"berr_solve": ref.solve_backward_error(host["a"], x, host["b"])}
    if full:
        lu = ref.decode(out["lu"], fmt.nbits, fmt.es)
        nums["berr_factor"] = ref.lu_backward_error(host["a"], lu,
                                                    out["ipiv"])
    return nums


def updates(cfg: dict):
    """The trailing updates one call needs (those of its LU)."""
    import counts
    return counts.lu_updates(cfg["n"], cfg["nb"])
