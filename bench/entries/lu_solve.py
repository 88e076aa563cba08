"""Cell entry: LU solve, ``decomp.rgetrf`` then ``solve.rgetrs``.

Checked: the solution's backward error on every call, and the
factorization's backward error |P A - L U| / |A| on a sample of calls.
"""
from __future__ import annotations

import reference as ref


def operand_sets(cfg: dict, seed: int, count: int) -> list[dict]:
    if cfg["nrhs"] != 1:
        raise ValueError("the solve entries take one right-hand side")
    n = cfg["n"]
    out = []
    for s in range(count):
        a = ref.make_matrix(cfg["matrix"], n, cfg["sigma"], seed, s)
        out.append({"a": a, "b": a @ ref.exact_solution(n)})
    return out


def to_device(host: dict, fmt) -> dict:
    import jax
    return {k: jax.device_put(ref.encode(v, fmt.nbits, fmt.es))
            for k, v in host.items()}


def make_call(cfg: dict, fmt, traffic: dict):
    from repro.lapack import decomp, solve
    nb, backend = cfg["nb"], cfg["gemm_backend"]

    def call(d):
        lu, ipiv = decomp.rgetrf(d["a"], nb=nb, gemm_backend=backend,
                                 fmt=fmt)
        return {"lu": lu, "ipiv": ipiv,
                "x": solve.rgetrs(lu, ipiv, d["b"], fmt=fmt)}
    return call


def check(host: dict, out: dict, fmt, full: bool, traffic: dict) -> dict:
    """The numbers of one call; ``full`` adds the factorization's."""
    x = ref.decode(out["x"], fmt.nbits, fmt.es)
    nums = {"berr_solve": ref.solve_backward_error(host["a"], x, host["b"])}
    if full:
        lu = ref.decode(out["lu"], fmt.nbits, fmt.es)
        nums["berr_factor"] = ref.lu_backward_error(host["a"], lu,
                                                    out["ipiv"])
    return nums


def updates(cfg: dict):
    """The trailing updates one call needs: (operations, bytes) each."""
    import counts
    return counts.lu_updates(cfg["n"], cfg["nb"])
