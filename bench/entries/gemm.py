"""Cell entry: the trailing-update GEMM, ``rgemm`` C <- alpha A B + beta C.

Three n x n operands per set from the configuration's ensemble, the
alpha and beta of the traffic (the factorizations' -1 and 1).

Checked: the componentwise error of every output word against binary64,
max_ij |C' - (alpha A B + beta C)| / (|alpha| |A| |B| + |beta| |C|), on a
sample of calls (every output of a set is the same computation).
"""
from __future__ import annotations

import reference as ref
from entries.lu_solve import to_device  # noqa: F401


def operand_sets(cfg: dict, seed: int, count: int) -> list[dict]:
    n = cfg["n"]
    mats = [ref.make_matrix(cfg["matrix"], n, cfg["sigma"], seed, s)
            for s in range(count + 2)]
    return [{"a": mats[s], "b": mats[s + 1], "c": mats[s + 2]}
            for s in range(count)]


def make_call(cfg: dict, fmt, traffic: dict):
    from repro.kernels.ops import rgemm
    alpha, beta = traffic["alpha"], traffic["beta"]
    backend = cfg["gemm_backend"]

    def call(d):
        return {"c": rgemm(d["a"], d["b"], d["c"], alpha=alpha, beta=beta,
                           backend=backend, fmt=fmt)}
    return call


def check(host: dict, out: dict, fmt, full: bool, traffic: dict) -> dict:
    if not full:
        return {}
    got = ref.decode(out["c"], fmt.nbits, fmt.es)
    return {"gemm_err": ref.gemm_error(got, host["a"], host["b"], host["c"],
                                       traffic["alpha"], traffic["beta"])}


def updates(cfg: dict):
    """One n x n x n GEMM per call."""
    import counts
    n = cfg["n"]
    return [counts.gemm(n, n, n)]
