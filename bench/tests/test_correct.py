"""``correct`` against the reference: the program passes, the control
(the program in its next-lower posit format) and planted faults fail.

Runs the harness end to end on the CPU at small orders, with the limits
the cells use at full size (bench/limits/): the program at n = 64, the
control and a skipped refinement at n = 256, where their errors already
exceed those limits."""
import io
import json
from contextlib import redirect_stdout

import pytest

N = 64
N_CONTROL = 256
SOLVES = ["general.lu_solve", "general.ir_solve"]
CELLS = SOLVES + ["general.gemm"]


def _result(run, workload, seed=11):
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", "0.2", "--trace", "0"]) == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_program_is_correct(small_cell, workload):
    res = _result(small_cell(N), workload)
    assert res["correct"] is True, res["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(small_cell, monkeypatch, workload):
    run = small_cell(N_CONTROL)
    fmt = run.program_format
    monkeypatch.setattr(run, "program_format",
                        lambda cfg, control=False: fmt(cfg, control=True))
    res = _result(run, workload)
    assert res["correct"] is False, res["checks"]


def _alter(words, index=(3,)):
    """One answer altered where it is produced: a word's sign flipped
    and a low bit changed."""
    import jax.numpy as jnp
    w = jnp.asarray(words)
    return w.at[index].set(-(w[index] ^ 1))


def _plant(monkeypatch, module, name, fix):
    orig = getattr(module, name)
    monkeypatch.setattr(module, name, lambda *a, **k: fix(orig(*a, **k)))


@pytest.mark.parametrize("workload,fault", [
    ("general.lu_solve", "solution"), ("general.lu_solve", "factor"),
    ("general.ir_solve", "solution"), ("general.gemm", "output")])
def test_altered_answer_is_not_correct(small_cell, monkeypatch, workload,
                                       fault):
    from repro.kernels import ops
    from repro.lapack import decomp, refine, solve

    run = small_cell(N)
    if workload == "general.gemm":
        _plant(monkeypatch, ops, "rgemm", lambda c: _alter(c, (5, 7)))
    elif workload == "general.ir_solve":
        _plant(monkeypatch, refine, "rgesv_ir",
               lambda r: ((_alter(r[0][0]), r[0][1]), r[1]))
    elif fault == "solution":
        _plant(monkeypatch, solve, "rgetrs", _alter)
    else:
        _plant(monkeypatch, decomp, "rgetrf",
               lambda r: (_alter(r[0], (N - 1, N - 2)), r[1]))
    res = _result(run, workload)
    assert res["correct"] is False, res["checks"]



def test_skipped_refinement_is_not_correct(small_cell, monkeypatch):
    """``rgesv_ir`` with its refinement sweeps left out returns the plain
    quire LU solve, whose backward error the IR cell's limit refuses."""
    from repro.lapack import refine

    run = small_cell(N_CONTROL)
    orig = refine.rgesv_ir
    monkeypatch.setattr(refine, "rgesv_ir",
                        lambda a, b, iters=3, **k: orig(a, b, iters=0, **k))
    res = _result(run, "general.ir_solve")
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["berr_solve"]["value"] > res["checks"][
        "berr_solve"]["limit"]
