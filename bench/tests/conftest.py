"""Harness tests: run by path (``python -m pytest bench/tests``), on the
CPU.  They import the harness's modules from bench/."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture
def small_cell(monkeypatch):
    """Make ``run.main`` run a cell on the CPU at order n: the device
    check passes without a TPU, and the configuration is cut to n."""
    import run

    start_jax, load_cell = run.start_jax, run.load_cell

    def shrink(n):
        monkeypatch.setattr(run, "start_jax",
                            lambda chips, require_tpu: start_jax(chips, False))
        monkeypatch.setattr(run, "load_cell", lambda name: _cut(
            run, load_cell(name), n))
        return run

    return shrink


def _cut(run, c, n):
    c["cfg"] = run.rehearsal_size(c["cfg"], n)
    return c
