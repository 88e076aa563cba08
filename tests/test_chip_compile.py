"""The posit LAPACK main path, compiled for a described TPU v5e chip.

Nothing runs on a chip: each test lowers and compiles a program at real
widths for one chip of a ``v5e:2x2`` topology that libtpu describes
without hardware, so whatever the TPU compiler refuses (f64 bit casts,
Mosaic lowering errors, blocks off the (8, 128) tiling, programs that do
not fit) fails here.  Where the Pallas kernel belongs, the compiled text
must hold a ``tpu_custom_call``: a kernel left in interpret mode would
lower to plain HLO instead.

The topology is described inside a module fixture, never at import:
only one process at a time may load libtpu, and pytest-xdist workers
all import this file.  Keep these compiles in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.formats import P16E1, P32E2
from repro.kernels.ops import rgemm
from repro.kernels.posit_gemm import posit_gemm
from repro.lapack import decomp

N = 512           # matrix order of the compiled programs
NB = 128          # factorization block = trailing-update K


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _compiled_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("fmt", [P32E2, P16E1], ids=lambda f: f.name)
def test_posit_gemm_compiles(one_chip, fmt):
    text = _compiled_text(lambda a, b: posit_gemm(a, b, fmt=fmt),
                          (N, N), (N, N), sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("backend", ["pallas_split3", "quire_exact"])
def test_trailing_update_compiles(one_chip, backend):
    """The factorizations' update C - A @ B (alpha=-1, beta=1)."""
    m = N - NB
    text = _compiled_text(
        lambda a, b, c: rgemm(a, b, c, alpha=-1.0, beta=1.0,
                              backend=backend),
        (m, NB), (NB, m), (m, m), sharding=one_chip)
    assert ("tpu_custom_call" in text) == backend.startswith("pallas")


@pytest.mark.parametrize("factor", ["rgetrf", "rpotrf"])
def test_factorization_compiles(one_chip, factor):
    fn = {"rgetrf": decomp.rgetrf, "rpotrf": decomp.rpotrf}[factor]
    text = _compiled_text(
        lambda a: fn(a, nb=NB, gemm_backend="pallas_split3"), (N, N),
        sharding=one_chip)
    assert "tpu_custom_call" in text
