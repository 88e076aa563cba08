"""The scope reducer and its readers (swap_s, solve_sweep_s, unscoped_s):
on a hand-made trace, on the committed traces of a program without
scopes, and on traces recorded on a TPU v5 lite with the scoped program
(``data/*_scoped_n256``: one traced window of two calls each of
``general.lu_solve`` and ``general.gemm`` at n = 256, nb = 128)."""
import gzip
import json
import os

import pytest

import scope_reduce as sr
import trace_reduce as tr
from metrics import solve_sweep_s, swap_s, unscoped_s

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
KERNEL = json.load(open(os.path.join(METRICS, "kernels.json")))[
    "update_kernel"]
READERS = {"swap_s": swap_s, "solve_sweep_s": solve_sweep_s,
           "unscoped_s": unscoped_s}

LU = "jit(_rgetrf_jit)"
HAND = {
    "devices": 1,
    "spans": [[0, 100, "bench.window"], [1, 60, "bench.call"],
              [61, 99, "bench.call"]],
    "ops": [                                  # start, end, instr, module, op
        [2, 4, "copy.0", "jit_f", ""],        # XLA's copy: no scope
        [5, 30, "while.1", "jit_f", LU + "/posit.swap/while"],
        [6, 10, "fusion.2", "jit_f", ""],     # in the loop, no metadata
        [12, 20, "fusion.3", "jit_f",
         LU + "/posit.panel/jit(getf2)/while/body/mul"],
        [40, 50, "kernel.4", "jit_f",
         LU + "/posit.update/jit(_rgemm_jit)/posit.update/"
         "jit(posit_gemm_f32)/cond/branch_0_fun/posit_gemm_f32_p32e2/"
         "pallas_call"],
        [70, 80, "fusion.5", "jit_s",
         "jit(rgetrs)/posit.sweep/jit(rtrsv_lower)/select_n"],
        [80, 95, "copy.6", "jit_s", "jit(rgetrs)/transpose"],
    ],
}


def test_innermost_scope_of_an_op():
    assert sr.scope_of(LU + "/posit.swap/while/body/dynamic_slice") == \
        "posit.swap"
    assert sr.scope_of(LU + "/posit.trsm/jit(rtrsm_left_lower)/while") == \
        "posit.trsm"
    # the inner scope wins: the GEMM's own scope inside the LU's update
    assert sr.scope_of("jit(f)/posit.panel/jit(g)/posit.update/x") == \
        "posit.update"
    # host span names, file names and unknown scopes are no scope
    for name in ("", "jit(rgetrs)/while/body", "jit(f)/posit.py/x",
                 "jit(f)/posit.rgetrf/x", "jit(f)/posit.other/x"):
        assert sr.scope_of(name) is None


def test_ops_without_a_scope_take_their_loops():
    got = {op[2]: s for op, _, s in sr.scoped(HAND)}
    assert got == {"copy.0": None, "while.1": "posit.swap",
                   "fusion.2": "posit.swap", "fusion.3": "posit.panel",
                   "kernel.4": "posit.update", "fusion.5": "posit.sweep",
                   "copy.6": None}
    assert sr.seconds_by_scope(HAND) == pytest.approx({
        None: 17e-9, "posit.swap": 17e-9, "posit.panel": 8e-9,
        "posit.update": 10e-9, "posit.sweep": 10e-9})


def test_readers_on_the_hand_made_trace():
    ctx = {"trace": HAND, "calls": 2}
    assert swap_s.read(ctx) == pytest.approx(8.5e-9)
    assert solve_sweep_s.read(ctx) == pytest.approx(5e-9)
    assert unscoped_s.read(ctx) == pytest.approx(8.5e-9)
    no_sweep = {**HAND, "ops": HAND["ops"][:5]}
    assert solve_sweep_s.read({"trace": no_sweep, "calls": 2}) == 0.0


def test_a_program_without_scopes_reads_none():
    bare = {**HAND, "ops": [[*op[:4], op[4].replace("/posit.", "/p_")]
                            for op in HAND["ops"]]}
    assert sr.seconds_by_scope(bare) is None
    for reader in READERS.values():
        assert reader.read({"trace": bare, "calls": 2}) is None


def _load(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / f"{name}.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", name + ".xplane.pb.gz"),
                   "rb") as f:
        path.write_bytes(f.read())
    return tr.load(str(path))


class _Traces(dict):
    def __init__(self, tmp_path_factory):
        super().__init__()
        self.tmp = tmp_path_factory

    def __missing__(self, name):
        self[name] = _load(self.tmp, name)
        return self[name]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The recorded traces, each loaded when a test first asks for it."""
    return _Traces(tmp_path_factory)


@pytest.mark.parametrize("name", ["lu_solve_n256", "gemm_n256"])
@pytest.mark.parametrize("metric", sorted(READERS))
def test_unscoped_program_traces_read_none(recorded, name, metric):
    """The committed traces of the program before it named its layers."""
    assert READERS[metric].read({"trace": recorded[name], "calls": 2}) is None


@pytest.mark.parametrize("name", ["lu_solve_scoped_n256",
                                  "gemm_scoped_n256"])
def test_scopes_and_unscoped_sum_to_busy_time(recorded, name):
    t = recorded[name]
    calls = sum(1 for s in t["spans"] if s[2] == "bench.call")
    assert calls == 2 and tr.complete(t)
    ctx = {"trace": t, "calls": calls}
    secs = sr.seconds_by_scope(t)
    per_call = sum(sr.per_call(ctx, s) for s in secs)
    assert per_call == pytest.approx(unscoped_s.read(ctx) + sum(
        sr.per_call(ctx, s) for s in sr.SCOPES), rel=1e-12)
    busy_s, _ = tr.busy_and_window(t)
    assert per_call == pytest.approx(busy_s / calls, rel=0.01)


def test_scoped_lu_trace_names_its_layers(recorded):
    t = recorded["lu_solve_scoped_n256"]
    secs = sr.seconds_by_scope(t)
    assert {"posit.panel", "posit.swap", "posit.trsm", "posit.update",
            "posit.sweep"} <= set(secs)
    assert not {"posit.quire_sweep", "posit.quire_residual",
                "posit.pair_update"} & set(secs)
    ctx = {"trace": t, "calls": 2}
    for reader in READERS.values():
        assert reader.read(ctx) > 0
    # the frame and kernel readers still find what they found before
    layers = tr.seconds_by_layer(t, tr.load_layers(METRICS))
    assert layers["panel_s"] > 0 and layers["trsm_s"] > 0
    assert 0 < tr.kernel_seconds(t, KERNEL) <= secs["posit.update"]


def test_scoped_gemm_trace_is_all_update(recorded):
    t = recorded["gemm_scoped_n256"]
    secs = sr.seconds_by_scope(t)
    assert set(secs) <= {"posit.update", None}
    busy_s, _ = tr.busy_and_window(t)
    assert secs["posit.update"] == pytest.approx(busy_s, rel=0.01)
    assert 0 < tr.kernel_seconds(t, KERNEL) < secs["posit.update"]
