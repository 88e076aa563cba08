"""The trace reducer: busy union, idle gaps, frame-to-layer attribution,
on hand-made traces and on small traces recorded on a TPU v5 lite
(``data/``: one traced window of two calls each of ``general.lu_solve``
and ``general.gemm`` at n = 256, nb = 128)."""
import gzip
import os
import re

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
TABLE = tr.load_layers(METRICS)
KERNEL = __import__("json").load(open(os.path.join(
    METRICS, "kernels.json")))["update_kernel"]

GETF2 = "jit(_rgetrf_jit)/jit(getf2)/while"
HAND = {
    "devices": 1,
    "spans": [[0, 100, "bench.window"], [1, 60, "bench.call"],
              [61, 99, "bench.call"]],
    "ops": [                                  # start, end, instr, module, op
        [5, 30, "while.1", "jit_f", GETF2],
        [6, 10, "fusion.2", "jit_f", ""],     # in the loop, no metadata
        [12, 20, "fusion.3", "jit_f", GETF2 + "/body/jit(rtrsv_lower)/x"],
        [40, 50, "kernel.4", "jit_f",
         "jit(_rgetrf_jit)/jit(_rgemm_jit)/jit(posit_gemm_f32)/cond/"
         "branch_0_fun/pallas_call"],
        [70, 80, "fusion.5", "jit_s",
         "jit(rgetrs)/jit(rtrsv_lower_quire)/jit(_where)/select_n"],
        [80, 95, "copy.6", "jit_s", ""],
    ],
}


def test_union_and_gaps_by_hand():
    busy = tr.merged((o[0], o[1]) for o in HAND["ops"])
    assert busy == [[5, 30], [40, 50], [70, 95]]
    assert tr.gaps(busy, 0, 100) == [[0, 5], [30, 40], [50, 70], [95, 100]]
    assert tr.busy_and_window(HAND) == (60e-9, 100e-9)


def test_window_widens_to_hold_every_device_op():
    early = {**HAND, "ops": [[-7, -2, "copy.0", "jit_f", ""]] + HAND["ops"]}
    assert tr.window_of(early) == (-7, 100)


def test_a_trace_that_lost_its_tail_is_not_complete():
    assert tr.complete(HAND) and tr.lost_tail_s(HAND) == 5e-9
    cut = {**HAND, "spans": [[0, 10**9, "bench.window"]]}
    assert not tr.complete(cut)
    assert tr.window_of(cut) == (0, 95)        # cut at the last op


def test_nesting_gives_own_time_and_parents():
    own, parent = tr.nesting(HAND["ops"])
    assert own == [25 - 4 - 8, 4, 8, 10, 10, 15]
    assert parent == [-1, 0, 0, -1, -1, -1]


def test_layers_by_innermost_named_frame_else_the_enclosing_op():
    assert tr.layer_of(GETF2 + "/mul", TABLE) == "panel_s"
    assert tr.layer_of("jit(rgetrs)/jit(rtrsv_lower_quire)/jit(rtrsv_lower)",
                       TABLE) == "trsm_s"
    assert tr.layer_of("jit(rgesv_ir)/jit(rgetrs)/jit(rtrsv_upper_quire)/a",
                       TABLE) is None
    assert tr.layer_of("jit(rgetrs)/while/body", TABLE) is None
    assert tr.seconds_by_layer(HAND, TABLE) == pytest.approx(
        {"panel_s": 17e-9, "trsm_s": 8e-9, None: 35e-9})


def test_kernel_seconds_and_breakdown():
    assert tr.kernel_seconds(HAND, KERNEL) == pytest.approx(10e-9)
    bd = tr.breakdown(HAND, top=3)
    assert bd["device_ops"] == [["jit_s/copy.6", 15e-9],
                                ["jit_f/while.1", 13e-9],
                                ["jit_f/kernel.4", 10e-9]]
    assert bd["idle_gaps"] == [["bench.window after jit_f/kernel.4", 20e-9],
                               ["bench.call after jit_f/while.1", 10e-9],
                               ["bench.call after start", 5e-9]]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    out = {}
    for name in ("lu_solve_n256", "gemm_n256"):
        path = tmp_path_factory.mktemp("trace") / f"{name}.xplane.pb"
        with gzip.open(os.path.join(HERE, "data", name + ".xplane.pb.gz"),
                       "rb") as f:
            path.write_bytes(f.read())
        out[name] = tr.load(str(path))
    return out


def _union_by_sweep(intervals) -> int:
    """The busy union counted another way: a sweep over start and end
    events, busy while any op is open."""
    events = sorted([(s, 1) for s, _ in intervals]
                    + [(e, -1) for _, e in intervals])
    busy, depth, since = 0, 0, None
    for t, step in events:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_lu_trace_structure(recorded):
    t = recorded["lu_solve_n256"]
    assert t["devices"] == 1 and len(t["ops"]) == 49536
    assert [s[2] for s in t["spans"]] == ["bench.window", "bench.call",
                                          "bench.call"]
    # every op ran inside one of the two programs, and most carry the
    # JAX op metadata of their HLO instruction
    assert {o[3] for o in t["ops"]} == {"jit__rgetrf_jit", "jit_rgetrs"}
    named = [o for o in t["ops"] if o[4]]
    assert len(named) > 0.6 * len(t["ops"])
    assert all(o[4].startswith(("jit(_rgetrf_jit)", "jit(rgetrs)", "lu_p"))
               for o in named)


def test_recorded_lu_trace_busy_and_idle(recorded):
    t = recorded["lu_solve_n256"]
    lo, hi = tr.window_of(t)
    busy_ns = _union_by_sweep([(o[0], o[1]) for o in t["ops"]])
    busy_s, window_s = tr.busy_and_window(t)
    assert busy_s == pytest.approx(busy_ns / 1e9, abs=1e-12)
    assert window_s == pytest.approx((hi - lo) / 1e9, abs=1e-12)
    idle = tr.gaps(tr.merged((o[0], o[1]) for o in t["ops"]), lo, hi)
    assert sum(e - s for s, e in idle) + busy_ns == hi - lo
    # own times add up to the busy time: ops nest and do not overlap
    # (each op's interval is rounded to whole nanoseconds)
    own, _ = tr.nesting(t["ops"])
    assert abs(sum(own) - busy_ns) <= 1e-5 * busy_ns


def test_recorded_lu_trace_layers(recorded):
    t = recorded["lu_solve_n256"]
    secs = tr.seconds_by_layer(t, TABLE)
    assert set(secs) == {"panel_s", "trsm_s", None}
    assert sum(secs.values()) == pytest.approx(tr.busy_and_window(t)[0],
                                               rel=1e-5)
    # every op whose own metadata names getf2 is counted in panel_s
    own, _ = tr.nesting(t["ops"])
    getf2 = sum(w for o, w in zip(t["ops"], own)
                if re.search(r"jit\(getf2\)", o[4]) and not re.search(
                    r"jit\(getf2\)/.*jit\(rt", o[4]))
    assert secs["panel_s"] * 1e9 >= getf2 > 0
    # two LU calls with two blocks: one trailing update each
    kernels = [o for o in t["ops"] if re.search(KERNEL, o[4])]
    assert len(kernels) == 2
    assert tr.kernel_seconds(t, KERNEL) == pytest.approx(
        sum(o[1] - o[0] for o in kernels) / 1e9)


def test_recorded_gemm_trace_kernel(recorded):
    t = recorded["gemm_n256"]
    assert [o[2] for o in t["ops"]] == ["branch_0_fun.1", "fusion.33"] * 2
    kernel_s = tr.kernel_seconds(t, KERNEL)
    assert kernel_s == pytest.approx(sum(
        o[1] - o[0] for o in t["ops"][::2]) / 1e9)
    assert 0 < kernel_s < tr.busy_and_window(t)[0]
