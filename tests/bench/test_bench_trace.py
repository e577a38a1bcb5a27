"""The trace reduction against hand counts."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import trace as TR  # noqa: E402

OPS = [("site_step_linear.6", 100, 300), ("fusion.1", 250, 400),
       ("reduce-scatter.2", 500, 700), ("fusion.2", 650, 660),
       ("copy.3", 900, 1000), ("fusion.4", 1000, 1200)]
HOST = [("bench.submit", 0, 80),
        ("bench.wait_batch", 650, 1000), ("bench.wait_batch", 80, 300)]


def test_union_busy_and_idle_by_hand():
    assert TR.union([(s, e) for _, s, e in OPS], 0, 1000) == [
        (100, 400), (500, 700), (900, 1000)]
    assert TR.busy_ns(OPS, 0, 1000) == 300 + 200 + 100
    assert TR.window([(0, 10), (990, 1000)]) == (0, 1000)


def test_collective_exposure_by_hand():
    # reduce-scatter [500, 700) minus compute [650, 660): 150 + 40
    assert TR.collective_exposed_ns(OPS, 0, 1000) == 190
    assert TR.collective_exposed_ns(OPS, 0, 600) == 100


def test_kernel_events_and_top_ops_by_hand():
    assert TR.kernel_events(OPS, r"^site_step_linear", 0, 1000) == [200]
    assert TR.kernel_events(OPS, r"^site_step_linear", 150, 1000) == []
    top = TR.top_ops({0: OPS, 1: OPS[:1]}, 0, 1000, k=2)
    # per chip: site step (200 + 200) / 2, reduce-scatter 200 / 2
    assert top == [["site_step_linear.6", 200e-9], ["reduce-scatter.2", 100e-9]]


def test_idle_gaps_are_labelled_by_the_covering_span():
    gaps = TR.idle_gaps(OPS, HOST, 0, 1000)
    assert gaps == [["bench.wait_batch", 200e-9], ["bench.submit", 100e-9],
                    ["idle", 100e-9]]


def test_subtract_by_hand():
    assert TR.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (25, 26)]) == [
        (0, 2), (4, 8), (22, 25), (26, 30)]


def test_op_names_from_hlo_text():
    assert TR.op_name("%site_step_linear.6 = (f32[8,128]{1,0}, s32[8,1]) "
                      "custom-call(%copy.21), custom_call_target=\"x\"") \
        == "site_step_linear"
    assert TR.op_name("%copy-start.1 = (f32[8]{0}, u32[]) copy-start("
                      "f32[8]{0} %l.1)") == "copy-start"
    assert TR.op_name("%fusion.5 = f32[16,8]{1,0} reduce-scatter("
                      "f32[16,32] %x)") == "fusion:reduce-scatter"


RECORDED = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "bench", "testdata",
    "seq_chi1024_n2048.xplane.pb")


def test_recorded_chip_trace_by_hand():
    """One traced macro batch of the seq walk on a TPU v5e (χ = 1024,
    N = 2048, 4 sites in 2 segments, ``bench/record_trace.py``).  Counted
    off the file by hand: two marker programs, two segment programs, and
    the fused site step once per site, 160,823 ns each."""
    import numpy as np

    devices, markers = TR.load(RECORDED)
    assert list(devices) == [0] and len(markers) == 2
    lo, hi = TR.window(markers)
    assert (lo, hi) == (50_184_119, 84_361_286)
    ops = devices[0]
    assert TR.kernel_events(ops, r"^site_step_linear$", lo, hi) == \
        [160_823] * 4
    assert sum(n == "while" and lo <= s < hi for n, s, _ in ops) == 2
    assert TR.collective_exposed_ns(ops, lo, hi) == 0
    # the busy union against a brute-force timeline of the window
    busy = np.zeros(hi - lo, bool)
    for _, s, e in ops:
        busy[max(s, lo) - lo:max(min(e, hi) - lo, 0)] = True
    assert TR.busy_ns(ops, lo, hi) == int(busy.sum()) == 985_872
