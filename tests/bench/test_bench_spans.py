"""The program's spans on the device clock (``bench/spans.py``) and the
per-layer metrics that read them, against hand counts on synthetic ops,
spans and counters."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench import spans as SP  # noqa: E402
from repro.obs.trace import Record  # noqa: E402

MS = 1_000_000
SHIFT = 7_000 * MS                  # device clock = program clock + SHIFT


def rec(name, s, e, sid, parent=None, **attrs):
    return Record(name, s, e, sid, parent, attrs)


def program_spans(slack_ns=(30, 1_000)):
    """Two batches of job 1 after a warm job 0, on the program's clock
    (ns): the consumer's waits sit inside the harness's by ``slack_ns``."""
    s0, s1 = slack_ns
    return [
        rec("dispatch.autotune", 100 * MS, 2_100 * MS, 1, 2, job=0),
        rec("dispatch.autotune", 2_100 * MS, 2_600 * MS, 2, 2, job=0),
        rec("service.batch", 0, 3_000 * MS, 3, job=0, batch=0),
        # batch 0 of the timed job: wait on Γ [250, 600) ms, fetch under it
        rec("engine.wait_gamma", 5_250 * MS, 5_600 * MS, 10, 12, job=1),
        rec("store.read", 5_200 * MS, 5_550 * MS, 11, 13, job=1),
        rec("engine.walk", 5_010 * MS, 5_990 * MS, 12, 14, job=1, batch=0),
        rec("engine.fetch", 5_150 * MS, 5_580 * MS, 13, 12, job=1),
        rec("service.batch", 5_000 * MS, 6_000 * MS, 14, job=1, batch=0),
        rec("service.stream_wait", 5_000 * MS + 10, 6_000 * MS + 20 - s0,
            15, job=1, batch=0),
        rec("dispatch.autotune", 6_100 * MS, 6_200 * MS, 16, 17, job=1),
        rec("engine.wait_gamma", 6_900 * MS, 7_100 * MS, 18, 17, job=1),
        rec("service.batch", 6_000 * MS, 7_200 * MS, 17, job=1, batch=1),
        rec("service.stream_wait", 6_000 * MS + 20, 7_200 * MS + 20 + 10
            - s1, 19, job=1, batch=1),
    ]


def late(spans):
    """What a ring that dropped every span closed before the window
    keeps."""
    return [s for s in spans if s.end_ns >= 5_000 * MS]


def context(**kw):
    """The harness's spans on the device clock: its wait for each batch
    begins 10 ns before the program's and ends the slack after it; the
    window is [5000, 6000) ms of program time."""
    ctx = types.SimpleNamespace(
        cell=types.SimpleNamespace(chips=1),
        host=[("bench.submit", SHIFT + 4_990 * MS, SHIFT + 5_000 * MS),
              ("bench.wait_batch", SHIFT + 5_000 * MS, SHIFT + 6_000 * MS
               + 20),
              ("bench.wait_batch", SHIFT + 6_000 * MS + 10,
               SHIFT + 7_200 * MS + 30)],
        lo=SHIFT + 5_000 * MS, hi=SHIFT + 6_000 * MS,
        # busy [5100, 5300) and [5500, 5700) ms
        devices={0: [("site_step_linear", SHIFT + 5_100 * MS,
                      SHIFT + 5_300 * MS),
                     ("copy", SHIFT + 5_500 * MS, SHIFT + 5_700 * MS)]},
        batches=[{"fetch_s": 1.5, "put_s": 2.0, "put_bytes": 3 * 10 ** 9},
                 {"fetch_s": 2.5, "put_s": 2.0, "put_bytes": 10 ** 9}],
        window_s=8.0)
    for k, v in kw.items():
        setattr(ctx, k, v)
    return ctx


def read(metric, ctx, monkeypatch, got):
    monkeypatch.setattr(SP, "recorded", lambda: got)
    return harness.load_reader(metric, ROOT)(ctx)


def test_alignment_recovers_a_planted_shift_within_its_bound():
    ctx = context()
    shift, bound = SP.align(ctx.host, program_spans(), 0, ctx.lo)
    # batch 0 pairs best: the harness's wait is 10 + 30 ns longer, and
    # the program's wait ended 30 ns before the harness's
    assert (shift, bound) == (SHIFT + 30, 40)
    assert shift - bound <= SHIFT <= shift
    placed = SP.on_device(ctx, (program_spans(), 0))
    walk = next(s for s in placed if s[0] == "engine.walk")
    assert walk[1:3] == (5_010 * MS + shift, 5_990 * MS + shift)


def test_alignment_refuses_what_it_cannot_place():
    ctx = context()
    spans = program_spans()
    # no consumer spans (a program without the recorder's service spans)
    assert SP.align(ctx.host, [s for s in spans
                               if s.name != "service.stream_wait"],
                    0, ctx.lo) is None
    # pairs that differ by more than the bound
    wide = program_spans(slack_ns=(6 * MS, 7 * MS))
    assert SP.align(ctx.host, wide, 0, ctx.lo) is None
    # dropped spans: harmless only when all closed before the window, so
    # before the oldest span the ring kept
    assert SP.align(ctx.host, spans, 3, ctx.lo) is not None
    assert SP.align(ctx.host, late(spans), 3, ctx.lo) is None
    assert SP.on_device(ctx, ([], 0)) is None


def test_gamma_wait_idle_share_by_hand(monkeypatch):
    ctx = context()
    # the wait [5250, 5600) ms less busy [5100, 5300) and [5500, 5700):
    # idle while waiting [5300, 5500) = 200 ms of a 1000 ms window; the
    # second wait lies outside the window
    v = read("gamma_wait_idle_share", ctx, monkeypatch, (program_spans(), 0))
    assert v == pytest.approx(20.0, abs=1e-5)
    assert read("gamma_wait_idle_share", ctx, monkeypatch, None) is None
    assert read("gamma_wait_idle_share", ctx, monkeypatch,
                (program_spans(slack_ns=(6 * MS, 7 * MS)), 0)) is None
    assert read("gamma_wait_idle_share", ctx, monkeypatch,
                (late(program_spans()), 5)) is None
    assert read("gamma_wait_idle_share", context(devices={}), monkeypatch,
                (program_spans(), 0)) is None


def test_fetch_busy_share_and_device_put_gbps_by_hand(monkeypatch):
    ctx = context()
    assert read("fetch_busy_share", ctx, monkeypatch, None) == \
        pytest.approx(100.0 * 4.0 / 8.0)
    assert read("device_put_gbps", ctx, monkeypatch, None) == \
        pytest.approx(4e9 / 4.0 / 1e9)
    # a program without the counters (or that counted nothing)
    bare = context(batches=[{"io_wait_s": 1.0}])
    assert read("fetch_busy_share", bare, monkeypatch, None) is None
    assert read("device_put_gbps", bare, monkeypatch, None) is None
    assert read("fetch_busy_share", context(batches=[]), monkeypatch,
                None) is None
    idle = context(batches=[{"fetch_s": 0.0, "put_s": 0.0, "put_bytes": 0}])
    assert read("device_put_gbps", idle, monkeypatch, None) is None


def test_autotune_s_by_hand(monkeypatch):
    ctx = context()
    # the two sweeps inside the warm job (2.0 s + 0.5 s); the one inside
    # the timed job starts after its first batch did
    assert read("autotune_s", ctx, monkeypatch, (program_spans(), 0)) == \
        pytest.approx(2.5)
    assert read("autotune_s", ctx, monkeypatch, None) is None
    assert read("autotune_s", ctx, monkeypatch, ([], 0)) is None
    assert read("autotune_s", ctx, monkeypatch, (program_spans(), 1)) is None
    no_sweep = [s for s in program_spans() if s.name != "dispatch.autotune"]
    assert read("autotune_s", ctx, monkeypatch, (no_sweep, 0)) == 0.0


def test_idle_time_goes_to_the_deepest_open_span():
    ops = [("k", 100, 300), ("k", 500, 700)]
    spans = [("service.stream_wait", 0, 1000, 1, None, {}),
             ("service.batch", 0, 1000, 2, None, {}),
             ("engine.walk", 50, 950, 3, 2, {}),
             ("engine.wait_gamma", 250, 600, 4, 3, {}),
             ("engine.fetch", 280, 450, 5, 3, {}),      # another thread
             ("store.read", 300, 400, 6, 5, {})]
    got = SP.idle_by_span(ops, spans, 0, 1000)
    assert got == pytest.approx({
        "service.batch": 100e-9,            # [0, 50) and [950, 1000)
        "engine.walk": 50e-9 + 250e-9,      # [50, 100) and [700, 950)
        "store.read": 100e-9,               # [300, 400)
        "engine.fetch": 50e-9,              # [400, 450): the shorter of two
        "engine.wait_gamma": 50e-9,         # [450, 500)
    })
    assert sum(got.values()) == pytest.approx(600e-9)
    assert SP.idle_by_span(ops, [], 0, 1000) == pytest.approx(
        {"none": 600e-9})
    by_name = SP.time_by_name(spans, 0, 500)
    assert by_name["engine.wait_gamma"] == pytest.approx(250e-9)
    assert by_name["store.read"] == pytest.approx(100e-9)
