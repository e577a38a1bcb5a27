"""The span recorder (``repro.obs.trace``): causes, identity, the bounded
ring, switching it off, and many threads writing at once."""
import sys
import threading
from concurrent.futures import ThreadPoolExecutor

import pytest

from repro.obs import trace


@pytest.fixture
def rec():
    return trace.Recorder(capacity=64)


def by_name(r):
    return {s.name: s for s in r.spans()}


def test_nesting_gives_parent_ids_and_durations(rec):
    with rec.span("service.batch") as outer:
        assert rec.current() is outer
        with rec.span("engine.walk") as mid:
            with rec.span("engine.segment") as inner:
                pass
        assert rec.current() is outer
    assert rec.current() is None
    got = by_name(rec)
    assert got["service.batch"].parent_id is None
    assert got["engine.walk"].parent_id == outer.span_id
    assert got["engine.segment"].parent_id == mid.span_id
    # recorded in the order they close, with the handle's own clock reads
    assert [s.name for s in rec.spans()] == [
        "engine.segment", "engine.walk", "service.batch"]
    seg = got["engine.segment"]
    assert (seg.start_ns, seg.end_ns) == (inner.start_ns, inner.end_ns)
    assert inner.seconds == (seg.end_ns - seg.start_ns) / 1e9
    assert outer.start_ns <= mid.start_ns <= inner.start_ns
    assert inner.end_ns <= mid.end_ns <= outer.end_ns


def test_a_cause_passed_to_another_thread(rec):
    with rec.span("engine.walk", job=3, batch=1) as walk:
        cause = rec.current()

        def fetch():
            assert rec.current() is None        # nothing open on this thread
            with rec.span("engine.fetch", parent=cause, start=4):
                with rec.span("engine.stack"):
                    pass

        with ThreadPoolExecutor(1) as pool:
            pool.submit(fetch).result(timeout=10)
    got = by_name(rec)
    assert got["engine.fetch"].parent_id == walk.span_id
    assert got["engine.stack"].parent_id == got["engine.fetch"].span_id
    # a bare id is a cause too, without the parent's attributes
    with rec.span("store.read", parent=walk.span_id, site=9):
        pass
    assert by_name(rec)["store.read"].parent_id == walk.span_id
    assert by_name(rec)["store.read"].attrs == {"site": 9}


def test_every_span_of_a_batch_shares_its_identity(rec):
    with rec.span("service.batch", job=7, batch=2, lane="w0"):
        with rec.span("engine.walk"):
            with rec.span("engine.segment", start=0):
                pass
            with rec.span("engine.segment", start=2):
                pass
    for s in rec.spans():
        assert (s.attrs["job"], s.attrs["batch"]) == (7, 2)
    assert sorted(s.attrs.get("start", -1) for s in rec.spans()) == [
        -1, -1, 0, 2]


def test_the_ring_drops_the_oldest_and_counts_them():
    rec = trace.Recorder(capacity=4)
    for i in range(10):
        with rec.span("s", i=i):
            pass
    assert [s.attrs["i"] for s in rec.spans()] == [6, 7, 8, 9]
    assert rec.dropped() == 6
    rec.clear()
    assert rec.spans() == [] and rec.dropped() == 0


def test_switched_off_it_records_nothing_but_still_times(rec):
    rec.enable(False)
    with rec.span("engine.walk") as sp:
        with rec.span("engine.segment"):
            assert rec.current() is None
    assert rec.spans() == [] and rec.dropped() == 0
    assert sp.seconds >= 0 and sp.span_id is None
    rec.enable(True)
    with rec.span("engine.walk"):
        pass
    assert [s.name for s in rec.spans()] == ["engine.walk"]


def test_an_exception_closes_the_span(rec):
    with pytest.raises(KeyError):
        with rec.span("engine.fetch"):
            raise KeyError("x")
    assert [s.name for s in rec.spans()] == ["engine.fetch"]
    assert rec.current() is None


def test_concurrent_writers_lose_nothing():
    rec = trace.Recorder(capacity=10_000)
    n_threads, per = 16, 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(t):
            for i in range(per):
                with rec.span("outer", t=t) as o:
                    with rec.span("inner", i=i):
                        assert rec.current().name == "inner"
                    assert rec.current() is o
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    spans = rec.spans()
    assert len(spans) == 2 * n_threads * per and rec.dropped() == 0
    ids = {s.span_id for s in spans}
    assert len(ids) == len(spans)
    outer = {s.span_id: s.attrs["t"] for s in spans if s.name == "outer"}
    for s in spans:
        if s.name == "inner":
            assert outer[s.parent_id] == s.attrs["t"]


def test_the_process_recorder_is_on_by_default():
    trace.clear()
    with trace.span("dispatch.autotune", stage="site_step"):
        pass
    got = [s for s in trace.spans() if s.name == "dispatch.autotune"]
    assert got and got[-1].attrs == {"stage": "site_step"}
    trace.enable(False)
    try:
        with trace.span("dispatch.autotune"):
            pass
        assert len([s for s in trace.spans()
                    if s.name == "dispatch.autotune"]) == len(got)
    finally:
        trace.enable(True)
    assert trace.dropped() == 0
