"""Self-healing execution, proven by injection (PR 9 acceptance suite).

Every claim the fault taxonomy makes is exercised here with real injected
damage, never assumed:

* **verified Γ I/O** — a flipped bit / truncated site file surfaces as a
  structured :class:`CorruptSegment` BEFORE any sample is emitted, and the
  rotted file is quarantined (``*.quarantine``) so no later read can
  consume it;
* **peer repair** — on a 2-host sharded cluster, a corrupt owned site is
  re-materialized from the peer's healthy replica and the run completes
  bit-identical to the pristine single-host reference;
* **clean collective failure** — when nobody holds a healthy copy, every
  process raises the same structured fault in the same round (no hang, no
  garbage samples); the broadcast plane ships the error as a frame so
  non-root processes fail identically;
* **bounded retries + dead-letter** — a payload that deterministically
  kills its worker fails its OWN job (kind=poison) after
  ``max_batch_attempts`` hand-outs while an unrelated job on the same
  service completes bit-identically;
* **crash-loop quarantine** — a lane whose fault window is exhausted is
  quarantined with a cooldown readmit instead of hot-respawning forever;
* **durability satellites** — checkpoint leaf digests, sampler-state
  digests, result-cache corrupt-entry accounting, fault metrics.

The in-process :class:`FakePool` stands in for the persistent-process
``WorkerPool`` with the REAL ``LaneHealth`` policy and the real
``execute_payload`` worker half, so the service's fault paths run without
paying a jax import per worker process (the real-process equivalents live
in tests/test_fleet.py's slow tier).
"""
import json
import os
import shutil
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api.remote import execute_payload
from repro.api.service import SamplingService
from repro.data import gamma_store as GS
from repro.data.gamma_store import GammaStore
from repro.engine import StreamPlan, StreamingEngine
from repro.runtime import transport
from repro.runtime.elastic import WorkQueue
from repro.runtime.faults import (KINDS, CorruptSegment, CrashLoopLane,
                                  DeadLetter, Fault, FaultError, FaultReport,
                                  classify, dead_letter_kind)
from repro.runtime.transport import LaneHealth, TransportError, WorkerDied


# ---------------------------------------------------------------------------
# fixtures / helpers
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chain(tmp_path_factory, linear_mps_10x6):
    """A pristine float64 Γ store WITH its digest manifest — tests that
    inject damage always work on a copy (see :func:`_copy_store`)."""
    root = str(tmp_path_factory.mktemp("faults_gamma"))
    with GammaStore(root, storage_dtype=jnp.float64,
                    compute_dtype=jnp.float64) as store:
        store.write_mps(linear_mps_10x6)
        store.write_digest_manifest()
    return root


def _copy_store(src: str, dst: str) -> str:
    shutil.copytree(src, dst)
    return dst


def _flip_bytes(path: str, n: int = 8) -> None:
    """XOR ``n`` bytes in the middle of a file — simulated disk rot."""
    with open(path, "r+b") as f:
        f.seek(0, os.SEEK_END)
        mid = f.tell() // 2
        f.seek(mid)
        chunk = f.read(n)
        f.seek(mid)
        f.write(bytes(b ^ 0xFF for b in chunk))


def _site_path(root: str, i: int) -> str:
    return os.path.join(root, GS.site_filename(i))


def _baseline(root, n_samples, key, macro_batches):
    """Single-thread-lane reference every fault scenario must match."""
    with SamplingService(workers=1) as svc:
        h = svc.submit(root, n_samples=n_samples, key=key,
                       macro_batches=macro_batches)
        return h.result(timeout=300)


def _run_cluster(runtimes, make_config, sources, n, key):
    """Per-process sources (sharded repair needs per-host roots); returns
    (outs, stats, errs) keyed by process index — callers assert on errs
    instead of this helper, because several tests EXPECT every process to
    fail with the same structured fault."""
    outs, stats, errs = {}, {}, {}

    def run(rt):
        p = rt.process_index
        try:
            with api.SamplingSession(sources[p], make_config(rt)) as sess:
                outs[p] = sess.sample(n, key)
                stats[p] = dict(sess.stats)
        except BaseException as e:      # noqa: BLE001 — asserted by caller
            errs[p] = e

    threads = [threading.Thread(target=run, args=(rt,), daemon=True)
               for rt in runtimes]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads), "cluster run hung"
    return outs, stats, errs


class _FakeWorker:
    def __init__(self):
        self.alive = True
        self.batches = 0
        self.dispatch_bytes = 0


class FakePool:
    """In-process WorkerPool stand-in: the real ``LaneHealth`` policy, the
    real ``execute_payload`` worker half, and a ``fail_filter(name,
    payload) -> bool`` chaos seam that kills the (fake) worker."""

    def __init__(self, health=None):
        self.workers: dict[str, _FakeWorker] = {}
        self.injectors: list = []
        self.spawned = 0
        self.reaped = 0
        self.faults = 0
        self.health = LaneHealth() if health is None else health
        self.observer = None
        self.fail_filter = None
        self._cache: dict = {}          # persistent sessions, like serve()

    def spawn(self, name):
        if name in self.workers and self.workers[name].alive:
            raise ValueError(f"worker {name!r} already running")
        w = _FakeWorker()
        self.workers[name] = w
        self.spawned += 1
        return w

    def reap(self, name, kill=False):
        if self.workers.pop(name, None) is not None:
            self.reaped += 1

    def respawn(self, name):
        delay = self.health.check_respawn(name)   # may raise CrashLoopLane
        if delay:
            time.sleep(min(delay, 0.05))
        self.reap(name, kill=True)
        return self.spawn(name)

    def call(self, name, payload):
        w = self.workers.get(name)
        if w is None:
            raise WorkerDied(f"no worker {name!r} in the pool")
        try:
            if self.fail_filter is not None and self.fail_filter(name,
                                                                 payload):
                w.alive = False
                raise WorkerDied(f"worker {name!r} killed by injected fault")
            out = execute_payload(payload, cache=self._cache)
            w.batches += 1
            self.health.record_success(name)
            return out
        except TransportError:
            self.faults += 1
            self.health.record_fault(name)
            raise

    def stats(self):
        out = {"workers": len(self.workers), "spawned": self.spawned,
               "reaped": self.reaped, "faults": self.faults,
               "batches": {n: w.batches for n, w in self.workers.items()},
               "dispatch_bytes": 0}
        out.update(self.health.stats())
        return out

    def close(self):
        self.workers.clear()
        for sess in self._cache.values():
            sess.close()
        self._cache.clear()


# ---------------------------------------------------------------------------
# taxonomy units
# ---------------------------------------------------------------------------

def test_fault_kind_closed_set():
    for k in KINDS:
        Fault(kind=k, message="ok")
    with pytest.raises(ValueError):
        Fault(kind="gremlins", message="no such kind")


def test_fault_to_dict_and_context():
    f = Fault(kind="corruption", message="m", site=3)
    d = f.to_dict()
    assert d["kind"] == "corruption" and d["site"] == 3
    assert "batch" not in d and "lane" not in d     # empty context omitted
    g = f.with_context(site=9, batch=1, lane="lane-0")
    assert g.site == 3                  # never overwrites existing context
    assert g.batch == 1 and g.lane == "lane-0"
    assert f.with_context() is f


def test_fault_report_counts_and_dict():
    r = FaultReport()
    r.add(Fault(kind="transport", message="a", batch=1))
    r.add(Fault(kind="transport", message="b", batch=1))
    r.add(Fault(kind="corruption", message="c", site=4))
    counts = r.counts()
    assert counts["transport"] == 2 and counts["corruption"] == 1
    assert counts["poison"] == 0        # every kind present, zero when clean
    d = r.to_dict()
    assert len(d["faults"]) == 3 and d["dead_letter"] is None


def test_classify_matrix():
    assert classify(WorkerDied("gone"), batch=2).kind == "transport"
    assert classify(TransportError("x exceeded the 5s deadline")
                    ).kind == "timeout"
    assert classify(TransportError("pipe broke")).kind == "transport"
    assert classify(TimeoutError("slow")).kind == "timeout"
    assert classify(MemoryError()).kind == "resource"
    assert classify(OSError("disk full")).kind == "resource"
    assert classify(ValueError("a plain job error")) is None
    # a FaultError keeps its own fault, context fills only the gaps
    inner = CorruptSegment(Fault(kind="corruption", message="rot", site=7))
    out = classify(inner, batch=3, site=99)
    assert out.kind == "corruption" and out.site == 7 and out.batch == 3


def test_dead_letter_kind_poison_signature():
    t = lambda: Fault(kind="transport", message="died", batch=0)  # noqa: E731
    assert dead_letter_kind([t(), t(), t()]) == "poison"
    assert dead_letter_kind([t(), t()]) == "poison"
    assert dead_letter_kind([t()]) == "transport"
    assert dead_letter_kind([]) == "transport"
    assert dead_letter_kind(
        [Fault(kind="timeout", message="ewma", batch=0),
         Fault(kind="timeout", message="ewma", batch=0),
         t()]) == "timeout"             # dominant kind when not crash-looping


def test_workqueue_counts_attempts():
    q = WorkQueue(2)
    assert q.attempts(0) == 0
    b = q.claim("w0", now=0.0)
    assert q.attempts(b) == 1
    q.fail("w0")
    assert q.claim("w1", now=0.0) == b          # requeued re-offers first
    assert q.attempts(b) == 2
    q.complete(b, worker="w1")
    assert q.attempts(b) == 2


# ---------------------------------------------------------------------------
# wire checksums
# ---------------------------------------------------------------------------

def test_frame_crc_mismatch_rejected_at_decode():
    import io
    buf = io.BytesIO()
    transport.write_frame(buf, b"hello fastmps frame")
    data = bytearray(buf.getvalue())
    data[-3] ^= 0x01                    # flip one body byte
    with pytest.raises(TransportError) as ei:
        transport.read_frame(io.BytesIO(bytes(data)))
    assert not isinstance(ei.value, WorkerDied)
    assert "checksum" in str(ei.value)


def test_segment_payload_crc_rejected(chain):
    with GammaStore(chain, storage_dtype=jnp.float64,
                    compute_dtype=jnp.float64) as store:
        payload = store.get_segment_raw(2, 2)
        GS.decode_segment(payload)              # clean payload decodes
        bad = dict(payload)
        lam = np.array(payload["lam"], copy=True)
        lam.flat[0] += 1.0                      # corrupt in flight
        bad["lam"] = lam
        with pytest.raises(CorruptSegment) as ei:
            GS.decode_segment(bad)
        assert ei.value.fault.kind == "corruption"
        assert ei.value.fault.site == 2


# ---------------------------------------------------------------------------
# verified Γ I/O: detect, quarantine
# ---------------------------------------------------------------------------

#: the three ways a segment's Γ leaves the store: the per-site ``get``
#: (``np.load``), ``get_segment`` and the streaming engine's fetch (both
#: land each site straight in a segment buffer)
READERS = ("get", "get_segment", "engine_fetch")


def _read_segment(store, start, stop, via):
    if via == "get":
        for i in range(start, stop):
            store.get(i, prefetch_next=False)
    elif via == "get_segment":
        store.get_segment(start, stop - start)
    else:
        eng = StreamingEngine(store, plan=StreamPlan(segment_len=2))
        try:
            gd, ld, _ = eng._fetch(start, stop, eng.chi)
            eng._release(gd, ld)
        finally:
            eng.close(close_store=False)


@pytest.mark.parametrize("start", [2, 3])
@pytest.mark.parametrize("via", READERS)
def test_bitflip_detected_and_quarantined(chain, tmp_path, via, start):
    """Site 3 rotted, read in a segment that starts before it or at it
    (where the segment buffer's header probe meets the damage first)."""
    root = _copy_store(chain, str(tmp_path / "rot"))
    _flip_bytes(_site_path(root, 3))
    with GammaStore(root, storage_dtype=jnp.float64,
                    compute_dtype=jnp.float64) as store:
        # single host, verify off: the structural npz catch still fires
        with pytest.raises(CorruptSegment) as ei:
            _read_segment(store, start, 4, via)
        f = ei.value.fault
        assert f.kind == "corruption" and f.site == 3 and f.store == root
        assert store.quarantined_sites == 1
    assert not os.path.exists(_site_path(root, 3))
    assert os.path.exists(_site_path(root, 3) + ".quarantine")


@pytest.mark.parametrize("via", READERS)
def test_digest_mismatch_detected_when_verify_on(chain, tmp_path, via):
    root = _copy_store(chain, str(tmp_path / "stale"))
    with GammaStore(root, storage_dtype=jnp.float64,
                    compute_dtype=jnp.float64, verify=True) as store:
        g, lam = store.get(0, prefetch_next=False)   # healthy: verified read
        _read_segment(store, 0, 2, via)
        assert store.verified_reads >= 3
        # overwrite site 2 with a structurally VALID but different file —
        # only the manifest digest can catch this
        np.savez(_site_path(root, 2), gamma=np.zeros_like(g),
                 gshape=np.array(g.shape), lam=np.zeros_like(lam),
                 two_byte=np.array(False))
        with pytest.raises(CorruptSegment) as ei:
            _read_segment(store, 2, 3, via)
        f = ei.value.fault
        assert f.kind == "corruption" and f.site == 2 and f.store == root
        assert "digest" in f.message
    assert os.path.exists(_site_path(root, 2) + ".quarantine")


@pytest.mark.parametrize("via", READERS)
def test_truncated_site_detected(chain, tmp_path, via):
    root = _copy_store(chain, str(tmp_path / "torn"))
    path = _site_path(root, 5)
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)
    with GammaStore(root, storage_dtype=jnp.float64,
                    compute_dtype=jnp.float64) as store:
        with pytest.raises(CorruptSegment) as ei:
            _read_segment(store, 4, 6, via)
        f = ei.value.fault
        assert f.kind == "corruption" and f.site == 5 and f.store == root
    assert os.path.exists(path + ".quarantine")


def test_corrupt_store_fails_job_with_structured_fault(chain, tmp_path):
    """End to end on one host: the service job FAILS with the taxonomy
    fault — no samples emitted, fault_report served on the handle."""
    root = _copy_store(chain, str(tmp_path / "svc_rot"))
    _flip_bytes(_site_path(root, 3))
    with SamplingService(workers=1) as svc:
        h = svc.submit(root, api.SamplerConfig(backend="streamed",
                                               segment_len=2),
                       n_samples=8, key=jax.random.key(0))
        with pytest.raises(CorruptSegment):
            h.result(timeout=120)
        assert h.status() == "failed"
        report = h.fault_report()
        assert report["counts"]["corruption"] >= 1
        assert svc.stats()["faults"]["corruption"] >= 1


# ---------------------------------------------------------------------------
# cluster planes: error frames, peer repair, aligned failure
# ---------------------------------------------------------------------------

def test_broadcast_plane_corrupt_site_fails_every_process(chain, tmp_path):
    """Non-sharded 2-host broadcast: the root detects the rot, ships the
    fault as an error FRAME, and every process raises the same structured
    CorruptSegment instead of hanging in the collective.  Site 9 sits in
    the last segment, so the failure round has no in-flight prefetch."""
    root = _copy_store(chain, str(tmp_path / "bcast_rot"))
    _flip_bytes(_site_path(root, 9))
    outs, _, errs = _run_cluster(
        api.emulated_cluster(2),
        lambda rt: api.SamplerConfig(runtime=rt, backend="streamed",
                                     segment_len=2),
        {0: root, 1: root}, 8, jax.random.key(3))
    assert not outs, "no process may emit samples from rotted bytes"
    assert set(errs) == {0, 1}
    for e in errs.values():
        assert isinstance(e, CorruptSegment)
        assert e.fault.kind == "corruption" and e.fault.site == 9


def test_sharded_peer_repair_bitidentical(chain, tmp_path):
    """The headline repair cell: 2 sharded hosts with per-host replica
    roots; host 0's copy of an owned site is rotted.  The pre-walk repair
    round re-materializes it from host 1's healthy replica over the tagged
    send/recv, and the run completes bit-identical to the pristine
    single-host reference."""
    key = jax.random.key(23)
    with api.SamplingSession(chain, api.SamplerConfig(
            backend="streamed", segment_len=2)) as sess:
        ref = sess.sample(16, key)
    r0 = _copy_store(chain, str(tmp_path / "host0"))
    r1 = _copy_store(chain, str(tmp_path / "host1"))
    with open(_site_path(chain, 4), "rb") as f:
        pristine = f.read()
    _flip_bytes(_site_path(r0, 4))      # block=2 → site 4 is host0-owned
    outs, stats, errs = _run_cluster(
        api.emulated_cluster(2),
        lambda rt: api.SamplerConfig(runtime=rt, backend="streamed",
                                     segment_len=2, shard="auto"),
        {0: r0, 1: r1}, 16, key)
    assert not errs, errs
    assert np.array_equal(outs[0], ref)
    assert np.array_equal(outs[1], ref)
    assert stats[0]["quarantined_sites"] == 1
    assert stats[0]["repaired_sites"] == 1
    assert stats[1]["repaired_sites"] == 0
    # host 0's file is byte-identical to the pristine source again and the
    # quarantined copy was cleared by the restore
    with open(_site_path(r0, 4), "rb") as f:
        assert f.read() == pristine
    assert not os.path.exists(_site_path(r0, 4) + ".quarantine")


def test_sharded_unrepairable_fails_every_process_cleanly(chain, tmp_path):
    """Shared-root sharded cluster: the only copy of an owned site is rot,
    so there is no healthy holder — EVERY process must raise the same
    structured fault in the same collective round (aligned failure, no
    hang, no samples)."""
    root = _copy_store(chain, str(tmp_path / "shard_rot"))
    _flip_bytes(_site_path(root, 4))
    outs, _, errs = _run_cluster(
        api.emulated_cluster(2),
        lambda rt: api.SamplerConfig(runtime=rt, backend="streamed",
                                     segment_len=2, shard="auto"),
        {0: root, 1: root}, 16, jax.random.key(5))
    assert not outs
    assert set(errs) == {0, 1}
    for e in errs.values():
        assert isinstance(e, CorruptSegment)
        assert e.fault.kind == "corruption" and e.fault.site == 4
        assert "no peer holds a healthy copy" in e.fault.message


# ---------------------------------------------------------------------------
# bounded retries, dead-letter, crash-loop quarantine (FakePool lanes)
# ---------------------------------------------------------------------------

def test_poison_batch_dead_letters_its_job_only(chain):
    """A payload that deterministically kills its worker dead-letters its
    JOB (kind=poison) in exactly max_batch_attempts hand-outs — and an
    unrelated job on the same service completes bit-identically to the
    thread-lane baseline.  The lane is NOT quarantined: 3 faults sit under
    the default 5-per-window crash-loop threshold."""
    key = jax.random.key(11)
    ref = _baseline(chain, 16, key, 2)
    pool = FakePool(health=LaneHealth(backoff_base=0.001))
    pool.fail_filter = (lambda name, payload:
                        (payload.get("job") or {}).get("job_id") == 0
                        and payload["job"]["batch_id"] == 1)
    try:
        with SamplingService(workers=1, pool=pool,
                             max_batch_attempts=3) as svc:
            h_poison = svc.submit(chain, n_samples=16, key=key,
                                  macro_batches=2)
            with pytest.raises(DeadLetter) as ei:
                h_poison.result(timeout=300)
            assert h_poison.status() == "failed"
            assert ei.value.fault.kind == "poison"
            assert ei.value.report.dead_letter == {
                "batch": 1, "attempts": 3, "kind": "poison"}
            report = h_poison.fault_report()
            assert report["dead_letter"]["kind"] == "poison"
            assert report["counts"]["transport"] == 3
            assert report["counts"]["poison"] == 1
            # batch 0 completed before the poison batch killed the job
            assert h_poison.progress["blocks"] == 1

            # the fleet keeps flowing: an unrelated job is bit-exact
            h_ok = svc.submit(chain, n_samples=16, key=key, macro_batches=2)
            assert np.array_equal(h_ok.result(timeout=300), ref)

            st = svc.stats()
            assert st["dead_letters"] == 1
            assert st["faults"]["poison"] == 1
            assert st["faults"]["transport"] == 3
            assert st["transport"]["lane_quarantines"] == 0
            assert st["transport"]["quarantined"] == []
    finally:
        pool.close()


def test_crash_loop_lane_quarantined_then_readmitted(chain):
    """A lane that faults on EVERY dispatch exhausts its fault window, is
    quarantined (removed + cooldown) while the healthy lane finishes the
    job bit-identically, and is readmitted under its stable name once the
    cooldown expires."""
    key = jax.random.key(17)
    ref = _baseline(chain, 32, key, 4)
    broken = {"lane-0"}
    pool = FakePool(health=LaneHealth(backoff_base=0.001,
                                      max_faults_per_window=2))
    pool.fail_filter = lambda name, payload: name in broken
    try:
        with SamplingService(workers=2, pool=pool, max_batch_attempts=50,
                             lane_quarantine_s=0.4) as svc:
            h = svc.submit(chain, n_samples=32, key=key, macro_batches=4)
            assert np.array_equal(h.result(timeout=300), ref)

            deadline = time.monotonic() + 30
            while (svc.stats()["transport"]["lane_quarantines"] < 1
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            st = svc.stats()
            assert st["transport"]["lane_quarantines"] == 1
            assert st["faults"]["transport"] >= 2

            broken.clear()              # the lane's host "recovered"
            deadline = time.monotonic() + 30
            while (svc.stats()["transport"]["lane_readmits"] < 1
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            st = svc.stats()
            assert st["transport"]["lane_readmits"] == 1
            assert st["transport"]["quarantined"] == []
            assert "lane-0" in svc.workers()
    finally:
        pool.close()


# ---------------------------------------------------------------------------
# durability satellites: checkpoints, sampler state, result cache, metrics
# ---------------------------------------------------------------------------

def test_checkpoint_leaf_digest_detects_rot(tmp_path):
    from repro.checkpoint.store import load_checkpoint, save_checkpoint

    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": jnp.ones((5,), jnp.float32)}
    d = save_checkpoint(str(tmp_path), 1, tree)
    out, step, _ = load_checkpoint(str(tmp_path), tree)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.asarray(tree["a"]))
    # .npy has no internal checksum: the manifest digest is the ONLY thing
    # standing between a flipped bit and a silent bad resume
    leaf = sorted(f for f in os.listdir(d) if f.endswith(".npy"))[0]
    _flip_bytes(os.path.join(d, leaf), n=1)
    with pytest.raises(CorruptSegment) as ei:
        load_checkpoint(str(tmp_path), tree)
    assert ei.value.fault.kind == "corruption"
    assert "digest mismatch" in ei.value.fault.message


def test_sampler_state_digest_detects_tamper(tmp_path):
    from repro.checkpoint.sampler_state import (load_sampler_state,
                                                save_sampler_state)
    from repro.core.sampler import SamplerState

    state = SamplerState(jnp.ones((4, 6)), jax.random.key(0),
                         jnp.zeros((4,)))
    samples = np.arange(8, dtype=np.int8).reshape(4, 2)
    fn = save_sampler_state(str(tmp_path), 3, state, samples)
    site, loaded, got = load_sampler_state(str(tmp_path))
    assert site == 3
    np.testing.assert_array_equal(got, samples)
    np.testing.assert_array_equal(np.asarray(loaded.env),
                                  np.asarray(state.env))
    # tamper: rewrite the npz with modified samples but the OLD digest
    with np.load(fn) as z:
        arrs = {k: z[k] for k in z.files}
    arrs["samples"] = arrs["samples"] + 1
    np.savez(fn, **arrs)
    with pytest.raises(CorruptSegment) as ei:
        load_sampler_state(str(tmp_path))
    assert ei.value.fault.kind == "corruption" and ei.value.fault.site == 3


def test_result_cache_corrupt_entry_dropped_loudly(tmp_path):
    from repro.runtime.transport import array_to_frame
    from repro.serve.cache import ResultCache

    d = str(tmp_path / "cache")
    c1 = ResultCache(cache_dir=d)
    entry, status = c1.get_or_begin("k1", 1)
    assert status == "miss"
    entry.publish(0, array_to_frame(np.arange(6, dtype=np.int8)))
    entry.finish()
    c1.seal(entry)
    # a fresh cache serves the sealed entry from disk
    assert ResultCache(cache_dir=d).get_or_begin("k1", 1)[1] == "hit"

    with open(os.path.join(d, "k1", "meta.json"), "w") as f:
        f.write("{this is not json")
    events = []
    c3 = ResultCache(cache_dir=d)
    c3.observer = lambda ev, **kw: events.append((ev, kw))
    _, s3 = c3.get_or_begin("k1", 1)
    assert s3 == "miss"                 # falls through to a clean recompute
    assert c3.corrupt_entries == 1
    assert c3.stats()["corrupt_entries"] == 1
    assert ("cache_corrupt", {"key": "k1"}) in events
    assert not os.path.exists(os.path.join(d, "k1"))


def test_fault_metrics_rendered():
    from repro.obs.metrics import MetricsRegistry, instrument_service

    reg = MetricsRegistry()
    with SamplingService(workers=0) as svc:
        obs = instrument_service(svc, reg)
        obs("fault", kind="corruption")
        obs("fault", kind="poison")
        obs("lane_quarantine", worker="lane-0")
        obs("lane_readmit", worker="lane-0")
        snap = reg.snapshot()
    faults = snap["fastmps_faults_total"]
    assert faults[("", (("kind", "corruption"),))] == 1
    assert faults[("", (("kind", "poison"),))] == 1
    assert snap["fastmps_lane_quarantines_total"][("", ())] == 1
    assert snap["fastmps_lane_readmits_total"][("", ())] == 1
    assert snap["fastmps_dead_letters"][("", ())] == 0
    assert snap["fastmps_quarantined_lanes"][("", ())] == 0
    text = reg.render()
    assert 'fastmps_faults_total{kind="corruption"}' in text


def test_lane_health_forgive_clears_window():
    h = LaneHealth(max_faults_per_window=2, backoff_base=0.001)
    h.record_fault("w")
    h.record_fault("w")
    with pytest.raises(CrashLoopLane) as ei:
        h.check_respawn("w")
    assert ei.value.fault.lane == "w"
    h.forgive("w")                      # quarantine cooldown IS the penalty
    assert h.window_faults("w") == 0
    assert h.check_respawn("w") == 0.0  # readmit respawns clean


# ---------------------------------------------------------------------------
# the operator-facing failure path (slow: one subprocess jax import)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_launch_cli_structured_failure_on_corrupt_store(chain, tmp_path):
    """``python -m repro.launch.sample`` against a rotted store exits with
    code 2 and a machine-readable fault record on stderr — "your data is
    bad", distinguishable from a driver crash."""
    root = _copy_store(chain, str(tmp_path / "cli_rot"))
    _flip_bytes(_site_path(root, 3))
    out_dir = str(tmp_path / "cli_out")
    src = os.path.dirname(os.path.dirname(os.path.abspath(api.__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.sample", "--stream",
         "--store", root, "--sites", "10", "--chi", "6", "--samples", "8",
         "--macro-batches", "1", "--segment-len", "2", "--out", out_dir],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 2, (proc.stdout, proc.stderr)
    i = proc.stderr.rindex('"fault"')
    record = json.loads(proc.stderr[proc.stderr.rindex("{", 0, i):])
    assert record["fault"]["kind"] == "corruption"
    assert record["fault"]["site"] == 3
    # no batch file was written from rotted bytes
    assert not [f for f in os.listdir(out_dir) if f.startswith("batch_")]
