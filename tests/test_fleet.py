"""Fleet-scale service tests: one job table, many processes, proven under
chaos.

The headline assertion (ISSUE acceptance): a multi-process fleet run —
persistent worker processes behind ``SamplingService(pool=True)`` — with
an injected mid-job lane kill AND a forced straggler reclaim returns
samples **bit-identical** to a single-lane ``runtime="local"`` run of the
same (source, config, key).  Everything else here triangulates the same
property from cheaper angles: thread-lane chaos, seeded WorkQueue storms,
straggler EWMA math, admission backpressure, and the raw frame protocol.

Worker processes pay a jax import each, so anything spawning them is
``slow`` (CI's fleet-smoke job runs them; tier-1 keeps the thread-lane
and control-plane tests).
"""
import io
import random
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chaos import (DelayBatch, DropDispatch, DropResult, DuplicateDelivery,
                   HoldUntil, HookChain, KillLane, QueueInvariantError,
                   run_queue_script)
from repro import api
from repro.api.service import SamplingService, batch_key
from repro.data.gamma_store import GammaStore
from repro.runtime import transport
from repro.runtime.elastic import WorkQueue
from repro.runtime.stragglers import StragglerMitigator


@pytest.fixture(scope="module")
def chain(tmp_path_factory, linear_mps_10x6):
    root = str(tmp_path_factory.mktemp("fleet_gamma"))
    with GammaStore(root, storage_dtype=jnp.float64,
                    compute_dtype=jnp.float64) as store:
        store.write_mps(linear_mps_10x6)
    return root


def _baseline(root, n_samples, key, macro_batches):
    """The single-lane runtime="local" reference the fleet must match."""
    with SamplingService(workers=1) as svc:
        h = svc.submit(root, n_samples=n_samples, key=key,
                       macro_batches=macro_batches)
        return h.result(timeout=300)


# ---------------------------------------------------------------------------
# frame protocol (no processes)
# ---------------------------------------------------------------------------

def test_frame_roundtrip():
    buf = io.BytesIO()
    transport.write_json(buf, {"kind": "batch", "payload": {"x": 1}})
    transport.write_frame(buf, transport.array_to_frame(
        np.arange(12, dtype=np.float64).reshape(3, 4)))
    buf.seek(0)
    assert transport.read_json(buf) == {"kind": "batch", "payload": {"x": 1}}
    out = transport.array_from_frame(transport.read_frame(buf))
    np.testing.assert_array_equal(out, np.arange(12.0).reshape(3, 4))


def test_frame_eof_raises_worker_died():
    buf = io.BytesIO(b"\x00\x00\x00")          # truncated length prefix
    with pytest.raises(transport.WorkerDied):
        transport.read_frame(buf)
    half = io.BytesIO()
    transport.write_frame(half, b"full frame")
    truncated = io.BytesIO(half.getvalue()[:-4])
    with pytest.raises(transport.WorkerDied):
        transport.read_frame(truncated)


def test_transport_error_is_not_a_job_error():
    # the service routes RuntimeError to job-failure and TransportError to
    # requeue-and-respawn; the subclass order must keep those separable
    assert issubclass(transport.TransportError, RuntimeError)
    assert issubclass(transport.WorkerDied, transport.TransportError)


# ---------------------------------------------------------------------------
# WorkQueue regressions: double-complete, steal, ownership
# ---------------------------------------------------------------------------

def test_double_complete_rejected():
    q = WorkQueue(2)
    assert q.claim("a", now=0.0) == 0
    assert q.complete(0, worker="a") is True
    assert q.complete(0, worker="a") is False      # duplicate delivery
    assert q.complete(0) is False                  # even ownerless
    assert q.stats()["done"] == 1


def test_steal_reassigns_and_leaves_fifo_clean():
    q = WorkQueue(3)
    assert q.claim("a", now=0.0) == 0
    assert q.claim("b", now=0.0) == 1
    assert q.reclaim_stale(5.0, now=10.0) == [0, 1]
    assert q.steal(0, "c", now=10.0) is True
    assert q.records[0].owner == "c"
    # 0 left the re-offer FIFO with the steal; a fresh claim gets 1 then 2
    assert q.claim("d", now=10.0) == 1
    assert q.claim("d", now=10.0) == 2
    # stealing an owned or done batch refuses
    assert q.steal(1, "e") is False
    q.complete(2, worker="d")
    assert q.steal(2, "e") is False


def test_late_completion_after_reclaim_rejected():
    q = WorkQueue(1)
    q.claim("slow", now=0.0)
    q.reclaim_stale(1.0, now=100.0)
    assert q.steal(0, "fast", now=100.0)
    assert q.complete(0, worker="slow") is False   # the late original
    assert q.complete(0, worker="fast") is True
    assert q.stats()["done"] == 1


# ---------------------------------------------------------------------------
# StragglerMitigator regressions: EWMA deadline math + steal integration
# ---------------------------------------------------------------------------

def test_ewma_deadline_math():
    m = StragglerMitigator(WorkQueue(1), k=2.0, ewma_alpha=0.5)
    assert m.deadline is None and m.stats()["ewma_s"] is None
    m.observe_completion(4.0)
    assert m.deadline == pytest.approx(8.0)        # first sample seeds EWMA
    m.observe_completion(2.0)
    assert m._ewma == pytest.approx(3.0)           # 0.5·2 + 0.5·4
    assert m.deadline == pytest.approx(6.0)
    assert m.stats() == {"ewma_s": pytest.approx(3.0),
                         "deadline_s": pytest.approx(6.0), "duplicates": 0}


def test_maybe_steal_respects_deadline():
    q = WorkQueue(2)
    m = StragglerMitigator(q, k=2.0, ewma_alpha=0.5)
    q.claim("slow", now=0.0)
    assert m.maybe_steal("idle", now=100.0) is None   # no EWMA yet
    m.observe_completion(1.0)                          # deadline = 2.0
    assert m.maybe_steal("idle", now=1.5) is None      # not late yet
    assert m.maybe_steal("idle", now=3.0) == 0         # 3.0 > 2.0: reclaim
    assert q.records[0].owner == "idle"
    assert m.duplicates == 1
    assert q.complete(0, worker="slow") is False       # late original
    assert q.complete(0, worker="idle") is True


# ---------------------------------------------------------------------------
# seeded WorkQueue storms (the no-hypothesis interleaving matrix)
# ---------------------------------------------------------------------------

def _random_ops(rng: random.Random, n_ops: int):
    kinds = ["add", "remove", "claim", "claim", "claim", "complete",
             "complete", "reclaim", "tick"]
    ops = []
    for _ in range(n_ops):
        k = rng.choice(kinds)
        if k == "tick":
            ops.append(("tick",))
        elif k == "reclaim":
            ops.append(("reclaim", rng.randint(0, 3)))
        else:
            ops.append((k, rng.randint(0, 3)))
    return ops


@pytest.mark.parametrize("seed", range(25))
def test_queue_storm_never_loses_or_double_counts(seed):
    rng = random.Random(seed)
    n_batches = rng.randint(1, 12)
    out = run_queue_script(n_batches, _random_ops(rng, 120))
    assert all(v == 1 for v in out["counted"].values())
    assert len(out["counted"]) == n_batches


def test_queue_script_catches_a_planted_violation():
    # the checker itself must not be vacuous: a queue that claims success
    # without recording completion (lost/duplicated work) trips it
    orig = WorkQueue.complete
    try:
        WorkQueue.complete = lambda self, b, worker=None: True
        with pytest.raises(QueueInvariantError):
            run_queue_script(2, [("add", 0), ("claim", 0), ("complete", 0)])
    finally:
        WorkQueue.complete = orig


# ---------------------------------------------------------------------------
# thread-lane chaos (fast: no worker processes)
# ---------------------------------------------------------------------------

def test_straggler_reclaim_thread_lanes(chain):
    """End-to-end straggler path on thread lanes: the lane holding the
    last batch stalls until an idle lane's EWMA-deadline reclaim steals
    it; the late original's completion is ownership-rejected; the result
    is bit-identical to the single-lane baseline."""
    key = jax.random.key(11)
    ref = _baseline(chain, 96, key, 6)
    stalled = {}

    with SamplingService(workers=2, straggler_k=0.2,
                         steal_poll_s=0.01) as svc:
        def stall_last(job, b, worker):
            if b != 5 or stalled:
                return
            stalled["lane"] = worker
            t0 = time.monotonic()
            # release exactly when the reclaim lands (deterministic), with
            # a generous escape hatch so a broken steal fails the asserts,
            # not the suite's clock
            while (job.queue.records[b].owner == worker
                   and time.monotonic() - t0 < 60.0):
                time.sleep(0.01)
        svc.batch_hook = stall_last
        h = svc.submit(chain, n_samples=96, key=key, macro_batches=6)
        out = h.result(timeout=300)
        assert np.array_equal(out, ref)
        assert h.progress["duplicates"] >= 1
        st = svc.stats()
        assert st["stragglers"]["duplicates"] >= 1
        assert st["stragglers"]["steals"] >= 1
    assert stalled, "the stall hook never saw batch 5"
    # the stalled lane's late execution (if it ran) was discarded by the
    # ownership check — either way, every batch counted exactly once
    assert h.progress["done"] == 6


def test_kill_lane_thread_mode(chain):
    """Mid-job lane kill on thread lanes: the victim's claim requeues and
    the survivor finishes; bit-identity holds."""
    key = jax.random.key(13)
    ref = _baseline(chain, 64, key, 4)
    with SamplingService(workers=2, straggler_k=None) as svc:
        kill = KillLane(svc, on_batch=1)
        svc.batch_hook = kill
        h = svc.submit(chain, n_samples=64, key=key, macro_batches=4)
        out = h.result(timeout=300)
    assert kill.victim is not None
    assert np.array_equal(out, ref)
    assert h.progress["requeues"] >= 1


def test_admission_backpressure(chain):
    """A burst over the perfmodel budget queues in priority order with the
    backpressure visible in stats(); the queue drains as jobs finish."""
    key = jax.random.key(17)
    # probe the modeled footprint of one job without running anything
    with SamplingService(workers=0) as probe:
        mb = probe.submit(chain, n_samples=32, key=key).progress["model_bytes"]
    assert mb > 0

    gate = threading.Event()
    started = threading.Event()
    with SamplingService(workers=1,
                         max_active_bytes=1.5 * mb) as svc:
        def hold_first(job, b, worker):
            if job.job_id == 0:
                started.set()
                gate.wait(timeout=60.0)
        svc.batch_hook = hold_first
        h1 = svc.submit(chain, n_samples=32, key=key)
        assert started.wait(timeout=60.0)
        h2 = svc.submit(chain, n_samples=32, key=jax.random.key(18))
        time.sleep(0.05)
        st = svc.stats()
        assert st["admission"]["queued_jobs"] == 1
        assert st["admission"]["backpressure"] is True
        assert st["admission"]["active_model_bytes"] == pytest.approx(mb)
        assert st["queue_depth"] == 2
        gate.set()
        r1, r2 = h1.result(timeout=300), h2.result(timeout=300)
        st = svc.stats()
        assert st["admission"]["backpressure"] is False
        assert st["admission"]["queued_jobs"] == 0
    assert r1.shape == r2.shape == (32, 10)
    assert not np.array_equal(r1, r2)              # different keys


def test_admission_always_admits_one(chain):
    """A job bigger than the whole budget still runs — alone."""
    key = jax.random.key(19)
    ref = _baseline(chain, 32, key, 1)
    with SamplingService(workers=1, max_active_bytes=1.0) as svc:
        h = svc.submit(chain, n_samples=32, key=key)
        out = h.result(timeout=300)
    assert np.array_equal(out, ref)


def test_fleet_submit_validation(chain, tmp_path):
    """Fleet lanes reject job shapes they can't dispatch (local chain-walk
    state) — at submit time, on the caller's thread."""
    with SamplingService(workers=0, pool=True) as svc:
        with pytest.raises(ValueError, match="skip_batches"):
            svc.submit(chain, n_samples=8, key=jax.random.key(0),
                       checkpoint_root=str(tmp_path / "ck"))


def test_lane_batches_in_stats(chain):
    key = jax.random.key(23)
    with SamplingService(workers=1) as svc:
        h = svc.submit(chain, n_samples=64, key=key, macro_batches=4)
        h.result(timeout=300)
        lanes = svc.stats()["lane_batches"]
    assert sum(lanes.values()) == 4


# ---------------------------------------------------------------------------
# the fleet itself (worker processes — slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_acceptance_kill_and_reclaim(chain):
    """THE acceptance run: ≥2 persistent worker processes, a mid-job lane
    kill AND a forced straggler reclaim, and the assembled samples are
    bit-identical to the single-lane runtime="local" baseline."""
    key = jax.random.key(42)
    n, k = 192, 8
    ref = _baseline(chain, n, key, k)

    with SamplingService(workers=3, pool=True, straggler_k=0.3,
                         steal_poll_s=0.02) as svc:
        kill = KillLane(svc, on_batch=1)
        svc.batch_hook = kill
        hold = HoldUntil(
            lambda: svc.stats()["stragglers"]["duplicates"] > 0,
            batch_ids={k - 1}, max_wait_s=120.0)
        svc._pool.injectors.append(hold)
        h = svc.submit(chain, n_samples=n, key=key, macro_batches=k)
        out = h.result(timeout=560)
        st = svc.stats()
        assert np.array_equal(out, ref), "fleet result diverged from baseline"
        assert kill.victim is not None, "lane kill never fired"
        assert h.progress["requeues"] >= 1              # the kill's claim
        assert st["stragglers"]["duplicates"] >= 1      # the forced reclaim
        assert st["transport"]["workers"] >= 2          # ≥2 live processes
        assert sum(st["lane_batches"].values()) >= k    # incl. duplicates? no:
        # lane_batches counts COUNTED completions only — exactly k
        assert sum(st["lane_batches"].values()) == k
    svc.close()
    assert svc.stats()["stragglers"]["rejected_results"] >= 0


@pytest.mark.slow
@pytest.mark.parametrize("fault", ["drop_dispatch", "drop_result",
                                   "duplicate"])
def test_fleet_chaos_matrix(chain, fault):
    """Each transport fault class, injected mid-job, leaves the result
    bit-identical to the baseline."""
    key = jax.random.key(5)
    n, k = 96, 4
    ref = _baseline(chain, n, key, k)
    inj = {"drop_dispatch": DropDispatch(batch_ids={2}),
           "drop_result": DropResult(batch_ids={2}),
           "duplicate": DuplicateDelivery(batch_ids={2})}[fault]
    with SamplingService(workers=2, pool=True, straggler_k=None) as svc:
        svc._pool.injectors.append(inj)
        h = svc.submit(chain, n_samples=n, key=key, macro_batches=k)
        out = h.result(timeout=560)
        st = svc.stats()
    assert np.array_equal(out, ref)
    assert inj.fired, f"{fault} injector never matched"
    if fault.startswith("drop"):
        # the fault surfaced as a lane fault and the batch was recomputed
        assert st["transport"]["lane_faults"] >= 1
        assert h.progress["requeues"] >= 1
    assert h.progress["done"] == k


@pytest.mark.slow
def test_fleet_worker_death_respawns(chain):
    """SIGKILL a worker process mid-run: its lane absorbs the fault,
    respawns a fresh process under the same lane name, and the job
    completes bit-identically."""
    key = jax.random.key(29)
    n, k = 96, 4
    ref = _baseline(chain, n, key, k)
    with SamplingService(workers=2, pool=True, straggler_k=None) as svc:
        fired = {}

        def murder(job, b, worker):
            if b == 2 and not fired:
                fired["lane"] = worker
                svc._pool.workers[worker]._proc.kill()
        svc.batch_hook = murder
        h = svc.submit(chain, n_samples=n, key=key, macro_batches=k)
        out = h.result(timeout=560)
        st = svc.stats()
    assert fired
    assert np.array_equal(out, ref)
    assert st["transport"]["lane_faults"] >= 1
    assert st["transport"]["spawned"] >= 3          # 2 lanes + ≥1 respawn


@pytest.mark.slow
def test_remote_runtime_persistent_worker_reuse(chain):
    """runtime="remote" now keeps ONE worker across submits (warm jit
    cache) instead of a subprocess per batch; both modes agree bitwise."""
    key = jax.random.key(31)
    cfg = api.SamplerConfig(backend="remote", runtime="remote")
    with api.SamplingSession(chain, cfg) as s:
        a = np.asarray(s.sample(16, key))
        pid1 = s.runtime._worker.pid
        b = np.asarray(s.sample(16, jax.random.key(32)))
        assert s.runtime._worker.pid == pid1        # same process, reused
        io_c = s.runtime.io_counters()
        assert io_c["persistent_worker"] is True
        assert io_c["dispatches"] == 2
    rt = api.RemoteRuntime(persistent=False)
    cfg2 = api.SamplerConfig(backend="remote", runtime=rt)
    with api.SamplingSession(chain, cfg2) as s:
        assert np.array_equal(np.asarray(s.sample(16, key)), a)
    assert not np.array_equal(a, b)


def test_no_child_spawned_from_a_tpu_parent(monkeypatch):
    """One process per chip: a parent holding a TPU refuses to start a
    worker interpreter (it would fail or hang on the chip) — for the
    service pool and both RemoteRuntime modes — and spawns nothing."""
    from repro.api.remote import RemoteRuntime
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pool = transport.WorkerPool()
    with pytest.raises(RuntimeError, match="one process"):
        pool.spawn("lane-0")
    assert pool.spawned == 0 and not pool.workers
    for persistent in (True, False):
        rt = RemoteRuntime(persistent=persistent)
        with pytest.raises(RuntimeError, match="one process"):
            rt.submit({"v": 2})
        assert rt._worker is None
