"""Streaming engine: segment-streamed chains ≡ the in-memory paths.

The engine's contract (paper §3.1 + §4.1): for the same seed and Γ, a
segment-streamed walk is bit-identical to the all-in-memory scan, holds at
most two Γ segments on device, and survives a mid-chain kill exactly.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import mps as M
from repro.core import sampler as S
from repro.core.perfmodel import Hardware, Workload, choose_tp_scheme
from repro.data.gamma_store import GammaStore
from repro.engine import (StreamPlan, StreamingEngine, explain_plan,
                          plan_stream)
from repro.engine.streaming import fill_identity
from repro.runtime.elastic import WorkQueue


@pytest.fixture(scope="module")
def chain(tmp_path_factory, linear_mps_10x6):
    """A 10-site chain written once to disk (fp64: no storage rounding, so
    the in-memory MPS is the exact reference)."""
    root = str(tmp_path_factory.mktemp("gamma"))
    store = GammaStore(root, storage_dtype=jnp.float64,
                       compute_dtype=jnp.float64)
    store.write_mps(linear_mps_10x6)
    store.close()
    return root, linear_mps_10x6


def _store(root):
    return GammaStore(root, storage_dtype=jnp.float64,
                      compute_dtype=jnp.float64)


@pytest.mark.parametrize("segment_len", [4, 5, 16])
def test_stream_bitexact_vs_inmemory(chain, segment_len):
    """Remainder segments (4: 4+4+2), exact division (5), and a single
    padded over-long segment (16 > M) all reproduce sample() exactly."""
    root, mps = chain
    key = jax.random.key(3)
    ref = np.asarray(S.sample(mps, 24, key))
    eng = StreamingEngine(_store(root),
                          plan=StreamPlan(segment_len=segment_len))
    out = eng.sample(24, key)
    assert np.array_equal(out, ref)
    assert eng.stats["max_live_segments"] <= 2
    eng.close()


def test_stream_reads_each_site_once_per_walk(chain):
    root, mps = chain
    store = _store(root)
    eng = StreamingEngine(store, plan=StreamPlan(segment_len=4))
    eng.sample(8, jax.random.key(0))
    per_site = mps.gammas[0].size * 8 + mps.lambdas[0].size * 8
    # the constructor's metadata probe is header-only — exactly one payload
    # read per site for the whole walk
    assert store.io_bytes == mps.n_sites * per_site
    eng.close()


def test_micro_batched_stream_matches_sample_batched(chain):
    root, mps = chain
    key = jax.random.key(9)
    ref = np.asarray(S.sample_batched(mps, 24, key, micro_batch=8))
    eng = StreamingEngine(_store(root),
                          plan=StreamPlan(segment_len=4, micro_batch=8))
    out = eng.sample(24, key)
    assert np.array_equal(out, ref)
    eng.close()


def test_kill_and_resume_bitexact(chain, tmp_path):
    root, mps = chain
    key = jax.random.key(11)
    ref = np.asarray(S.sample(mps, 16, key))
    plan = StreamPlan(segment_len=4, checkpoint_every=1)

    crashed = StreamingEngine(_store(root), plan=plan,
                              checkpoint_dir=str(tmp_path))
    part = crashed.sample(16, key, stop_after_segments=2)
    assert part.shape == (16, 8)                 # 2 of 3 segments done
    assert np.array_equal(part, ref[:, :8])
    crashed.close()

    resumed = StreamingEngine(_store(root), plan=plan,
                              checkpoint_dir=str(tmp_path))
    out = resumed.sample(16, key, resume=True)
    assert np.array_equal(out, ref)
    assert resumed.stats["segments"] == 1        # only the remaining work
    # checkpoint-per-segment must not accumulate the chain's history
    ckpts = [f for f in tmp_path.iterdir() if f.suffix == ".npz"]
    assert len(ckpts) <= 3
    resumed.close()


def test_workqueue_macro_batches_idempotent(chain):
    """Macro batches as engine work items: batch = f(seed, id) exactly as
    runtime/elastic.py requires, so results are owner/order-independent."""
    root, mps = chain
    base = jax.random.key(21)
    eng = StreamingEngine(_store(root), plan=StreamPlan(segment_len=5))
    q = WorkQueue(3)
    outs = eng.run_queue(q, 8, base)
    assert q.finished
    for b in range(3):
        ref = np.asarray(S.sample(mps, 8, jax.random.fold_in(base, b)))
        assert np.array_equal(outs[b], ref)
    eng.close()


def test_born_semantics_stream(tmp_path, born_mps_6x4):
    mps = born_mps_6x4
    key = jax.random.key(2)
    cfg = S.SamplerConfig(semantics="born")
    ref = np.asarray(S.sample(mps, 16, key, cfg))
    with GammaStore(str(tmp_path), storage_dtype=jnp.complex128,
                    compute_dtype=jnp.complex128) as store:
        store.write_mps(mps)
        with StreamingEngine(store, semantics="born", config=cfg,
                             plan=StreamPlan(segment_len=4)) as eng:
            out = eng.sample(16, key)
    assert np.array_equal(out, ref)


def test_multihost_engine_root_reads_peers_receive(chain):
    """Tentpole unit test at the engine level: on a 2-process emulated
    runtime, ONLY the root engine issues GammaStore payload reads (its
    per-engine store-I/O delta covers the whole chain; the peer's is zero)
    and both walks are bit-identical to the single-process one."""
    import threading

    from repro.api.runtime import emulated_cluster

    root, mps = chain
    key = jax.random.key(5)
    ref = np.asarray(S.sample(mps, 16, key))
    per_site = mps.gammas[0].size * 8 + mps.lambdas[0].size * 8

    runtimes = emulated_cluster(2)
    outs, stats, errs = {}, {}, []

    def walk(rt):
        try:
            with _store(root) as store:
                eng = StreamingEngine(store, plan=StreamPlan(segment_len=4),
                                      runtime=rt)
                outs[rt.process_index] = eng.sample(16, key)
                stats[rt.process_index] = dict(eng.stats)
                eng.close(close_store=False)
        except Exception as e:          # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=walk, args=(rt,)) for rt in runtimes]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs, errs
    assert np.array_equal(outs[0], ref) and np.array_equal(outs[1], ref)
    # the §3.1 contract: one reader, everyone else on the interconnect
    assert stats[0]["io_bytes"] == mps.n_sites * per_site
    assert stats[1]["io_bytes"] == 0
    assert stats[0]["broadcast_send_bytes"] == mps.n_sites * per_site
    assert stats[1]["broadcast_recv_bytes"] == mps.n_sites * per_site
    assert stats[1]["broadcast_segments"] == stats[1]["segments"]


def _count_segment_buffers(store):
    """Wrap ``store.segment_buffer`` to keep every buffer it allocates."""
    made = []
    alloc = store.segment_buffer

    def segment_buffer(*args):
        made.append(alloc(*args))
        return made[-1]
    store.segment_buffer = segment_buffer
    return made


@pytest.mark.parametrize("storage", ["float64", "bfloat16"])
def test_pipelined_batches_reuse_the_host_segment(chain, tmp_path, storage):
    """Two pipelined macro batches on one engine (the second's first
    segment fetched behind the first's tail; the 10-site chain's last
    segment of 4 padded over stale slots) give the samples of a fresh
    engine per batch, through one host segment buffer."""
    root, mps = chain
    if storage == "bfloat16":
        root = str(tmp_path / "bf16")
        with GammaStore(root, storage_dtype=jnp.bfloat16,
                        compute_dtype=jnp.float32) as st:
            st.write_mps(mps)

    def store():
        return GammaStore(root, storage_dtype=getattr(jnp, storage),
                          compute_dtype=jnp.float64 if storage == "float64"
                          else jnp.float32)
    keys = [jax.random.fold_in(jax.random.key(5), b) for b in (0, 1)]
    shared = store()
    made = _count_segment_buffers(shared)
    with StreamingEngine(shared, plan=StreamPlan(segment_len=4)) as eng:
        outs = [eng.sample(8, k, pipeline=True) for k in keys]
        assert len(made) == 1 and made[0][0].shape[0] == 4
    for k, out in zip(keys, outs):
        with StreamingEngine(store(), plan=StreamPlan(segment_len=4)) as eng:
            assert np.array_equal(out, eng.sample(8, k))


def test_fetched_segment_owns_its_device_memory(chain):
    """Overwriting the host segment buffer after a fetch — what the next
    fetch does while this segment computes — leaves the fetched device
    arrays as they were, also where the CPU backend would adopt an aligned
    host buffer instead of copying it."""
    from repro.engine.streaming import _device_copy
    root, _ = chain
    store = _store(root)
    made = _count_segment_buffers(store)
    with StreamingEngine(store, plan=StreamPlan(segment_len=4)) as eng:
        gd, ld, real = eng._fetch(0, 4, eng.chi)
        want = np.array(gd), np.array(ld)
        for buf in made[0]:
            buf.view(np.uint8).fill(0x7F)
        assert real == 4
        assert np.array_equal(np.asarray(gd), want[0])
        assert np.array_equal(np.asarray(ld), want[1])
        eng._release(gd, ld)
    raw = np.empty(4096 + 64, np.uint8)
    at = -raw.ctypes.data % 64
    x = raw[at:at + 4096].view(np.float64)
    x.fill(1.0)
    xd = _device_copy(x)
    x.fill(2.0)
    assert np.all(np.asarray(xd) == 1.0)


def test_identity_pad_sites_are_noops():
    g, lam = np.full((2, 4, 4, 3), 7.0), np.full((2, 4), 7.0)
    fill_identity(g, lam)                 # in place, over stale sites
    np.testing.assert_array_equal(lam, 1.0)
    env = np.array([[0.2, 0.5, 0.1, 0.0]])
    temp = np.einsum("nl,lrs->nrs", env, g[0])
    np.testing.assert_array_equal(temp[:, :, 0], env)   # outcome 0 = identity
    np.testing.assert_array_equal(temp[:, :, 1:], 0.0)  # others impossible


# ---------------------------------------------------------------------------
# Planner (perfmodel-driven)
# ---------------------------------------------------------------------------

def _wl(**kw):
    base = dict(n_samples=80_000, n_sites=512, chi=128, d=3,
                macro_batch=20_000, micro_batch=5_000)
    base.update(kw)
    return Workload(**base)


def test_planner_segment_shrinks_with_budget():
    hw = Hardware()
    w = _wl()
    big = plan_stream(w, hw, device_budget=16e9)
    small = plan_stream(w, hw, device_budget=1e9)
    assert big.segment_len >= small.segment_len
    assert small.segment_len >= 2
    assert big.segment_len % 2 == 0 and small.segment_len % 2 == 0
    assert big.segment_len <= w.n_sites


def test_planner_raises_when_env_does_not_fit():
    with pytest.raises(ValueError):
        plan_stream(_wl(), Hardware(), device_budget=1e6)


def test_planner_scheme_selection():
    hw = Hardware()
    w = _wl()
    assert plan_stream(w, hw).scheme == "inmem"
    assert plan_stream(w, hw, p1=4).scheme == "dp"
    tp = plan_stream(w, hw, p2=4)
    assert tp.scheme == "tp_" + choose_tp_scheme(w, hw, 4)
    assert tp.micro_batch == 5_000       # N₂ now composes with DP/TP too
    dp = plan_stream(w, hw, p1=4)
    assert dp.micro_batch == 5_000 // 4  # per data shard


def test_planner_micro_batch_passthrough():
    plan = plan_stream(_wl(), Hardware(), device_budget=16e9)
    assert plan.micro_batch == 5_000
    info = explain_plan(plan, _wl(), Hardware())
    assert info["io_overlapped"] == (info["t_compute_per_site_s"]
                                     >= info["t_io_per_site_s"])
    assert info["min_macro_batch_for_overlap"] > 0


# ---------------------------------------------------------------------------
# Spans and counters (repro.obs.trace)
# ---------------------------------------------------------------------------

def _mine(job):
    from repro.obs import trace
    return [s for s in trace.spans() if s.attrs.get("job") == job]


def test_streamed_walk_records_its_spans_and_counters(chain):
    """10 sites in segments of 4: three fetches (the last padded), three
    segment computes, every store read under the fetch that scheduled
    it, and the walk's counters equal to the sums of their spans."""
    from repro.obs import trace
    root, mps = chain
    trace.clear()
    eng = StreamingEngine(_store(root), plan=StreamPlan(segment_len=4))
    with trace.span("service.batch", job="engine-test", batch=1) as batch:
        _, stats = eng.sample_with_stats(8, jax.random.key(0))
    eng.close()
    spans = _mine("engine-test")
    named = {}
    for s in spans:
        named.setdefault(s.name, []).append(s)
    (walk,) = named["engine.walk"]
    assert walk.parent_id == batch.span_id
    assert all(s.attrs["batch"] == 1 for s in spans)
    for step in ("engine.wait_gamma", "engine.segment", "engine.fetch"):
        assert len(named[step]) == 3
        assert {s.parent_id for s in named[step]} == {walk.span_id}
    assert sorted(s.attrs["start"] for s in named["engine.segment"]) == [
        0, 4, 8]
    segs = {s.span_id for s in named["engine.segment"]}
    for step in ("engine.dispatch", "engine.samples_to_host", "engine.sync"):
        assert len(named[step]) == 3
        assert {s.parent_id for s in named[step]} == segs
    fetches = {s.span_id: s for s in named["engine.fetch"]}
    assert sorted(s.attrs["start"] for s in fetches.values()) == [0, 4, 8]
    assert "engine.stack" not in named         # sites land in place
    for step in ("store.decode", "engine.device_put"):
        assert len(named[step]) == 3           # one per segment
        assert {s.parent_id for s in named[step]} == set(fetches)
    (pad,) = named["engine.pad"]               # only the tail has pad sites
    assert fetches[pad.parent_id].attrs["start"] == 8
    # per site: the payload read, and the header lookup and CRC check
    reads = sorted(s.attrs["site"] for s in named["store.read"])
    assert reads == list(range(10))
    assert sorted(s.attrs["site"] for s in named["store.parse"]) == sorted(
        2 * reads)
    for step in ("store.read", "store.parse"):
        assert {s.parent_id for s in named[step]} == set(fetches)
    assert stats["payload_reads"] == stats["direct_reads"] == 10

    def total(step):
        return pytest.approx(sum(s.end_ns - s.start_ns
                                 for s in named[step]) / 1e9, abs=1e-6)
    assert stats["io_wait_s"] == total("engine.wait_gamma")
    assert stats["compute_s"] == total("engine.segment")
    assert stats["fetch_s"] == total("engine.fetch")
    assert stats["put_s"] == total("engine.device_put")
    site_bytes = mps.gammas[0].size * 8 + mps.lambdas[0].size * 8
    assert stats["put_bytes"] == 12 * site_bytes     # 10 sites + 2 pads
    assert stats["fetch_s"] >= stats["put_s"] > 0
    assert stats["io_hidden_frac"] == pytest.approx(
        min(1.0, max(0.0, 1 - stats["io_wait_s"] / stats["fetch_s"])))


def test_service_batches_carry_the_job_down_to_the_store(chain):
    """A two-batch job: each batch's walk sits under its ``service.batch``,
    the consumer's waits are ``service.stream_wait``, the first walk
    causes the gang-scheduled fetch of the second batch's first segment,
    and the per-batch counters add up to every fetch the job made."""
    from repro import api
    from repro.obs import trace
    root, _ = chain
    trace.clear()
    with api.SamplingService() as svc:
        h = svc.submit(root, api.SamplerConfig(segment_len=4), n_samples=16,
                       key=jax.random.key(1), macro_batches=2)
        assert [b for b, _ in h.stream()] == [0, 1]
        stats = h.stats
    spans = _mine(h.job_id)
    named = {}
    for s in spans:
        named.setdefault(s.name, []).append(s)
    assert sorted(s.attrs["batch"] for s in named["service.stream_wait"]) \
        == [0, 1]
    batches = {s.attrs["batch"]: s for s in named["service.batch"]}
    walks = {s.attrs["batch"]: s for s in named["engine.walk"]}
    assert sorted(batches) == sorted(walks) == [0, 1]
    for b in (0, 1):
        assert walks[b].parent_id == batches[b].span_id
        w = batches[b]
        assert w.start_ns <= walks[b].start_ns <= walks[b].end_ns <= w.end_ns
    caused = [s.parent_id for s in named["engine.fetch"]]
    assert caused.count(walks[0].span_id) == 4       # 3 + the warm fetch
    assert caused.count(walks[1].span_id) == 2
    fetch_s = sum(s.end_ns - s.start_ns for s in named["engine.fetch"]) / 1e9
    assert sum(st["fetch_s"] for st in stats.values()) == pytest.approx(
        fetch_s, abs=1e-6)
