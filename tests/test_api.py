"""Unified `SamplingSession` API: one front door, bit-identical everywhere.

The facade's contract (paper §4.1 composed over every level): for one seed,
every supported cell of {inmem, streamed, remote} × {local, multihost,
remote runtime} × {seq, dp, tp_single, tp_double} × {static, dynamic-χ} ×
{whole-batch, micro-batched} emits bit-identical samples, and a killed
streamed run resumes exactly.  Single-device cells run in-process; the
DP/TP matrix runs in a subprocess with 8 forced host devices (the main
pytest process must keep the real device view); the multi-process runtime
cells emulate a 2-process cluster (`api.emulated_cluster`) with one driver
thread per "process", slow-marked alongside the subprocess remote dispatch.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.core import dynamic_bond as DB
from repro.core import mps as M
from repro.core import sampler as S
from repro.data.gamma_store import GammaStore


# ---------------------------------------------------------------------------
# Single-device cells (seq scheme): facade vs the legacy references
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chain(tmp_path_factory, linear_mps_10x6):
    root = str(tmp_path_factory.mktemp("api_gamma"))
    with GammaStore(root, storage_dtype=jnp.float64,
                    compute_dtype=jnp.float64) as store:
        store.write_mps(linear_mps_10x6)
    return root, linear_mps_10x6


def test_inmem_seq_matches_legacy_sampler(linear_mps_10x6):
    mps = linear_mps_10x6
    key = jax.random.key(3)
    with api.SamplingSession(mps) as sess:
        out = sess.sample(24, key)
    assert np.array_equal(out, np.asarray(S.sample(mps, 24, key)))


def test_streamed_seq_matches_legacy_sampler(chain):
    root, mps = chain
    key = jax.random.key(3)
    cfg = api.SamplerConfig(segment_len=4)
    with api.SamplingSession(root, cfg) as sess:
        assert sess.plan(24).backend == "streamed"   # auto from the store
        out = sess.sample(24, key)
        assert sess.stats["max_live_segments"] <= 2
    assert np.array_equal(out, np.asarray(S.sample(mps, 24, key)))


def test_session_from_mps_materializes_identity_store(linear_mps_10x6):
    """backend="streamed" over an MPS source: the session writes a store in
    the MPS's own dtype, so no storage rounding breaks bit-identity."""
    mps = linear_mps_10x6
    key = jax.random.key(5)
    cfg = api.SamplerConfig(backend="streamed", segment_len=5)
    with api.SamplingSession(mps, cfg) as sess:
        out = sess.sample(16, key)
    assert np.array_equal(out, np.asarray(S.sample(mps, 16, key)))


def test_micro_batch_both_backends(chain):
    root, mps = chain
    key = jax.random.key(9)
    ref = np.asarray(S.sample_batched(mps, 24, key, micro_batch=8))
    with api.SamplingSession(mps, api.SamplerConfig(micro_batch=8)) as sess:
        assert np.array_equal(sess.sample(24, key), ref)
    cfg = api.SamplerConfig(micro_batch=8, segment_len=4)
    with api.SamplingSession(root, cfg) as sess:
        assert np.array_equal(sess.sample(24, key), ref)


def test_dynamic_chi_both_backends(chain):
    root, mps = chain
    key = jax.random.key(11)
    prof = DB.bucketize(DB.area_law_profile(10, 6), [4, 6])
    ref = np.asarray(DB.sample_staged(mps, prof, 24, key))
    cfg = api.SamplerConfig(chi_profile=tuple(int(c) for c in prof))
    with api.SamplingSession(mps, cfg) as sess:
        plan = sess.plan(24)
        assert plan.stages is not None and len(plan.stages) >= 2
        assert np.array_equal(sess.sample(24, key), ref)
    cfg = api.SamplerConfig(chi_profile=tuple(int(c) for c in prof),
                            segment_len=3)
    with api.SamplingSession(root, cfg) as sess:
        assert np.array_equal(sess.sample(24, key), ref)


def test_streamed_kill_and_resume(chain, tmp_path):
    root, mps = chain
    key = jax.random.key(13)
    ref = np.asarray(S.sample(mps, 16, key))
    cfg = api.SamplerConfig(segment_len=4, checkpoint_every=1,
                            checkpoint_dir=str(tmp_path))
    with api.SamplingSession(root, cfg) as sess:
        part = sess.sample(16, key, stop_after_segments=2)
        assert part.shape == (16, 8)
        assert np.array_equal(part, ref[:, :8])
        out = sess.sample(16, key, resume=True)
        assert sess.stats["segments"] == 1           # only the remaining work
    assert np.array_equal(out, ref)


def test_run_queue_macro_batches(chain):
    """Macro batches through the facade: batch = f(seed, id), results
    owner/order-independent (runtime/elastic.py contract)."""
    from repro.runtime.elastic import WorkQueue
    root, mps = chain
    base = jax.random.key(21)
    with api.SamplingSession(root, api.SamplerConfig(segment_len=5)) as sess:
        q = WorkQueue(3)
        outs = sess.run_queue(q, 8, base)
        assert q.finished
    for b in range(3):
        ref = np.asarray(S.sample(mps, 8, jax.random.fold_in(base, b)))
        assert np.array_equal(outs[b], ref)


def test_born_semantics_both_backends(tmp_path, born_mps_6x4):
    mps = born_mps_6x4
    key = jax.random.key(2)
    ref = np.asarray(S.sample(mps, 16, key,
                              S.SamplerConfig(semantics="born")))
    with api.SamplingSession(mps) as sess:
        assert sess.plan(16).semantics == "born"     # auto from the MPS
        assert np.array_equal(sess.sample(16, key), ref)
    with GammaStore(str(tmp_path), storage_dtype=jnp.complex128,
                    compute_dtype=jnp.complex128) as store:
        store.write_mps(mps)
        cfg = api.SamplerConfig(semantics="born", segment_len=4)
        with api.SamplingSession(store, cfg) as sess:
            assert np.array_equal(sess.sample(16, key), ref)


# ---------------------------------------------------------------------------
# Planning, registry, lifecycle, deprecation
# ---------------------------------------------------------------------------

def test_plan_and_explain(chain):
    root, _ = chain
    with api.SamplingSession(root) as sess:
        plan = sess.plan(24)
        assert plan.backend == "streamed" and plan.scheme == "seq"
        assert plan.segment_len and plan.segment_len >= 1
        info = sess.explain(24)
        assert info["backend"] == "streamed"
        assert info["chi_buckets"] == [6]
        assert "io_overlapped" in info and "segment_len" in info


def test_backend_registry():
    assert set(api.available_backends()) >= {"inmem", "streamed"}
    assert api.get_backend("inmem").name == "inmem"
    with pytest.raises(ValueError, match="no backend"):
        api.get_backend("nope")

    @api.register_backend("_test_backend")
    class _TB(api.Backend):
        name = "_test_backend"

        def sample(self, req):
            return np.zeros((req.n_samples, 1), np.int32)

    try:
        assert "_test_backend" in api.available_backends()
    finally:
        from repro.api import backends as B
        B._REGISTRY.pop("_test_backend", None)


def test_resolution_errors(linear_mps_10x6):
    mps = linear_mps_10x6
    with api.SamplingSession(mps, api.SamplerConfig(scheme="dp")) as sess:
        with pytest.raises(ValueError, match="needs a mesh"):
            sess.plan(8)
    with api.SamplingSession(mps, api.SamplerConfig(micro_batch=7)) as sess:
        with pytest.raises(ValueError, match="micro_batch"):
            sess.plan(24)
    bad_prof = (6,) * 9                              # covers 9 of 10 sites
    with api.SamplingSession(
            mps, api.SamplerConfig(chi_profile=bad_prof)) as sess:
        with pytest.raises(ValueError, match="chi_profile"):
            sess.plan(8)
    with api.SamplingSession(mps) as sess:
        with pytest.raises(ValueError, match="resume"):
            sess.sample(8, jax.random.key(0), resume=True)


def test_micro_batch_plus_dynamic_chi_inmem_seq(chain):
    """PR 2's last routing gap is closed: micro batching and dynamic χ
    compose directly on the in-memory seq path (no silent reroute to the
    streamed backend), bit-identical to the streamed cell and to the
    sample_batched key schedule."""
    root, mps = chain
    key = jax.random.key(15)
    prof = DB.bucketize(DB.area_law_profile(10, 6), [4, 6])
    cfgi = api.SamplerConfig(chi_profile=tuple(int(c) for c in prof),
                             micro_batch=8)
    with api.SamplingSession(mps, cfgi) as sess:
        plan = sess.plan(24)
        assert plan.backend == "inmem" and plan.scheme == "seq"
        assert plan.micro_batch == 8 and plan.stages is not None
        out = sess.sample(24, key)
    assert np.array_equal(
        out, np.asarray(DB.sample_staged_batched(mps, prof, 24, key, 8)))
    cfgs = api.SamplerConfig(chi_profile=tuple(int(c) for c in prof),
                             micro_batch=8, segment_len=3)
    with api.SamplingSession(root, cfgs) as sess:
        assert np.array_equal(sess.sample(24, key), out)
    # AUTO micro now resolves to a real chunk size on this path too
    cfga = api.SamplerConfig(micro_batch=api.AUTO,
                             chi_profile=tuple(int(c) for c in prof),
                             device_budget=2e4)
    with api.SamplingSession(mps, cfga) as sess:
        assert sess.plan(24).micro_batch is not None


def test_gamma_store_context_manager(tmp_path, linear_mps_10x6):
    with GammaStore(str(tmp_path), storage_dtype=jnp.float64,
                    compute_dtype=jnp.float64) as store:
        store.write_mps(linear_mps_10x6)
        assert store.n_sites == 10
    assert not store._thread.is_alive()              # prefetch thread joined


def test_legacy_entry_points_removed():
    """The ROADMAP scheduled the deprecation-shimmed entry points for
    removal one release after the PR 2 facade — they are gone; the
    session is the only front door (internal segment-runner callables
    remain, underscore-prefixed)."""
    import repro.engine as engine
    from repro.core import parallel as PP
    for name in ("multilevel_sample", "dp_sample", "baseline19_sample"):
        assert not hasattr(PP, name), name
    assert not hasattr(engine, "stream_sample")
    assert not hasattr(engine.streaming, "stream_sample")
    # the internal data plane the backends route through is still there
    assert callable(PP._multilevel_sample) and callable(PP.sample_segment)


def test_parallel_log_scale_parity(linear_mps_10x6):
    """Satellite: the DP segment runner carries the same per-sample
    log_scale diagnostic as the in-memory chain scan."""
    from repro.core import parallel as PP
    mps = linear_mps_10x6
    key = jax.random.key(4)
    # dp hands shard i the key split(key, p1)[i]; p1 = 1 here
    state = S.init_state(mps, 8, jax.random.split(key, 1)[0])
    res = S.sample_chain(mps, state, S.SamplerConfig())
    mesh = jax.make_mesh((1,), ("data",))
    env = PP.segment_env_init(8, mps.chi, mps.gammas.dtype)
    _, _, ls = PP.sample_segment(mesh, mps, env, key, 0,
                                 PP.ParallelConfig("dp"), S.SamplerConfig())
    np.testing.assert_allclose(np.asarray(ls),
                               np.asarray(res.state.log_scale), rtol=1e-12)


# ---------------------------------------------------------------------------
# The full DP/TP matrix (8 forced host devices, subprocess)
# ---------------------------------------------------------------------------

_CHILD = textwrap.dedent("""
    import json, os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.core import dynamic_bond as DB, mps as M, parallel as PP
    from repro.core import sampler as S
    from repro.data.gamma_store import GammaStore
    from repro.launch.mesh import make_host_mesh

    m = M.random_linear_mps(jax.random.key(0), 8, 8, 3)
    mesh = make_host_mesh(model=4)             # 2 data x 4 model
    key = jax.random.key(7)

    # the internal segment-runner data plane is the static reference
    ref = np.asarray(PP._multilevel_sample(mesh, m, 64, key,
                                           PP.ParallelConfig("dp")))

    root = tempfile.mkdtemp()
    with GammaStore(root, storage_dtype=jnp.float64,
                    compute_dtype=jnp.float64) as st:
        st.write_mps(m)

    # dynamic-χ reference: per-shard staged chains (even-aligned stages so
    # tp_double's site pairs never straddle a χ transition)
    prof = np.array([4, 4, 8, 8, 8, 8, 4, 4])
    sk = jax.random.split(key, 2)
    ref_dyn = np.concatenate([np.asarray(DB.sample_staged(m, prof, 32, sk[i]))
                              for i in range(2)], 0)
    ref_mb = np.concatenate([np.asarray(S.sample_batched(m, 32, sk[i], 8))
                             for i in range(2)], 0)

    out = {}
    for backend, src in (("inmem", m), ("streamed", root)):
        for scheme in ("dp", "tp_single", "tp_double"):
            cfg = api.SamplerConfig(backend=backend, scheme=scheme,
                                    segment_len=2)
            with api.SamplingSession(src, cfg, mesh=mesh) as sess:
                out[f"{backend}_{scheme}_static"] = bool(
                    np.array_equal(sess.sample(64, key), ref))
            cfgd = api.SamplerConfig(backend=backend, scheme=scheme,
                                     segment_len=2,
                                     chi_profile=tuple(int(c) for c in prof))
            with api.SamplingSession(src, cfgd, mesh=mesh) as sess:
                out[f"{backend}_{scheme}_dynamic"] = bool(
                    np.array_equal(sess.sample(64, key), ref_dyn))
        # micro batching N2 under a parallel scheme (per data shard)
        cfgm = api.SamplerConfig(backend=backend, scheme="tp_single",
                                 segment_len=4, micro_batch=8)
        with api.SamplingSession(src, cfgm, mesh=mesh) as sess:
            out[f"{backend}_tp_single_micro"] = bool(
                np.array_equal(sess.sample(64, key), ref_mb))

    # log_scale diagnostic parity: the TP segment runners accumulate the
    # same per-sample rescale log as the DP path (satellite)
    envd = PP.segment_env_init(64, 8, m.gammas.dtype)
    _, _, lsd = PP.sample_segment(mesh, m, envd, key, 0,
                                  PP.ParallelConfig("dp"), S.SamplerConfig())
    _, _, ls1 = PP.sample_segment(mesh, m, envd, key, 0,
                                  PP.ParallelConfig("tp_single"),
                                  S.SamplerConfig())
    _, _, ls2 = PP.sample_segment(mesh, m, envd, key, 0,
                                  PP.ParallelConfig("tp_double"),
                                  S.SamplerConfig())
    out["log_scale_tp_parity"] = bool(
        np.allclose(lsd, ls1, rtol=1e-12)
        and np.allclose(lsd, ls2, rtol=1e-12))

    # multi-pod mesh: "pod" folds into data parallel — the resolved
    # ParallelConfig.data_axes must cover every non-model axis
    mesh3 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    with api.SamplingSession(m, api.SamplerConfig(scheme="dp"),
                             mesh=mesh3) as sess:
        assert sess.plan(64).p1 == 4
        out3 = sess.sample(64, key)
    sk4 = jax.random.split(key, 4)
    ref3 = np.concatenate([np.asarray(S.sample(m, 16, sk4[i]))
                           for i in range(4)], 0)
    out["multipod_dp"] = bool(np.array_equal(out3, ref3))

    # plan-time validation: fixed-χ TP divisibility surfaces pre-compile
    m_bad = M.random_linear_mps(jax.random.key(1), 6, 6, 3)
    try:
        with api.SamplingSession(m_bad, api.SamplerConfig(scheme="tp_single"),
                                 mesh=mesh) as sess:
            sess.plan(64)
        out["tp_chi_plan_error"] = False
    except ValueError:
        out["tp_chi_plan_error"] = True

    # kill-and-resume through the facade: streamed dp, dynamic chi
    ck = tempfile.mkdtemp()
    cfg = api.SamplerConfig(backend="streamed", scheme="dp", segment_len=2,
                            chi_profile=tuple(int(c) for c in prof),
                            checkpoint_dir=ck, checkpoint_every=1)
    with api.SamplingSession(root, cfg, mesh=mesh) as sess:
        sess.sample(64, key, stop_after_segments=2)
    with api.SamplingSession(root, cfg, mesh=mesh) as sess:
        out["resume_dynamic_dp"] = bool(
            np.array_equal(sess.sample(64, key, resume=True), ref_dyn))
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def matrix_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", [
    f"{b}_{s}_{m}"
    for b in ("inmem", "streamed")
    for s in ("dp", "tp_single", "tp_double")
    for m in ("static", "dynamic")
] + ["inmem_tp_single_micro", "streamed_tp_single_micro",
     "resume_dynamic_dp", "log_scale_tp_parity",
     "multipod_dp", "tp_chi_plan_error"])
def test_cross_backend_matrix(matrix_results, cell):
    """One seed ⇒ bit-identical samples in every supported cell of
    {inmem, streamed} × {dp, tp_single, tp_double} × {static, dynamic-χ},
    micro-batched DP/TP, and a kill-and-resume — all through the facade."""
    assert matrix_results[cell]


# ---------------------------------------------------------------------------
# Cluster runtime × data plane (ClusterRuntime layer)
# ---------------------------------------------------------------------------

def _run_emulated_cluster(runtimes, make_config, source, n, key, mesh=None):
    """Drive one session per runtime instance concurrently (each 'process'
    on its own thread, the way a real multi-process launch runs one driver
    per host); returns ({process: samples}, {process: stats})."""
    import threading

    outs, stats, errs = {}, {}, []

    def run(rt):
        try:
            with api.SamplingSession(source, make_config(rt),
                                     mesh=mesh) as sess:
                outs[rt.process_index] = sess.sample(n, key)
                stats[rt.process_index] = dict(sess.stats)
        except Exception as e:          # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=run, args=(rt,)) for rt in runtimes]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errs, errs
    return outs, stats


def test_multihost_streamed_bitidentical_to_local(chain):
    """Acceptance cell: runtime='multihost' (fake 2-process cluster) ×
    backend='streamed' emits bit-identical samples to runtime='local' for
    the same seed, with the GammaStore read-counters showing exactly one
    process reading each segment."""
    root, mps = chain
    key = jax.random.key(23)
    with api.SamplingSession(
            root, api.SamplerConfig(segment_len=4)) as sess:
        ref = sess.sample(16, key)
        local_bytes = sess.stats["io_bytes"]
    assert np.array_equal(ref, np.asarray(S.sample(mps, 16, key)))

    runtimes = api.emulated_cluster(2)
    outs, stats = _run_emulated_cluster(
        runtimes,
        lambda rt: api.SamplerConfig(runtime=rt, backend="streamed",
                                     segment_len=4),
        root, 16, key)
    assert np.array_equal(outs[0], ref)
    assert np.array_equal(outs[1], ref)
    # one reader: the root's per-engine store-I/O delta covers the chain
    # exactly once; the peer never touches the store payload
    assert stats[0]["io_bytes"] == local_bytes
    assert stats[1]["io_bytes"] == 0
    assert stats[0]["broadcast_send_bytes"] == local_bytes
    assert stats[1]["broadcast_recv_bytes"] == local_bytes


def test_remote_backend_loopback_dispatch(chain):
    """backend='remote' on the local runtime: the request crosses the
    serialization boundary (config → JSON payload → worker session) and
    comes back bit-identical — the dispatch path, minus the subprocess."""
    root, mps = chain
    key = jax.random.key(29)
    ref = np.asarray(S.sample(mps, 16, key))
    cfg = api.SamplerConfig(backend="remote", segment_len=4)
    with api.SamplingSession(root, cfg) as sess:
        plan = sess.plan(16)
        assert plan.backend == "remote" and plan.runtime == "local"
        out = sess.sample(16, key)
        assert sess.stats["runtime_dispatch_bytes"] > 0
    assert np.array_equal(out, ref)


@pytest.mark.slow
def test_remote_runtime_subprocess_dispatch(chain):
    """runtime='remote': the serialized SamplerConfig is dispatched to a
    fresh worker interpreter (python -m repro.api.remote) — full process
    isolation, bit-identical samples back."""
    root, mps = chain
    key = jax.random.key(31)
    ref = np.asarray(S.sample(mps, 16, key))
    cfg = api.SamplerConfig(runtime="remote", segment_len=4)
    with api.SamplingSession(root, cfg) as sess:
        plan = sess.plan(16)
        assert plan.backend == "remote" and plan.runtime == "remote"
        out = sess.sample(16, key)
        counters = sess.runtime.io_counters()
        assert counters["dispatches"] == 1 and counters["dispatch_bytes"] > 0
    assert np.array_equal(out, ref)


def test_wire_payload_roundtrip_is_lossless(chain):
    """The jax.distributed broadcast frames the segment payload as
    (length, uint8 npz blob) — the round-trip must reproduce the raw
    storage bytes exactly (any loss here would break the §4.1 bit-identity
    of a real multi-host run)."""
    from repro.api.runtime import payload_from_bytes, payload_to_bytes
    from repro.data.gamma_store import decode_segment

    root, mps = chain
    with GammaStore(root, storage_dtype=jnp.float64,
                    compute_dtype=jnp.float64) as store:
        payload = store.get_segment_raw(2, 5)
        back = payload_from_bytes(payload_to_bytes(payload))
        assert back["start"] == payload["start"]
        np.testing.assert_array_equal(back["gamma"], payload["gamma"])
        np.testing.assert_array_equal(back["lam"], payload["lam"])
        g0, l0 = decode_segment(payload)
        g1, l1 = decode_segment(back)
        np.testing.assert_array_equal(g0, g1)
        np.testing.assert_array_equal(l0, l1)
    # bf16 storage survives the uint16 view framing too
    with GammaStore(str(root) + "_bf16") as bstore:
        bstore.write_mps(mps)
        payload = bstore.get_segment_raw(0, 3)
        back = payload_from_bytes(payload_to_bytes(payload))
        assert np.dtype(back["storage_dtype"]) == np.dtype(jnp.bfloat16)
        g0, l0 = decode_segment(payload)
        g1, l1 = decode_segment(back)
        np.testing.assert_array_equal(g0, g1)
        np.testing.assert_array_equal(l0, l1)


def test_runtime_registry_and_cell_validation(chain, linear_mps_10x6):
    root, _ = chain
    assert set(api.available_runtimes()) >= {"local", "multihost", "remote"}
    assert api.resolve_runtime(api.AUTO).name == "local"
    assert api.resolve_runtime("local").process_count == 1
    with pytest.raises(ValueError, match="no runtime"):
        api.resolve_runtime("nope")
    # multihost needs the streamed data plane (the broadcast is a segment
    # concern) — surfaced at plan time, before any compilation
    rt = api.emulated_cluster(2)[0]
    cfg = api.SamplerConfig(runtime=rt, backend="inmem")
    with api.SamplingSession(linear_mps_10x6, cfg) as sess:
        with pytest.raises(ValueError, match="streamed"):
            sess.plan(8)
    # a remote runtime only dispatches — local data planes are rejected
    cfg = api.SamplerConfig(runtime="remote", backend="streamed")
    with api.SamplingSession(root, cfg) as sess:
        with pytest.raises(ValueError, match="remote"):
            sess.plan(8)
    # remote resolves placement on the worker: no local mesh / dp scheme
    cfg = api.SamplerConfig(backend="remote", scheme="dp")
    with api.SamplingSession(root, cfg) as sess:
        with pytest.raises(ValueError, match="worker"):
            sess.plan(8)
    # checkpointing does not ship across the dispatch boundary — rejected
    # at plan time, not silently dropped
    cfg = api.SamplerConfig(backend="remote", checkpoint_dir="/tmp/nope")
    with api.SamplingSession(root, cfg) as sess:
        with pytest.raises(ValueError, match="checkpoint"):
            sess.plan(8)
    # single-process 'multihost' by name points at emulated_cluster
    with pytest.raises(ValueError, match="emulated_cluster"):
        api.resolve_runtime("multihost")


_RUNTIME_CHILD = textwrap.dedent("""
    import json, os, tempfile, threading
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.core import mps as M
    from repro.data.gamma_store import GammaStore
    from repro.launch.mesh import make_host_mesh

    m = M.random_linear_mps(jax.random.key(0), 8, 8, 3)
    key = jax.random.key(7)
    root = tempfile.mkdtemp()
    with GammaStore(root, storage_dtype=jnp.float64,
                    compute_dtype=jnp.float64) as st:
        st.write_mps(m)

    out = {}
    for scheme, model in (("dp", 1), ("tp_single", 4)):
        mesh = make_host_mesh(model=model)
        cfg = api.SamplerConfig(backend="streamed", scheme=scheme,
                                segment_len=2)
        with api.SamplingSession(root, cfg, mesh=mesh) as sess:
            ref = sess.sample(64, key)
            local_bytes = sess.stats["io_bytes"]

        runtimes = api.emulated_cluster(2, timeout=300.0)
        res, stats, errs = {}, {}, []

        def run(rt):
            try:
                c = api.SamplerConfig(runtime=rt, backend="streamed",
                                      scheme=scheme, segment_len=2)
                with api.SamplingSession(root, c, mesh=mesh) as sess:
                    res[rt.process_index] = sess.sample(64, key)
                    stats[rt.process_index] = dict(sess.stats)
            except Exception as e:
                errs.append(repr(e))

        ts = [threading.Thread(target=run, args=(rt,)) for rt in runtimes]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=500)
        out[scheme + "_errs"] = errs
        out[scheme + "_root"] = bool(np.array_equal(res.get(0), ref))
        out[scheme + "_peer"] = bool(np.array_equal(res.get(1), ref))
        out[scheme + "_one_reader"] = bool(
            stats[0]["io_bytes"] == local_bytes
            and stats[1]["io_bytes"] == 0
            and stats[1]["broadcast_recv_bytes"] == local_bytes)
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def runtime_matrix_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _RUNTIME_CHILD], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("cell", [
    f"{s}_{w}" for s in ("dp", "tp_single")
    for w in ("root", "peer", "one_reader")])
def test_runtime_matrix_multihost_dp_tp(runtime_matrix_results, cell):
    """The {local, multihost} × streamed × {dp, tp_single} matrix on 8
    forced host devices with a fake 2-process runtime: every process emits
    the local run's exact samples and only the root reads the store."""
    scheme = cell.rsplit("_", 1)[0] if not cell.endswith("one_reader") \
        else cell[: -len("_one_reader")]
    assert runtime_matrix_results[scheme + "_errs"] == []
    assert runtime_matrix_results[cell]


def test_compile_cache_location(monkeypatch, tmp_path):
    """The entry points' compile cache: $JAX_COMPILATION_CACHE_DIR when set
    (and nothing else is set), else a fixed <checkout>/.jax_cache."""
    from repro.launch import compile_cache as CC
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert CC.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = CC.enable_compile_cache()
        assert path == os.path.join(CC.CHECKOUT, ".jax_cache")
        assert os.path.isfile(os.path.join(CC.CHECKOUT, "ROADMAP.md"))
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
