"""Chain-sharded data plane (ROADMAP item 3): block-cyclic Γ distribution
with pipelined env handoff.

Layers under test, bottom-up: the pure ownership algebra (``ShardMap``),
the enforcing store view (``ShardedGammaStore`` — a foreign Γ read raises,
it never silently falls back), the slice-with-manifest digest story
(``materialize_shard``), plan-time resolution (``SamplerConfig.shard``),
the perfmodel wire accounting, and the acceptance contract itself: an
emulated multi-host sharded walk is bit-identical to the single-host
unsharded run for the same seed, with per-engine counters proving every
host read only the Γ blocks it owns and only the tiny (N, χ) environment
crossed the interconnect.  The 4-host {seq, dp} × {static, dynamic-χ}
matrix and the SIGKILL chaos resume run in subprocesses (slow-marked, 8
forced host devices) alongside tests/test_api.py's matrix.

Hypothesis property tests for the shard algebra live in
tests/test_property.py (the module that already guards on hypothesis
being installed).
"""
import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import mps as M
from repro.core import sampler as S
from repro.core.perfmodel import Workload, shard_wire_bytes
from repro.data.gamma_store import MANIFEST_NAME, GammaStore
from repro.shard import (ShardMap, ShardViolation, ShardedGammaStore,
                         chain_segments, materialize_shard)


@pytest.fixture(scope="module")
def chain(tmp_path_factory, linear_mps_10x6):
    root = str(tmp_path_factory.mktemp("shard_gamma"))
    with GammaStore(root, storage_dtype=jnp.float64,
                    compute_dtype=jnp.float64) as store:
        store.write_mps(linear_mps_10x6)
    return root, linear_mps_10x6


# ---------------------------------------------------------------------------
# ShardMap — the pure ownership algebra
# ---------------------------------------------------------------------------

def test_owner_is_block_cyclic():
    sm = ShardMap(n_sites=10, n_hosts=3, block=2)
    assert [sm.owner(i) for i in range(10)] == [0, 0, 1, 1, 2, 2, 0, 0, 1, 1]
    assert sm.n_blocks == 5
    with pytest.raises(IndexError):
        sm.owner(10)
    with pytest.raises(IndexError):
        sm.owner(-1)


def test_owned_sites_partition_sweep():
    """Seeded sweep: for any (n_sites, hosts, block), the hosts' owned-site
    sets partition the chain — every site computed exactly once."""
    rng = np.random.default_rng(0)
    for _ in range(50):
        n = int(rng.integers(1, 64))
        h = int(rng.integers(1, 8))
        b = int(rng.integers(1, 16))
        sm = ShardMap(n_sites=n, n_hosts=h, block=b)
        seen = []
        for host in range(h):
            owned = sm.owned_sites(host)
            assert all(sm.owns(host, i) for i in owned)
            seen += owned
        assert sorted(seen) == list(range(n))


def test_segment_owner_and_straddle():
    sm = ShardMap(n_sites=10, n_hosts=2, block=2)
    assert sm.segment_owner(0, 2) == 0
    assert sm.segment_owner(2, 4) == 1
    assert sm.segment_owner(4, 5) == 0          # sub-block segment is fine
    with pytest.raises(ValueError, match="straddles"):
        sm.segment_owner(1, 3)
    # one host: nothing can straddle
    assert ShardMap(n_sites=10, n_hosts=1, block=2).segment_owner(1, 9) == 0
    with pytest.raises(IndexError):
        sm.segment_owner(8, 11)


def test_handoffs_follow_chain_order():
    sm = ShardMap(n_sites=10, n_hosts=3, block=2)
    sched = chain_segments(10, 2)
    assert sm.owners_for(sched) == [0, 1, 2, 0, 1]
    hs = sm.handoffs(sched)
    assert hs == [(2, 0, 1), (4, 1, 2), (6, 2, 0), (8, 0, 1)]
    boundaries = [b for b, _, _ in hs]
    assert boundaries == sorted(boundaries)
    for b, src, dst in hs:
        assert sm.owner(b - 1) == src and sm.owner(b) == dst


def test_chain_segments_respects_stages():
    # χ-stage boundaries clip segments exactly as the engine's schedule does
    stages = [(0, 3, 4), (3, 8, 8), (8, 10, 4)]
    segs = chain_segments(10, 2, stages)
    assert segs == [(0, 2, 4), (2, 3, 4), (3, 5, 8), (5, 7, 8),
                    (7, 8, 8), (8, 10, 4)]
    covered = [i for s, e, _ in segs for i in range(s, e)]
    assert covered == list(range(10))
    assert chain_segments(6, 10) == [(0, 6, None)]


def test_shardmap_validation():
    for bad in (dict(n_sites=0, n_hosts=1, block=1),
                dict(n_sites=4, n_hosts=0, block=1),
                dict(n_sites=4, n_hosts=1, block=0)):
        with pytest.raises(ValueError):
            ShardMap(**bad)


# ---------------------------------------------------------------------------
# ShardedGammaStore — ownership enforcement + slice digests
# ---------------------------------------------------------------------------

def test_foreign_read_raises_prefetch_is_advisory(chain):
    root, _ = chain
    sm = ShardMap(n_sites=10, n_hosts=2, block=2)
    with ShardedGammaStore(root, sm, host=0, storage_dtype=jnp.float64,
                           compute_dtype=jnp.float64) as view:
        assert view.n_sites == 10              # global chain, not file count
        g, lam = view.get(0, prefetch_next=False)
        assert g.shape == (6, 6, 3)
        with pytest.raises(ShardViolation, match="owned by host 1"):
            view.get(2, prefetch_next=False)
        with pytest.raises(ShardViolation):
            view.get_segment(2, 2)
        # blanket prefetch over a boundary is skipped, not fatal
        for i in (3, 0, 1, 2):
            view.prefetch(i)
        g2, _ = view.get(1, prefetch_next=False)   # still healthy after
        assert g2.shape == (6, 6, 3)
        with pytest.raises(ShardViolation, match="write"):
            view.put(2, np.zeros((6, 6, 3)), np.zeros(6))


def test_segment_landing_refuses_foreign_sites_before_reading(chain):
    """``read_segment_into`` checks every site of the segment before it
    reads any: a segment that runs into a foreign site raises
    ShardViolation with no payload byte read and the buffer untouched."""
    root, _ = chain
    sm = ShardMap(n_sites=10, n_hosts=2, block=2)
    with ShardedGammaStore(root, sm, host=0, storage_dtype=jnp.float64,
                           compute_dtype=jnp.float64) as view:
        g, lam = view.segment_buffer(2, 2)   # probe redirects to site 0
        g.fill(-1.0)
        with pytest.raises(ShardViolation, match="site 2, owned by host 1"):
            view.read_segment_into(1, 3, g, lam)
        assert view.payload_reads == view.io_bytes == 0
        assert np.all(g == -1.0)
        view.read_segment_into(0, 2, g, lam)      # owned: lands directly
        assert view.direct_reads == view.payload_reads == 2


def test_meta_redirects_and_empty_host_raises(chain, tmp_path):
    root, _ = chain
    sm = ShardMap(n_sites=10, n_hosts=2, block=2)
    with ShardedGammaStore(root, sm, host=1, storage_dtype=jnp.float64,
                           compute_dtype=jnp.float64) as view:
        assert view.meta(0) == view.meta(2)    # foreign probe → owned shape
    lonely = ShardMap(n_sites=2, n_hosts=4, block=2)   # hosts 2,3 own nothing
    with ShardedGammaStore(str(tmp_path), lonely, host=3) as view:
        with pytest.raises(ShardViolation, match="owns no sites"):
            view.meta(0)


def test_materialized_slice_reproduces_global_digest(chain, tmp_path):
    """Acceptance (satellite 2): each host's slice holds only its owned
    files + the manifest, yet ``digest()`` answers with the WHOLE store's
    Merkle root — the key the gateway's ResultCache addresses results by."""
    root, _ = chain
    with GammaStore(root, storage_dtype=jnp.float64,
                    compute_dtype=jnp.float64) as whole:
        global_digest = whole.digest()
    sm = ShardMap(n_sites=10, n_hosts=3, block=2)
    for host in range(3):
        dst = str(tmp_path / f"slice{host}")
        materialize_shard(root, dst, sm, host)
        files = sorted(f for f in os.listdir(dst) if f.endswith(".npz"))
        assert len(files) == len(sm.owned_sites(host))   # capacity scales
        assert os.path.exists(os.path.join(dst, MANIFEST_NAME))
        with ShardedGammaStore(dst, sm, host, storage_dtype=jnp.float64,
                               compute_dtype=jnp.float64) as view:
            assert view.digest() == global_digest
            # and the slice actually serves its own sites
            s0 = sm.owned_sites(host)[0]
            g, _ = view.get(s0, prefetch_next=False)
            assert g.shape == (6, 6, 3)


def test_shared_root_digest_without_manifest(chain):
    # shared-filesystem deployment: foreign files are present, no manifest
    # was ever written — digest() hashes them directly (metadata read)
    root, _ = chain
    with GammaStore(root, storage_dtype=jnp.float64,
                    compute_dtype=jnp.float64) as whole:
        global_digest = whole.digest()
    sm = ShardMap(n_sites=10, n_hosts=2, block=4)
    with ShardedGammaStore(root, sm, host=1, storage_dtype=jnp.float64,
                           compute_dtype=jnp.float64) as view:
        assert view.digest() == global_digest


def test_sliced_digest_missing_manifest_raises(chain, tmp_path):
    root, _ = chain
    sm = ShardMap(n_sites=10, n_hosts=2, block=2)
    dst = str(tmp_path / "bare")
    materialize_shard(root, dst, sm, host=0)
    os.remove(os.path.join(dst, MANIFEST_NAME))
    with ShardedGammaStore(dst, sm, host=0, storage_dtype=jnp.float64,
                           compute_dtype=jnp.float64) as view:
        with pytest.raises(FileNotFoundError, match=MANIFEST_NAME):
            view.digest()


def test_put_changes_merkle_digest(tmp_path, linear_mps_10x6):
    root = str(tmp_path / "mut")
    with GammaStore(root, storage_dtype=jnp.float64,
                    compute_dtype=jnp.float64) as st:
        st.write_mps(linear_mps_10x6)
        d0 = st.digest()
        assert st.digest() == d0                       # cached, stable
        g, lam = st.get(3, prefetch_next=False)
        st.put(3, np.asarray(g) * 2.0, np.asarray(lam))
        assert st.digest() != d0                       # put invalidates
        leaves = st.site_digests()
        assert set(leaves) == {f"site_{i:06d}.npz" for i in range(10)}


# ---------------------------------------------------------------------------
# Plan-time resolution (SamplerConfig.shard → SessionPlan.shard_block)
# ---------------------------------------------------------------------------

def test_shard_resolution_validation(chain, linear_mps_10x6):
    root, _ = chain
    with api.SamplingSession(linear_mps_10x6,
                             api.SamplerConfig(backend="inmem",
                                               shard="auto")) as sess:
        with pytest.raises(ValueError, match="streamed"):
            sess.plan(8)
    with api.SamplingSession(root, api.SamplerConfig(
            backend="streamed", segment_len=4, shard=2)) as sess:
        with pytest.raises(ValueError, match="whole number of segments"):
            sess.plan(8)


def test_shard_auto_single_host_bitidentical(chain):
    """H=1 is the degenerate shard: same plan fields, same walk, same
    bits — which is also what a remote worker receiving a sharded config
    runs."""
    root, mps = chain
    key = jax.random.key(11)
    ref = np.asarray(S.sample(mps, 24, key))
    with api.SamplingSession(root, api.SamplerConfig(
            backend="streamed", segment_len=2, shard="auto")) as sess:
        plan = sess.plan(24)
        assert plan.shard_block == 2               # AUTO → segment_len
        out = sess.sample(24, key)
        info = sess.explain(24)
    assert np.array_equal(out, ref)
    assert info["shard"]["hosts"] == 1
    assert info["shard"]["sharded_bytes"] == 0     # nothing crosses a wire


def test_remote_backend_carries_shard_config(chain):
    # the serialized config rides to the loopback worker, which resolves
    # the degenerate 1-host shard against its own runtime
    root, mps = chain
    key = jax.random.key(13)
    ref = np.asarray(S.sample(mps, 16, key))
    with api.SamplingSession(root, api.SamplerConfig(
            backend="remote", segment_len=2, shard="auto")) as sess:
        plan = sess.plan(16)
        assert plan.backend == "remote" and plan.shard_block is None
        out = sess.sample(16, key)
    assert np.array_equal(out, ref)


def test_shard_wire_bytes_model():
    w = Workload(n_samples=1000, n_sites=100, chi=512, d=3)
    one = shard_wire_bytes(w, 1, block=10)
    assert one["broadcast_bytes"] == 0 and one["sharded_bytes"] == 0
    four = shard_wire_bytes(w, 4, block=10)
    eight = shard_wire_bytes(w, 8, block=10)
    # broadcast grows with host count; the sharded plane's handoff term
    # depends only on chain boundaries — O(chain), not O(hosts × chain)
    assert eight["broadcast_bytes"] == 7 * four["broadcast_bytes"] / 3
    assert four["handoff_bytes"] == eight["handoff_bytes"]
    assert four["handoff_bytes"] == 9 * 1000 * 512 * 8
    # large-χ regime: Γ broadcast dwarfs env handoff + sample gather
    assert four["sharded_bytes"] < four["broadcast_bytes"]


# ---------------------------------------------------------------------------
# Emulated cluster: sharded walk ≡ single-host unsharded walk
# ---------------------------------------------------------------------------

def _run_cluster(runtimes, make_config, source, n, key, resume=False):
    outs, stats, errs = {}, {}, []

    def run(rt):
        try:
            with api.SamplingSession(source, make_config(rt)) as sess:
                outs[rt.process_index] = sess.sample(n, key, resume=resume)
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


def test_sharded_2host_bitidentical_with_owned_only_io(chain):
    """The acceptance cell, fast shape: 2 emulated hosts, block-cyclic Γ,
    bit-identical to the unsharded local run — and the counters prove the
    data-plane claim: zero broadcast bytes, per-host store I/O exactly
    proportional to owned sites, only tiny env handoffs on the wire."""
    root, mps = chain
    key = jax.random.key(23)
    with api.SamplingSession(
            root, api.SamplerConfig(backend="streamed",
                                    segment_len=2)) as sess:
        ref = sess.sample(16, key)
        local_bytes = sess.stats["io_bytes"]
    assert np.array_equal(ref, np.asarray(S.sample(mps, 16, key)))

    outs, stats = _run_cluster(
        api.emulated_cluster(2),
        lambda rt: api.SamplerConfig(runtime=rt, backend="streamed",
                                     segment_len=2, shard="auto"),
        root, 16, key)
    assert np.array_equal(outs[0], ref)
    assert np.array_equal(outs[1], ref)
    # block=2, 10 sites → host0 owns {0,1,4,5,8,9}, host1 owns {2,3,6,7}
    assert stats[0]["io_bytes"] == local_bytes * 6 // 10
    assert stats[1]["io_bytes"] == local_bytes * 4 // 10
    assert stats[0]["io_bytes"] + stats[1]["io_bytes"] == local_bytes
    for p in (0, 1):
        assert stats[p]["broadcast_send_bytes"] == 0
        assert stats[p]["broadcast_recv_bytes"] == 0
        # 4 ownership boundaries, every one touches both hosts (send|recv)
        assert stats[p]["handoffs"] == 4
        assert stats[p]["handoff_send_bytes"] > 0
        assert stats[p]["handoff_recv_bytes"] > 0
        # the wire carried envs + the final sample gather — never Γ blocks
        wire = stats[p]["p2p_recv_bytes"]
        assert 0 < wire < local_bytes
    assert stats[0]["owned_segments"] == 3
    assert stats[1]["owned_segments"] == 2


def test_sharded_2host_dynamic_chi_bitidentical(chain):
    root, _ = chain
    key = jax.random.key(29)
    prof = (4, 4, 6, 6, 6, 6, 6, 6, 4, 4)
    with api.SamplingSession(root, api.SamplerConfig(
            backend="streamed", segment_len=2,
            chi_profile=prof)) as sess:
        ref = sess.sample(16, key)
    outs, stats = _run_cluster(
        api.emulated_cluster(2),
        lambda rt: api.SamplerConfig(runtime=rt, backend="streamed",
                                     segment_len=2, chi_profile=prof,
                                     shard="auto"),
        root, 16, key)
    assert np.array_equal(outs[0], ref)
    assert np.array_equal(outs[1], ref)
    assert stats[0]["broadcast_recv_bytes"] == 0
    assert stats[1]["broadcast_recv_bytes"] == 0


def test_shard_misaligned_chi_stage_rejected(chain):
    # a χ stage that splits a block mid-way yields a straddling segment —
    # caught at plan time by the proof against the REAL schedule
    root, _ = chain
    prof = (4,) * 3 + (6,) * 7                 # stage break at site 3
    with api.SamplingSession(root, api.SamplerConfig(
            backend="streamed", segment_len=2, chi_profile=prof,
            runtime=api.emulated_cluster(2)[0], shard=4)) as sess:
        with pytest.raises(ValueError, match="straddles"):
            sess.plan(16)


# ---------------------------------------------------------------------------
# Cluster-synchronized resume (satellite 1)
# ---------------------------------------------------------------------------

def test_broadcast_resume_agrees_on_min_boundary(chain, tmp_path):
    """Two processes stopped at DIFFERENT boundaries (site 6 vs site 4):
    the old engine refused multi-process resume outright; now the cluster
    agrees on min(newest) = 4 and both walk from there in lockstep,
    bit-identical to the uninterrupted run."""
    root, _ = chain
    key = jax.random.key(31)
    with api.SamplingSession(root, api.SamplerConfig(
            backend="streamed", segment_len=2)) as sess:
        ref = sess.sample(16, key)

    dirs = [str(tmp_path / "ck0"), str(tmp_path / "ck1")]
    for d, stop in zip(dirs, (3, 2)):          # newest site 6 vs site 4
        with api.SamplingSession(root, api.SamplerConfig(
                backend="streamed", segment_len=2, checkpoint_dir=d,
                checkpoint_every=1)) as sess:
            sess.sample(16, key, stop_after_segments=stop)

    outs, _ = _run_cluster(
        api.emulated_cluster(2),
        lambda rt: api.SamplerConfig(runtime=rt, backend="streamed",
                                     segment_len=2, checkpoint_every=1,
                                     checkpoint_dir=dirs[rt.process_index]),
        root, 16, key, resume=True)
    assert np.array_equal(outs[0], ref)
    assert np.array_equal(outs[1], ref)


def test_sharded_resume_from_agreed_boundary(chain, tmp_path):
    """Sharded crash consistency: truncate the two hosts' checkpoint dirs
    to different prefixes (an unclean stop), resume — the cluster agrees
    on the min boundary, reloads durable blocks below it, and the rest of
    the walk (including re-handoffs) reproduces the reference exactly."""
    root, _ = chain
    key = jax.random.key(37)
    with api.SamplingSession(root, api.SamplerConfig(
            backend="streamed", segment_len=2)) as sess:
        ref = sess.sample(16, key)

    dirs = [str(tmp_path / "sh0"), str(tmp_path / "sh1")]
    mk = lambda rt: api.SamplerConfig(   # noqa: E731
        runtime=rt, backend="streamed", segment_len=2, shard="auto",
        checkpoint_every=1, checkpoint_dir=dirs[rt.process_index])
    outs, _ = _run_cluster(api.emulated_cluster(2), mk, root, 16, key)
    assert np.array_equal(outs[0], ref)

    # unclean stop: host0 durable through site 4, host1 through site 6
    for d, keep_to in zip(dirs, (4, 6)):
        for f in os.listdir(d):
            site = int(f.split("_")[1].split(".")[0])
            if site > keep_to:
                os.remove(os.path.join(d, f))

    outs, stats = _run_cluster(api.emulated_cluster(2), mk, root, 16, key,
                               resume=True)
    assert np.array_equal(outs[0], ref)
    assert np.array_equal(outs[1], ref)
    # agreed boundary 4 → segments (4,6),(8,10) recompute on host0,
    # (6,8) on host1; blocks below 4 came off disk
    assert stats[0]["owned_segments"] == 2
    assert stats[1]["owned_segments"] == 1


def test_sharded_rejects_stop_after_segments(chain):
    root, _ = chain
    runtimes = api.emulated_cluster(2)
    errs = []

    def run(rt):
        try:
            with api.SamplingSession(root, api.SamplerConfig(
                    runtime=rt, backend="streamed", segment_len=2,
                    shard="auto")) as sess:
                sess.sample(16, jax.random.key(1), stop_after_segments=1)
        except ValueError as e:
            errs.append(str(e))

    threads = [threading.Thread(target=run, args=(rt,)) for rt in runtimes]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert len(errs) == 2 and all("kill" in e for e in errs)


# ---------------------------------------------------------------------------
# 4-host {seq, dp} × {static, dynamic-χ} matrix (subprocess, 8 devices)
# ---------------------------------------------------------------------------

_SHARD_CHILD = textwrap.dedent("""
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
    prof = (4, 4, 8, 8, 8, 8, 4, 4)

    out = {}
    for scheme in ("seq", "dp"):
        mesh = make_host_mesh(model=1) if scheme == "dp" else None
        for kind, chi_profile in (("static", None), ("dynamic", prof)):
            cfg = dict(backend="streamed", scheme=scheme, segment_len=2,
                       chi_profile=chi_profile)
            with api.SamplingSession(root, api.SamplerConfig(**cfg),
                                     mesh=mesh) as sess:
                ref = sess.sample(64, key)
                local_bytes = sess.stats["io_bytes"]

            res, stats, errs = {}, {}, []

            def run(rt):
                try:
                    c = api.SamplerConfig(runtime=rt, shard="auto", **cfg)
                    with api.SamplingSession(root, c, mesh=mesh) as sess:
                        res[rt.process_index] = sess.sample(64, key)
                        stats[rt.process_index] = dict(sess.stats)
                except Exception as e:
                    errs.append(repr(e))

            ts = [threading.Thread(target=run, args=(rt,))
                  for rt in api.emulated_cluster(4, timeout=300.0)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=500)
            cell = f"{scheme}_{kind}"
            out[cell + "_errs"] = errs
            out[cell + "_identical"] = bool(all(
                np.array_equal(res.get(p), ref) for p in range(4)))
            # owned-only Γ I/O: 8 sites / block 2 / 4 hosts → one block
            # each; zero broadcast; sum of reads covers the chain once
            out[cell + "_owned_io"] = bool(
                all(stats[p]["io_bytes"] == local_bytes // 4
                    and stats[p]["broadcast_recv_bytes"] == 0
                    and stats[p]["broadcast_send_bytes"] == 0
                    and stats[p]["owned_segments"] == 1
                    for p in range(4))
                and sum(stats[p]["io_bytes"] for p in range(4))
                == local_bytes)
            # the wire carried envs + sample gather, not Γ: each host's
            # p2p traffic stays well under its share of the Γ bytes
            out[cell + "_wire_o_chain"] = bool(all(
                0 < stats[p]["p2p_recv_bytes"] < local_bytes
                for p in range(4)))
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def shard_matrix_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SHARD_CHILD], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("cell", [
    f"{s}_{k}_{w}" for s in ("seq", "dp") for k in ("static", "dynamic")
    for w in ("identical", "owned_io", "wire_o_chain")])
def test_shard_matrix_4host(shard_matrix_results, cell):
    """Acceptance: emulated 4-host sharded run ≡ single-host unsharded
    across {seq, dp} × {static, dynamic-χ}, with counters proving no host
    read or received a foreign Γ segment."""
    scheme_kind = cell.rsplit("_", 1)[0] if cell.endswith("identical") \
        else cell[: cell.index("_", cell.index("_") + 1)]
    assert shard_matrix_results[scheme_kind + "_errs"] == []
    assert shard_matrix_results[cell]


# ---------------------------------------------------------------------------
# SIGKILL chaos: reclaimed sharded walk is bit-identical (satellite 3)
# ---------------------------------------------------------------------------

_CHAOS_COMMON = textwrap.dedent("""
    import os, sys, threading
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.core import mps as M
    from repro.data.gamma_store import GammaStore

    root, ck0, ck1 = sys.argv[1], sys.argv[2], sys.argv[3]
    m = M.random_linear_mps(jax.random.key(0), 12, 6, 3)
    key = jax.random.key(41)
    if not os.path.exists(os.path.join(root, "site_000000.npz")):
        with GammaStore(root, storage_dtype=jnp.float64,
                        compute_dtype=jnp.float64) as st:
            st.write_mps(m)

    def run_cluster(resume):
        outs, errs = {}, []
        dirs = [ck0, ck1]

        def run(rt):
            try:
                cfg = api.SamplerConfig(
                    runtime=rt, backend="streamed", segment_len=2,
                    shard="auto", checkpoint_every=1,
                    checkpoint_dir=dirs[rt.process_index])
                with api.SamplingSession(root, cfg) as sess:
                    outs[rt.process_index] = sess.sample(32, key,
                                                         resume=resume)
            except Exception as e:
                errs.append(repr(e))
        ts = [threading.Thread(target=run, args=(rt,))
              for rt in api.emulated_cluster(2, timeout=120.0)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        assert not errs, errs
        return outs
""")

_CHAOS_KILL = _CHAOS_COMMON + textwrap.dedent("""
    import signal, time
    from repro.engine import streaming

    # slow each segment down so the SIGKILL provably lands mid-walk
    _orig = streaming.StreamingEngine._run_segment

    def _slow(self, *a, **k):
        time.sleep(0.25)
        return _orig(self, *a, **k)
    streaming.StreamingEngine._run_segment = _slow

    def watchdog():
        while True:
            done = [f for d in (ck0, ck1) if os.path.isdir(d)
                    for f in os.listdir(d) if f.startswith("site_")]
            if len(done) >= 3:                 # mid-walk, both hosts live
                os.kill(os.getpid(), signal.SIGKILL)
            time.sleep(0.01)
    threading.Thread(target=watchdog, daemon=True).start()
    run_cluster(resume=False)
    print("SURVIVED")                          # must be unreachable
""")

_CHAOS_RESUME = _CHAOS_COMMON + textwrap.dedent("""
    import json
    from repro.core import sampler as S
    ref = np.asarray(S.sample(m, 32, key))
    outs = run_cluster(resume=True)
    print(json.dumps({
        "match0": bool(np.array_equal(outs[0], ref)),
        "match1": bool(np.array_equal(outs[1], ref)),
    }))
""")


@pytest.mark.slow
def test_sharded_sigkill_resume_bitidentical(tmp_path):
    """Chaos acceptance: SIGKILL the whole emulated cluster mid-walk (both
    hosts' checkpoints at whatever boundary they reached), then resume —
    the cluster-min agreement reclaims the walk and the samples are
    bit-identical to an uninterrupted single-host run."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    args = [str(tmp_path / "store"), str(tmp_path / "ck0"),
            str(tmp_path / "ck1")]
    proc = subprocess.run([sys.executable, "-c", _CHAOS_KILL] + args,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == -signal.SIGKILL, (proc.returncode,
                                                proc.stdout, proc.stderr)
    assert "SURVIVED" not in proc.stdout
    # the kill landed mid-walk: some but not all boundaries are durable
    ck_files = [f for d in args[1:] for f in os.listdir(d)
                if f.startswith("site_")]
    assert ck_files, "kill fired before any checkpoint was written"

    proc = subprocess.run([sys.executable, "-c", _CHAOS_RESUME] + args,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["match0"] and out["match1"]
