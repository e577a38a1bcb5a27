"""Property-based tests (hypothesis) for the system's invariants."""
import pytest

hypothesis = pytest.importorskip("hypothesis")
import hypothesis.extra.numpy as hnp
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dynamic_bond as DB
from repro.core import precision
from repro.core.sampler import draw_from_probs
from repro.optim import compression as C

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=25,
    suppress_health_check=[hypothesis.HealthCheck.too_slow])
hypothesis.settings.load_profile("ci")


@hypothesis.given(
    probs=hnp.arrays(np.float64, hnp.array_shapes(min_dims=2, max_dims=2,
                                                  min_side=1, max_side=16),
                     elements=st.floats(0, 1e6, allow_nan=False)),
    seed=st.integers(0, 2 ** 31 - 1),
)
def test_draw_in_range(probs, seed):
    out = draw_from_probs(jnp.asarray(probs), jax.random.key(seed))
    d = probs.shape[1]
    assert out.shape == (probs.shape[0],)
    assert np.all((np.asarray(out) >= 0) & (np.asarray(out) < d))


@hypothesis.given(
    scale_exp=st.lists(st.floats(-30, 30), min_size=1, max_size=8),
    seed=st.integers(0, 2 ** 31 - 1),
)
def test_draw_invariant_under_row_scaling(scale_exp, seed):
    """Alg.1 normalisation ⇒ the draw depends only on the *relative* probs
    per row — the foundation of per-sample scaling (§3.3)."""
    n = len(scale_exp)
    probs = np.asarray(jax.random.uniform(jax.random.key(1), (n, 4),
                                          dtype=jnp.float64)) + 1e-3
    scaled = probs * (10.0 ** np.asarray(scale_exp))[:, None]
    a = draw_from_probs(jnp.asarray(probs), jax.random.key(seed))
    b = draw_from_probs(jnp.asarray(scaled), jax.random.key(seed))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@hypothesis.given(
    env=hnp.arrays(np.float64,
                   hnp.array_shapes(min_dims=2, max_dims=2, min_side=1,
                                    max_side=32),
                   elements=st.one_of(
                       st.just(0.0),
                       st.floats(1e-100, 1e100),
                       st.floats(-1e100, -1e-100))),
    mode=st.sampled_from(["none", "global", "per_sample"]),
)
def test_rescale_invariants(env, mode):
    out, lg = precision.rescale(jnp.asarray(env), mode)
    assert out.shape == env.shape
    assert bool(jnp.all(jnp.isfinite(out)))
    if mode == "per_sample":
        m = np.max(np.abs(np.asarray(out)), axis=1)
        nz = np.max(np.abs(env), axis=1) > 0
        np.testing.assert_allclose(m[nz], 1.0, rtol=1e-12)
    # rescale must be exactly invertible through the log factor
    if mode != "none":
        back = np.asarray(out) * (10.0 ** np.asarray(lg))[:, None]
        ok = np.isfinite(back)
        np.testing.assert_allclose(back[ok], env[ok], rtol=1e-9, atol=1e-300)


@hypothesis.given(
    x=hnp.arrays(np.float32,
                 st.integers(1, 2000),
                 elements=st.floats(-1e4, 1e4, allow_nan=False, width=32)),
)
def test_int8_compression_bound(x):
    q, scale = C.int8_compress(jnp.asarray(x))
    y = np.asarray(C.int8_decompress(q, scale, x.shape, jnp.float32))
    pad = (-x.size) % C.BLOCK
    bound = np.repeat(np.asarray(scale) / 2, C.BLOCK)[: x.size] + 1e-6
    assert np.all(np.abs(y - x) <= bound)


@hypothesis.given(
    n=st.integers(3, 200),
    chi_max=st.integers(2, 512),
    photons=st.floats(0.05, 4.0),
)
def test_area_law_profile_properties(n, chi_max, photons):
    prof = DB.area_law_profile(n, chi_max, photons)
    assert prof.min() >= 1 and prof.max() <= chi_max
    mid = (n - 1) // 2          # bond i splits i+1 | n-1-i sites
    assert prof[0] <= prof[mid] and prof[-1] <= prof[mid]   # edge ≤ centre
    m = DB.table1_metrics(prof, chi_max)
    assert 0 < m["comp_ratio"] <= 1.0
    assert m["equiv_chi"] <= chi_max


@hypothesis.given(
    buckets=st.lists(st.integers(1, 100), min_size=1, max_size=5, unique=True),
    data=st.data(),
)
def test_bucketize_dominates(buckets, data):
    n = data.draw(st.integers(1, 50))
    prof = np.asarray(data.draw(st.lists(
        st.integers(1, max(buckets)), min_size=n, max_size=n)))
    buck = DB.bucketize(prof, buckets)
    assert np.all(buck >= prof)
    assert set(np.unique(buck)) <= set(buckets)


# ---------------------------------------------------------------------------
# WorkQueue invariants under arbitrary interleavings (fleet control plane)
# ---------------------------------------------------------------------------
# The op vocabulary and the invariant checker live in tests/chaos.py
# (run_queue_script), shared with the seeded-random storms in
# tests/test_fleet.py so the same engine runs with and without hypothesis.

_worker_ix = st.integers(0, 3)
_queue_op = st.one_of(
    st.tuples(st.just("add"), _worker_ix),
    st.tuples(st.just("remove"), _worker_ix),
    st.tuples(st.just("claim"), _worker_ix),
    st.tuples(st.just("complete"), _worker_ix),
    st.tuples(st.just("reclaim"), st.integers(0, 4)),
    st.tuples(st.just("tick")),
)


@hypothesis.given(n_batches=st.integers(1, 12),
                  ops=st.lists(_queue_op, max_size=150))
def test_workqueue_never_loses_never_double_counts(n_batches, ops):
    """Any interleaving of add/remove/claim/complete/reclaim_stale leaves
    every batch completable exactly once: no batch is ever lost, no
    completion is ever double-counted, and requeued work re-offers FIFO
    before fresh work (checked op-by-op inside the script runner)."""
    from chaos import run_queue_script

    out = run_queue_script(n_batches, ops)
    assert len(out["counted"]) == n_batches
    assert all(v == 1 for v in out["counted"].values())


@hypothesis.given(
    durations=st.lists(st.floats(1e-3, 1e3, allow_nan=False,
                                 allow_infinity=False),
                       min_size=1, max_size=32),
    k=st.floats(0.1, 10.0), alpha=st.floats(0.01, 1.0),
)
def test_straggler_ewma_bounded_by_observations(durations, k, alpha):
    """The EWMA (and so the reclaim deadline) always stays inside the
    [min, max] envelope of observed batch times, scaled by k — the
    deadline can never run away from the data."""
    from repro.runtime.elastic import WorkQueue
    from repro.runtime.stragglers import StragglerMitigator

    m = StragglerMitigator(WorkQueue(1), k=k, ewma_alpha=alpha)
    for d in durations:
        m.observe_completion(d)
    assert min(durations) <= m._ewma <= max(durations)
    assert m.deadline == pytest.approx(k * m._ewma)


# ---------------------------------------------------------------------------
# Chain-shard ownership algebra (repro.shard, ROADMAP item 3)
# ---------------------------------------------------------------------------

@hypothesis.given(
    n_sites=st.integers(1, 96), n_hosts=st.integers(1, 8),
    block=st.integers(1, 24),
)
def test_shard_ownership_partitions_chain(n_sites, n_hosts, block):
    """For ANY (n_sites, hosts, block), the hosts' owned-site sets
    partition the chain — every site is computed exactly once, the
    load-balance invariant the whole sharded walk rests on."""
    from repro.shard import ShardMap

    sm = ShardMap(n_sites=n_sites, n_hosts=n_hosts, block=block)
    owned = [sm.owned_sites(h) for h in range(n_hosts)]
    assert sorted(i for sites in owned for i in sites) == list(range(n_sites))
    for h, sites in enumerate(owned):
        assert all(sm.owner(i) == h for i in sites)
        # block-cyclic: a host's sites come in runs of ≤ block consecutive
        # (a lone host owns every block, so its runs merge into one)
        runs, prev = 1, None
        for i in sites:
            runs = runs + 1 if prev is not None and i == prev + 1 else 1
            assert runs <= (block if n_hosts > 1 else n_sites)
            prev = i


@hypothesis.given(
    segment_len=st.integers(1, 8), mult=st.integers(1, 4),
    n_sites=st.integers(1, 96), n_hosts=st.integers(1, 6),
)
def test_shard_handoffs_follow_chain_order(segment_len, mult, n_sites,
                                           n_hosts):
    """With the shard block a whole number of segments (the plan-time
    alignment rule), every scheduled segment has exactly one owner and the
    handoff sequence marches left→right: boundaries strictly increase,
    each transfer's src is the owner on the left of the boundary and its
    dst the owner on the right."""
    from repro.shard import ShardMap, chain_segments

    sm = ShardMap(n_sites=n_sites, n_hosts=n_hosts,
                  block=segment_len * mult)
    sched = chain_segments(n_sites, segment_len)
    assert [i for s, e, _ in sched for i in range(s, e)] == \
        list(range(n_sites))
    owners = sm.owners_for(sched)           # raises if any segment straddles
    hs = sm.handoffs(sched)
    assert len(hs) == sum(1 for a, b in zip(owners, owners[1:]) if a != b)
    prev_b = -1
    for b, src, dst in hs:
        assert b > prev_b
        prev_b = b
        assert src != dst
        assert sm.owner(b - 1) == src and sm.owner(b) == dst


@hypothesis.given(
    n_sites=st.integers(1, 64), segment_len=st.integers(1, 8),
    breaks=st.lists(st.integers(1, 63), max_size=4), seed=st.integers(0, 99),
)
def test_shard_chain_segments_cover_stages_exactly(n_sites, segment_len,
                                                   breaks, seed):
    """chain_segments tiles [0, n_sites) exactly once for any χ-stage
    split, and no segment crosses a stage boundary — the schedule shape
    the engine and the planner's shard proof must share."""
    from repro.shard import chain_segments

    cuts = sorted({b for b in breaks if b < n_sites})
    edges = [0] + cuts + [n_sites]
    rng = np.random.default_rng(seed)
    stages = [(a, b, int(rng.integers(2, 9)))
              for a, b in zip(edges, edges[1:])]
    sched = chain_segments(n_sites, segment_len, stages)
    assert [i for s, e, _ in sched for i in range(s, e)] == \
        list(range(n_sites))
    for s, e, chi in sched:
        assert e - s <= segment_len
        assert any(a <= s and e <= b and chi == c for a, b, c in stages)
