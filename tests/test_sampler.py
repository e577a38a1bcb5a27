"""Sampler correctness against the exact enumeration oracle (paper Fig.1 + Alg.1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mps as M
from repro.core import sampler as S


def _tv_distance(samples: np.ndarray, probs: np.ndarray, d: int) -> float:
    n, m = samples.shape
    idx = np.ravel_multi_index(samples.T, (d,) * m)
    emp = np.bincount(idx, minlength=d ** m) / n
    return 0.5 * np.abs(emp - probs).sum()


@pytest.mark.parametrize("semantics,chi,m,d", [
    ("linear", 4, 5, 3),
    ("linear", 8, 4, 2),
    ("born", 4, 4, 2),
    ("born", 3, 3, 3),
])
def test_sampler_matches_enumeration(semantics, chi, m, d):
    key = jax.random.key(42)
    if semantics == "linear":
        mps = M.random_linear_mps(key, m, chi, d)
    else:
        mps = M.random_born_mps(key, m, chi, d)
    probs = M.enumerate_probabilities(mps)
    n = 40_000
    out = S.sample(mps, n, jax.random.key(1), S.SamplerConfig(semantics=semantics))
    tv = _tv_distance(np.asarray(out), probs, d)
    # TV of empirical vs truth concentrates ~ sqrt(K/N); bound loosely.
    assert tv < 4.0 * np.sqrt(d ** m / n), tv


def test_sampler_deterministic_per_seed(linear_mps_small):
    mps = linear_mps_small
    a = S.sample(mps, 100, jax.random.key(5))
    b = S.sample(mps, 100, jax.random.key(5))
    c = S.sample(mps, 100, jax.random.key(6))
    assert jnp.all(a == b)
    assert not jnp.all(a == c)


def test_micro_batching_equals_memory_model():
    """sample_batched must produce valid outcomes with the Eq.(3) layout."""
    mps = M.random_linear_mps(jax.random.key(2), 5, 4, 3)
    out = S.sample_batched(mps, 64, jax.random.key(3), micro_batch=16)
    assert out.shape == (64, 5)
    assert int(out.min()) >= 0 and int(out.max()) < 3


def test_draw_from_probs_inverse_cdf():
    probs = jnp.array([[0.5, 0.5, 0.0], [0.0, 0.0, 1.0], [1.0, 0.0, 0.0]])
    out = S.draw_from_probs(jnp.tile(probs, (100, 1)), jax.random.key(0))
    out = out.reshape(100, 3)
    assert jnp.all(out[:, 1] == 2)          # deterministic rows
    assert jnp.all(out[:, 2] == 0)
    assert jnp.all((out[:, 0] == 0) | (out[:, 0] == 1))


def test_draw_from_probs_underflow_guard():
    """Fully-underflowed rows (the Fig. 6 failure) fall back to uniform."""
    probs = jnp.zeros((512, 4))
    out = S.draw_from_probs(probs, jax.random.key(0))
    counts = np.bincount(np.asarray(out), minlength=4)
    assert counts.min() > 0                  # all outcomes occur


def test_mixed_precision_path_close_to_fp64():
    mps = M.random_linear_mps(jax.random.key(7), 6, 8, 3)
    cfg64 = S.SamplerConfig()
    cfg_mx = S.SamplerConfig(compute_dtype=jnp.bfloat16)
    # identical seeds: outcome sequences should agree for the vast majority
    # of draws (bf16 GEMM perturbs probabilities only slightly)
    a = S.sample(mps.astype(jnp.float32), 2000, jax.random.key(8), cfg64)
    b = S.sample(mps.astype(jnp.float32), 2000, jax.random.key(8), cfg_mx)
    agree = float(jnp.mean((a == b).astype(jnp.float32)))
    assert agree > 0.95, agree


def test_resume_mid_chain_exact(linear_mps_10x6):
    """Paper §4.1 seed-consistency: mid-chain restart reproduces the full run."""
    mps = linear_mps_10x6
    cfg = S.SamplerConfig()
    state0 = S.init_state(mps, 32, jax.random.key(1), cfg)
    full = S.sample_chain(mps, state0, cfg)

    head = M.MPS(mps.gammas[:3], mps.lambdas[:3], mps.semantics)
    part = S.sample_chain(head, state0, cfg)
    rest = S.sample_resumable(mps, part.state, 3, cfg)
    stitched = jnp.concatenate([part.samples, rest.samples], axis=0)
    assert jnp.all(stitched == full.samples)


def test_site_stats_shape(linear_mps_small):
    mps = linear_mps_small
    state = S.init_state(mps, 16, jax.random.key(1))
    res = S.sample_chain(mps, state)
    assert res.site_stats.shape == (6, 3)
    assert bool(jnp.all(jnp.isfinite(res.site_stats)))


def test_draw_running_sum_matches_cumsum_reference():
    """The running-sum draw (shared by the fused kernels and the XLA path)
    picks the same outcome as normalise → cumsum → threshold."""
    from repro.kernels.site_impls import draw_from_uniform
    k1, k2 = jax.random.split(jax.random.key(5))
    probs = jax.random.uniform(k1, (512, 4), dtype=jnp.float64)
    probs = probs.at[:8].set(0.0)                 # underflowed rows
    u = jax.random.uniform(k2, (512, 1), dtype=jnp.float64)
    total = probs.sum(axis=1, keepdims=True)
    safe = jnp.where(total > 0, probs / jnp.where(total > 0, total, 1.0),
                     0.25)
    want = jnp.sum(u > jnp.cumsum(safe, axis=1), axis=1).clip(0, 3)
    got = draw_from_uniform(probs, u)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("semantics", ["linear", "born"])
def test_prefix_marginals_match_enumeration(semantics):
    """The batched-prefix oracle (what the chip smoke run checks against at
    χ = 10⁴) equals the enumeration oracle, and bond padding is exact."""
    if semantics == "linear":
        m = M.gbs_like_mps(jax.random.key(3), 4, 6, 3)
    else:
        m = M.random_born_mps(jax.random.key(4), 3, 5, 2)
    exact = M.exact_site_marginals(m)
    got = M.prefix_marginals(zip(m.gammas, m.lambdas), semantics)
    np.testing.assert_allclose(got, exact, atol=1e-12)
    padded = M.pad_bond(m, 8)
    assert padded.chi == 8
    np.testing.assert_allclose(M.exact_site_marginals(padded), exact,
                               atol=1e-12)
