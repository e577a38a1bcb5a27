"""Clamped (conditional) chain walks — the workloads-subsystem data plane.

A clamp fixes the outcome of a subset of sites (``repro.workloads.clamp``
spec, carried on the session config as ``SamplerConfig.clamp``).  The
walk here is the plain Alg. 1 schedule with one twist at each site::

    samples = where(mask_i, forced_outcome, inverse_cdf_draw)

— the forced outcome goes into the *existing* collapse path (a collapse
is "apply a selected outcome"; clamping just selects it for the sampler),
so the environment after a clamped site is exactly the conditional
environment.  Because each site's uniform comes from ``fold_in(key, i)``
independently of every other site, forcing site i leaves all other
draws untouched: a clamped run IS the unclamped run conditioned on the
clamped branch, rejection-free.

The walk additionally accumulates the clamped branch's Born weight,

    log_prob[n] = Σ_{i ∈ clamp} ln P(s_i = clamp_i | s_{<i})

(natural log; the unclamped sites contribute nothing).  ``w = exp(
log_prob)`` is the exact probability of the clamped outcomes given each
sample's prefix, which makes the self-normalized estimator

    P(s_j = x | clamp) ≈ Σ_n w_n · 1{s_j^n = x} / Σ_n w_n

an exact conditional-marginal estimator for every unclamped site j (and
``mean(w)`` an unbiased estimate of the clamp's marginal probability).

Two placements, mirroring ``core/parallel.py``:

- :func:`clamped_segment` — the seq/in-memory segment (with §3.1 micro
  batching via the ``sample_batched`` chunk-key schedule);
- :func:`sample_segment_clamped` — the DP shard_map segment, a clone of
  the unclamped dp cell with (mask, vals) as extra traced operands and
  ``log_prob`` as an extra sharded carry.  TP schemes route through this
  dp walk over the mesh's non-model axes (the repo's §4.1 contract makes
  every schedule draw-identical per seed, so there is nothing a clamped
  tp cell would compute differently — see ``api/backends.py``).

The site body is the reference XLA arithmetic (``contract_parallel`` /
``measure_probs_xla`` / ``draw_from_uniform`` — the same cells the
dispatched ops reduce to); ``kernels="pallas"`` plans fall back to it
when clamped, like born-TP measurement does by design.

An *empty* clamp never reaches this module: ``normalize_clamp`` turns it
into ``None`` and None-clamp plans run the unchanged unclamped paths —
empty-clamp bit-identity holds by construction, not by test luck (though
the tests assert it anyway).
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import precision
from repro.core.mps import MPS
from repro.core.parallel import ParallelConfig, _tp_rescale
from repro.core.sampler import SamplerConfig, init_state
from repro.kernels.site_impls import (contract_parallel, draw_from_uniform,
                                      measure_probs_xla, site_probs_dtype)

Array = jax.Array


def _clamped_site_update(env, gamma, lam, u, mask_i, vals_i,
                         config: SamplerConfig):
    """One site: contract → measure → (draw | force) → collapse → rescale.

    Returns ``(env', samples, dlog_scale, dlog_prob)`` where ``dlog_prob``
    is ``ln P(s_i | s_{<i})`` for clamped sites and 0 elsewhere.
    """
    temp = contract_parallel(env, gamma, config.compute_dtype)  # (N, χ, d)
    probs = measure_probs_xla(temp, lam, config.semantics)      # (N, d) ≥ 0
    drawn = draw_from_uniform(probs, u)
    samples = jnp.where(mask_i, vals_i, drawn)
    env_new = jnp.take_along_axis(
        temp, samples[:, None, None], axis=2)[:, :, 0]
    if config.semantics == "born":
        env_new = env_new * lam[None, :]
    env_new, dlog = _tp_rescale(env_new, config.scaling)

    rdt = precision.real_dtype_of(env.dtype)
    total = jnp.sum(probs, axis=1).astype(rdt)
    psel = jnp.take_along_axis(probs, samples[:, None],
                               axis=1)[:, 0].astype(rdt)
    cond = jnp.clip(psel / total, jnp.finfo(rdt).tiny)
    dlogp = jnp.where(mask_i, jnp.log(cond), jnp.zeros((), dtype=rdt))
    return env_new, samples, dlog, dlogp


def _chain_scan(gammas, lambdas, env, key, log_scale, log_prob, mask, vals,
                config: SamplerConfig, start_site):
    """Scan sites [start, start+L): the clamped twin of ``sample_chain``.

    Draws site i's uniform from ``fold_in(key, i)`` with the dispatch
    layer's dtype rule — the clamped walk consumes the same PRNG stream
    as every unclamped schedule.
    """
    L = gammas.shape[0]
    sites = (jnp.asarray(start_site, dtype=jnp.int32)
             + jnp.arange(L, dtype=jnp.int32))

    def body(carry, xs):
        e, ls, lp = carry
        g, lam, i, m, v = xs
        sub = jax.random.fold_in(key, i)
        u = jax.random.uniform(
            sub, (e.shape[0], 1),
            dtype=site_probs_dtype(e, g, lam, config.semantics,
                                   config.compute_dtype))
        e2, smp, dlog, dlogp = _clamped_site_update(e, g, lam, u, m, v,
                                                    config)
        return (e2, ls + dlog, lp + dlogp.astype(lp.dtype)), smp

    (env, ls, lp), samples = jax.lax.scan(
        body, (env, log_scale, log_prob),
        (gammas, lambdas, sites, mask, vals))
    return samples, env, ls, lp


@partial(jax.jit, static_argnames=("config",))
def _chain_whole(gammas, lambdas, env, key, log_scale, log_prob, mask, vals,
                 config: SamplerConfig, start_site=0):
    return _chain_scan(gammas, lambdas, env, key, log_scale, log_prob,
                       mask, vals, config, start_site)


@partial(jax.jit, static_argnames=("config", "n_micro"))
def _chain_micro(gammas, lambdas, env, key, log_scale, log_prob, mask, vals,
                 config: SamplerConfig, n_micro: int, start_site=0):
    """§3.1 micro batching: chunk keys ``split(key, n_micro)`` — the exact
    ``sampler.sample_batched`` schedule, clamped."""
    L, n = vals.shape
    n2 = n // n_micro
    chi = env.shape[1]
    keys = jax.random.split(key, n_micro)
    vals_c = jnp.transpose(vals.reshape(L, n_micro, n2), (1, 0, 2))

    def one(xs):
        k, e, ls, lp, v = xs
        return _chain_scan(gammas, lambdas, e, k, ls, lp, mask, v,
                           config, start_site)

    smp, env_o, ls_o, lp_o = jax.lax.map(
        one, (keys, env.reshape(n_micro, n2, chi),
              log_scale.reshape(n_micro, n2),
              log_prob.reshape(n_micro, n2), vals_c))
    samples = jnp.transpose(smp, (1, 0, 2)).reshape(L, n)
    return (samples, env_o.reshape(n, chi), ls_o.reshape(n),
            lp_o.reshape(n))


def clamped_segment(gammas, lambdas, env, key, start_site, mask, vals,
                    config: SamplerConfig,
                    log_scale: Optional[Array] = None,
                    log_prob: Optional[Array] = None,
                    micro_batch: Optional[int] = None):
    """Run one clamped seq segment from a full (N, χ) environment.

    ``mask (L,) bool`` / ``vals (L, N) int32`` come from
    ``workloads.clamp.segment_clamp_arrays``.  Returns
    ``(samples (L, N), env', log_scale', log_prob')``.
    """
    n = env.shape[0]
    rdt = precision.real_dtype_of(env.dtype)
    if log_scale is None:
        log_scale = jnp.zeros((n,), dtype=rdt)
    if log_prob is None:
        log_prob = jnp.zeros((n,), dtype=rdt)
    mask = jnp.asarray(mask, dtype=bool)
    vals = jnp.asarray(vals, dtype=jnp.int32)
    start = jnp.asarray(start_site, dtype=jnp.int32)
    if micro_batch is not None:
        # chunk even when n_micro == 1: the chunk key is split(key, 1)[0],
        # not key — the sample_batched schedule, kept draw-for-draw
        assert n % micro_batch == 0, (n, micro_batch)
        return _chain_micro(gammas, lambdas, env, key, log_scale, log_prob,
                            mask, vals, config, n // micro_batch, start)
    return _chain_whole(gammas, lambdas, env, key, log_scale, log_prob,
                        mask, vals, config, start)


def sample_clamped(mps: MPS, n_samples: int, key: Array,
                   config: SamplerConfig, mask, vals,
                   micro_batch: Optional[int] = None
                   ) -> tuple[Array, Array]:
    """Whole-chain clamped walk.  Returns ``(samples (N, M), log_prob (N,))``."""
    state = init_state(mps, n_samples, key, config)
    samples, _, _, log_prob = clamped_segment(
        mps.gammas, mps.lambdas, state.env, state.key, 0, mask, vals,
        config, log_scale=state.log_scale, micro_batch=micro_batch)
    return samples.T, log_prob


# ---------------------------------------------------------------------------
# DP segment runner — the clamped clone of parallel._segment_callable's dp
# cell: (mask, vals) ride as traced operands (vals sample-sharded alongside
# the environment), log_prob as a fourth sharded carry.
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _clamped_segment_callable(mesh: Mesh, pconfig: ParallelConfig,
                              config: SamplerConfig):
    d_axes = pconfig.data_axes
    n2 = pconfig.micro_batch

    def shard_fn(keys_local, env_l, ls_l, lp_l, gammas, lambdas, mask,
                 vals_l, start_r):
        base = jax.random.wrap_key_data(keys_local[0].astype(jnp.uint32))
        L = gammas.shape[0]
        n_loc = env_l.shape[0]

        def chain(k, e, ls, lp, v):
            return _chain_scan(gammas, lambdas, e, k, ls, lp, mask, v,
                               config, start_r)

        if n2 is None:
            return chain(base, env_l, ls_l, lp_l, vals_l)
        n_micro = n_loc // n2
        keys_c = jax.random.split(base, n_micro)
        vals_c = jnp.transpose(vals_l.reshape(L, n_micro, n2), (1, 0, 2))

        def one(xs):
            k, e, ls, lp, v = xs
            return chain(k, e, ls, lp, v)

        smp, env_o, ls_o, lp_o = jax.lax.map(
            one, (keys_c, env_l.reshape(n_micro, n2, -1),
                  ls_l.reshape(n_micro, n2), lp_l.reshape(n_micro, n2),
                  vals_c))
        samples = jnp.transpose(smp, (1, 0, 2)).reshape(L, n_loc)
        return (samples, env_o.reshape(n_loc, -1), ls_o.reshape(n_loc),
                lp_o.reshape(n_loc))

    return jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(d_axes), P(d_axes), P(d_axes), P(d_axes), P(), P(),
                  P(), P(None, d_axes), P()),
        out_specs=(P(None, d_axes), P(d_axes), P(d_axes), P(d_axes)),
        check_vma=False,
    ))


def sample_segment_clamped(mesh: Mesh, mps: MPS, env: Array, key: Array,
                           start_site, mask, vals,
                           pconfig: ParallelConfig,
                           config: SamplerConfig,
                           log_scale: Optional[Array] = None,
                           log_prob: Optional[Array] = None
                           ) -> tuple[Array, Array, Array, Array]:
    """Clamped twin of ``parallel.sample_segment`` (dp placement only;
    backends route tp plans here over the mesh's non-model axes).

    Returns ``(samples (L, N), env', log_scale', log_prob')``.
    """
    assert pconfig.scheme == "dp", pconfig.scheme
    p1 = 1
    for ax in pconfig.data_axes:
        p1 *= mesh.shape[ax]
    n_samples = env.shape[0]
    assert n_samples % p1 == 0, (n_samples, p1)
    if pconfig.micro_batch is not None:
        assert (n_samples // p1) % pconfig.micro_batch == 0, \
            (n_samples, p1, pconfig.micro_batch)
    rdt = precision.real_dtype_of(env.dtype)
    if log_scale is None:
        log_scale = jnp.zeros((n_samples,), dtype=rdt)
    if log_prob is None:
        log_prob = jnp.zeros((n_samples,), dtype=rdt)
    mask = jnp.asarray(mask, dtype=bool)
    vals = jnp.asarray(vals, dtype=jnp.int32)
    start = jnp.asarray(start_site, dtype=jnp.int32)
    dp_keys = jax.random.key_data(jax.random.split(key, p1))
    f = _clamped_segment_callable(mesh, pconfig, config)
    return f(dp_keys, env, log_scale, log_prob, mps.gammas, mps.lambdas,
             mask, vals, start)


def dp_equivalent_pconfig(pconfig: ParallelConfig) -> ParallelConfig:
    """The dp placement a clamped tp plan routes through: batch sharded
    over the same data axes, model axis left replicated.  Valid because
    every schedule draws the same randoms per (shard, site) — §4.1 — so
    the clamped dp walk emits exactly what a clamped tp walk would."""
    if pconfig.scheme == "dp":
        return pconfig
    return ParallelConfig(scheme="dp", data_axes=pconfig.data_axes,
                          model_axis=pconfig.model_axis,
                          micro_batch=pconfig.micro_batch)


__all__ = ["clamped_segment", "dp_equivalent_pconfig", "sample_clamped",
           "sample_segment_clamped"]
