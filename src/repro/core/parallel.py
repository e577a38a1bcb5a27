"""Multi-level parallel MPS sampling (paper §3.1–3.2) + the [19] baseline.

Mesh layout (shared with the LM stack, see launch/mesh.py):

    ("data", "model")            single pod, p = p₁ × p₂
    ("pod", "data", "model")     multi-pod; "pod" is folded into data parallel

* **Data parallel** (§3.1): samples are independent; each of the p₁ data
  groups owns N/p₁ samples and walks the full chain.  Γ is replicated
  (broadcast from the loader — in-XLA this is the implicit all-gather of a
  fully-replicated operand; the host-side streaming version lives in
  ``data/gamma_store.py``).

* **Tensor parallel** (§3.2): within a group, Γᵢ and the environment are
  split along the bond axis χ over p₂ workers.

  - ``single``-site: split-K GEMM over the *left* bond; measurement is
    computed from partial probabilities (a tiny ``psum`` of (N₂, d)) *before*
    the big collective, so the wire carries the measured (N₂, χ) environment
    — a factor d smaller — via ``psum_scatter``.  Bandwidth-optimal.
    (Valid because Alg. 1 is linear in the environment; for ``born``
    semantics this is invalid — |Σ·|² ≠ Σ|·|² — so we fall back to
    ``psum_scatter`` of the unmeasured (N₂, χ, d) + tiny psum of partial
    square-weights.)
  - ``double``-site: one ``psum`` (AllReduce) of the unmeasured (N₂, χ, d)
    every *two* sites.  The even site's Γ is split along the *right* bond, so
    its GEMM is communication-free and leaves the environment pre-sliced for
    the next odd site.  Half the collective count → latency-optimal; odd-site
    measurement is replicated (the η=1 vs η=p₂ trade of Eq. 7).

All schemes draw identical randoms within a TP group (the key is replicated
over "model"), so DP and both TP schedules produce bit-identical samples for
the same seed — asserted in tests.

This module is the *data plane*.  The only application front door is
:class:`repro.api.SamplingSession` — the deprecation-shimmed legacy entry
points (``multilevel_sample`` / ``dp_sample`` / ``baseline19_sample``)
were removed one release after the facade shipped, as scheduled; the
internal ``_multilevel_sample`` / ``_baseline19_sample`` /
``sample_segment`` callables below are what the registered backends route
through.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.mps import MPS
from repro.core import precision
from repro.core.sampler import SamplerConfig, draw_from_probs
from repro.kernels import dispatch
from repro.kernels.site_impls import contract_parallel, measure_probs_xla

Array = jax.Array


def _env_dtype(gamma_dtype):
    """Environments accumulate across sites — keep them ≥ fp32 even when Γ
    is stored low-precision (§3.3.2: storage ≠ compute precision)."""
    return (jnp.float32 if gamma_dtype in (jnp.bfloat16, jnp.float16)
            else gamma_dtype)


def _contract(env: Array, gamma: Array, config: SamplerConfig) -> Array:
    """temp[n,r,s] = Σ_l env[n,l] Γ[l,r,s] under the configured precision
    (one shared implementation with the dispatched xla cells)."""
    return contract_parallel(env, gamma, config.compute_dtype)


_measure = measure_probs_xla


def _tp_rescale(env: Array, mode: str, axis: Optional[str] = None
                ) -> tuple[Array, Array]:
    """Adaptive rescale of a (possibly bond-sharded) environment.

    Mirrors ``precision.rescale`` with the max taken across the TP group
    (``pmax`` over ``axis``) when the environment is sharded, so every shard
    divides by the same factor.  Returns (env', per-sample log10 factor) —
    the same diagnostic the in-memory path accumulates in
    ``SamplerState.log_scale``.
    """
    rdt = precision.real_dtype_of(env.dtype)
    n = env.shape[0]
    if mode == "none":
        return env, jnp.zeros((n,), dtype=rdt)
    a = jnp.abs(env)
    if mode == "per_sample":
        m = jnp.max(a, axis=1, keepdims=True)
        if axis is not None:
            m = jax.lax.pmax(m, axis)
        factor = jnp.where(m > 0, m, 1.0).astype(rdt)
        return env / factor, jnp.log10(factor[:, 0])
    if mode == "global":
        m = jnp.max(a)
        if axis is not None:
            m = jax.lax.pmax(m, axis)
        factor = jnp.where(m > 0, m, 1.0).astype(rdt)
        return env / factor, jnp.broadcast_to(jnp.log10(factor), (n,))
    raise ValueError(f"unknown scaling mode: {mode}")


# ---------------------------------------------------------------------------
# Tensor parallel — single-site (ReduceScatter) schedule
# ---------------------------------------------------------------------------

def _tp_single_site_step(env, gamma_l, lam, key, config, axis,
                         wire_dtype=None):
    """One site with env (N, χ/p₂) and Γ sharded on the left bond.

    Returns (new sharded env, per-sample log10 rescale factor, samples).
    """
    semantics = config.semantics
    dtype = env.dtype
    if semantics == "linear":
        # contract + partial measure in one dispatched op (the Pallas cell
        # fuses them so the partial temp makes one HBM pass, not two), then
        # measure-before-communicate: tiny psum of (N, d) partial probs
        cm = dispatch.get_site_op("contract_measure", semantics,
                                  config.kernels)
        temp_partial, probs_partial = cm(env, gamma_l, lam,
                                         semantics=semantics,
                                         compute_dtype=config.compute_dtype)
        probs = jax.lax.psum(probs_partial, axis)
        samples = draw_from_probs(probs, key)
        collapsed = jnp.take_along_axis(
            temp_partial, samples[:, None, None], axis=2)[:, :, 0]  # (N, χ) partial
        if wire_dtype is not None:
            collapsed = collapsed.astype(wire_dtype)
        env_new = jax.lax.psum_scatter(
            collapsed, axis, scatter_dimension=1, tiled=True)       # (N, χ/p₂)
        env_new = env_new.astype(dtype)
    else:
        # born: must sum split-K partials before squaring (|Σ·|² ≠ Σ|·|², so
        # there is no valid fused-measure cell here — stays XLA by design).
        temp_partial = _contract(env, gamma_l, config)    # (N, χ, d) partial
        temp = jax.lax.psum_scatter(temp_partial, axis,
                                    scatter_dimension=1, tiled=True)  # (N, χ/p₂, d)
        p2 = jax.lax.axis_size(axis)
        idx = jax.lax.axis_index(axis)
        lam_shard = jax.lax.dynamic_slice_in_dim(
            lam, idx * (lam.shape[0] // p2), lam.shape[0] // p2)
        probs = jax.lax.psum(_measure(temp, lam_shard, semantics), axis)
        samples = draw_from_probs(probs, key)
        env_new = jnp.take_along_axis(
            temp, samples[:, None, None], axis=2)[:, :, 0] * lam_shard[None, :]
    # per-sample rescale: the max must be consistent across the TP group
    env_new, dlog = _tp_rescale(env_new, config.scaling, axis)
    return env_new, dlog, samples


def _tp_single_site_step_measure_first(env, gamma_l, w_l, key, config, axis,
                                       wire_dtype=None):
    """tp-3: probs from the tiny env@W GEMM; collapse via select-GEMM.

    env (N, χ/p₂) sharded; gamma_l (χ/p₂, χ, d); w_l (χ/p₂, d).  Both ops
    are dispatched: the Pallas cells are ``kernels/site_step.measure_probs``
    and ``kernels/collapse_select.collapse_select`` (masked operand
    VMEM-resident — the (N, χ, d) temp never exists anywhere).
    """
    dtype = env.dtype
    measure_op = dispatch.get_site_op("measure", "linear", config.kernels)
    collapse_op = dispatch.get_site_op("collapse", "linear", config.kernels)
    probs = jax.lax.psum(
        measure_op(env, w_l, compute_dtype=config.compute_dtype)
        .astype(dtype), axis)
    samples = draw_from_probs(probs, key)
    collapsed = collapse_op(env, gamma_l, samples,
                            compute_dtype=config.compute_dtype)  # (N, χ)
    if wire_dtype is not None:
        collapsed = collapsed.astype(wire_dtype)
    env_new = jax.lax.psum_scatter(
        collapsed, axis, scatter_dimension=1, tiled=True).astype(dtype)
    env_new, dlog = _tp_rescale(env_new, config.scaling, axis)
    return env_new, dlog, samples


# ---------------------------------------------------------------------------
# Tensor parallel — double-site (AllReduce) schedule
# ---------------------------------------------------------------------------

def _tp_double_site_pair(env, gamma_odd_l, lam_odd, gamma_even_r, lam_even,
                         key_pair, config, axis, wire_dtype=None):
    """Two sites per round: AllReduce once, even site communication-free."""
    semantics = config.semantics
    k_odd, k_even = key_pair
    fused = (dispatch.resolve_kernels(config.kernels) == "pallas"
             and semantics == "linear")

    # --- odd site: split-K over left bond, AllReduce the unmeasured temp ----
    if fused and wire_dtype is None:
        # Pallas cell: partial probs come out of the contraction's output
        # tiles (one HBM pass over the partial temp instead of two); the
        # measurement linearity makes psum-of-partial-measures ≡ measure-of-
        # psum, and the extra (N, d) psum is noise next to the (N, χ, d) one.
        # With a wire_dtype the XLA reference measures the *wire-rounded*
        # psummed temp, which partial measures cannot reproduce — that cell
        # keeps the reference structure below so pallas ≡ xla stays exact.
        cm = dispatch.get_site_op("contract_measure", semantics,
                                  config.kernels)
        temp, probs_partial = cm(env, gamma_odd_l, lam_odd,
                                 semantics=semantics,
                                 compute_dtype=config.compute_dtype)
        temp = jax.lax.psum(temp, axis).astype(env.dtype)   # (N, χ, d) full
        probs = jax.lax.psum(probs_partial, axis)
    else:
        temp = _contract(env, gamma_odd_l, config)
        if wire_dtype is not None:
            temp = temp.astype(wire_dtype)
        temp = jax.lax.psum(temp, axis).astype(env.dtype)   # (N, χ, d) full
        probs = _measure(temp, lam_odd, semantics)      # replicated (η overhead)
    samples_odd = draw_from_probs(probs, k_odd)
    env_full = jnp.take_along_axis(temp, samples_odd[:, None, None], axis=2)[:, :, 0]
    if semantics == "born":
        env_full = env_full * lam_odd[None, :]
    # full (replicated) environment: every shard computes the same max
    env_full, dlog_odd = _tp_rescale(env_full, config.scaling)

    # --- even site: Γ split on the right bond; local GEMM, no collective ----
    p2 = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    lam_shard = jax.lax.dynamic_slice_in_dim(
        lam_even, idx * (lam_even.shape[0] // p2), lam_even.shape[0] // p2)
    if fused:
        cm = dispatch.get_site_op("contract_measure", semantics,
                                  config.kernels)
        temp_loc, probs_partial = cm(env_full, gamma_even_r, lam_shard,
                                     semantics=semantics,
                                     compute_dtype=config.compute_dtype)
        probs = jax.lax.psum(probs_partial, axis)          # tiny (N, d)
    else:
        temp_loc = _contract(env_full, gamma_even_r, config)  # (N, χ/p₂, d)
        probs = jax.lax.psum(_measure(temp_loc, lam_shard, semantics),
                             axis)                         # tiny
    samples_even = draw_from_probs(probs, k_even)
    env_new = jnp.take_along_axis(temp_loc, samples_even[:, None, None], axis=2)[:, :, 0]
    if semantics == "born":
        env_new = env_new * lam_shard[None, :]
    env_new, dlog_even = _tp_rescale(env_new, config.scaling, axis)
    return env_new, dlog_odd + dlog_even, (samples_odd, samples_even)


# ---------------------------------------------------------------------------
# Top-level multi-level sampler
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    scheme: str = "dp"                 # "dp" | "tp_single" | "tp_double" | "baseline19"
    data_axes: tuple[str, ...] = ("data",)
    model_axis: str = "model"
    # §3.3.2 extended to the TP wire (beyond-paper, §Perf iteration tp-2):
    # cast the collapsed environment to this dtype before the big collective.
    # bf16 keeps fp32's exponent range, so with per-sample scaling the wire
    # cast cannot under/overflow — it only rounds the 8-bit mantissa.
    wire_dtype: Optional[jnp.dtype] = None
    # measure-first reformulation (beyond-paper, §Perf iteration tp-3):
    # probs = env @ (Γ·Λ) by associativity of Alg. 1, so the (N, χ, d)
    # unmeasured temp is never materialized; the collapse becomes a
    # sample-selected GEMM (kernels/collapse_select.py keeps the masked
    # operand VMEM-resident on TPU; the XLA fallback loops over the d
    # outcomes with a per-sample row mask).  Linear semantics only.
    measure_first: bool = False
    # §3.1 micro batching N₂ *per data shard*: the chain walk runs over
    # n_local/N₂ chunks with chunk keys split(shard_key, n_micro) — the
    # exact ``sampler.sample_batched`` schedule — so the (N₂, χ, d)
    # unmeasured intermediate is bounded under every DP/TP placement.
    micro_batch: Optional[int] = None


def _multilevel_sample(mesh: Mesh, mps: MPS, n_samples: int, key: Array,
                       pconfig: ParallelConfig = ParallelConfig(),
                       config: SamplerConfig = SamplerConfig()) -> Array:
    """DP over samples × TP over χ.  Returns (N, M) outcomes.

    The data plane is the segment runner below, run over the whole chain as
    one segment — an in-memory call and a streamed walk therefore share one
    code path (and one jit cache entry per shape).
    """
    if pconfig.scheme == "baseline19":
        return _baseline19_sample(mesh, mps, n_samples, key, config,
                                  pipeline_axis=pconfig.data_axes[-1])
    if pconfig.scheme not in ("dp", "tp_single", "tp_double"):
        raise ValueError(f"unknown scheme {pconfig.scheme!r}")
    env = segment_env_init(n_samples, mps.chi, mps.gammas.dtype)
    samples, _, _ = sample_segment(mesh, mps, env, key, 0, pconfig, config)
    return samples.T


# ---------------------------------------------------------------------------
# Segment runner (the shared DP×TP data plane, paper §3.1 + §3.3.2)
#
# This entry point runs ONE contiguous segment of the chain under any DP×TP
# placement, carrying the full (N, χ) left environment and the per-sample
# ``log_scale`` diagnostic between calls.  ``_multilevel_sample`` is the
# whole chain as a single segment; the streaming engine walks fixed-size
# segments through the same callable.  All PRNG draws use
# fold_in(base_key, global_site) — per micro chunk when
# ``pconfig.micro_batch`` is set, with chunk keys split(shard_key, n_micro)
# exactly as ``sampler.sample_batched`` — so a segmented walk is
# bit-identical to the corresponding single-shot schedule.  ``start_site``
# is a traced operand and the jitted shard_map callable is cached per
# (mesh, pconfig, config), so every equally-shaped segment reuses one
# compilation regardless of its chain offset (and a dynamic-χ walk costs
# one compilation per χ bucket).
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _segment_callable(mesh: Mesh, pconfig: ParallelConfig,
                      config: SamplerConfig):
    """Build the cached shard_map program for one segment of the chain.

    Key data (not typed key arrays) crosses the shard_map boundary, as in
    ``baseline19_sample``.
    """
    from repro.core import sampler as S

    d_axes, m_axis = pconfig.data_axes, pconfig.model_axis
    n2 = pconfig.micro_batch

    def _with_micro(chain_fn, base, env_l, ls_l, L):
        """§3.1 micro batching under any placement: run the shard's batch
        through ``chain_fn`` whole, or as n_local/N₂ chunks with chunk keys
        split(shard_key, n_micro) — the ``sampler.sample_batched`` schedule,
        so DP/TP micro-batched walks match the in-memory batched sampler
        draw-for-draw."""
        if n2 is None:
            return chain_fn(base, env_l, ls_l)
        n_loc = env_l.shape[0]
        n_micro = n_loc // n2
        keys_c = jax.random.split(base, n_micro)

        def one(xs):
            k, e, ls = xs
            return chain_fn(k, e, ls)

        smp, env_o, ls_o = jax.lax.map(
            one, (keys_c, env_l.reshape(n_micro, n2, -1),
                  ls_l.reshape(n_micro, n2)))
        samples = jnp.transpose(smp, (1, 0, 2)).reshape(L, n_loc)
        return samples, env_o.reshape(n_loc, -1), ls_o.reshape(n_loc)

    if pconfig.scheme == "dp":

        def shard_fn(keys_local, env_l, ls_l, gammas, lambdas, start_r):
            base = jax.random.wrap_key_data(keys_local[0].astype(jnp.uint32))
            L = gammas.shape[0]
            sites = start_r + jnp.arange(L, dtype=jnp.int32)

            def chain(k, e, ls):
                def body(carry, xs):
                    g, lam, i = xs
                    st, (smp, _) = S.site_step(
                        S.SamplerState(carry[0], k, carry[1]),
                        (g, lam, i), config)
                    return (st.env, st.log_scale), smp

                (env_out, ls_out), samples = jax.lax.scan(
                    body, (e, ls), (gammas, lambdas, sites))
                return samples, env_out, ls_out   # (L, n), (n, χ), (n,)

            return _with_micro(chain, base, env_l, ls_l, L)

        return jax.jit(jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(d_axes), P(d_axes), P(d_axes), P(), P(), P()),
            out_specs=(P(None, d_axes), P(d_axes), P(d_axes)),
            check_vma=False,
        ))

    if pconfig.scheme == "tp_single":
        measure_first = (pconfig.measure_first
                         and config.semantics == "linear")

        def shard_fn(keys_local, env_l, ls_l, gammas_l, lambdas, start_r):
            base = jax.random.wrap_key_data(keys_local[0].astype(jnp.uint32))
            L = gammas_l.shape[0]
            sites = start_r + jnp.arange(L, dtype=jnp.int32)

            if measure_first:
                # per-site measure-first operator W — identical per-site
                # arithmetic to the default schedule's probs, so the tp-3
                # path stays bit-identical when segmented or micro-batched
                w_l = jnp.einsum("mlrs,mr->mls",
                                 gammas_l.astype(jnp.float32),
                                 lambdas.astype(jnp.float32))

                def chain(k, e, ls):
                    def body(carry, xs):
                        g, w, i = xs
                        env_c, dlog, smp = _tp_single_site_step_measure_first(
                            carry[0], g, w, jax.random.fold_in(k, i), config,
                            m_axis, wire_dtype=pconfig.wire_dtype)
                        return (env_c, carry[1] + dlog), smp

                    (env_out, ls_out), samples = jax.lax.scan(
                        body, (e, ls), (gammas_l, w_l, sites))
                    return samples, env_out, ls_out
            else:
                def chain(k, e, ls):
                    def body(carry, xs):
                        g, lam, i = xs
                        env_c, dlog, smp = _tp_single_site_step(
                            carry[0], g, lam, jax.random.fold_in(k, i),
                            config, m_axis, wire_dtype=pconfig.wire_dtype)
                        return (env_c, carry[1] + dlog), smp

                    (env_out, ls_out), samples = jax.lax.scan(
                        body, (e, ls), (gammas_l, lambdas, sites))
                    return samples, env_out, ls_out

            return _with_micro(chain, base, env_l, ls_l, L)

        return jax.jit(jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(d_axes), P(d_axes, m_axis), P(d_axes),
                      P(None, m_axis, None, None), P(), P()),
            out_specs=(P(None, d_axes), P(d_axes, m_axis), P(d_axes)),
            check_vma=False,
        ))

    if pconfig.scheme == "tp_double":

        def shard_fn(keys_local, env_l, ls_l, godd_l, lamo, geven_r, lame,
                     start_r):
            base = jax.random.wrap_key_data(keys_local[0].astype(jnp.uint32))
            n_pairs = godd_l.shape[0]

            def chain(k, e, ls):
                def body(carry, xs):
                    go, lo, ge, le, j = xs
                    kp = (jax.random.fold_in(k, start_r + 2 * j),
                          jax.random.fold_in(k, start_r + 2 * j + 1))
                    env_c, dlog, (so, se) = _tp_double_site_pair(
                        carry[0], go, lo, ge, le, kp, config, m_axis,
                        wire_dtype=pconfig.wire_dtype)
                    return (env_c, carry[1] + dlog), jnp.stack([so, se])

                (env_out, ls_out), samples = jax.lax.scan(
                    body, (e, ls),
                    (godd_l, lamo, geven_r, lame,
                     jnp.arange(n_pairs, dtype=jnp.int32)))
                return (samples.reshape(2 * n_pairs, e.shape[0]),
                        env_out, ls_out)

            return _with_micro(chain, base, env_l, ls_l, 2 * n_pairs)

        return jax.jit(jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(d_axes), P(d_axes, m_axis), P(d_axes),
                      P(None, m_axis, None, None), P(),
                      P(None, None, m_axis, None), P(), P()),
            out_specs=(P(None, d_axes), P(d_axes, m_axis), P(d_axes)),
            check_vma=False,
        ))

    raise ValueError(f"segment runner has no scheme {pconfig.scheme!r}")


def sample_segment(mesh: Mesh, mps: MPS, env: Array, key: Array,
                   start_site: Array | int,
                   pconfig: ParallelConfig = ParallelConfig(),
                   config: SamplerConfig = SamplerConfig(),
                   log_scale: Optional[Array] = None
                   ) -> tuple[Array, Array, Array]:
    """Run sites [start, start+L) of the chain from a full environment.

    mps holds only the segment's L site tensors; returns
    (samples (L, N) int32 site-major, env' (N, χ), log_scale' (N,)).
    ``log_scale`` is the accumulated per-sample log10 rescale factor —
    diagnostic parity with the in-memory ``SamplerState.log_scale``;
    ``None`` starts the carry at zero.
    """
    d_axes, m_axis = pconfig.data_axes, pconfig.model_axis
    p1 = 1
    for ax in d_axes:
        p1 *= mesh.shape[ax]
    n_samples, chi = env.shape
    assert n_samples % p1 == 0, (n_samples, p1)
    if pconfig.scheme != "dp":
        p2 = mesh.shape[m_axis]
        assert chi % p2 == 0, (chi, p2)
    if pconfig.micro_batch is not None:
        assert (n_samples // p1) % pconfig.micro_batch == 0, \
            (n_samples, p1, pconfig.micro_batch)
    if log_scale is None:
        log_scale = jnp.zeros((n_samples,),
                              dtype=precision.real_dtype_of(env.dtype))
    start = jnp.asarray(start_site, dtype=jnp.int32)
    dp_keys = jax.random.key_data(jax.random.split(key, p1))  # (p1, key_size)
    f = _segment_callable(mesh, pconfig, config)

    if pconfig.scheme in ("dp", "tp_single"):
        return f(dp_keys, env, log_scale, mps.gammas, mps.lambdas, start)
    if pconfig.scheme == "tp_double":
        assert mps.n_sites % 2 == 0, \
            "double-site segments need an even site count"
        return f(dp_keys, env, log_scale, mps.gammas[0::2], mps.lambdas[0::2],
                 mps.gammas[1::2], mps.lambdas[1::2], start)
    raise ValueError(f"segment runner has no scheme {pconfig.scheme!r}")


def segment_env_init(n_samples: int, chi: int, gamma_dtype) -> Array:
    """Boundary environment for site 0: one-hot row 0, full (unsharded) view.
    TP shards slice it — shard 0 holds the hot column, others zeros —
    matching ``_multilevel_sample``'s per-shard initialisation exactly."""
    env = jnp.zeros((n_samples, chi), dtype=_env_dtype(gamma_dtype))
    return env.at[:, 0].set(1.0)


# ---------------------------------------------------------------------------
# Baseline [19]: one worker per site, macro-batch pipeline over a ring
# ---------------------------------------------------------------------------

def _baseline19_sample(mesh: Mesh, mps: MPS, n_samples: int, key: Array,
                       config: SamplerConfig = SamplerConfig(),
                       pipeline_axis: str = "data",
                       n_macro: Optional[int] = None) -> Array:
    """The model-parallel scheme of [19] (Fig. 2), for comparison benches.

    p processes = M sites (p must equal M here).  The left environment of
    each macro batch flows down a ``ppermute`` chain; at time step t, worker i
    processes macro batch (t − i).  Total steps = n₁ + M − 1 (the pipeline
    fill the paper criticises).  Emitted samples: worker i produces site i's
    outcomes for every macro batch.
    """
    p = mesh.shape[pipeline_axis]
    M = mps.n_sites
    assert p == M, f"[19] binds one process per site (p={p}, M={M})"
    n1 = n_macro or config_macro_batches(n_samples)
    assert n_samples % n1 == 0, (n_samples, n1)
    N1 = n_samples // n1
    semantics = mps.semantics

    # One base key per macro batch; worker i draws with fold_in(base_b, i) —
    # the same (batch, site) schedule as the data-parallel sampler, so [19]
    # and FastMPS produce identical samples from the same seed.
    base_keys = jax.random.key_data(jax.random.split(key, n1))  # (n1, key_size)
    base_keys = jnp.broadcast_to(base_keys[:, None, :],
                                 (n1, M, base_keys.shape[-1]))

    def shard_fn(gamma, lam, keys_batch):
        # gamma (1, χ, χ, d) local site tensor; keys_batch (n1, 1, key_size)
        gamma = gamma[0]
        lam = lam[0]
        i = jax.lax.axis_index(pipeline_axis)
        T = n1 + M - 1
        chi = gamma.shape[0]
        dt = gamma.dtype

        # ring buffer: env of whichever macro batch currently sits here
        env0 = jnp.zeros((N1, chi), dt).at[:, 0].set(1.0)

        def step(carry, t):
            env_in = carry
            b = t - i                      # macro batch index at this worker
            active = (b >= 0) & (b < n1)
            kb = jax.random.fold_in(
                jax.random.wrap_key_data(
                    keys_batch[jnp.clip(b, 0, n1 - 1), 0].astype(jnp.uint32)),
                i)
            temp = jnp.einsum("nl,lrs->nrs", env_in, gamma)
            probs = _measure(temp, lam, semantics)
            s = draw_from_probs(probs, kb)
            env_out = jnp.take_along_axis(temp, s[:, None, None], axis=2)[:, :, 0]
            if semantics == "born":
                env_out = env_out * lam[None, :]
            m = jnp.max(jnp.abs(env_out), axis=1, keepdims=True)
            env_out = env_out / jnp.where(m > 0, m, 1.0)
            s = jnp.where(active, s, -1)
            # fresh batches enter at worker 0
            fresh = jnp.zeros((N1, chi), dt).at[:, 0].set(1.0)
            send = jnp.where(active, env_out, env_in)
            nxt = jax.lax.ppermute(send, pipeline_axis,
                                   [(j, (j + 1) % M) for j in range(M)])
            nxt = jnp.where(i == 0, fresh, nxt)
            return nxt, s

        _, emitted = jax.lax.scan(step, env0, jnp.arange(T))
        # emitted (T, N1): site-i outcomes of batch b are at t = b + i
        rows = jnp.arange(n1) + i
        return emitted[rows][None]          # (1, n1, N1)

    f = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(pipeline_axis), P(pipeline_axis), P(None, pipeline_axis)),
        out_specs=P(pipeline_axis), check_vma=False,
    )
    out = f(mps.gammas, mps.lambdas, base_keys)  # (M, n1, N1)
    return out.transpose(1, 2, 0).reshape(n_samples, M)


def config_macro_batches(n_samples: int, target: int = 4) -> int:
    """n₁: number of macro batches (kept small for the CPU test harness)."""
    for n1 in range(target, 0, -1):
        if n_samples % n1 == 0:
            return n1
    return 1
