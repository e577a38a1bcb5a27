"""Registered implementations behind ``kernels/dispatch.py``.

Every stage has an ``xla`` implementation that is *literally the shipping
math* the data planes ran before the dispatch layer existed (moved here,
not rewritten — the ``kernels="xla"`` cell of every schedule must stay
bit-identical to the pre-dispatch code), plus a ``pallas`` implementation
routing to the fused kernels with autotuned block sizes
(``interpret=True`` off-TPU, so CI runs the same program the TPU compiles).

Uniform stage signatures (semantics is part of the registry key):

* ``site_step(env, gamma, lam, u, *, scaling, compute_dtype)``
  → ``(env', samples, dlog)``
* ``contract_measure(env, gamma, lam, *, compute_dtype)`` → ``(temp, probs)``
* ``measure(env, w, *, compute_dtype)`` → partial probs ``(N, d)``
* ``collapse(env, gamma, samples, *, compute_dtype)`` → ``env' (N, R)``
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import precision
from repro.kernels import collapse_select as CS
from repro.kernels import contract_measure as CM
from repro.kernels import site_step as SS
from repro.kernels.dispatch import autotune, on_tpu, register_site_op

Array = jax.Array


def draw_from_uniform(probs: Array, u: Array) -> Array:
    """Alg. 1 lines 2-4 given the per-sample uniforms: normalise, running
    sum, threshold draw — the same :func:`site_step.draw_columns` the fused
    kernels run.  probs (N, d) ≥ 0; u (N, 1) in [0, 1) → samples (N,)."""
    cols = [probs[:, s:s + 1] for s in range(probs.shape[1])]
    return SS.draw_columns(cols, u)[:, 0]


# ---------------------------------------------------------------------------
# site_step — the whole Alg. 1 pipeline for one site
# ---------------------------------------------------------------------------

def _contract_site(env: Array, gamma: Array, compute_dtype,
                   semantics: str) -> Array:
    """The contraction exactly as ``core/sampler.site_step`` ran it."""
    if compute_dtype is not None and semantics == "linear":
        return jax.lax.dot_general(
            env.astype(compute_dtype),
            gamma.reshape(gamma.shape[0], -1).astype(compute_dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(env.shape[0], gamma.shape[1],
                  gamma.shape[2]).astype(env.dtype)
    return jnp.einsum("nl,lrs->nrs", env, gamma)


def measure_probs_xla(temp: Array, lam: Array, semantics: str) -> Array:
    """Alg. 1 line 1 for either semantics (shared by sampler & parallel)."""
    if semantics == "linear":
        return jnp.einsum("nrs,r->ns", temp, lam)
    scaled = temp * lam[None, :, None]
    return jnp.sum(jnp.abs(scaled) ** 2, axis=1)


def site_probs_dtype(env: Array, gamma: Array, lam: Array, semantics: str,
                     compute_dtype) -> jnp.dtype:
    """The dtype the measurement probabilities (and hence the inverse-CDF
    uniforms) come out as — callers pre-draw ``u`` with exactly this dtype
    so the fused path consumes the same bits the XLA path would."""
    out = jax.eval_shape(
        lambda e, g, l: measure_probs_xla(
            _contract_site(e, g, compute_dtype, semantics), l, semantics),
        env, gamma, lam)
    return out.dtype


def _site_step_xla(env, gamma, lam, u, *, semantics, scaling, compute_dtype):
    temp = _contract_site(env, gamma, compute_dtype, semantics)
    probs = measure_probs_xla(temp, lam, semantics)
    samples = draw_from_uniform(probs, u)
    new_env = jnp.take_along_axis(
        temp, samples[:, None, None].astype(jnp.int32), axis=2)[:, :, 0]
    if semantics == "born":
        new_env = new_env * lam[None, :]
    new_env, dlog = precision.rescale(new_env, mode=scaling)
    return new_env, samples, dlog


@register_site_op("site_step", "linear", "xla")
def site_step_linear_xla(env, gamma, lam, u, *, scaling, compute_dtype):
    return _site_step_xla(env, gamma, lam, u, semantics="linear",
                          scaling=scaling, compute_dtype=compute_dtype)


@register_site_op("site_step", "born", "xla")
def site_step_born_xla(env, gamma, lam, u, *, scaling, compute_dtype):
    return _site_step_xla(env, gamma, lam, u, semantics="born",
                          scaling=scaling, compute_dtype=compute_dtype)


def _fused_blocks(stage, env, gamma, planes):
    n, chi_l = env.shape
    chi_r, d = gamma.shape[1], gamma.shape[2]
    return autotune(stage, n=n, chi_l=chi_l, chi_r=chi_r, d=d,
                    dtype=env.dtype, planes=planes)


@register_site_op("site_step", "linear", "pallas")
def site_step_linear_pallas(env, gamma, lam, u, *, scaling, compute_dtype):
    cfg = _fused_blocks("site_step", env, gamma, planes=1)
    fused_scaling = scaling if scaling in ("per_sample", "none") else "none"
    env2, samples, dlog = SS.site_step_linear(
        env, gamma, lam, u[:, 0], bn=cfg.bn, br=cfg.br, bl=cfg.bl,
        scaling=fused_scaling, compute_dtype=compute_dtype,
        interpret=not on_tpu())
    if scaling == "global":            # the global max crosses n-tiles
        env2, dlog = precision.rescale(env2, "global")
    return env2, samples, dlog


@register_site_op("site_step", "born", "pallas")
def site_step_born_pallas(env, gamma, lam, u, *, scaling, compute_dtype):
    del compute_dtype                  # born runs in the amplitudes' dtype
    cfg = _fused_blocks("site_step", env, gamma, planes=2)
    fused_scaling = scaling if scaling in ("per_sample", "none") else "none"
    env2, samples, dlog = SS.site_step_born(
        env, gamma, lam, u[:, 0], bn=cfg.bn, br=cfg.br, bl=cfg.bl,
        scaling=fused_scaling, interpret=not on_tpu())
    if scaling == "global":
        env2, dlog = precision.rescale(env2, "global")
    return env2, samples, dlog


# ---------------------------------------------------------------------------
# contract_measure — the split-K TP schedules' (temp, probs) pair
# ---------------------------------------------------------------------------

def contract_parallel(env: Array, gamma: Array, compute_dtype) -> Array:
    """The segment-runner contraction (compute_dtype applies to both
    semantics, unlike the seq-scan one above) — ``core/parallel._contract``
    delegates here so the dispatched xla cells and the born split-K paths
    share ONE implementation."""
    n = env.shape[0]
    r, d = gamma.shape[1], gamma.shape[2]
    if compute_dtype is not None:
        out = jax.lax.dot_general(
            env.astype(compute_dtype),
            gamma.reshape(gamma.shape[0], -1).astype(compute_dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(env.dtype)
        return out.reshape(n, r, d)
    return jnp.einsum("nl,lrs->nrs", env, gamma)


@register_site_op("contract_measure", "*", "xla")
def contract_measure_xla(env, gamma, lam, *, semantics, compute_dtype):
    temp = contract_parallel(env, gamma, compute_dtype)
    return temp, measure_probs_xla(temp, lam, semantics)


@register_site_op("contract_measure", "linear", "pallas")
def contract_measure_pallas(env, gamma, lam, *, semantics, compute_dtype):
    del semantics                      # registry key guarantees "linear"
    cfg = _fused_blocks("contract_measure", env, gamma, planes=1)
    e, g = env, gamma
    if compute_dtype is not None:
        e, g = env.astype(compute_dtype), gamma.astype(compute_dtype)
    temp, probs = CM.contract_measure(e, g, lam, bn=cfg.bn, br=cfg.br,
                                      bl=cfg.bl, interpret=not on_tpu())
    if temp.dtype != env.dtype and env.dtype not in (jnp.bfloat16,
                                                     jnp.float16):
        temp, probs = temp.astype(env.dtype), probs.astype(env.dtype)
    return temp, probs


# ---------------------------------------------------------------------------
# measure — the tp-3 measure-first partial probs (linear only)
# ---------------------------------------------------------------------------

@register_site_op("measure", "linear", "xla")
def measure_xla(env, w, *, compute_dtype):
    if compute_dtype is not None:
        return jax.lax.dot_general(
            env.astype(compute_dtype), w.astype(compute_dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.float32)
    return env @ w


@register_site_op("measure", "linear", "pallas")
def measure_pallas(env, w, *, compute_dtype):
    n, L = env.shape
    cfg = autotune("measure", n=n, chi_l=L, chi_r=L, d=w.shape[1],
                   dtype=env.dtype)
    out = SS.measure_probs(env, w, bn=cfg.bn, bl=cfg.bl,
                           compute_dtype=compute_dtype,
                           interpret=not on_tpu())
    if compute_dtype is not None:
        out = out.astype(jnp.float32)
    return out


# ---------------------------------------------------------------------------
# collapse — the sample-selected collapse GEMM (linear only)
# ---------------------------------------------------------------------------

@register_site_op("collapse", "linear", "xla")
def collapse_xla(env, gamma, samples, *, compute_dtype):
    """d masked GEMMs — the XLA analogue of the fused select."""
    d = gamma.shape[2]
    acc = None
    for s in range(d):
        mask = (samples == s).astype(env.dtype)[:, None]
        part = measure_xla(env * mask, gamma[:, :, s],
                           compute_dtype=compute_dtype)
        acc = part if acc is None else acc + part
    return acc


@register_site_op("collapse", "linear", "pallas")
def collapse_pallas(env, gamma, samples, *, compute_dtype):
    cfg = _fused_blocks("collapse", env, gamma, planes=1)
    e, g = env, gamma
    if compute_dtype is not None:
        e, g = env.astype(compute_dtype), gamma.astype(compute_dtype)
    return CS.collapse_select(e, g, samples, bn=cfg.bn, br=cfg.br,
                              bl=cfg.bl, interpret=not on_tpu())


# ---------------------------------------------------------------------------
# Autotuner warm-up (the timed TPU sweep must run OUTSIDE any jit trace)
# ---------------------------------------------------------------------------

def warm_site_step(n: int, chi: int, d: int, dtype, *, semantics: str,
                   scaling: str = "per_sample", compute_dtype=None) -> None:
    """Populate the autotuner cache for one site-step shape.

    Off-TPU this just seeds the heuristic entry (no compilation).  On TPU
    it runs the timed sweep with concrete zero operands, so the in-trace
    ``autotune`` lookups that follow are pure cache hits — which is why
    the session backends call this *before* jitting the chain walk.
    """
    planes = 2 if semantics == "born" else 1
    # the walk's env never carries a half-precision Γ storage dtype, and
    # the in-trace lookups key on the env — warm under that key
    env_dt = _env_dtype_of(dtype)
    rdt = jnp.zeros((), dtype=env_dt).real.dtype
    probe = None
    if on_tpu():
        env = jnp.zeros((n, chi), dtype=env_dt)
        gamma = jnp.zeros((chi, chi, d), dtype=dtype)
        lam = jnp.zeros((chi,), dtype=rdt)
        u = jnp.zeros((n,), dtype=rdt)
        kern = (SS.site_step_born if semantics == "born"
                else SS.site_step_linear)
        kw = {} if semantics == "born" else {"compute_dtype": compute_dtype}
        fused_scaling = (scaling if scaling in ("per_sample", "none")
                         else "none")

        def probe(cfg):
            return lambda: kern(env, gamma, lam, u, bn=cfg.bn, br=cfg.br,
                                bl=cfg.bl, scaling=fused_scaling, **kw)

    autotune("site_step", n=n, chi_l=chi, chi_r=chi, d=d, dtype=env_dt,
             planes=planes, probe=probe)


def _env_dtype_of(gamma_dtype):
    """The dtype the walk's environment carries (what the in-trace autotune
    lookups are keyed on): Γ storage may be half-precision, environments
    never are (§3.3.2 storage ≠ compute)."""
    dt = jnp.dtype(gamma_dtype)
    return jnp.float32 if dt in (jnp.bfloat16, jnp.float16) else dt


def warm_tp_stages(n: int, chi: int, d: int, dtype, *, p2: int, scheme: str,
                   measure_first: bool = False, compute_dtype=None) -> None:
    """Populate the autotuner cache for the sharded TP stage shapes.

    The TP schedules never run the fused ``site_step`` — their per-site
    work is the dispatched ``contract_measure`` / ``measure`` / ``collapse``
    stages over bond-sharded operands (χ/p₂ splits), so warming the
    seq/dp site-step shape alone leaves every TP lookup a cold miss (and on
    TPU the timed sweep cannot run inside the shard_map trace at all).
    Shapes mirror ``core/parallel`` exactly:

    * ``tp_single``        — contract_measure(env (N₂, χ/p₂), Γ (χ/p₂, χ, d))
    * ``tp_single`` + tp-3 — measure(env (N₂, χ/p₂), W (χ/p₂, d)) and
                             collapse(env (N₂, χ/p₂), Γ (χ/p₂, χ, d))
    * ``tp_double``        — the odd half-site's (χ/p₂ → χ) contract_measure
                             plus the even half-site's (χ → χ/p₂) one

    Linear semantics only: the Born split-K TP cells keep their XLA
    implementations by design (|Σ·|² ≠ Σ|·|²), so there is nothing to warm.
    """
    assert chi % p2 == 0, (chi, p2)
    env_dt = _env_dtype_of(dtype)
    chi_shard = chi // p2
    itp = not on_tpu()

    def _warm(stage, chi_l, chi_r, kern_probe):
        probe = None
        if on_tpu():
            env = jnp.zeros((n, chi_l), dtype=env_dt)

            def probe(cfg, _env=env, _chi_r=chi_r, _kp=kern_probe):
                return lambda: _kp(_env, _chi_r, cfg)
        autotune(stage, n=n, chi_l=chi_l, chi_r=chi_r, d=d, dtype=env_dt,
                 planes=1, probe=probe)

    def _cm(env, chi_r, cfg):
        gamma = jnp.zeros((env.shape[1], chi_r, d), dtype=env_dt)
        lam = jnp.zeros((chi_r,), dtype=env_dt)
        e, g = env, gamma
        if compute_dtype is not None:
            e, g = env.astype(compute_dtype), gamma.astype(compute_dtype)
        return CM.contract_measure(e, g, lam, bn=cfg.bn, br=cfg.br,
                                   bl=cfg.bl, interpret=itp)

    def _ms(env, chi_r, cfg):
        w = jnp.zeros((env.shape[1], d), dtype=env_dt)
        return SS.measure_probs(env, w, bn=cfg.bn, bl=cfg.bl,
                                compute_dtype=compute_dtype, interpret=itp)

    def _cl(env, chi_r, cfg):
        gamma = jnp.zeros((env.shape[1], chi_r, d), dtype=env_dt)
        samples = jnp.zeros((n,), dtype=jnp.int32)
        e, g = env, gamma
        if compute_dtype is not None:
            e, g = env.astype(compute_dtype), gamma.astype(compute_dtype)
        return CS.collapse_select(e, g, samples, bn=cfg.bn, br=cfg.br,
                                  bl=cfg.bl, interpret=itp)

    if scheme == "tp_single" and measure_first:
        _warm("measure", chi_shard, chi_shard, _ms)
        _warm("collapse", chi_shard, chi, _cl)
    elif scheme == "tp_single":
        _warm("contract_measure", chi_shard, chi, _cm)
    elif scheme == "tp_double":
        _warm("contract_measure", chi_shard, chi, _cm)   # odd half-site
        _warm("contract_measure", chi, chi_shard, _cm)   # even half-site
    else:
        raise ValueError(f"warm_tp_stages covers the TP schemes, "
                         f"got {scheme!r}")
