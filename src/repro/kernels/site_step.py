"""Fused MPS site-step pipeline — Pallas TPU kernels (§Perf iteration ks-4).

One site of Alg. 1 is contract → measure → normalise/running-sum/draw →
collapse(+λ) → per-sample rescale.  Run as separate XLA ops the unmeasured
``temp[N, χ, d]`` intermediate makes **three** HBM round trips per site
(write after the GEMM, read for the measurement, read again for the
collapse) — exactly the traffic ``bench_roofline.py`` models as the
memory-bound term at large χ.  These kernels keep ``temp`` VMEM-resident
for the whole pipeline: per n-tile the full ``(d, BN, χ_r)`` slab lives in
a VMEM scratch across the (r, l) tile sweep, the inverse-CDF draw and the
collapse happen on-chip, and only ``env'[N, χ_r]``, ``samples[N]`` and
``dlog[N]`` are ever written back — the ``(N, χ, d)`` intermediate never
touches HBM.

TPU layout.  XLA keeps a ``(χ_l, χ_r, d)`` Γ in HBM with the bond ``χ_r``
minor and ``d`` next (layout ``{1,2,0}``), i.e. physically ``(χ_l, d,
χ_r)``.  The wrappers hand the kernels ``swapaxes(Γ, 1, 2)`` — a bitcast,
no copy — so every Γ block is ``(BL, d, BR)`` and each outcome's
``(BL, BR)`` slice is lane-dense.  Per-sample vectors (u, samples, dlog)
travel as ``(N, 1)`` columns and λ as a ``(1, χ_r)`` row, so every block
is 2-D or lane-dense and aligned to the (8, 128) tiling.  Block sizes must
be multiples of (8, 128) or whole dimensions: a χ with no such divisor
(10⁴) is padded once at store time (``core.mps.pad_bond``), never here.

Kernels (all dispatched through ``kernels/dispatch.py``):

* :func:`site_step_linear` — the full fused pipeline, linear semantics
  (paper Alg. 1).  Grid ``(n_tiles, r_tiles, l_tiles)``, l innermost
  (sequential split-K on TPU); the draw/collapse/rescale epilogue runs once
  per n-tile on the last (r, l) program.
* :func:`site_step_born` — same pipeline for Born semantics.  Complex
  amplitudes ride as split re/im planes (the MXU has no complex type):
  two GEMMs per plane, ``probs = Σ_r (re² + im²)·λ²``, collapse ×λ, and
  the per-sample max over ``|env'| = √(re² + im²)``.
* :func:`measure_probs` — measure-only variant for the TP split-K
  schedules: the tp-3 ``probs_partial = env_shard @ W_shard`` GEMM whose
  (N, d) output is what crosses the wire *before* the big collective.
* the collapse-only variant is :func:`kernels.collapse_select.collapse_select`
  (sample-selected GEMM, masked operand VMEM-resident).

Randomness stays outside: the caller passes the per-site uniforms
``u[N]`` (drawn from the same folded key as the XLA path), and the draw is
:func:`draw_columns`, shared with the XLA path, so the fused path is
draw-for-draw identical to ``core/sampler.site_step`` — the §4.1 seed
contract extends across the kernel boundary and is asserted in
``tests/test_site_step.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

#: scoped VMEM the compiled kernels may use.  A v5e TensorCore has 128 MiB
#: of VMEM; the compiler's default scope (16 MiB) cannot hold the resident
#: temp slab at χ ≈ 10⁴, and the autotuner's working-set model
#: (``dispatch._VMEM_BUDGET_BYTES``) stays below this with headroom for the
#: compiler's own temporaries.
VMEM_LIMIT_BYTES = 100 * 2 ** 20
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)

#: block index 0 for the index maps, as int32: under x64 a Python literal
#: traces as int64, which Mosaic cannot return from an index map
I0 = np.int32(0)


def acc_dtype_for(dtype, interpret: bool) -> jnp.dtype:
    """Accumulator dtype of a kernel fed ``dtype`` operands.  Mosaic has no
    float64: a compiled kernel refuses it here, not deep in the lowering."""
    if jnp.dtype(dtype) == jnp.float64:
        if not interpret:
            raise TypeError(
                "float64 operands cannot run in a compiled Pallas TPU kernel "
                "(Mosaic has no f64) — use kernels='xla' or float32/bfloat16 "
                "operands")
        return jnp.float64
    return jnp.float32


def out_dtype_for(dtype) -> jnp.dtype:
    """Half-precision inputs produce float32 results (storage ≠ compute)."""
    return jnp.float32 if dtype in (jnp.bfloat16, jnp.float16) else dtype


def draw_columns(cols: list, u: Array) -> Array:
    """Alg. 1 lines 2-4 on per-outcome probability columns: clip → normalise
    → running-sum CDF → count the thresholds ``u`` exceeds.

    ``cols`` holds the d ≤ ~6 outcome columns (each shaped like ``u``).  An
    explicit running sum replaces ``cumsum`` (Mosaic cannot lower it), and
    the XLA path (``site_impls.draw_from_uniform``) calls this same
    function, so both draw the same outcome from the same bits."""
    d = len(cols)
    cols = [jnp.maximum(c, 0.0) for c in cols]
    total = cols[0]
    for c in cols[1:]:
        total = total + c
    # a fully-underflowed row falls back to uniform (paper Fig. 6 failure
    # mode — with per-sample scaling this should never trigger)
    ok = total > 0
    denom = jnp.where(ok, total, 1.0)
    # 1/d as a constant of the column dtype (under x64 a Python float
    # would be narrowed in-kernel, which Mosaic cannot lower)
    uniform = np.asarray(1.0 / d, dtype=total.dtype)
    cdf = None
    count = jnp.zeros(u.shape, jnp.int32)
    for c in cols:
        p = jnp.where(ok, c / denom, uniform)
        cdf = p if cdf is None else cdf + p
        count = count + (u > cdf).astype(jnp.int32)
    return jnp.minimum(count, d - 1)


def _select(slab_ref, samples: Array, d: int) -> Array:
    """slab (d, BN, χr) → slab[s_n, n, :] per row (exact selection)."""
    out = slab_ref[d - 1]
    for s in range(d - 2, -1, -1):
        out = jnp.where(samples == s, slab_ref[s], out)
    return out


def _rescale(mag: Array, scaling: str, dtype):
    """Per-sample §3.3 factor from ``mag`` = |env'| on full (BN, χr) rows.

    Returns (factor (BN, 1), dlog (BN, 1)).  ``scaling == "global"`` cannot
    be fused (the max crosses n-tiles) — the wrapper rejects it."""
    n = mag.shape[0]
    if scaling == "none":
        return jnp.ones((n, 1), dtype), jnp.zeros((n, 1), dtype)
    m = jnp.max(mag, axis=1, keepdims=True)
    factor = jnp.where(m > 0, m, 1.0)
    return factor, jnp.log10(factor)


def _check_scaling(scaling: str) -> None:
    if scaling not in ("per_sample", "none"):
        raise ValueError(f"fused site step cannot do scaling={scaling!r} "
                         "(the max crosses n-tiles); rescale outside")


def block_grid(n, chi_l, chi_r, bn, br, bl):
    """Clamp (bn, br, bl) to the operand and return them with the
    (n, r, l) grid; blocks must divide their dimension."""
    bn, br, bl = min(bn, n), min(br, chi_r), min(bl, chi_l)
    if n % bn or chi_r % br or chi_l % bl:
        raise ValueError(f"blocks (bn={bn}, br={br}, bl={bl}) must divide "
                         f"(N={n}, χr={chi_r}, χl={chi_l})")
    return bn, br, bl, (n // bn, chi_r // br, chi_l // bl)


def _gemm(a: Array, b: Array, acc_dtype) -> Array:
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=acc_dtype)


# ---------------------------------------------------------------------------
# Linear semantics: the paper-faithful Alg. 1 pipeline
# ---------------------------------------------------------------------------

def _linear_kernel(env_ref, gamma_ref, lam_ref, u_ref,
                   env_out_ref, samples_ref, dlog_ref,
                   slab_ref, acc_ref, probs_ref,
                   *, n_r: int, n_l: int, br: int, d: int,
                   scaling: str, out_dtype, compute_dtype):
    j = pl.program_id(1)      # r tile
    k = pl.program_id(2)      # l tile (sequential reduction)
    acc_dtype = acc_ref.dtype

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # GEMM inputs: compute_dtype when set, else the env's (a bf16-stored Γ
    # promotes, as in the XLA einsum)
    cdt = compute_dtype if compute_dtype is not None else env_ref.dtype
    env = env_ref[...].astype(cdt)                  # (BN, BL)
    for s in range(d):
        gam = gamma_ref[:, s, :].astype(cdt)        # (BL, BR), lane-dense
        acc_ref[s] += _gemm(env, gam, acc_dtype)

    @pl.when(k == n_l - 1)
    def _measured():
        lam = lam_ref[...].astype(acc_dtype)        # (1, BR)
        r0 = pl.multiple_of(j * br, br)
        for s in range(d):
            temp = acc_ref[s]                       # (BN, BR)
            # park this r tile of temp in the VMEM slab (never leaves the chip)
            slab_ref[s, :, pl.ds(r0, br)] = temp
            contrib = jnp.sum(temp * lam, axis=1, keepdims=True)

            @pl.when(j == 0)
            def _set():
                probs_ref[s] = contrib

            @pl.when(j > 0)
            def _add():
                probs_ref[s] += contrib

    @pl.when((j == n_r - 1) & (k == n_l - 1))
    def _epilogue():
        # whole-site state for this n tile is on-chip: draw, collapse, rescale
        samples = draw_columns(
            [probs_ref[s].astype(out_dtype) for s in range(d)], u_ref[...])
        env_new = _select(slab_ref, samples, d).astype(out_dtype)
        factor, dlog = _rescale(jnp.abs(env_new), scaling, out_dtype)
        env_out_ref[...] = env_new / factor
        samples_ref[...] = samples
        dlog_ref[...] = dlog.astype(dlog_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "br", "bl", "scaling",
                                             "compute_dtype", "interpret"))
def site_step_linear(env: Array, gamma: Array, lam: Array, u: Array,
                     bn: int = 256, br: int = 256, bl: int = 256,
                     scaling: str = "per_sample",
                     compute_dtype=None,
                     interpret: bool = False):
    """Fused site step: env (N, χl), Γ (χl, χr, d), Λ (χr), u (N,) →
    (env' (N, χr), samples (N,) int32, dlog (N,)).

    VMEM working set ≈ BN·BL + d·BL·BR + 2·d·BN·BR + **d·BN·χr** (the
    resident temp slab) + BN·χr words — the autotuner sizes BN so the slab
    fits; χr itself is never tiled out of VMEM, which is the whole point.
    """
    n, chi_l = env.shape
    _, chi_r, d = gamma.shape
    _check_scaling(scaling)
    bn, br, bl, grid = block_grid(n, chi_l, chi_r, bn, br, bl)
    out_dtype = out_dtype_for(env.dtype)
    acc_dtype = acc_dtype_for(env.dtype, interpret)

    kern = functools.partial(
        _linear_kernel, n_r=grid[1], n_l=grid[2], br=br, d=d,
        scaling=scaling, out_dtype=out_dtype, compute_dtype=compute_dtype)
    env_new, samples, dlog = pl.pallas_call(
        kern,
        name="site_step_linear",  # the HLO and trace op name
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bl), lambda i, j, k: (i, k)),
            pl.BlockSpec((bl, d, br), lambda i, j, k: (k, I0, j)),
            pl.BlockSpec((1, br), lambda i, j, k: (I0, j)),
            pl.BlockSpec((bn, 1), lambda i, j, k: (i, I0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, chi_r), lambda i, j, k: (i, I0)),
            pl.BlockSpec((bn, 1), lambda i, j, k: (i, I0)),
            pl.BlockSpec((bn, 1), lambda i, j, k: (i, I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, chi_r), out_dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), out_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, bn, chi_r), acc_dtype),    # the resident temp slab
            pltpu.VMEM((d, bn, br), acc_dtype),       # split-K accumulator
            pltpu.VMEM((d, bn, 1), acc_dtype),        # probs accumulator
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(env, jnp.swapaxes(gamma, 1, 2), lam.reshape(1, -1), u.reshape(-1, 1))
    return env_new, samples[:, 0], dlog[:, 0]


# ---------------------------------------------------------------------------
# Born semantics: complex amplitudes as split re/im planes
# ---------------------------------------------------------------------------

def _born_kernel(ere_ref, eim_ref, gre_ref, gim_ref, lam_ref, u_ref,
                 ore_ref, oim_ref, samples_ref, dlog_ref,
                 sre_ref, sim_ref, acc_re_ref, acc_im_ref, probs_ref,
                 *, n_r: int, n_l: int, br: int, d: int,
                 scaling: str, out_dtype):
    j = pl.program_id(1)
    k = pl.program_id(2)
    acc_dtype = acc_re_ref.dtype

    @pl.when(k == 0)
    def _init_acc():
        acc_re_ref[...] = jnp.zeros_like(acc_re_ref)
        acc_im_ref[...] = jnp.zeros_like(acc_im_ref)

    ere, eim = ere_ref[...], eim_ref[...]           # (BN, BL)
    for s in range(d):
        gre, gim = gre_ref[:, s, :], gim_ref[:, s, :]   # (BL, BR)
        # (ere + i·eim)(gre + i·gim): four real GEMMs per tile
        acc_re_ref[s] += (_gemm(ere, gre, acc_dtype)
                          - _gemm(eim, gim, acc_dtype))
        acc_im_ref[s] += (_gemm(ere, gim, acc_dtype)
                          + _gemm(eim, gre, acc_dtype))

    @pl.when(k == n_l - 1)
    def _measured():
        lam = lam_ref[...].astype(acc_dtype)        # (1, BR)
        r0 = pl.multiple_of(j * br, br)
        for s in range(d):
            # the slab holds temp·λ: it IS the measurement operand *and* the
            # born-collapsed environment (env' = temp[:, :, s]·λ), so no
            # second λ pass is needed in the epilogue
            sre = acc_re_ref[s] * lam
            sim = acc_im_ref[s] * lam
            sre_ref[s, :, pl.ds(r0, br)] = sre
            sim_ref[s, :, pl.ds(r0, br)] = sim
            contrib = jnp.sum(sre * sre + sim * sim, axis=1, keepdims=True)

            @pl.when(j == 0)
            def _set():
                probs_ref[s] = contrib

            @pl.when(j > 0)
            def _add():
                probs_ref[s] += contrib

    @pl.when((j == n_r - 1) & (k == n_l - 1))
    def _epilogue():
        samples = draw_columns(
            [probs_ref[s].astype(out_dtype) for s in range(d)], u_ref[...])
        ore = _select(sre_ref, samples, d).astype(out_dtype)
        oim = _select(sim_ref, samples, d).astype(out_dtype)
        factor, dlog = _rescale(jnp.sqrt(ore * ore + oim * oim), scaling,
                                out_dtype)
        ore_ref[...] = ore / factor
        oim_ref[...] = oim / factor
        samples_ref[...] = samples
        dlog_ref[...] = dlog.astype(dlog_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "br", "bl", "scaling",
                                             "interpret"))
def site_step_born(env: Array, gamma: Array, lam: Array, u: Array,
                   bn: int = 256, br: int = 256, bl: int = 256,
                   scaling: str = "per_sample",
                   interpret: bool = False):
    """Fused Born site step on complex operands via split re/im planes.

    env (N, χl) complex, Γ (χl, χr, d) complex, λ (χr) real, u (N,) real →
    (env' (N, χr) complex, samples (N,) int32, dlog (N,) real).
    """
    n, chi_l = env.shape
    _, chi_r, d = gamma.shape
    _check_scaling(scaling)
    bn, br, bl, grid = block_grid(n, chi_l, chi_r, bn, br, bl)
    rdt = jnp.zeros((), dtype=env.dtype).real.dtype
    out_dtype = out_dtype_for(rdt)
    acc_dtype = acc_dtype_for(out_dtype, interpret)

    kern = functools.partial(_born_kernel, n_r=grid[1], n_l=grid[2], br=br,
                             d=d, scaling=scaling, out_dtype=out_dtype)
    plane_spec = pl.BlockSpec((bn, bl), lambda i, j, k: (i, k))
    gamma_spec = pl.BlockSpec((bl, d, br), lambda i, j, k: (k, I0, j))
    col_spec = pl.BlockSpec((bn, 1), lambda i, j, k: (i, I0))
    row_spec = pl.BlockSpec((bn, chi_r), lambda i, j, k: (i, I0))
    gt = jnp.swapaxes(gamma, 1, 2)
    ore, oim, samples, dlog = pl.pallas_call(
        kern,
        name="site_step_born",  # the HLO and trace op name
        grid=grid,
        in_specs=[
            plane_spec, plane_spec, gamma_spec, gamma_spec,
            pl.BlockSpec((1, br), lambda i, j, k: (I0, j)),
            col_spec,
        ],
        out_specs=[row_spec, row_spec, col_spec, col_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, chi_r), out_dtype),
            jax.ShapeDtypeStruct((n, chi_r), out_dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), out_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, bn, chi_r), acc_dtype),    # temp·λ slab, re plane
            pltpu.VMEM((d, bn, chi_r), acc_dtype),    # temp·λ slab, im plane
            pltpu.VMEM((d, bn, br), acc_dtype),
            pltpu.VMEM((d, bn, br), acc_dtype),
            pltpu.VMEM((d, bn, 1), acc_dtype),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(jnp.real(env).astype(out_dtype), jnp.imag(env).astype(out_dtype),
      jnp.real(gt).astype(out_dtype), jnp.imag(gt).astype(out_dtype),
      lam.astype(out_dtype).reshape(1, -1), u.reshape(-1, 1))
    return (ore + 1j * oim).astype(env.dtype), samples[:, 0], dlog[:, 0]


# ---------------------------------------------------------------------------
# Measure-only variant (tp-3 split-K schedule): probs_partial = env @ W
# ---------------------------------------------------------------------------

def _measure_kernel(env_ref, w_ref, probs_ref, acc_ref, *, n_l: int,
                    out_dtype, compute_dtype):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    env = env_ref[...]                               # (BN, BL)
    w = w_ref[...]                                   # (BL, d)
    if compute_dtype is not None:
        env = env.astype(compute_dtype)
        w = w.astype(compute_dtype)
    acc_ref[...] += _gemm(env, w, acc_ref.dtype)

    @pl.when(k == n_l - 1)
    def _emit():
        probs_ref[...] = acc_ref[...].astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bn", "bl", "compute_dtype",
                                             "interpret"))
def measure_probs(env: Array, w: Array, bn: int = 256, bl: int = 256,
                  compute_dtype=None, interpret: bool = False) -> Array:
    """env (N, L) · W (L, d) → partial probs (N, d) — the tp-3 measure-first
    GEMM for one bond shard (the caller psums over the TP group)."""
    n, L = env.shape
    d = w.shape[1]
    bn, bl = min(bn, n), min(bl, L)
    if n % bn or L % bl:
        raise ValueError(f"blocks (bn={bn}, bl={bl}) must divide "
                         f"(N={n}, L={L})")
    grid = (n // bn, L // bl)
    out_dtype = out_dtype_for(env.dtype)
    acc_dtype = acc_dtype_for(env.dtype, interpret)
    kern = functools.partial(_measure_kernel, n_l=grid[1],
                             out_dtype=out_dtype, compute_dtype=compute_dtype)
    return pl.pallas_call(
        kern,
        name="measure_probs",  # the HLO and trace op name
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bl), lambda i, k: (i, k)),
            pl.BlockSpec((bl, d), lambda i, k: (k, I0)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i, k: (i, I0)),
        out_shape=jax.ShapeDtypeStruct((n, d), out_dtype),
        scratch_shapes=[pltpu.VMEM((bn, d), acc_dtype)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(env, w)
