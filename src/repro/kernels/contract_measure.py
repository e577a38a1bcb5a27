"""Fused MPS site contraction + linear measurement — Pallas TPU kernel.

This is the hot spot of the whole framework: per site,
``temp[n,r,s] = Σ_l env[n,l]·Γ[l,r,s]`` (a (N×χ)·(χ×χd) GEMM, ~97 % of
FLOPs) immediately followed by the measurement probabilities
``probs[n,s] = Σ_r temp[n,r,s]·Λ[r]``.  Computing probs *inside* the GEMM's
output tiles means temp never makes a round trip to HBM before measurement —
the paper's "measure before communicate" insight applied to the memory
hierarchy (HBM↔VMEM instead of NIC).

TPU mapping (DESIGN.md §2):
  * grid = (n_tiles, r_tiles, l_tiles), l innermost (sequential reduction on
    TPU, accumulator lives in a VMEM scratch tile).
  * MXU tiles: d GEMMs BN×BL · BL×BR per step (one per outcome, on the
    lane-dense ``(χl, d, χr)`` view of Γ) with fp32 accumulation
    (``preferred_element_type``); inputs may be bf16 (the paper's TF32 tier).
  * probs is accumulated across r-tiles into the same (d, BN, 1) output block —
    legal because TPU grids execute sequentially and the probs BlockSpec
    ignores the r/l grid axes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.site_step import (COMPILER_PARAMS, I0, acc_dtype_for,
                                     block_grid, out_dtype_for)

Array = jax.Array


def _kernel(env_ref, gamma_ref, lam_ref, temp_ref, probs_ref, acc_ref,
            *, n_l: int, d: int, out_dtype):
    j = pl.program_id(1)      # r tile
    k = pl.program_id(2)      # l tile (reduction)
    acc_dtype = acc_ref.dtype

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    env = env_ref[...]                              # (BN, BL)
    for s in range(d):
        acc_ref[s] += jax.lax.dot_general(
            env, gamma_ref[:, s, :],                # (BL, BR), lane-dense
            (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype)

    @pl.when(k == n_l - 1)
    def _emit():
        lam = lam_ref[...].astype(acc_dtype)        # (1, BR)
        for s in range(d):
            temp = acc_ref[s]                       # (BN, BR)
            temp_ref[:, s, :] = temp.astype(out_dtype)
            # partial measurement over this r tile: (BN, BR) · (BR,) → (BN, 1)
            contrib = jnp.sum(temp * lam, axis=1,
                              keepdims=True).astype(out_dtype)

            @pl.when(j == 0)
            def _set():
                probs_ref[s] = contrib

            @pl.when(j > 0)
            def _add():
                probs_ref[s] += contrib


@functools.partial(jax.jit, static_argnames=("bn", "br", "bl", "interpret"))
def contract_measure(env: Array, gamma: Array, lam: Array,
                     bn: int = 256, br: int = 256, bl: int = 256,
                     interpret: bool = False):
    """env (N, χ), Γ (χ, χ, d), Λ (χ) → (temp (N, χ, d), probs (N, d)).

    Γ enters as its ``(χl, d, χr)`` bitcast and temp leaves as ``(N, d, χr)``
    (bitcast back by the wrapper), so every block is lane-dense; the probs
    accumulate as ``(d, N, 1)`` columns.  VMEM working set ≈ BN·BL +
    d·BL·BR + 2·d·BN·BR words.
    """
    n, chi = env.shape
    _, chir, d = gamma.shape
    bn, br, bl, grid = block_grid(n, chi, chir, bn, br, bl)
    out_dtype = out_dtype_for(env.dtype)
    acc_dtype = acc_dtype_for(env.dtype, interpret)

    kern = functools.partial(_kernel, n_l=grid[2], d=d, out_dtype=out_dtype)
    temp, probs = pl.pallas_call(
        kern,
        name="contract_measure",  # the HLO and trace op name
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bl), lambda i, j, k: (i, k)),
            pl.BlockSpec((bl, d, br), lambda i, j, k: (k, I0, j)),
            pl.BlockSpec((1, br), lambda i, j, k: (I0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bn, d, br), lambda i, j, k: (i, I0, j)),
            pl.BlockSpec((d, bn, 1), lambda i, j, k: (I0, i, I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d, chir), out_dtype),
            jax.ShapeDtypeStruct((d, n, 1), out_dtype),
        ],
        scratch_shapes=[pltpu.VMEM((d, bn, br), acc_dtype)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(env, jnp.swapaxes(gamma, 1, 2), lam.reshape(1, -1))
    return jnp.swapaxes(temp, 1, 2), probs[:, :, 0].T
