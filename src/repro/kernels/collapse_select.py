"""Sample-selected collapse GEMM — Pallas TPU kernel (§Perf iteration tp-3).

The measure-first reformulation: by associativity of Alg. 1's linear
measurement,

    probs[n, s] = Σ_r (Σ_l env[n,l] Γ[l,r,s]) Λ[r] = env @ W,
    W[l, s]     = Σ_r Γ[l,r,s] Λ[r]                       (tiny, per site)

so the (N, χ, d) unmeasured temp is never needed to *draw*.  After drawing
s_n, the new environment is

    env'[n, r] = Σ_l env[n, l] · Γ[l, r, s_n]

— a GEMM whose rhs differs per sample only through the physical index.
This kernel computes it with the per-sample select fused *inside* the MXU
loop: per (n, r, l) tile it keeps an (BN, BR) accumulator in VMEM and adds
``dot(env ⊙ [s_n = s], Γ[:, :, s])`` for each of the d outcomes.  The
masked operand lives only in VMEM/registers, so HBM traffic is env + Γ +
out — the (N, χ, d) temp round-trip of the naive path is gone entirely
(the memory term of the tp_single roofline drops ~20× at χ=10⁴; see
EXPERIMENTS.md §Perf).

FLOPs are unchanged (2NΧ²d — each outcome's dot still runs); the win is
pure memory traffic, which is what dominates the baseline.
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


def _kernel(env_ref, gamma_ref, samples_ref, out_ref, acc_ref,
            *, n_l: int, d: int, out_dtype):
    k = pl.program_id(2)      # l tile (sequential reduction)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    env = env_ref[...]                         # (BN, BL)
    s_n = samples_ref[...]                     # (BN, 1) int32
    acc_dtype = acc_ref.dtype

    for s in range(d):                         # d ≤ ~6: unrolled, VMEM-local
        masked = jnp.where(s_n == s, env, jnp.zeros_like(env))
        acc_ref[...] += jax.lax.dot_general(
            masked, gamma_ref[:, s, :],        # (BL, BR), lane-dense
            (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dtype,
        )

    @pl.when(k == n_l - 1)
    def _emit():
        out_ref[...] = acc_ref[...].astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bn", "br", "bl", "interpret"))
def collapse_select(env: Array, gamma: Array, samples: Array,
                    bn: int = 256, br: int = 256, bl: int = 256,
                    interpret: bool = False) -> Array:
    """env (N, L), Γ (L, R, d), samples (N,) → env' (N, R).

    L is the (possibly sharded) left bond, R the right bond.  Γ enters as
    its lane-dense ``(L, d, R)`` bitcast and the samples as an (N, 1)
    column; VMEM working set ≈ BN·BL + d·BL·BR + BN·BR fp32 words.
    """
    n, L = env.shape
    _, R, d = gamma.shape
    bn, br, bl, grid = block_grid(n, L, R, bn, br, bl)
    out_dtype = out_dtype_for(env.dtype)
    acc_dtype = acc_dtype_for(env.dtype, interpret)

    kern = functools.partial(_kernel, n_l=grid[2], d=d, out_dtype=out_dtype)
    return pl.pallas_call(
        kern,
        name="collapse_select",  # the HLO and trace op name
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bl), lambda i, j, k: (i, k)),
            pl.BlockSpec((bl, d, br), lambda i, j, k: (k, I0, j)),
            pl.BlockSpec((bn, 1), lambda i, j, k: (i, I0)),
        ],
        out_specs=pl.BlockSpec((bn, br), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, R), out_dtype),
        scratch_shapes=[pltpu.VMEM((bn, br), acc_dtype)],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(env, jnp.swapaxes(gamma, 1, 2),
      samples.astype(jnp.int32).reshape(-1, 1))


def measure_weights(gamma: Array, lam: Array) -> Array:
    """W[l, s] = Σ_r Γ[l,r,s]·Λ[r] — the per-site measure-first operator."""
    return jnp.einsum("lrs,r->ls", gamma, lam)
