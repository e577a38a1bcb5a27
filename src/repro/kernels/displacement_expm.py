"""Batched triangular displacement operator — Pallas TPU kernel (§3.4.1).

Per sample n we need D(μₙ) = e^{−|μₙ|²/2}·exp(μₙ a†)·exp(−μₙ* a), a d×d
complex matrix with d ≤ 16.  The factors are closed-form triangular
(generated elementwise), so the whole batch is embarrassingly parallel.

TPU adaptation of the paper's CUDA layout trick: the paper transposes the
batch to the last (contiguous) position so warp lanes touch interleaved
memory.  On TPU the analogue is putting the **batch on the lane (last,
128-wide) dimension**: all tensors in the kernel are (d, d, BB) with BB a
multiple of 128, so the tiny (j, k) loops broadcast across sublanes and the
VPU vectorizes over samples.  Complex numbers are carried as split re/im
planes (the MXU/VPU have no complex type; DESIGN.md §2).

The (L·U) product is a fori-loop of d rank-1 updates — d ≤ 16 so this is
d² FMA passes over (d, BB) vectors, entirely in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

Array = jax.Array


def _coeff_table(d: int) -> np.ndarray:
    """√(j!/k!)/(j−k)! for j ≥ k else 0, and the μ-power matrix m = j−k."""
    j = np.arange(d)[:, None].astype(np.float64)
    k = np.arange(d)[None, :].astype(np.float64)
    m = j - k
    from scipy.special import gammaln
    logc = 0.5 * (gammaln(j + 1) - gammaln(k + 1)) - gammaln(np.where(m >= 0, m, 0) + 1)
    coeff = np.where(m >= 0, np.exp(logc), 0.0)
    return m, coeff, (m >= 0)


def _kernel(mure_ref, muim_ref, mpow_ref, coeff_ref, outre_ref, outim_ref,
            *, d: int):
    mre = mure_ref[...]                     # (BB,)
    mim = muim_ref[...]
    bb = mre.shape[0]
    m_pow = mpow_ref[...]
    coeff = coeff_ref[...]
    mask = m_pow >= 0

    # polar form for μ^m: r^m·(cos mθ, sin mθ); guard μ=0 (m=0 ⇒ 1).
    r2 = mre * mre + mim * mim
    r = jnp.sqrt(r2)
    theta = jnp.arctan2(mim, mre)
    logr = jnp.log(jnp.where(r > 0, r, 1.0))

    mp = jnp.where(mask, m_pow, 0.0)[:, :, None]   # (d, d, 1)
    co = coeff[:, :, None]
    mk = mask[:, :, None]
    rm = jnp.exp(mp * logr[None, None, :])  # (d, d, BB)
    rm = jnp.where((mp == 0) | (r[None, None, :] > 0), rm, 0.0)
    ang = mp * theta[None, None, :]
    # exp(μ a†): entries μ^{j−k}·coeff  (lower triangular)
    lre = jnp.where(mk, co * rm * jnp.cos(ang), 0.0)
    lim = jnp.where(mk, co * rm * jnp.sin(ang), 0.0)
    # exp(−μ* a) = transpose of exp((−μ*)·a†)-style factor: entries
    # (−μ*)^{k−j}·coeff[k,j] — build from the lower factor of (−μ*) and
    # transpose the matrix dims (batch stays on lanes).
    nre, nim = -mre, mim                    # −μ* = (−re, +im)
    nr = jnp.sqrt(nre * nre + nim * nim)
    ntheta = jnp.arctan2(nim, nre)
    nlogr = jnp.log(jnp.where(nr > 0, nr, 1.0))
    nrm = jnp.exp(mp * nlogr[None, None, :])
    nrm = jnp.where((mp == 0) | (nr[None, None, :] > 0), nrm, 0.0)
    nang = mp * ntheta[None, None, :]
    ure = jnp.where(mk, co * nrm * jnp.cos(nang), 0.0).swapaxes(0, 1)
    uim = jnp.where(mk, co * nrm * jnp.sin(nang), 0.0).swapaxes(0, 1)

    pref = jnp.exp(-0.5 * r2)               # (BB,)

    # out = pref · L @ U, batched over lanes: d rank-1 accumulation steps.
    def body(jj, acc):
        are, aim = acc
        lre_j = jax.lax.dynamic_slice_in_dim(lre, jj, 1, axis=1)  # (d, 1, BB)
        lim_j = jax.lax.dynamic_slice_in_dim(lim, jj, 1, axis=1)
        ure_j = jax.lax.dynamic_slice_in_dim(ure, jj, 1, axis=0)  # (1, d, BB)
        uim_j = jax.lax.dynamic_slice_in_dim(uim, jj, 1, axis=0)
        are = are + lre_j * ure_j - lim_j * uim_j
        aim = aim + lre_j * uim_j + lim_j * ure_j
        return are, aim

    zero = jnp.zeros((d, d, bb), dtype=mre.dtype)
    outre, outim = jax.lax.fori_loop(0, d, body, (zero, zero))
    outre_ref[...] = outre * pref[None, None, :]
    outim_ref[...] = outim * pref[None, None, :]


@functools.partial(jax.jit, static_argnames=("d", "bb", "interpret"))
def displacement_expm(mu_re: Array, mu_im: Array, d: int,
                      bb: int = 128, interpret: bool = False):
    """(B,) μ re/im → (B, d, d) re/im planes of D(μ).  B % bb == 0."""
    B = mu_re.shape[0]
    bb = min(bb, B)
    assert B % bb == 0
    m_pow, coeff, _ = _coeff_table(d)
    dt = mu_re.dtype
    kern = functools.partial(_kernel, d=d)

    outre, outim = pl.pallas_call(
        kern,
        name="displacement_expm",  # the HLO and trace op name
        grid=(B // bb,),
        in_specs=[pl.BlockSpec((bb,), lambda i: (i,)),
                  pl.BlockSpec((bb,), lambda i: (i,)),
                  pl.BlockSpec((d, d), lambda i: (0, 0)),
                  pl.BlockSpec((d, d), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((d, d, bb), lambda i: (0, 0, i)),
                   pl.BlockSpec((d, d, bb), lambda i: (0, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((d, d, B), dt),
                   jax.ShapeDtypeStruct((d, d, B), dt)],
        interpret=interpret,
    )(mu_re, mu_im, jnp.asarray(m_pow, dt), jnp.asarray(coeff, dt))
    # user-facing layout (B, d, d); the kernel-internal layout keeps batch on
    # lanes, this transpose is fused into the consumer by XLA.
    return outre.transpose(2, 0, 1), outim.transpose(2, 0, 1)
