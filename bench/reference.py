"""Plain reference for the streamed sampler's draws, and its control.

It imports nothing of the program.  Given the samples a run delivered, it
recomputes every checked row's walk in float32 at HIGHEST precision from
the benchmark's own chain (``bench.chain``, at the published χ, unpadded),
following the row's delivered outcomes site by site (teacher forcing, as a
served model's reference follows its served tokens).  At each site it
reproduces the uniform the program drew for that row, from the seed and
the program's documented key schedule (``fold_in(job, batch)`` per macro
batch; ``fold_in(key, site)`` per site; ``split`` over data shards and
micro batches), and measures how far that uniform lies outside the
reference's CDF interval of the delivered outcome.  The widest such gap is
the number compared: 0 when every draw is the one the reference makes, and
about a probability when an outcome is altered.

The control is the same walk with its GEMM inputs rounded to float8 (e4m3,
one scale per tensor), the step below the bfloat16 inputs the
configurations state: at each checked position it draws its own outcome
from the same uniform and reads that outcome's gap.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import chain

HI = jax.lax.Precision.HIGHEST
ROW_CHUNK = 4096        # rows per GEMM, so the (rows, χ, d) product fits


def site_uniforms(batch_key, site: int, n: int, *, segment_runner: bool,
                  p1: int = 1, micro_batch=None) -> np.ndarray:
    """The (n,) float32 uniforms batch ``batch_key`` draws at ``site``.

    ``segment_runner`` is the DP/TP schedules' walk, which splits the batch
    key over ``p1`` data shards first; the sequential scan uses it as is.
    ``micro_batch`` splits each shard's key over its micro batches."""
    shard_keys = (jax.random.split(batch_key, p1) if segment_runner
                  else batch_key[None])
    n_local = n // shard_keys.shape[0]
    out = []
    for sk in shard_keys:
        if micro_batch:
            chunk_keys = jax.random.split(sk, n_local // micro_batch)
            rows = micro_batch
        else:
            chunk_keys, rows = sk[None], n_local
        for ck in chunk_keys:
            out.append(jax.random.uniform(jax.random.fold_in(ck, site),
                                          (rows, 1), jnp.float32)[:, 0])
    return np.asarray(jnp.concatenate(out))


def _fp8(x):
    """Round to float8 e4m3 with one scale for the tensor, and back."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _cdf(env, gmat, lam, d):
    temp = jnp.dot(env, gmat, precision=HI).reshape(env.shape[0], -1, d)
    cond = jnp.maximum(jnp.einsum("rcs,c->rs", temp, lam, precision=HI), 0.0)
    return temp, jnp.cumsum(cond / jnp.sum(cond, axis=1, keepdims=True),
                            axis=1)


def _gap(cdf, x, u):
    """How far u lies outside the CDF interval of outcome x (the program's
    draw counts the thresholds u exceeds, clipped to d − 1)."""
    d = cdf.shape[1]
    lo = jnp.where(x > 0, jnp.take_along_axis(
        cdf, jnp.maximum(x - 1, 0)[:, None], axis=1)[:, 0], 0.0)
    hi = jnp.where(x < d - 1, jnp.take_along_axis(
        cdf, x[:, None], axis=1)[:, 0], jnp.inf)
    return jnp.maximum(jnp.maximum(lo - u, u - hi), 0.0)


@partial(jax.jit, static_argnames=("control",))
def _site(env, gamma, lam, x, u, control: bool):
    chi, _, d = gamma.shape
    gmat = gamma.astype(jnp.float32).reshape(chi, chi * d)
    temp, cdf = _cdf(env, gmat, lam, d)
    gap = jnp.max(_gap(cdf, x, u))
    cgap = jnp.zeros((), jnp.float32)
    if control:
        _, cdf8 = _cdf(_fp8(env), _fp8(gmat), lam, d)
        x8 = jnp.minimum(jnp.sum(u[:, None] > cdf8, axis=1), d - 1)
        cgap = jnp.max(_gap(cdf, x8.astype(jnp.int32), u))
    nxt = jnp.take_along_axis(temp, x[:, None, None], axis=2)[:, :, 0]
    m = jnp.max(jnp.abs(nxt), axis=1, keepdims=True)
    return nxt / jnp.where(m > 0, m, 1.0), gap, cgap


def widest_gaps(samples: np.ndarray, uniforms: np.ndarray, *, key,
                n_sites: int, chi: int, d: int, storage_dtype: str,
                control: bool = False) -> tuple[float, float]:
    """(program's widest gap, control's widest gap) over the checked rows.

    ``samples`` (R, M) int are the delivered outcomes of the checked rows,
    ``uniforms`` (R, M) the uniforms the program drew for them."""
    r = samples.shape[0]
    env = jnp.zeros((r, chi), jnp.float32).at[:, 0].set(1.0)
    gap = cgap = 0.0
    for site in range(n_sites):
        gamma, lam = chain.site_tensor(key, site, n_sites=n_sites, chi=chi,
                                       d=d, storage_dtype=storage_dtype)
        parts = []
        for lo in range(0, r, ROW_CHUNK):
            sl = slice(lo, lo + ROW_CHUNK)
            e, g, c = _site(env[sl], gamma, lam,
                            jnp.asarray(samples[sl, site], jnp.int32),
                            jnp.asarray(uniforms[sl, site], jnp.float32),
                            control)
            parts.append(e)
            gap, cgap = max(gap, float(g)), max(cgap, float(c))
        env = jnp.concatenate(parts)
        del gamma, lam, parts
    return gap, cgap
