"""The benchmark's own seeded chain: a GBS-like linear-semantics MPS.

The structure of the program's ``gbs_like_mps`` (non-negative site tensors,
rows normalised over (right bond, outcome), the vacuum outcome boosted
towards the chain's edges and renormalised, Λ uniform in [1, 2)), with one
addition: the bond is cut into ``BLOCKS`` contiguous blocks and every entry
is scaled by a per-site random preference ``T[block(l), block(r), s]``
(uniform cubed, so preferences span three decades).  The environment's mass
per block then evolves like the hidden state of a Markov chain, so each
outcome's probability depends on the outcomes drawn before it, as photon
counts correlate in a GBS state.  With i.i.d. uniform entries alone, every
conditional at χ = 10⁴ is the same for every prefix to about 10⁻⁵ (each is
a sum of 10⁴ terms), and no draw could tell a correct walk from one that
ignores its environment or rounds it to float8.

Sites are drawn from ``fold_in(key, site)``, one row block at a time, so any
one site can be regenerated alone (the reference does) and the device never
holds more than a block of float32 rows.  The per-site magnitude factor of
``random_linear_mps(decay=...)`` is left out: the renormalisation divides
it out again.

A site comes out as ``(Γ (χ, χ, d) in the storage dtype, Λ (χ,) float32)``
at the published χ; padding for the chip's tiling is the caller's business.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

BLOCKS = 8              # bond blocks; Γ rows are drawn one block at a time


def chain_key(seed: int) -> jax.Array:
    """A key from any whole number up to 64 bits, with or without x64."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@partial(jax.jit, static_argnames=("n_sites", "chi", "d", "storage_dtype"))
def site_tensor(key, site, *, n_sites: int, chi: int, d: int,
                storage_dtype: str = "bfloat16"):
    """Site ``site`` of an ``n_sites``-site chain drawn from ``key``."""
    kg, kl, kt = jax.random.split(jax.random.fold_in(key, site), 3)
    centre = (n_sites - 1) / 2.0
    edge = (jnp.abs(site - centre) / centre) if n_sites > 1 else 0.0
    boost = jnp.ones((d,), jnp.float32).at[0].set(1.0 + 4.0 * edge)
    rows = -(-chi // BLOCKS)
    pref = jax.random.uniform(kt, (BLOCKS, BLOCKS, d), jnp.float32) ** 3
    col_block = jnp.arange(chi) // rows

    def block(b):
        g = jax.random.uniform(jax.random.fold_in(kg, b), (rows, chi, d),
                               jnp.float32)
        g = g * pref[b][col_block][None]
        g = g / jnp.sum(g, axis=(1, 2), keepdims=True)
        g = g * boost
        g = g / jnp.sum(g, axis=(1, 2), keepdims=True)
        return g.astype(storage_dtype)

    g = jax.lax.map(block, jnp.arange(BLOCKS))
    g = g.reshape(BLOCKS * rows, chi, d)[:chi]
    lam = 1.0 + jax.random.uniform(kl, (chi,), jnp.float32)
    return g, lam


def padded(gamma, lam, multiple: int):
    """Zero-pad the bond to a multiple of ``multiple`` (exact: padded rows
    and columns of Γ and entries of Λ are zero, so no probability moves)."""
    pad = -gamma.shape[0] % multiple
    if pad == 0:
        return gamma, lam
    return (jnp.pad(gamma, ((0, pad), (0, pad), (0, 0))),
            jnp.pad(lam, (0, pad)))
