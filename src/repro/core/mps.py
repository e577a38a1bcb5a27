"""Matrix Product State data structures and exact oracles.

Two semantics are supported throughout the framework (see DESIGN.md §1):

- ``linear``: the MPS carries non-negative weights and the measurement of
  Algorithm 1 in the paper is *linear* in the left environment
  (``probs = temp · Λ``).  This is the paper-faithful mode and is
  mathematically a hidden-Markov / non-negative Born machine, so exact
  marginals are cheap — we use it as the test oracle.
- ``born``: the MPS carries complex amplitudes in Vidal canonical form
  (Γ, λ) and ``p(s) = Σ_r |temp[n, r, s]|² λ_r²``.

An MPS here is a stacked array of site tensors ``gammas[M, chi, chi, d]``
plus per-bond coefficient vectors ``lambdas[M, chi]`` (the Λ of Alg. 1).
Boundary sites use row/column 0 conventions: the left environment starts as
``gammas[0, 0, :, :]`` measured at site 0.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MPS:
    """Uniform-χ stacked MPS.

    gammas : (M, chi, chi, d) site tensors.  ``gammas[i][l, r, s]`` maps the
        left bond ``l`` to the right bond ``r`` when the physical outcome at
        site ``i`` is ``s``.
    lambdas : (M, chi) measurement coefficient vector Λ_i used by Alg. 1
        (``linear``) or the Schmidt weights of the right bond (``born``).
    semantics : "linear" | "born".
    """

    gammas: Array
    lambdas: Array
    semantics: str = "linear"

    # -- pytree plumbing ---------------------------------------------------
    def tree_flatten(self):
        return (self.gammas, self.lambdas), self.semantics

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux)

    # -- shapes ------------------------------------------------------------
    @property
    def n_sites(self) -> int:
        return self.gammas.shape[0]

    @property
    def chi(self) -> int:
        return self.gammas.shape[1]

    @property
    def phys_dim(self) -> int:
        return self.gammas.shape[3]

    def astype(self, dtype) -> "MPS":
        return MPS(self.gammas.astype(dtype), self.lambdas.astype(dtype), self.semantics)


# ---------------------------------------------------------------------------
# Random MPS generation
# ---------------------------------------------------------------------------

def random_linear_mps(key: Array, n_sites: int, chi: int, d: int,
                      decay: float = 0.0, dtype=jnp.float64) -> MPS:
    """Random non-negative ("linear" semantics) MPS, i.e. an HMM.

    ``decay`` reproduces the paper's Fig. 5/6 magnitude phenomenon: each site
    shrinks the environment magnitude by roughly ``10**-decay`` with a large
    *per-sample variance*, so unnormalized environments span many orders of
    magnitude across samples — the regime where a global auto-scale fails and
    the per-sample scale of §3.3 is required.
    """
    kg, kl, kd = jax.random.split(key, 3)
    gammas = jax.random.uniform(kg, (n_sites, chi, chi, d), dtype=dtype, minval=0.0, maxval=1.0)
    # Row-normalise so that summing over (r, s) with Λ=1 yields a stochastic
    # map; then apply a per-site random magnitude factor to create the
    # dynamic-range spread.
    gammas = gammas / jnp.sum(gammas, axis=(2, 3), keepdims=True)
    if decay:
        site_scale = 10.0 ** (-decay * (1.0 + jax.random.uniform(kd, (n_sites, 1, 1, 1), dtype=dtype)))
        gammas = gammas * site_scale
    lambdas = jnp.ones((n_sites, chi), dtype=dtype) + jax.random.uniform(kl, (n_sites, chi), dtype=dtype)
    return MPS(gammas, lambdas, "linear")


def random_born_mps(key: Array, n_sites: int, chi: int, d: int,
                    dtype=jnp.complex128) -> MPS:
    """Random complex-amplitude MPS in (approximate) right-canonical Vidal form.

    Built by QR-orthogonalising random site tensors from the right so that
    ``Σ_s Γ^s Γ^{s†} ≈ I`` and the conditional probabilities from left-to-right
    sampling are normalized up to the boundary vector.  Exactness of the
    sampler is *not* assumed from canonical form — tests always compare
    against :func:`enumerate_probabilities`, which needs no canonicity.
    """
    real_dtype = jnp.float64 if dtype == jnp.complex128 else jnp.float32
    keys = jax.random.split(key, n_sites)

    def one_site(k):
        kr, ki = jax.random.split(k)
        a = (jax.random.normal(kr, (chi, chi * d), dtype=real_dtype)
             + 1j * jax.random.normal(ki, (chi, chi * d), dtype=real_dtype)).astype(dtype)
        # Right-canonicalise: rows orthonormal.
        q, _ = jnp.linalg.qr(a.conj().T, mode="reduced")  # (chi*d, chi)
        b = q.conj().T.reshape(chi, chi, d)
        return b

    gammas = jax.vmap(one_site)(keys)
    lambdas = jnp.ones((n_sites, chi), dtype=real_dtype)
    return MPS(gammas, lambdas, "born")


def gbs_like_mps(key: Array, n_sites: int, chi: int, d: int,
                 photon_decay: float = 0.002, dtype=jnp.float64) -> MPS:
    """Synthetic GBS-flavoured MPS (linear semantics).

    Mean photon number per site decays from the chain centre following the
    area-law-like entanglement profile, so that dynamic bond dimension
    (§3.4.2) has real structure to exploit, and the environment magnitude
    decays with site index as in Eq. (5) of the paper.
    """
    base = random_linear_mps(key, n_sites, chi, d, decay=photon_decay * 50, dtype=dtype)
    # Bias outcome 0 (vacuum) increasingly towards the edges.
    pos = jnp.arange(n_sites, dtype=dtype)
    centre = (n_sites - 1) / 2.0
    edge = jnp.abs(pos - centre) / centre  # 1 at edges, 0 at centre
    vac_boost = 1.0 + 4.0 * edge[:, None, None]  # (M,1,1)
    g = base.gammas.at[:, :, :, 0].multiply(vac_boost)
    g = g / jnp.sum(g, axis=(2, 3), keepdims=True)
    return MPS(g, base.lambdas, "linear")


def pad_bond(mps: MPS, multiple: int) -> MPS:
    """Zero-pad the bond dimension up to a multiple of ``multiple``.

    Exact: the padded rows/columns of Γ and entries of Λ are zero, so the
    padded environment columns stay zero and no probability changes.  The
    compiled TPU kernels need χ to split into 128-lane blocks (and χ/p₂
    too under tensor parallelism); χ = 10⁴ does not, so a store is padded
    once when it is written — never inside a site step, where it would
    copy Γ on every call.
    """
    pad = -mps.chi % multiple
    if pad == 0:
        return mps
    g = jnp.pad(mps.gammas, ((0, 0), (0, pad), (0, pad), (0, 0)))
    lam = jnp.pad(mps.lambdas, ((0, 0), (0, pad)))
    return MPS(g, lam, mps.semantics)


# ---------------------------------------------------------------------------
# Exact oracles (for tests and validation — exponential in M, keep M small)
# ---------------------------------------------------------------------------

def enumerate_probabilities(mps: MPS) -> np.ndarray:
    """Exact joint distribution over all d**M outcomes.

    The sequential sampler draws each site from a *normalised per-site
    conditional* (Alg. 1).  The joint it targets is therefore the product of
    those conditionals — this oracle mirrors the sampler's arithmetic exactly
    (in float64), so it is valid for arbitrary (non-canonical) Γ/Λ.

    linear: cond(s | prefix) ∝ (env · Γ_i^s) · Λ_i ;  env' = env · Γ_i^s
    born:   cond(s | prefix) ∝ Σ_r |(env · Γ_i^s)_r λ_i[r]|² ; env' = env·Γ_i^s·λ_i
    """
    g = np.asarray(mps.gammas)
    lam = np.asarray(mps.lambdas)
    M, chi, _, d = g.shape
    outcomes = np.stack(np.meshgrid(*([np.arange(d)] * M), indexing="ij"), axis=-1).reshape(-1, M)

    linear = mps.semantics == "linear"
    probs = np.zeros(len(outcomes))
    for idx, s in enumerate(outcomes):
        env = np.zeros(chi, dtype=complex)
        env[0] = 1.0
        logp = 0.0
        for i in range(M):
            temp = np.einsum("l,lrs->rs", env, g[i])  # (chi, d)
            if linear:
                cond = np.real(temp.T @ lam[i])  # (d,)
            else:
                cond = np.sum(np.abs(temp.T * lam[i][None, :]) ** 2, axis=1)  # (d,)
            total = cond.sum()
            logp += np.log(cond[s[i]] / total)
            env = temp[:, s[i]]
            if not linear:
                env = env * lam[i]
            # renormalise env for numeric stability (does not change conds)
            nrm = np.abs(env).sum()
            if nrm > 0:
                env = env / nrm
        probs[idx] = np.exp(logp)
    return probs / probs.sum()


def prefix_marginals(sites, semantics: str = "linear") -> np.ndarray:
    """Per-site marginals (M, d) of the same joint as
    :func:`enumerate_probabilities`, for chains too wide for it.

    ``sites`` yields ``(Γ_i, Λ_i)`` one site at a time (e.g. from a
    ``GammaStore``), so the chain never has to fit in memory at once.
    All d^i outcome prefixes walk together as one (d^i, χ) environment —
    one GEMM per site on the default device, at HIGHEST precision and at
    least float32 — so χ = 10⁴ is cheap while M stays small (memory grows
    as d^M·χ).
    """
    hi = jax.lax.Precision.HIGHEST
    env = logp = None
    n_sites = 0
    for g, lam in sites:
        n_sites += 1
        dt = jnp.result_type(g.dtype, jnp.float32)
        g, lam = jnp.asarray(g, dt), jnp.asarray(lam)
        chi, d = g.shape[0], g.shape[2]
        if env is None:
            env = jnp.zeros((1, chi), dt).at[0, 0].set(1.0)
            logp = jnp.zeros((1,), jnp.real(env).dtype)
        temp = jnp.einsum("pl,lrs->prs", env, g, precision=hi)
        if semantics == "linear":
            cond = jnp.real(jnp.einsum("prs,r->ps", temp, lam.astype(dt),
                                       precision=hi))
        else:
            cond = jnp.sum(jnp.abs(temp * lam[None, :, None]) ** 2, axis=1)
            temp = temp * lam[None, :, None]
        logp = (logp[:, None]
                + jnp.log(cond / jnp.sum(cond, axis=1, keepdims=True)))
        logp = logp.reshape(-1)              # prefix-major, outcome-minor
        env = jnp.moveaxis(temp, 2, 1).reshape(-1, chi)
        nrm = jnp.sum(jnp.abs(env), axis=1, keepdims=True)
        env = env / jnp.where(nrm > 0, nrm, 1.0)
    p = np.exp(np.asarray(logp, dtype=np.float64))
    p = p / p.sum()
    per_site = p.reshape((d,) * n_sites)
    return np.stack([
        per_site.sum(axis=tuple(a for a in range(n_sites) if a != i))
        for i in range(n_sites)])


def exact_site_marginals(mps: MPS) -> np.ndarray:
    """Per-site marginal distribution, (M, d), via the joint (small M only)."""
    g = np.asarray(mps.gammas)
    M, chi, _, d = g.shape
    joint = enumerate_probabilities(mps)
    outcomes = np.stack(np.meshgrid(*([np.arange(d)] * M), indexing="ij"), axis=-1).reshape(-1, M)
    marg = np.zeros((M, d))
    for i in range(M):
        for s in range(d):
            marg[i, s] = joint[outcomes[:, i] == s].sum()
    return marg
