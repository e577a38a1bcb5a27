"""Scenario runner: end-to-end workloads as self-scoring eval harnesses.

A *scenario* is a named build → sample → score pipeline: construct a
model MPS, run the full sampling stack through the public session API,
and score the output against an exact oracle or a task metric.  Each run
emits one BENCH-trajectory row (the :mod:`benchmarks.common` record
schema), so scenario quality is tracked across PRs exactly like the perf
numbers — a regression in sampler correctness shows up as a score drop
in the same file.

Shipped scenarios
-----------------
``gbs``
    The paper's workload: a GBS-flavoured linear MPS; empirical per-site
    marginals vs :func:`repro.core.mps.exact_site_marginals`.
``conditional_marginals``
    The tentpole's acceptance harness: clamp one site, estimate the
    conditional marginals of the *other* sites with the per-sample
    ``log_prob`` importance weights, and compare against conditionals
    computed by restricting the exact joint.  Passing means the clamped
    walk's weights are the true branch probabilities — the rejection-free
    conditioning claim, end to end.
``mnist_classify_generate``
    A Born-machine-style generate/classify loop on 4×4 binary digit
    prototypes: one product-form MPS per class (pixel flip noise 0.1),
    generate from each, classify every sample by per-class
    log-likelihood.  Scores generative-model fidelity rather than a
    distributional distance.

Register new scenarios with the :func:`scenario` decorator; the CLI
(``python -m repro.launch.scenarios``) and the CI smoke job pick them up
from the registry.
"""
from __future__ import annotations

import dataclasses
import datetime
import itertools
import json
import os
import tempfile
import time
from typing import Callable, Optional

import numpy as np

__all__ = ["ScenarioConfig", "ScenarioResult", "available_scenarios",
           "run_scenario", "scenario"]


@dataclasses.dataclass(frozen=True)
class ScenarioConfig:
    """Runner knobs shared by every scenario (scenario-specific sizes are
    fixed by the scenario itself so scores stay comparable across runs)."""

    n_samples: int = 4000
    seed: int = 0
    backend: str = "inmem"        # "inmem" | "streamed"
    scheme: str = "seq"           # "seq" | "dp"
    json_path: Optional[str] = None   # BENCH trajectory (None/"" = no append)


@dataclasses.dataclass(frozen=True)
class ScenarioResult:
    name: str
    passed: bool
    score: float                  # scenario-native quality number
    threshold: float              # pass bar (direction is per-metric)
    wall_s: float
    metrics: dict

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


# -- registry -----------------------------------------------------------------

_REGISTRY: dict[str, Callable] = {}


def scenario(name: str, summary: str):
    """Register ``fn(cfg: ScenarioConfig) -> (passed, score, threshold,
    metrics)`` under ``name``."""
    def deco(fn):
        fn.scenario_name = name
        fn.summary = summary
        _REGISTRY[name] = fn
        return fn
    return deco


def available_scenarios() -> dict[str, str]:
    """{name: one-line summary} for the CLI and docs."""
    return {n: f.summary for n, f in sorted(_REGISTRY.items())}


def _append_record(json_path: Optional[str], bench: str, config: dict,
                   **payload) -> dict:
    """One BENCH-trajectory row.  ``benchmarks/`` is a repo-root package
    not importable under the library's ``PYTHONPATH=src`` deployments, so
    this falls back to an inline writer with the identical record schema
    — the trajectory file cannot tell the two writers apart."""
    try:
        from benchmarks.common import append_bench_record
        return append_bench_record(json_path, bench, config, **payload)
    except ImportError:
        pass
    record = {
        "bench": bench,
        "utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "config": config,
        **payload,
    }
    if not json_path:
        return record
    trajectory = []
    if os.path.exists(json_path):
        with open(json_path) as f:
            trajectory = json.load(f)
    trajectory.append(record)
    with open(json_path, "w") as f:
        json.dump(trajectory, f, indent=1)
    return record


def run_scenario(name: str, cfg: Optional[ScenarioConfig] = None
                 ) -> ScenarioResult:
    """Run one registered scenario and append its trajectory row."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown scenario {name!r}; available: "
                       f"{sorted(_REGISTRY)}")
    cfg = cfg or ScenarioConfig()
    t0 = time.perf_counter()
    passed, score, threshold, metrics = _REGISTRY[name](cfg)
    wall = time.perf_counter() - t0
    result = ScenarioResult(name=name, passed=bool(passed),
                            score=float(score), threshold=float(threshold),
                            wall_s=wall, metrics=metrics)
    _append_record(
        cfg.json_path, "scenario",
        {"scenario": name, "n_samples": cfg.n_samples, "seed": cfg.seed,
         "backend": cfg.backend, "scheme": cfg.scheme},
        passed=result.passed, score=result.score,
        threshold=result.threshold, wall_s=round(wall, 4), metrics=metrics)
    return result


# -- shared sampling helper ---------------------------------------------------

def _sample(mps, n: int, cfg: ScenarioConfig, clamp=None):
    """One session run through the PUBLIC API → (samples (N, M), stats).

    ``backend="streamed"`` round-trips the MPS through a temporary
    full-precision GammaStore so the scenario exercises the segment
    walker + digest-manifest path rather than the in-memory scan.
    """
    import jax

    from repro import api
    config = api.SamplerConfig(scheme=cfg.scheme, backend=cfg.backend,
                               clamp=clamp)
    key = jax.random.key(cfg.seed + 1)
    from repro.launch.mesh import make_mesh
    mesh = (make_mesh((jax.device_count(),), ("data",))
            if cfg.scheme == "dp" else None)
    if cfg.backend == "streamed":
        import jax.numpy as jnp

        from repro.data.gamma_store import GammaStore
        rdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        with tempfile.TemporaryDirectory(prefix="scenario_store_") as tmp:
            with GammaStore(os.path.join(tmp, "store"), storage_dtype=rdt,
                            compute_dtype=rdt) as store:
                store.write_mps(mps)
                store.write_digest_manifest()
                with api.SamplingSession(store, config, mesh=mesh) as session:
                    out = session.sample(n, key)
                    return np.asarray(out), dict(session.stats)
    with api.SamplingSession(mps, config, mesh=mesh) as session:
        out = session.sample(n, key)
        return np.asarray(out), dict(session.stats)


# -- scenarios ----------------------------------------------------------------

@scenario("gbs", "GBS workload: empirical site marginals vs exact oracle")
def _gbs(cfg: ScenarioConfig):
    import jax

    from repro.core import mps as M
    sites, chi, d = 8, 4, 3
    mps = M.gbs_like_mps(jax.random.key(cfg.seed), sites, chi, d)
    samples, _ = _sample(mps, cfg.n_samples, cfg)
    exact = M.exact_site_marginals(mps)
    emp = np.stack([(samples == s).mean(axis=0) for s in range(d)], axis=1)
    err = float(np.abs(emp - exact).max())
    threshold = 0.05   # ~4.5σ at N=4000 for a worst-case p=0.5 cell
    return err < threshold, err, threshold, {
        "sites": sites, "chi": chi, "d": d,
        "mean_photons": float(samples.mean())}


@scenario("conditional_marginals",
          "clamped sampling vs exact conditionals (the tentpole gate)")
def _conditional_marginals(cfg: ScenarioConfig):
    import jax

    from repro.core import mps as M
    sites, chi, d = 6, 4, 3
    clamp_site, clamp_val = 2, 1
    mps = M.random_linear_mps(jax.random.key(cfg.seed), sites, chi, d)
    samples, stats = _sample(mps, cfg.n_samples, cfg,
                             clamp={clamp_site: clamp_val})
    if not np.all(samples[:, clamp_site] == clamp_val):
        return False, float("inf"), 0.0, {"error": "clamp not enforced"}
    lp = np.asarray(stats["log_prob"], dtype=np.float64)
    w = np.exp(lp)

    # oracle: restrict the exact joint to the clamped branch, renormalize
    joint = M.enumerate_probabilities(mps)
    outs = np.array(list(itertools.product(range(d), repeat=sites)))
    sel = outs[:, clamp_site] == clamp_val
    cond = joint[sel] / joint[sel].sum()
    outs_c = outs[sel]

    # estimator: self-normalized importance weights.  w = P(branch) per
    # sample, identical across samples for a scalar clamp, so this reduces
    # to plain frequencies — but the weighted form is what generalizes to
    # per-sample clamps, so score THAT path.
    err = 0.0
    for i in range(sites):
        if i == clamp_site:
            continue
        for s in range(d):
            est = float(w[samples[:, i] == s].sum() / w.sum())
            exact = float(cond[outs_c[:, i] == s].sum())
            err = max(err, abs(est - exact))
    # the branch-marginal estimate: E[w] = P(clamp); w varies only through
    # the sampled prefix s_{<clamp}, so the MC error is tiny but not zero
    p_branch = float(joint[sel].sum())
    branch_err = abs(float(w.mean()) - p_branch)
    threshold = 0.05
    return (err < threshold and branch_err < 5e-3), err, threshold, {
        "clamp": {str(clamp_site): clamp_val},
        "p_branch_exact": p_branch, "p_branch_est": float(w.mean()),
        "branch_err": branch_err}


#: 4×4 binary digit prototypes (one per class) for the generate/classify
#: loop — distinct in ≥ 5 pixels pairwise, so flip noise 0.1 is separable
_DIGITS = {
    0: ("1111", "1001", "1001", "1111"),
    1: ("0010", "0110", "0010", "0111"),
    2: ("1110", "0010", "0100", "1111"),
    3: ("1111", "0001", "0111", "1110"),
}
_FLIP = 0.1


def _digit_mps(cls: int):
    """Class prototype → a product-form linear MPS over 16 binary sites:
    ``gammas[i, 0, 0, s] = p_i(s)`` with flip noise, everything else 0
    (χ=2 embedding; only bond index 0 is reachable from the boundary)."""
    import jax.numpy as jnp

    from repro.core.mps import MPS
    bits = [int(b) for row in _DIGITS[cls] for b in row]
    g = np.zeros((16, 2, 2, 2))
    for i, b in enumerate(bits):
        g[i, 0, 0, b] = 1.0 - _FLIP
        g[i, 0, 0, 1 - b] = _FLIP
    return MPS(jnp.asarray(g), jnp.ones((16, 2)), "linear"), bits


def _digit_loglik(samples: np.ndarray, bits: list[int]) -> np.ndarray:
    """(N, 16) binary samples → per-sample log-likelihood under a class."""
    proto = np.asarray(bits)[None, :]
    match = samples == proto
    return np.where(match, np.log(1.0 - _FLIP), np.log(_FLIP)).sum(axis=1)


@scenario("mnist_classify_generate",
          "per-class digit MPS: generate samples, classify by log-likelihood")
def _mnist(cfg: ScenarioConfig):
    per_class = max(cfg.n_samples // (4 * 8), 25)   # cheap: 4 full sessions
    all_samples, labels, protos = [], [], {}
    for cls in sorted(_DIGITS):
        mps, bits = _digit_mps(cls)
        protos[cls] = bits
        sub = dataclasses.replace(cfg, seed=cfg.seed + 17 * (cls + 1))
        samples, _ = _sample(mps, per_class, sub)
        all_samples.append(samples)
        labels.append(np.full(len(samples), cls))
    samples = np.concatenate(all_samples)
    labels = np.concatenate(labels)
    loglik = np.stack([_digit_loglik(samples, protos[c])
                       for c in sorted(protos)], axis=1)
    pred = loglik.argmax(axis=1)
    acc = float((pred == labels).mean())
    threshold = 0.9
    flip_rate = float(np.concatenate([
        s != np.asarray(protos[c])[None, :]
        for s, c in zip(all_samples, sorted(protos))], axis=0).mean())
    return acc >= threshold, acc, threshold, {
        "per_class": per_class, "classes": len(protos),
        "observed_flip_rate": flip_rate, "nominal_flip_rate": _FLIP}
