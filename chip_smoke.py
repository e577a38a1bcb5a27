"""Chip smoke run: the streamed GBS sampler at Jiuzhang2 width on a TPU.

Drives the paper's main path once, through the front door a user calls —
``SamplingSession`` → streamed backend → ``StreamingEngine`` →
``kernels/dispatch`` → the compiled fused Pallas site step — at the bond
width of the ``jiuzhang2`` preset (``configs/gbs.py``: χ = 10⁴, d = 4),
with only M and N cut:

* M = 4 sites (of 144) and N = 4096 samples per macro batch × 2 batches;
* a seeded ``gbs_like_mps`` with Γ stored in bf16, the environment in f32
  and bf16 GEMM inputs (the §3.3.2 setting of ``launch/dryrun --gbs-opt``);
* χ zero-padded once at store time to 10240 (``core.mps.pad_bond``, exact),
  so χ and χ/4 both split into 128-lane blocks.

It samples the same store and keys with ``kernels="pallas"`` and with
``kernels="xla"`` and checks: sample shape and range, agreement of the two
paths' per-site marginals, and agreement of each with the exact marginals
(``core.mps.prefix_marginals``, every outcome prefix enumerated on the
chip), all within a stated statistical tolerance.  Exact bit-identity of
the two kernel paths on the chip is reported, not assumed.

    python chip_smoke.py             # one chip: pallas vs xla
    python chip_smoke.py --chips 4   # tp_single / tp_double on a (1, 4)
                                     # mesh vs seq on one of those chips

It exits non-zero, and prints no result, when JAX finds no TPU or the
repository's ``src/`` is not beside it.  Only when every phase passed is
the last line ``{"ok": true, "device": {...}}``.  The Γ store (~3.4 GB) is
written under ``.smoke_store/`` in the checkout and deleted at the end.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
STORE = os.path.join(HERE, ".smoke_store")

PRESET = "jiuzhang2"
M_SITES = 4                 # of the preset's 144
N_BATCH = 4096              # samples per macro batch
N_MACRO = 2                 # macro batches
SEGMENT_LEN = 2             # two streamed segments: the prefetch overlaps
PAD_TO = 512                # 128 lanes × p₂ = 4
SEED = 0
SIGMAS = 5.0                # marginal tolerance, in binomial std devs


def log(*args) -> None:
    print(*args, flush=True)


def mem(dev, key: str):
    return (dev.memory_stats() or {}).get(key)


def marginals(samples, d: int):
    import numpy as np
    return np.stack([np.bincount(col, minlength=d) for col in samples.T]
                    ) / samples.shape[0]


def tolerance(n: int, runs: int = 1) -> float:
    """SIGMAS standard deviations of a marginal estimate from n draws at
    the worst case p = ½ (runs = 2 compares two independent estimates)."""
    return SIGMAS * (runs * 0.25 / n) ** 0.5


def build_store(chi: int, d: int):
    """Seeded gbs_like chain, padded, written to STORE in bf16.  It is
    generated on the host's CPU backend: in f32 the generator needs ~15 GiB
    at this width, more than the chip's HBM."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import mps as M
    from repro.data.gamma_store import GammaStore

    @jax.jit
    def make(key):
        m = M.pad_bond(M.gbs_like_mps(key, M_SITES, chi, d,
                                      dtype=jnp.float32), PAD_TO)
        return m.gammas.astype(jnp.bfloat16), m.lambdas

    shutil.rmtree(STORE, ignore_errors=True)
    store = GammaStore(STORE, storage_dtype=jnp.bfloat16,
                       compute_dtype=jnp.bfloat16)
    with jax.default_device(jax.devices("cpu")[0]):
        g, lam = make(jax.random.key(SEED))
    for i in range(M_SITES):
        store.put(i, np.asarray(g[i]), np.asarray(lam[i]))
    del g, lam
    return store


def run_session(store, config, mesh=None, *, label: str):
    """N_MACRO macro batches through SamplingSession.run_queue; returns
    (samples (N_MACRO·N_BATCH, M), per-batch wall seconds, plan)."""
    import jax
    import numpy as np

    from repro import api
    from repro.runtime.elastic import WorkQueue

    stamps = []
    with api.SamplingSession(store, config, mesh=mesh) as session:
        plan = session.plan(N_BATCH)
        t0 = time.perf_counter()
        out = session.run_queue(
            WorkQueue(N_MACRO), N_BATCH, jax.random.key(SEED + 1),
            on_batch=lambda b, s: stamps.append((b, s, time.perf_counter())))
    del out
    walls, prev = [], t0
    for _, _, t in stamps:
        walls.append(t - prev)
        prev = t
    samples = np.concatenate([s for _, s, _ in sorted(stamps,
                                                      key=lambda x: x[0])])
    log(f"[{label}] scheme={plan.scheme} kernels={plan.kernels} "
        f"segment_len={plan.segment_len} batch walls (s): "
        f"{[round(w, 3) for w in walls]}")
    return samples, walls, plan


def check_samples(samples, d: int, label: str) -> list[str]:
    import numpy as np
    errs = []
    want = (N_MACRO * N_BATCH, M_SITES)
    if samples.shape != want:
        errs.append(f"{label}: samples shape {samples.shape} != {want}")
    elif samples.min() < 0 or samples.max() >= d:
        errs.append(f"{label}: sample values outside [0, {d})")
    elif not np.issubdtype(samples.dtype, np.integer):
        errs.append(f"{label}: samples dtype {samples.dtype}")
    return errs


def compare(a, b, d: int, label: str, runs: int = 2) -> list[str]:
    """Exact-match fractions and the largest per-site marginal gap."""
    import numpy as np
    gap = float(np.abs(marginals(a, d) - marginals(b, d)).max())
    tol = tolerance(a.shape[0], runs)
    log(f"[{label}] exact-match: elements {float(np.mean(a == b)):.6f} "
        f"whole chains {float(np.mean(np.all(a == b, axis=1))):.6f}  "
        f"max marginal gap {gap:.6f} (tolerance {tol:.6f})")
    return [] if gap <= tol else [f"{label}: marginal gap {gap} > {tol}"]


def against_exact(samples, exact, d: int, label: str) -> list[str]:
    import numpy as np
    gap = float(np.abs(marginals(samples, d) - exact).max())
    tol = tolerance(samples.shape[0])
    log(f"[{label}] vs exact marginals: max gap {gap:.6f} "
        f"(tolerance {tol:.6f})")
    return [] if gap <= tol else [f"{label}: exact-marginal gap {gap} > {tol}"]


def exact_marginals(store):
    from repro.core import mps as M

    def sites():
        for i in range(store.n_sites):
            g, lam = store.get_segment(i, 1)
            yield g[0], lam[0]
    return M.prefix_marginals(sites())


def stage_impls(plan) -> dict:
    from repro.kernels import dispatch
    return {st: getattr(dispatch.get_site_op(st, plan.semantics,
                                             plan.kernels), "__name__", "?")
            for st in dispatch.STAGES}


def sample_all(store, d: int, runs) -> tuple[dict, list[str]]:
    """Sample the store once per ``(label, scheme, kernels, mesh)`` run and
    print what each resolved to, compiled and held; then the autotuner's
    choices.  Returns the samples by label and the errors found."""
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.kernels import dispatch

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append(secs)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    errs, results = [], {}
    for label, scheme, kernels, mesh in runs:
        cfg = api.SamplerConfig(backend="streamed", scheme=scheme,
                                kernels=kernels, compute_dtype=jnp.bfloat16,
                                segment_len=SEGMENT_LEN)
        n0 = len(compiles)
        samples, walls, plan = run_session(store, cfg, mesh, label=label)
        want = "xla" if kernels == "xla" else "pallas"
        if plan.kernels != want:
            errs.append(f"{label}: kernels={kernels!r} resolved to "
                        f"{plan.kernels!r}")
        log(f"[{label}] stage implementations: {stage_impls(plan)} "
            f"(interpret={not dispatch.on_tpu()})")
        log(f"[{label}] compile: {len(compiles) - n0} XLA/Mosaic compiles, "
            f"{sum(compiles[n0:]):.2f} s; first batch {walls[0]:.3f} s "
            f"(compile + autotune + run), warm batch {walls[-1]:.3f} s")
        log(f"[{label}] bytes_in_use/peak_bytes_in_use per device: "
            + ", ".join(f"{dv.id}: {mem(dv, 'bytes_in_use')}"
                        f"/{mem(dv, 'peak_bytes_in_use')}"
                        for dv in jax.devices()))
        errs += check_samples(samples, d, label)
        results[label] = samples
    for rec in dispatch.autotune_report():
        log(f"[autotune] {rec['stage']} N={rec['n']} "
            f"χ=({rec['chi_l']},{rec['chi_r']}) d={rec['d']} "
            f"blocks={rec['blocks']} candidates={rec['candidates']} "
            f"rejected={rec['rejected']} error={rec['error']}")
    return results, errs


def one_chip(store, d: int) -> list[str]:
    results, errs = sample_all(store, d, [("pallas", "seq", "auto", None),
                                          ("xla", "seq", "xla", None)])
    errs += compare(results["pallas"], results["xla"], d, "pallas vs xla")
    exact = exact_marginals(store)
    for label, s in results.items():
        errs += against_exact(s, exact, d, label)
    return errs


def four_chips(store, d: int) -> list[str]:
    """tp_single and tp_double on a (1, 4) mesh, against two one-chip runs
    on the mesh's first chip: ``dp`` on a (1, 1) mesh (the segment runner,
    which draws from the same key stream as the TP schemes, so its samples
    should match them but for rounding) and ``seq`` (the streamed scan,
    whose key stream differs, so only the marginals are comparable).  The
    one-chip runs go last, so chip 0's full-Γ peak does not mask how the
    TP runs spread Γ over the four chips."""
    import jax

    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    if len(devs) != 4:
        return [f"--chips 4 needs 4 devices, JAX sees {len(devs)}"]
    tp = make_mesh((1, 4), ("data", "model"))
    one = make_mesh((1, 1), ("data", "model"), devices=devs[:1])
    results, errs = sample_all(store, d, [
        ("tp_single", "tp_single", "auto", tp),
        ("tp_double", "tp_double", "auto", tp),
        ("dp", "dp", "auto", one),
        ("seq", "seq", "auto", None)])
    for scheme in ("tp_single", "tp_double"):
        for ref in ("dp", "seq"):
            errs += compare(results[scheme], results[ref], d,
                            f"{scheme} vs {ref}")
    exact = exact_marginals(store)
    for label, s in results.items():
        errs += against_exact(s, exact, d, label)
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: tensor-parallel schemes on a (1, 4) mesh")
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no src/repro beside {__file__} — run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    from repro.configs.gbs import PRESETS
    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this run measures nothing on another backend",
              file=sys.stderr)
        return 1
    preset = PRESETS[PRESET]
    log(f"device: {dev.device_kind} × {len(devs)} ({dev.platform})")
    log(f"compile cache: {enable_compile_cache()}")
    log(f"config: {preset.name} χ={preset.chi} (padded to a multiple of "
        f"{PAD_TO}) d={preset.d}; M cut {preset.n_sites} → {M_SITES}, "
        f"N cut {preset.n_samples} → {N_MACRO} × {N_BATCH}")

    errs = []
    try:
        t0 = time.perf_counter()
        store = build_store(preset.chi, preset.d)
        log(f"store: {store.n_sites} sites of {store.meta(0)} bf16 in "
            f"{time.perf_counter() - t0:.1f} s")
        try:
            run = one_chip if args.chips == 1 else four_chips
            errs = run(store, preset.d)
        finally:
            store.close()
    except Exception as e:                  # report, never print a result
        import traceback
        traceback.print_exc()
        errs.append(f"{type(e).__name__}: {e}")
    finally:
        shutil.rmtree(STORE, ignore_errors=True)
    if errs:
        for e in errs:
            print("FAIL:", e, file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
