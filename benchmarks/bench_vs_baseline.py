"""Tables 2/3 — FastMPS data parallel vs the [19] site-bound pipeline.

Two comparisons:
  1. *measured* at container scale: both schemes on the same 8 forced host
     devices, same seeds → identical samples; derived = wall-time ratio.
     (One physical core serializes both, so this compares total work +
     scheduling overhead, which is exactly what differs between them.)
  2. *modelled* at paper scale (Eqs. 1/2 on A100 constants) for the
     Jiuzhang2/B-M288 rows; derived = predicted speedup (paper: ~10×).
"""
from __future__ import annotations

import textwrap

from benchmarks.common import emit, run_child
from repro.core import perfmodel as PM

_CHILD = textwrap.dedent("""
    import json, time
    import jax, jax.numpy as jnp
    jax.config.update("jax_enable_x64", True)
    from repro.core import mps as M, parallel as PP

    SITES, CHI, D, N = 8, 96, 3, 640
    mps = M.random_linear_mps(jax.random.key(0), SITES, CHI, D,
                              dtype=jnp.float32)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))

    def timed(make):
        fn = jax.jit(lambda g, lam: make(M.MPS(g, lam, "linear")))
        out = fn(mps.gammas, mps.lambdas)
        jax.block_until_ready(out)
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(mps.gammas, mps.lambdas))
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[1], out

    # the internal data-plane entry points: this bench times the jitted
    # scheme programs themselves, not the repro.api session orchestration
    t_dp, s_dp = timed(lambda m: PP._multilevel_sample(
        mesh, m, N, jax.random.key(9), PP.ParallelConfig("dp")))
    # n_macro = 8 so [19]'s macro-batch partition matches DP's 8 shards —
    # then both schemes emit bit-identical samples
    t_19, s_19 = timed(lambda m: PP._baseline19_sample(
        mesh, m, N, jax.random.key(9), n_macro=8))
    print(json.dumps({"t_dp": t_dp, "t_19": t_19,
                      "same": bool(jnp.all(s_dp == s_19))}))
""")


def run(quick: bool = True) -> None:
    out = run_child(_CHILD, devices=8)
    emit("table2_measured_dp_8dev", out["t_dp"],
         f"samples_identical={out['same']}")
    emit("table2_measured_baseline19_8dev", out["t_19"],
         f"{out['t_19'] / out['t_dp']:.2f}x_slower")

    # paper-scale model rows (A100 constants).  [19] runs fp64-ish fixed-χ
    # with generic expm; FastMPS = data parallel with the overlap-sized N₁
    # (§3.1's rule) × the three multiplicative optimizations (Fig. 11):
    # TF32-tier GEMMs, dynamic χ (Table 1 comp ratio), optimized expm.
    import dataclasses
    rows = {
        "jiuzhang2": (PM.Workload(10_000_000, 144, 10_000, 4,
                                  bytes_per_elt=16), 0.2023),
        "b_m288": (PM.Workload(10_000_000, 288, 10_000, 4,
                               bytes_per_elt=16), 0.8339),
        "m8176": (PM.Workload(10_000_000, 8_176, 10_000, 3,
                              bytes_per_elt=16), 0.7961),
    }
    fp64 = dataclasses.replace(PM.A100, peak_flops=19.5e12)   # A100 fp64 TC
    for name, (w, comp_ratio) in rows.items():
        p = w.n_sites                                          # equal resources
        # [19] at its own operating point (N₁ ~ 2e4, fp64, fixed χ)
        t19 = PM.eq1_model_parallel(w, fp64)
        # scheme change alone: same fp64 numerics, N₁ sized by the overlap
        # rule for fp64 throughput (§3.1), capped at N/p
        n1_64 = min(max(w.macro_batch,
                        PM.min_macro_batch_for_overlap(w, fp64)),
                    w.n_samples // p)
        t_scheme = PM.eq2_data_parallel(
            dataclasses.replace(w, macro_batch=n1_64), fp64, p=p)
        # full FastMPS: TF32-tier GEMMs + FP16 Γ storage (4 B/complex elt,
        # §3.3.2 quarters I/O) + dynamic χ (Table 1 comp ratio)
        n1_fast = min(max(w.macro_batch,
                          PM.min_macro_batch_for_overlap(
                              w, PM.A100, storage_bytes=4)),
                      w.n_samples // p)
        t_fast = PM.eq2_data_parallel(
            dataclasses.replace(w, macro_batch=n1_fast), PM.A100, p=p,
            storage_bytes=4) * comp_ratio
        emit(f"table2_model_{name}", t_fast,
             f"scheme_only={t19 / t_scheme:.1f}x|full={t19 / t_fast:.1f}x"
             f"|N1={n1_fast}")


if __name__ == "__main__":
    from benchmarks.common import header
    header()
    run()
