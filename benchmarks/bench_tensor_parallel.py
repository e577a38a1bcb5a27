"""Fig. 13 + Eq. 7 — single- vs double-site tensor parallel overhead.

Measured: per-site collective wire bytes of each schedule from the compiled
SPMD program (the structural quantity behind the paper's bandwidth
argument); the Eq. 7 overhead model then picks the schedule per hardware.

Paper's claim to reproduce: single-site moves (N·χ)·(p−1)/p... per site
(measured env, a factor d smaller than the unmeasured (N·χ·d) the
double-site AllReduce moves every *two* sites) — so the *average volume is
equal*, and the choice is latency (count) vs bandwidth-efficiency.
"""
from __future__ import annotations

import textwrap

from benchmarks.common import BENCH_JSON, append_bench_record, emit, \
    run_child
from repro.core import perfmodel as PM

_CHILD = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    jax.config.update("jax_enable_x64", True)
    from repro.core import mps as M, parallel as PP
    from repro.launch import hloanalysis as H

    scheme = "__SCHEME__"
    p2 = __P2__
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, p2), ("data", "model"))
    SITES, CHI, D, N = 8, 128, 3, 512
    mps = M.random_linear_mps(jax.random.key(0), SITES, CHI, D,
                              dtype=jnp.float32)

    def run(g, lam, seed):
        # internal data plane: this bench lowers the scheme program for HLO
        # analysis, not the repro.api session orchestration
        return PP._multilevel_sample(mesh, M.MPS(g, lam, "linear"), N,
                                     jax.random.key(seed),
                                     PP.ParallelConfig(scheme))
    c = jax.jit(run).lower(mps.gammas, mps.lambdas, 0).compile()
    cost = H.analyze(c.as_text())
    print(json.dumps({
        "wire": cost.collective_wire_bytes,
        "counts": cost.n_collectives,
        "per_type": cost.per_collective,
        "sites": SITES, "n": N, "chi": CHI, "d": D,
    }))
""")


def run(quick: bool = True, json_path: str | None = BENCH_JSON) -> None:
    p2 = 4
    results = {}
    for scheme in ("tp_single", "tp_double"):
        out = run_child(_CHILD.replace("__SCHEME__", scheme)
                        .replace("__P2__", str(p2)), devices=p2)
        results[scheme] = out
        per_site = out["wire"] / out["sites"]
        counts = {k: v / out["sites"] for k, v in out["counts"].items()}
        emit(f"fig13_{scheme}_wire_per_site", 0.0,
             f"{per_site:.0f}B|" + "|".join(
                 f"{k}={v:.2f}/site" for k, v in sorted(counts.items())))

    # the paper's structural claim: double-site halves the big-collective
    # count; average volumes are comparable
    n_single = sum(results["tp_single"]["counts"].values())
    n_double = sum(results["tp_double"]["counts"].values())
    emit("fig13_collective_count_ratio", 0.0,
         f"single/double={n_single / max(n_double, 1):.2f}")

    # Eq. 7 scheme choice on published hardware profiles
    w = PM.Workload(n_samples=10_000_000, n_sites=288, chi=10_000, d=3,
                    micro_batch=20_000)
    nvlink = PM.Hardware(peak_flops=156e12, hbm_bw=2039e9,
                         allreduce_bw=401e9, reducescatter_bw=46e9)
    emit("eq7_choice_nvlink_a100", 0.0, PM.choose_tp_scheme(w, nvlink, p2=4))
    v5e = PM.TPU_V5E
    emit("eq7_choice_tpu_v5e", 0.0, PM.choose_tp_scheme(w, v5e, p2=4))
    for scheme in ("single", "double"):
        o = PM.eq7_tp_overhead(w, v5e, 4, scheme)
        emit(f"eq7_overhead_v5e_{scheme}_p4", 0.0, f"{o:.2%}")

    append_bench_record(
        json_path, "tensor_parallel",
        {"p2": p2, "sites": results["tp_single"]["sites"],
         "chi": results["tp_single"]["chi"],
         "d": results["tp_single"]["d"],
         "samples": results["tp_single"]["n"], "quick": bool(quick)},
        wire_bytes_per_site={
            s: results[s]["wire"] / results[s]["sites"]
            for s in ("tp_single", "tp_double")},
        collective_count_ratio=n_single / max(n_double, 1))


if __name__ == "__main__":
    from benchmarks.common import header
    header()
    run()
