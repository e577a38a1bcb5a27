"""Fig. 12 — weak/strong scaling of data-parallel sampling.

One physical CPU core hosts the forced devices, so wall-clock "speedup" is
unmeasurable here; what IS measurable — and what actually determines the
paper's ≥95 % efficiency — is the *communication structure*: DP sampling
must compile to a per-shard program with **zero collectives in the chain
loop**.  derived reports the collective wire bytes per sample (0 ⇒
perfectly scalable) plus the Eq. 2 model efficiency on v5e constants.
"""
from __future__ import annotations

import textwrap

from benchmarks.common import emit, run_child
from repro.core import perfmodel as PM

_CHILD = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    jax.config.update("jax_enable_x64", True)
    from repro.core import mps as M, parallel as PP, sampler as S
    from repro.launch import hloanalysis as H
    from repro.launch.mesh import make_host_mesh, make_mesh

    p = __P__
    mesh = make_mesh((__P__,), ("data",))
    mps = M.random_linear_mps(jax.random.key(0), 8, 64, 3, dtype=jnp.float32)
    n = 256 * p                     # weak scaling: 256 samples per shard

    def run(g, lam, seed):
        # internal data plane: this bench lowers the scheme program for HLO
        # analysis, not the repro.api session orchestration
        return PP._multilevel_sample(mesh, M.MPS(g, lam, "linear"), n,
                                     jax.random.key(seed),
                                     PP.ParallelConfig("dp"))
    c = jax.jit(run).lower(mps.gammas, mps.lambdas, 0).compile()
    cost = H.analyze(c.as_text())
    print(json.dumps({"wire": cost.collective_wire_bytes,
                      "n_coll": sum(cost.n_collectives.values()),
                      "per_type": cost.per_collective}))
""")


def run(quick: bool = True) -> None:
    for p in (2, 4, 8):
        out = run_child(_CHILD.replace("__P__", str(p)), devices=p)
        emit(f"fig12_dp_collectives_p{p}", 0.0,
             f"wire_bytes={out['wire']:.0f}|n_coll={out['n_coll']:.0f}")

    # Eq.2-model strong-scaling efficiency on TPU v5e (paper's ≥95 % claim)
    w = PM.Workload(n_samples=10_000_000, n_sites=8176, chi=2000, d=3,
                    macro_batch=20_000, micro_batch=5_000)
    t1 = PM.eq2_data_parallel(w, PM.TPU_V5E, p=1)
    for p in (16, 256, 500):
        tp = PM.eq2_data_parallel(w, PM.TPU_V5E, p=p)
        eff = t1 / (p * tp)
        emit(f"fig12_eq2_strong_eff_p{p}", tp, f"{eff:.1%}")


if __name__ == "__main__":
    from benchmarks.common import header
    header()
    run()
