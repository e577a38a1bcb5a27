"""Multi-level parallel GBS sampling: DP × TP on an 8-device mesh.

Demonstrates the paper's core contribution — data parallelism over samples
combined with tensor parallelism over the bond dimension — plus dynamic
bond dimensions, all through the one :class:`repro.api.SamplingSession`
front door.  Forces 8 host devices, so run it as a standalone script (not
under pytest):

    PYTHONPATH=src python examples/gbs_multilevel.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.core import dynamic_bond as DB  # noqa: E402
from repro.core import mps as M  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def main() -> None:
    sites, chi, d, n = 16, 64, 3, 1024
    mps = M.gbs_like_mps(jax.random.key(0), sites, chi, d)
    key = jax.random.key(1)

    # 2 data groups × 4-way tensor parallel over χ (paper Fig. 4)
    mesh = make_mesh((2, 4), ("data", "model"))
    print(f"mesh: {dict(mesh.shape)}")

    # scheme=AUTO lets the Eq. 7 overhead selector pick single- vs
    # double-site TP for the configured hardware profile
    with api.SamplingSession(mps, mesh=mesh) as session:
        plan = session.plan(n)
        print(f"Eq. 7 schedule choice for v5e: {plan.scheme} "
              f"(p1={plan.p1}, p2={plan.p2})")
        out_tp = session.sample(n, key)

    # every schedule draws the same randoms per site: pure DP from the same
    # seed is bit-identical (paper §4.1 seed consistency)
    with api.SamplingSession(mps, api.SamplerConfig(scheme="dp"),
                             mesh=mesh) as session:
        out_dp = session.sample(n, key)
    print(f"TP == pure DP samples: {bool(np.all(out_tp == out_dp))}")

    # dynamic bond dimensions (§3.4.2): the Table 1 accounting, then the
    # same DP×TP session with a bucketed per-site χ profile
    prof = DB.area_law_profile(sites, chi, n_photon=1.0)
    buck = DB.bucketize(prof, [16, 32, 64])
    print("Table-1 metrics:", {k: round(v, 3) for k, v in
                               DB.table1_metrics(prof, chi).items()})
    # (tp_single: any χ-stage boundary works; tp_double additionally needs
    # even-aligned stages so site pairs never straddle a χ transition)
    with api.SamplingSession(
            mps, api.SamplerConfig(scheme="tp_single",
                                   chi_profile=tuple(int(c) for c in buck)),
            mesh=mesh) as session:
        staged = session.sample(n, key)
        print(f"staged sampler output: {staged.shape} "
              f"({session.plan(n).scheme} over "
              f"{len(session.plan(n).stages)} χ-stages)")

    # per-site mean photon number (the Fig. 6-style diagnostic)
    mean_photon = np.asarray(out_tp).mean(axis=0)
    print(f"mean photons/site: min {mean_photon.min():.3f} "
          f"max {mean_photon.max():.3f} (edges lower — area law)")


if __name__ == "__main__":
    main()
