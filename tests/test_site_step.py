"""Fused site-step pipeline + kernel dispatch layer.

Three layers of coverage:

* the fused Pallas kernels vs the pure-jnp oracle (interpret mode) across
  linear/born semantics and *awkward* shapes — non-power-of-two and
  non-multiple-of-tile χ, which the old ``test_kernels`` sweeps never hit;
* the dispatch registry + autotuner (heuristic table, cache behaviour,
  VMEM-model shrinking, graceful fallback for cells with no Pallas impl);
* the §4.1 seed contract across the dispatch boundary: ``kernels="pallas"``
  ≡ ``kernels="xla"`` bit-for-bit across seq/dp/tp_single/tp_double ×
  static/dynamic-χ (multi-device cells in a forced-8-device subprocess).
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dynamic_bond as DB
from repro.core import mps as M
from repro.core import sampler as S
from repro.kernels import dispatch, ref
from repro.kernels.site_step import measure_probs, site_step_born, \
    site_step_linear


# ---------------------------------------------------------------------------
# kernel vs oracle — interpret mode, awkward shapes included
# ---------------------------------------------------------------------------

# (n, chi, d): 96 = 3·32 non-power-of-two; 24/12 non-multiples of any MXU
# tile; 7 prime (blocks degrade to the whole dimension)
_SHAPES = [(8, 16, 2), (16, 96, 3), (32, 24, 4), (8, 12, 3), (16, 7, 2)]


def _operands(n, chi, d, dtype=jnp.float64, seed=0):
    k1, k2, k3, k4 = jax.random.split(jax.random.key(seed), 4)
    env = jax.random.uniform(k1, (n, chi), dtype=dtype)
    gamma = jax.random.uniform(k2, (chi, chi, d), dtype=dtype)
    lam = jax.random.uniform(k3, (chi,), dtype=dtype)
    u = jax.random.uniform(k4, (n,), dtype=dtype)
    return env, gamma, lam, u


def _blocks(n, chi):
    cfg = dispatch._heuristic("site_step", n, chi, chi, 3, 8, 1)
    return dict(bn=min(cfg.bn, 8), br=cfg.br, bl=cfg.bl)


@pytest.mark.parametrize("n,chi,d", _SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_site_step_linear_vs_ref(n, chi, d, dtype):
    env, gamma, lam, u = _operands(n, chi, d, dtype)
    e_r, s_r, dl_r = ref.site_step_ref(env, gamma, lam, u, "linear")
    e_k, s_k, dl_k = site_step_linear(env, gamma, lam, u, interpret=True,
                                      **_blocks(n, chi))
    tol = 1e-4 if dtype == jnp.float32 else 1e-9
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_r))
    np.testing.assert_allclose(np.asarray(e_k), np.asarray(e_r), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(dl_k), np.asarray(dl_r), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("n,chi,d", _SHAPES)
def test_site_step_born_vs_ref(n, chi, d):
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.key(1), 5)
    env = (jax.random.normal(k1, (n, chi), dtype=jnp.float64)
           + 1j * jax.random.normal(k5, (n, chi), dtype=jnp.float64))
    gamma = (jax.random.normal(k2, (chi, chi, d), dtype=jnp.float64)
             + 1j * jax.random.normal(k3, (chi, chi, d), dtype=jnp.float64))
    lam = jax.random.uniform(k3, (chi,), dtype=jnp.float64) + 0.5
    u = jax.random.uniform(k4, (n,), dtype=jnp.float64)
    e_r, s_r, dl_r = ref.site_step_ref(env, gamma, lam, u, "born")
    e_k, s_k, dl_k = site_step_born(env, gamma, lam, u, interpret=True,
                                    **_blocks(n, chi))
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_r))
    np.testing.assert_allclose(np.asarray(e_k), np.asarray(e_r), rtol=1e-9,
                               atol=1e-9)
    np.testing.assert_allclose(np.asarray(dl_k), np.asarray(dl_r), rtol=1e-9,
                               atol=1e-9)


def test_site_step_bf16_compute_dtype():
    """The §3.3 MXU tier: bf16 GEMM inputs, fp32 accumulate, inside the
    fused kernel — matches the XLA mixed-precision site step closely."""
    env, gamma, lam, u = _operands(16, 32, 3, jnp.float32, seed=3)
    e_k, s_k, _ = site_step_linear(env, gamma, lam, u, bn=8, br=16, bl=16,
                                   compute_dtype=jnp.bfloat16,
                                   interpret=True)
    e_r, s_r, _ = ref.site_step_ref(env, gamma, lam, u, "linear")
    assert e_k.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(e_k), np.asarray(e_r), atol=3e-2)


def test_measure_probs_vs_ref():
    for (n, L, d) in [(16, 32, 3), (8, 24, 4), (32, 7, 2)]:
        k1, k2 = jax.random.split(jax.random.key(4))
        env = jax.random.uniform(k1, (n, L), dtype=jnp.float64)
        w = jax.random.uniform(k2, (L, d), dtype=jnp.float64)
        cfg = dispatch._heuristic("measure", n, L, L, d, 8, 1)
        out = measure_probs(env, w, bn=cfg.bn, bl=cfg.bl, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(env @ w),
                                   rtol=1e-12)


def test_scaling_none_and_global_reject():
    env, gamma, lam, u = _operands(8, 16, 2)
    e_k, _, dl_k = site_step_linear(env, gamma, lam, u, bn=8, br=16, bl=16,
                                    scaling="none", interpret=True)
    e_r, _, dl_r = ref.site_step_ref(env, gamma, lam, u, scaling="none")
    np.testing.assert_allclose(np.asarray(e_k), np.asarray(e_r), rtol=1e-9)
    np.testing.assert_array_equal(np.asarray(dl_k), 0.0)
    with pytest.raises(ValueError, match="scaling"):
        site_step_linear(env, gamma, lam, u, scaling="global",
                         interpret=True)


# ---------------------------------------------------------------------------
# dispatch registry + autotuner
# ---------------------------------------------------------------------------

def test_registry_resolution_and_fallback():
    # every stage has an xla cell for linear
    for stage in dispatch.STAGES:
        assert dispatch.get_site_op(stage, "linear", "xla")
    # born split-K TP cells have no Pallas kernel → silent xla fallback
    # (|Σ·|² ≠ Σ|·|²: fusing the measure into the split-K GEMM is invalid)
    assert (dispatch.get_site_op("contract_measure", "born", "pallas")
            is dispatch.get_site_op("contract_measure", "born", "xla"))
    # born site_step DOES have a Pallas cell
    assert (dispatch.get_site_op("site_step", "born", "pallas")
            is not dispatch.get_site_op("site_step", "born", "xla"))
    with pytest.raises(ValueError, match="kernels must be one of"):
        dispatch.resolve_kernels("cuda")
    assert dispatch.resolve_kernels("auto") in ("pallas", "xla")


def test_autotuner_heuristic_divides_and_caches():
    dispatch.clear_autotune_cache()
    cfg = dispatch.autotune("site_step", n=96, chi_l=24, chi_r=24, d=3,
                            dtype=jnp.float64)
    assert 96 % cfg.bn == 0 and 24 % cfg.br == 0 and 24 % cfg.bl == 0
    stats0 = dispatch.autotune_cache_stats()
    assert stats0["entries"] == 1 and stats0["misses"] == 1
    cfg2 = dispatch.autotune("site_step", n=96, chi_l=24, chi_r=24, d=3,
                             dtype=jnp.float64)
    assert cfg2 == cfg
    assert dispatch.autotune_cache_stats()["hits"] == 1
    # prime χ degrades to whole-dimension blocks, still legal
    cfg3 = dispatch.autotune("site_step", n=8, chi_l=7, chi_r=7, d=2,
                             dtype=jnp.float64)
    assert 7 % cfg3.br == 0 and 7 % cfg3.bl == 0


def test_autotuner_vmem_model_shrinks_bn():
    """At large χ the resident temp slab dominates — BN must shrink until
    the working-set model fits the VMEM budget."""
    dispatch.clear_autotune_cache()
    cfg = dispatch.autotune("site_step", n=4096, chi_l=8192, chi_r=8192,
                            d=4, dtype=jnp.float32)
    bytes_ = dispatch._working_set_bytes("site_step", cfg, 8192, 4, 4, 1)
    assert bytes_ <= dispatch._VMEM_BUDGET_BYTES
    assert cfg.bn < 256                 # it had to shrink


def test_warm_site_step_seeds_cache():
    dispatch.clear_autotune_cache()
    from repro.kernels.site_impls import warm_site_step
    warm_site_step(64, 16, 3, jnp.float64, semantics="linear")
    assert dispatch.autotune_cache_stats()["entries"] == 1
    # the traced lookup that follows is a pure cache hit
    dispatch.autotune("site_step", n=64, chi_l=16, chi_r=16, d=3,
                      dtype=jnp.float64)
    assert dispatch.autotune_cache_stats()["hits"] == 1


# ---------------------------------------------------------------------------
# seed-bit-identity: kernels="pallas" ≡ kernels="xla" (§4.1 across the
# kernel boundary) — seq / dynamic-χ in-process, DP/TP in a subprocess
# ---------------------------------------------------------------------------

def test_seq_pallas_equals_xla(linear_mps_10x6):
    key = jax.random.key(11)
    a = S.sample(linear_mps_10x6, 48, key, S.SamplerConfig(kernels="xla"))
    b = S.sample(linear_mps_10x6, 48, key, S.SamplerConfig(kernels="pallas"))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_seq_born_pallas_equals_xla(born_mps_6x4):
    key = jax.random.key(12)
    cfg = dict(semantics="born")
    a = S.sample(born_mps_6x4, 32, key, S.SamplerConfig(kernels="xla", **cfg))
    b = S.sample(born_mps_6x4, 32, key,
                 S.SamplerConfig(kernels="pallas", **cfg))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dynamic_chi_pallas_equals_xla(linear_mps_10x6):
    """Staged (dynamic-χ) walks hit several kernel shapes in one chain —
    every bucket goes through the same dispatch."""
    prof = DB.bucketize(DB.area_law_profile(10, 6, n_photon=1.0),
                        [2, 3, 6])
    key = jax.random.key(13)
    a = DB.sample_staged(linear_mps_10x6, prof, 32, key,
                         S.SamplerConfig(kernels="xla"))
    b = DB.sample_staged(linear_mps_10x6, prof, 32, key,
                         S.SamplerConfig(kernels="pallas"))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_session_resolves_kernels(linear_mps_10x6):
    from repro import api
    with api.SamplingSession(linear_mps_10x6) as session:
        plan = session.plan(16)
        assert plan.kernels in ("pallas", "xla")      # AUTO resolved
        assert plan.sampler_config.kernels == plan.kernels
        assert session.explain(16)["kernels"] == plan.kernels
    cfg = api.SamplerConfig(kernels="pallas")
    with api.SamplingSession(linear_mps_10x6, cfg) as session:
        key = jax.random.key(3)
        out = session.sample(16, key)
    with api.SamplingSession(linear_mps_10x6,
                             api.SamplerConfig(kernels="xla")) as session:
        ref_out = session.sample(16, jax.random.key(3))
    np.testing.assert_array_equal(out, ref_out)


_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np
    from repro.core import dynamic_bond as DB
    from repro.core import mps as M, parallel as PP, sampler as S
    from repro.launch.mesh import make_host_mesh
    from repro import api

    out = {}
    m = M.random_linear_mps(jax.random.key(0), n_sites=6, chi=8, d=3)
    mb = M.random_born_mps(jax.random.key(2), 4, 8, 2)
    mesh = make_host_mesh(model=4)           # 2 data x 4 model
    key = jax.random.key(7)

    for scheme in ("dp", "tp_single", "tp_double"):
        pcs = [(scheme, PP.ParallelConfig(scheme))]
        if scheme == "tp_single":
            pcs.append((scheme + "_mf",
                        PP.ParallelConfig(scheme, measure_first=True)))
        if scheme in ("tp_single", "tp_double"):
            # §3.3.2-on-the-wire cast: the one cell where measure-of-psum vs
            # psum-of-partial-measures could diverge if mishandled
            pcs.append((scheme + "_wire",
                        PP.ParallelConfig(scheme, wire_dtype=jnp.bfloat16)))
        for tag, pc in pcs:
            x = PP._multilevel_sample(mesh, m, 64, key, pc,
                                      S.SamplerConfig(kernels="xla"))
            p = PP._multilevel_sample(mesh, m, 64, key, pc,
                                      S.SamplerConfig(kernels="pallas"))
            out[tag] = bool(jnp.all(x == p))
            xb = PP._multilevel_sample(mesh, mb, 32, key, pc,
                S.SamplerConfig(semantics="born", kernels="xla"))
            pb = PP._multilevel_sample(mesh, mb, 32, key, pc,
                S.SamplerConfig(semantics="born", kernels="pallas"))
            out["born_" + tag] = bool(jnp.all(xb == pb))

    # dynamic-χ under DP/TP through the session front door (stage
    # boundaries even so the profile also composes with tp_double)
    prof = (4, 4, 8, 8, 4, 4)
    for scheme in ("dp", "tp_single", "tp_double"):
        res = {}
        for kern in ("xla", "pallas"):
            cfg = api.SamplerConfig(scheme=scheme, kernels=kern,
                                    chi_profile=prof)
            with api.SamplingSession(m, cfg, mesh=mesh) as session:
                res[kern] = session.sample(64, key)
        out["dyn_" + scheme] = bool(
            np.array_equal(res["xla"], res["pallas"]))
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def kernel_matrix_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("cell", [
    "dp", "tp_single", "tp_single_mf", "tp_single_wire", "tp_double",
    "tp_double_wire",
    "born_dp", "born_tp_single", "born_tp_single_mf", "born_tp_single_wire",
    "born_tp_double", "born_tp_double_wire",
    "dyn_dp", "dyn_tp_single", "dyn_tp_double",
])
def test_kernel_bitidentity_matrix(kernel_matrix_results, cell):
    """kernels="pallas" ≡ kernels="xla" per seed, every schedule cell."""
    assert kernel_matrix_results[cell], (cell, kernel_matrix_results)


# ---------------------------------------------------------------------------
# TPU rules the interpret mode cannot see: legal tiles, VMEM padding,
# compiler rejections, f64
# ---------------------------------------------------------------------------

def test_legal_tiles_and_lane_padded_vmem_model():
    """Blocks are (8, 128)-aligned divisors or whole dims; a (BN, 1)
    column costs BN·128 words; χ = 10⁴ has no legal tiling that fits, and
    the heuristic says so (pad the bond) instead of the TPU compiler."""
    assert dispatch._legal_tile(10240, 512, 128) == 512
    assert dispatch._legal_tile(2560, 1024, 128) == 640
    assert dispatch._legal_tile(10000, 512, 128) == 10000
    assert dispatch._legal_tile(96, 256, 8) == 96
    assert dispatch._padded_bytes((64, 1), 4) == 64 * 128 * 4
    assert dispatch._padded_bytes((4, 512), 2) == 16 * 512 * 2
    cfg = dispatch._heuristic("site_step", 4096, 10240, 10240, 4, 4, 1)
    assert cfg.br % 128 == 0 and cfg.bl % 128 == 0 and cfg.bn % 8 == 0
    assert (dispatch._working_set_bytes("site_step", cfg, 10240, 4, 4, 1)
            <= dispatch._VMEM_BUDGET_BYTES)
    with pytest.raises(ValueError, match="pad_bond"):
        dispatch._heuristic("site_step", 4096, 10000, 10000, 4, 4, 1)


def test_autotuner_counts_rejections_and_raises_when_all_fail(monkeypatch):
    dispatch.clear_autotune_cache()
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    seen = []

    def probe(cfg):
        seen.append(cfg)
        if len(seen) == 1:
            raise RuntimeError("Mosaic: block too large")
        return lambda: jnp.zeros(())

    cfg = dispatch.autotune("site_step", n=512, chi_l=1024, chi_r=1024, d=4,
                            dtype=jnp.float32, probe=probe)
    (rec,) = dispatch.autotune_report()
    assert rec["rejected"] == 1 and rec["candidates"] == len(seen)
    assert "block too large" in rec["error"]
    assert rec["blocks"] == dataclasses.asdict(cfg) and cfg != seen[0]

    refused = []

    def refuse(cfg):
        refused.append(cfg)
        raise RuntimeError("Mosaic: no")
    with pytest.raises(RuntimeError, match="rejected all"):
        dispatch.autotune("collapse", n=512, chi_l=1024, chi_r=1024, d=4,
                          dtype=jnp.float32, probe=refuse)
    assert refused
    assert dispatch.autotune_cache_stats()["rejected"] == 1 + len(refused)
    dispatch.clear_autotune_cache()


def test_compiled_kernel_refuses_f64_clearly():
    env, gamma, lam, u = _operands(8, 16, 2, jnp.float64)
    with pytest.raises(TypeError, match="float64"):
        site_step_linear(env, gamma, lam, u, interpret=False)
    with pytest.raises(TypeError, match="float64"):
        measure_probs(env, gamma[:, 0, :], interpret=False)
