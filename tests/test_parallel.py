"""Multi-level parallel schemes (paper §3.1–3.2).

Multi-device tests run in a subprocess with XLA_FLAGS forcing 8 host
devices (the main pytest process must keep the real single-device view).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np
    from repro.core import mps as M, parallel as PP, sampler as S
    from repro.launch.mesh import make_host_mesh

    out = {}
    m = M.random_linear_mps(jax.random.key(0), n_sites=6, chi=8, d=3)
    mesh = make_host_mesh(model=4)           # 2 data x 4 model
    key = jax.random.key(7)
    cfg = S.SamplerConfig()
    dp = PP._multilevel_sample(mesh, m, 64, key, PP.ParallelConfig("dp"), cfg)
    ts = PP._multilevel_sample(mesh, m, 64, key, PP.ParallelConfig("tp_single"), cfg)
    td = PP._multilevel_sample(mesh, m, 64, key, PP.ParallelConfig("tp_double"), cfg)
    out["dp_eq_single"] = bool(jnp.all(dp == ts))
    out["dp_eq_double"] = bool(jnp.all(dp == td))
    out["shape_ok"] = list(dp.shape) == [64, 6]

    # born semantics through both TP schedules (psum-before-square correctness)
    mb = M.random_born_mps(jax.random.key(2), 4, 8, 2)
    cb = S.SamplerConfig(semantics="born")
    dpb = PP._multilevel_sample(mesh, mb, 32, key, PP.ParallelConfig("dp"), cb)
    tsb = PP._multilevel_sample(mesh, mb, 32, key, PP.ParallelConfig("tp_single"), cb)
    tdb = PP._multilevel_sample(mesh, mb, 32, key, PP.ParallelConfig("tp_double"), cb)
    out["born_dp_eq_single"] = bool(jnp.all(dpb == tsb))
    out["born_dp_eq_double"] = bool(jnp.all(dpb == tdb))

    # [19] baseline pipeline == per-macro-batch sequential chain
    mesh19 = jax.make_mesh((6,), ("data",))
    n, n1 = 60, PP.config_macro_batches(60)
    b19 = PP._baseline19_sample(mesh19, m, n, jax.random.key(9))
    bk = jax.random.split(jax.random.key(9), n1)
    ref = jnp.concatenate([S.sample(m, n // n1, bk[b]) for b in range(n1)], 0)
    out["baseline19_eq_seq"] = bool(jnp.all(b19 == ref))

    # single-device-sampler equivalence: DP with same per-shard base keys
    shard_keys = jax.random.split(key, 2)
    seq = jnp.concatenate([S.sample(m, 32, shard_keys[i], cfg) for i in range(2)], 0)
    out["dp_eq_sequential"] = bool(jnp.all(dp == seq))

    # ---- seed-consistency matrix (paper §4.1): the in-memory reference vs
    # every schedule, the streaming engine under every schedule, and a
    # kill-and-resume through sample_chain/sample_resumable ----
    import tempfile
    import numpy as np
    from repro.data.gamma_store import GammaStore
    from repro.engine import StreamPlan, StreamingEngine

    ref = np.asarray(seq)                   # == dp == tp_single == tp_double
    root = tempfile.mkdtemp()
    wstore = GammaStore(root, storage_dtype=jnp.float64,
                        compute_dtype=jnp.float64)
    wstore.write_mps(m)
    wstore.close()
    consistency = {
        "dp": bool(np.array_equal(np.asarray(dp), ref)),
        "tp_single": bool(np.array_equal(np.asarray(ts), ref)),
        "tp_double": bool(np.array_equal(np.asarray(td), ref)),
    }
    for scheme in ("dp", "tp_single", "tp_double"):
        store = GammaStore(root, storage_dtype=jnp.float64,
                           compute_dtype=jnp.float64)
        eng = StreamingEngine(store, plan=StreamPlan(segment_len=2,
                                                     scheme=scheme),
                              mesh=mesh)
        consistency["stream_" + scheme] = bool(
            np.array_equal(eng.sample(64, key), ref))
        eng.close()

    # kill after 2 segments, resume from the checkpoint: still == ref
    ck = tempfile.mkdtemp()
    store = GammaStore(root, storage_dtype=jnp.float64,
                       compute_dtype=jnp.float64)
    eng = StreamingEngine(store, plan=StreamPlan(segment_len=2, scheme="dp",
                                                 checkpoint_every=1),
                          mesh=mesh, checkpoint_dir=ck)
    eng.sample(64, key, stop_after_segments=2)
    eng.close()
    store = GammaStore(root, storage_dtype=jnp.float64,
                       compute_dtype=jnp.float64)
    eng = StreamingEngine(store, plan=StreamPlan(segment_len=2, scheme="dp",
                                                 checkpoint_every=1),
                          mesh=mesh, checkpoint_dir=ck)
    consistency["stream_resume"] = bool(
        np.array_equal(eng.sample(64, key, resume=True), ref))
    eng.close()

    # the sampler-level restart primitive the engine builds on
    st0 = S.init_state(m, 32, shard_keys[0])
    head = M.MPS(m.gammas[:3], m.lambdas[:3], m.semantics)
    part = S.sample_chain(head, st0, cfg)
    rest = S.sample_resumable(m, part.state, 3, cfg)
    stitched = jnp.concatenate([part.samples, rest.samples], 0).T
    consistency["sample_resumable"] = bool(
        np.array_equal(np.asarray(stitched), ref[:32]))
    out["consistency"] = consistency
    print(json.dumps(out))
""")
_CHILD = "import json\n" + _CHILD


@pytest.fixture(scope="module")
def child_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_dp_tp_single_seed_identical(child_results):
    assert child_results["dp_eq_single"]


def test_dp_tp_double_seed_identical(child_results):
    assert child_results["dp_eq_double"]


def test_output_shape(child_results):
    assert child_results["shape_ok"]


def test_born_semantics_tp(child_results):
    assert child_results["born_dp_eq_single"]
    assert child_results["born_dp_eq_double"]


def test_baseline19_pipeline_exact(child_results):
    assert child_results["baseline19_eq_seq"]


def test_dp_equals_sequential_per_shard(child_results):
    assert child_results["dp_eq_sequential"]


@pytest.mark.parametrize("schedule", [
    "dp", "tp_single", "tp_double",
    "stream_dp", "stream_tp_single", "stream_tp_double",
    "stream_resume", "sample_resumable",
])
def test_seed_consistency_across_schedules(child_results, schedule):
    """Paper §4.1: the per-shard in-memory sampler, every DP/TP schedule,
    the streaming engine under each scheme, and both restart paths emit
    bit-identical samples from one seed."""
    assert child_results["consistency"][schedule]


def test_meshes_use_auto_axes():
    """jax.make_mesh defaults to Explicit axes, under which slicing a
    sharded env (dynamic_bond.fit_env) raises; the repo's meshes are Auto."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_host_mesh, make_mesh
    for mesh in (make_host_mesh(), make_mesh((1,), ("data",))):
        assert all(t == AxisType.Auto for t in mesh.axis_types)
