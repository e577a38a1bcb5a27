"""The comparison that decides ``correct``: the program passes it, the
control (the reference with float8 GEMM inputs) fails it, and so does the
timed path with each fault a cell can have planted underneath.  Tiny width
on the CPU; the readings at the cells' own sizes are in PERF.md."""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

# Tiny width on the CPU, one batch of 8192 rows, every row checked: over
# fifteen seeds the program read 0 to 1.2e-5 and the control 3.4e-5 to
# 9.6e-4; the three seeds below read at most 1.2e-5 and at least 2.4e-4.
LIMIT = 5e-5


def tiny_cell(root, *, chi=250, n=8192, mesh=None):
    with open(os.path.join(ROOT, "bench", "configs", "m8176.json")) as fh:
        cfg = json.load(fh)
    cfg.update(chi=chi, pad_multiple=128, limits={"widest_gap": LIMIT})
    trf = {"samples_per_batch": n, "mesh": mesh, "max_batches": 1000,
           "rows_checked_per_batch": n}
    e2e = [{"name": "site_samples_per_s", "unit": "site-samples/s"},
           {"name": "setup_s", "unit": "s"}]
    return harness.Cell("tiny", 1, cfg, trf, e2e, [], str(root))


def run(cell, seed, control=False):
    return harness.run_cell(cell, seed, 0.0, False, t_start=time.time(),
                            control=control)


@pytest.mark.parametrize("seed", [11, 2 ** 40 + 12, 13])
def test_program_passes_and_control_fails(tmp_path, seed):
    out = run(tiny_cell(tmp_path), seed, control=True)
    assert out["correct"], out["checks"]
    assert out["checks"]["widest_gap"]["value"] <= LIMIT
    assert out["control_gap"] > 3 * LIMIT
    assert out["attempted"] == 1


def _faulty(fault):
    from repro.kernels import dispatch
    orig = dispatch.get_site_op

    def get(stage, semantics, kernels):
        op = orig(stage, semantics, kernels)
        if stage != "site_step":
            return op

        def broken(env, gamma, lam, u, **kw):
            new_env, samples, dlog = op(env, gamma, lam, u, **kw)
            n = samples.shape[0]
            if fault == "altered_outcome":
                samples = jnp.where(jnp.arange(n) % 5 == 0,
                                    (samples + 1) % gamma.shape[2], samples)
            elif fault == "state_unchanged":
                new_env, dlog = env, jnp.zeros_like(dlog)
            elif fault == "half_batch":
                h = n // 2
                new_env = jnp.concatenate([new_env[:h], new_env[:h]])
                samples = jnp.concatenate([samples[:h], samples[:h]])
                dlog = jnp.concatenate([dlog[:h], dlog[:h]])
            return new_env, samples, dlog
        return broken
    return get


@pytest.mark.parametrize("fault", ["altered_outcome", "state_unchanged",
                                   "half_batch"])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    from repro.kernels import dispatch
    monkeypatch.setattr(dispatch, "get_site_op", _faulty(fault))
    jax.clear_caches()
    try:
        out = run(tiny_cell(tmp_path), 21)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not out["correct"]
    assert out["checks"]["widest_gap"]["value"] > 10 * LIMIT


TP_SCRIPT = r"""
import json, sys, time
sys.path.insert(0, {root!r}); sys.path.insert(0, {src!r})
sys.path.insert(0, {tests!r})
import jax
from test_bench_check import tiny_cell, run
from bench import harness
from repro.core import parallel as PP
cell = tiny_cell({tmp!r}, chi=256, n=2048,
                 mesh={{"shape": [1, 4], "axes": ["data", "model"]}})
cell.chips = 4
clean = run(cell, 31)

def local_slice(x, axis_name, *, scatter_dimension=0, tiled=False):
    p, i = jax.lax.axis_size(axis_name), jax.lax.axis_index(axis_name)
    k = x.shape[scatter_dimension] // p
    return jax.lax.dynamic_slice_in_dim(x, i * k, k, axis=scatter_dimension)

jax.lax.psum_scatter = local_slice
jax.lax.psum = lambda x, axis_name, **kw: x
PP._segment_callable.cache_clear()
jax.clear_caches()
broken = run(cell, 31)
print(json.dumps({{"clean": clean, "broken": broken}}))
"""


def test_tp_without_the_exchange_is_not_correct(tmp_path):
    """Four virtual CPU devices in a child process: the TP walk on a
    (1, 4) mesh passes; with its collectives left out it does not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = TP_SCRIPT.format(root=ROOT, src=os.path.join(ROOT, "src"),
                              tests=os.path.dirname(__file__),
                              tmp=str(tmp_path))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["clean"]["plan"]["scheme"].startswith("tp_")
    assert res["clean"]["correct"], res["clean"]["checks"]
    assert not res["broken"]["correct"]
