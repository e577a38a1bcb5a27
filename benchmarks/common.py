"""Shared benchmark plumbing: timing + CSV emission + BENCH.json trajectory.

Every bench prints ``name,us_per_call,derived`` rows (one per sweep point).
``derived`` is the paper-facing number (speedup, efficiency, GFLOP/s, ...).

Benches that track a paper-facing quantity across PRs also append a JSON
record to the shared trajectory file (``benchmarks/BENCH.json``) via
:func:`append_bench_record` — broadcast I/O reduction, streaming overlap,
TP wire bytes, and the fused-site-step HBM model all live there, so the
perf history is one file.
"""
from __future__ import annotations

import datetime
import json
import os
import time
from typing import Callable, Optional

import jax

BENCH_JSON = os.path.join(os.path.dirname(__file__), "BENCH.json")

# the MPS oracles/benches compare against float64 (the paper's reference
# precision); model benches specify their dtypes explicitly
jax.config.update("jax_enable_x64", True)


def time_fn(fn: Callable, *args, warmup: int = 1, iters: int = 3,
            **kwargs) -> float:
    """Median wall time per call in seconds (block_until_ready'd)."""
    for _ in range(warmup):
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def emit(name: str, seconds: float, derived: str | float = "") -> None:
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)


def header() -> None:
    print("name,us_per_call,derived", flush=True)


def append_bench_record(json_path: Optional[str], bench: str, config: dict,
                        **payload) -> Optional[dict]:
    """Append one record to the BENCH trajectory file and return it.

    ``json_path`` of ``None``/``""`` disables the append (CI smoke runs pass
    ``--json ""`` so ephemeral runners never mutate the tracked history).
    The record carries the bench name, a UTC timestamp, the sweep config,
    and the bench-specific payload — successive PRs diff the trajectory.
    """
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
    print(f"# appended to {json_path} ({len(trajectory)} records)")
    return record


def run_child(code: str, devices: int = 8, timeout: int = 600) -> dict:
    """Run python ``code`` in a subprocess with N forced host devices.

    The child must print a single JSON object on its last stdout line.
    (The parent keeps the real 1-device view; see tests/conftest.py.)
    """
    import json
    import os
    import subprocess
    import sys

    from repro.runtime.transport import refuse_spawn_on_chip
    refuse_spawn_on_chip("run_child")
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    root = os.path.join(os.path.dirname(__file__), "..")
    env["PYTHONPATH"] = os.path.join(root, "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                          capture_output=True, timeout=timeout, cwd=root)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-3000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])
