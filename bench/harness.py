"""One run of one benchmark cell: set-up, the measured window, the check.

The cell is found by name in ``BENCHMARK.json``; its configuration file,
its traffic file (``bench/traffic/<traffic>.json``) and the reader of each
per-layer metric (``bench/metrics/<metric>.py``) are found by the names
there, so a new cell, configuration or metric is new files and entries,
never an edit here.

The window drives the path the paper's users run: one multi-batch job on a
``SamplingService`` over a ``SamplingSession`` on a ``GammaStore``
(``backend="streamed"``, ``kernels="auto"``, scheme AUTO), its macro
batches streamed out with ``JobHandle.stream()``.  Batches are counted up
to and including the first one delivered after ``--seconds``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# The cell, found by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: str


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as fh:
        config = json.load(fh)
    if not config.get("limits", {}).get("widest_gap"):
        raise ValueError(f"configuration {conf['name']!r} has no limit for "
                         f"widest_gap: set it from readings on the chip")
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as fh:
        traffic = json.load(fh)

    def mine(m):
        return name in m.get("workloads", [name])
    e2e = [m for m in spec["end_to_end"] if mine(m)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if mine(m) and m["moves"] in moved]
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer, root)


def load_reader(metric: str, root: str = ROOT):
    """The ``read(ctx)`` function of a per-layer metric's own file."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

def build_store(path: str, key, config: dict):
    """The seeded chain, padded, written site by site to a GammaStore."""
    import jax.numpy as jnp

    from bench import chain
    from repro.data.gamma_store import GammaStore

    shutil.rmtree(path, ignore_errors=True)
    dt = getattr(jnp, config["storage_dtype"])
    store = GammaStore(path, storage_dtype=dt, compute_dtype=dt)
    for i in range(config["n_sites"]):
        g, lam = chain.site_tensor(key, i, n_sites=config["n_sites"],
                                   chi=config["chi"], d=config["d"],
                                   storage_dtype=config["storage_dtype"])
        g, lam = chain.padded(g, lam, config["pad_multiple"])
        store.put(i, g, lam)
        del g, lam
    return store


def make_mesh(traffic: dict):
    mesh = traffic.get("mesh")
    if mesh is None:
        return None
    from repro.launch.mesh import make_mesh as mm
    return mm(tuple(mesh["shape"]), tuple(mesh["axes"]))


class Compiles:
    """Backend compile durations, stamped on the host clock."""

    def __init__(self):
        import jax
        self.events: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), secs))

    def between(self, t0: float, t1: float) -> list[float]:
        return [s for t, s in self.events if t0 <= t < t1]


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, control: bool = False,
             keep_trace: str | None = None) -> dict:
    """Set up, measure, check; returns the result object (not printed).

    ``control`` also reads the control's widest gap (``control_gap``);
    ``keep_trace`` copies the traced run's ``.xplane.pb`` there."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import chain, reference
    from repro import api

    cfg, trf = cell.config, cell.traffic
    compiles = Compiles()
    marker = _marker()
    work = os.path.join(cell.root, ".bench_run")
    store_dir = os.path.join(work, "store")
    trace_dir = os.path.join(work, "trace")
    root_key = chain.chain_key(seed)
    data_key = jax.random.fold_in(root_key, 0)
    job_key = jax.random.fold_in(root_key, 1)
    warm_key = jax.random.fold_in(root_key, 2)
    n = int(trf["samples_per_batch"])
    m_sites = int(cfg["n_sites"])
    setup: dict = {}
    store = svc = session = None
    try:
        t = time.perf_counter()
        store = build_store(store_dir, data_key, cfg)
        setup["store_write_s"] = time.perf_counter() - t

        mesh = make_mesh(trf)
        config = api.SamplerConfig(
            backend="streamed", kernels="auto",
            compute_dtype=getattr(jnp, cfg["gemm_dtype"]),
            segment_len=int(cfg["segment_len"]))
        session = api.SamplingSession(store, config, mesh=mesh)
        plan = session.plan(n)
        svc = api.SamplingService(workers=1)

        t = time.perf_counter()
        svc.submit(session, n_samples=n, key=warm_key).result()
        jax.block_until_ready(jax.random.fold_in(job_key, 0))
        mark = jnp.zeros((), jnp.float32)
        jax.block_until_ready(marker(mark))
        setup["warm_batch_s"] = time.perf_counter() - t
        from repro.kernels import dispatch
        setup["autotune_candidates"] = sum(
            r["candidates"] for r in dispatch.autotune_report())

        # -- the window -----------------------------------------------------
        # With --trace 1 the profiler records one steady macro batch: from
        # the delivery of the first batch to the delivery of the next.
        max_batches = int(trf["max_batches"])
        t_window = time.perf_counter()
        setup_s = time.time() - t_start
        setup["compile_s"] = sum(compiles.between(0.0, t_window))
        setup["compiles"] = len(compiles.between(0.0, t_window))
        log("setup: " + json.dumps({k: round(v, 3) if isinstance(v, float)
                                    else v for k, v in setup.items()}))
        delivered: dict[int, np.ndarray] = {}
        tracing, t_mark, spans = False, 0, []
        t0 = time.perf_counter_ns()
        handle = svc.submit(session, n_samples=n * max_batches, key=job_key,
                            macro_batches=max_batches)
        spans.append(("bench.submit", t0, time.perf_counter_ns()))
        batches = handle.stream()
        t_last = t_window
        while not delivered or t_last - t_window < seconds or tracing:
            t0 = time.perf_counter_ns()
            b, block = next(batches)
            spans.append(("bench.wait_batch", t0, time.perf_counter_ns()))
            t_last = time.perf_counter()
            delivered[b] = block
            if tracing:
                jax.block_until_ready(marker(mark))
                jax.profiler.stop_trace()
                tracing = False
            elif trace and len(delivered) == 1:
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=profile_options())
                t_mark = time.perf_counter_ns()
                jax.block_until_ready(marker(mark))
                tracing = True
        window_s = t_last - t_window
        handle.cancel()
        batch_stats = handle.stats
        window_compiles = compiles.between(t_window, t_last)
        svc.close()
        svc = None
        session.close()
        session = None
        devices = jax.devices()[:cell.chips]
        mem = [d.memory_stats() or {} for d in devices]

        # -- the check --------------------------------------------------------
        store.close()
        store = None
        shutil.rmtree(store_dir, ignore_errors=True)
        gc.collect()
        malformed = sum(
            1 for blk in delivered.values()
            if blk.shape != (n, m_sites) or blk.min() < 0
            or blk.max() >= cfg["d"])
        rng = np.random.default_rng([seed, 7])
        per = min(n, int(trf["rows_checked_per_batch"]))
        seg_runner = plan.scheme != "seq"
        rows, us = [], []
        for b in sorted(delivered):
            idx = np.sort(rng.choice(n, per, replace=False))
            rows.append(delivered[b][idx])
            bkey = jax.random.fold_in(job_key, b)
            us.append(np.stack([
                reference.site_uniforms(bkey, s, n,
                                        segment_runner=seg_runner,
                                        p1=plan.p1 if seg_runner else 1,
                                        micro_batch=plan.micro_batch)[idx]
                for s in range(m_sites)], axis=1))
        t = time.perf_counter()
        gap, cgap = reference.widest_gaps(
            np.concatenate(rows), np.concatenate(us), key=data_key,
            n_sites=m_sites, chi=cfg["chi"], d=cfg["d"],
            storage_dtype=cfg["storage_dtype"], control=control)
        check_s = time.perf_counter() - t
        limit = float(cfg["limits"]["widest_gap"])
        checks = {"widest_gap": {"value": gap, "limit": limit},
                  "malformed_batches": {"value": malformed, "limit": 0}}
        correct = gap <= limit and malformed == 0 and bool(delivered)

        # -- metrics --------------------------------------------------------
        rate = len(delivered) * n * m_sites / window_s
        ctx = Context(cell=cell, plan=plan, window_s=window_s, rate=rate,
                      batches=[batch_stats[b] for b in sorted(delivered)
                               if b in batch_stats],
                      memory=mem, setup=setup)
        out = {"correct": correct, "attempted": len(delivered),
               "failed": malformed}
        dev = {"platform": devices[0].platform,
               "kind": devices[0].device_kind, "count": len(devices),
               "memory_peak_bytes": max(
                   (m.get("peak_bytes_in_use", 0) for m in mem), default=0)}
        if trace:
            from bench import roofline
            from bench import trace as TR
            ctx.peaks = roofline.peaks(devices[0].device_kind)
            xplane = TR.find_xplane(trace_dir)
            if keep_trace:
                shutil.copyfile(xplane, keep_trace)
            ctx.devices, markers = TR.load(xplane)
            ctx.lo, ctx.hi = TR.window(markers)
            shift = markers[0][0] - t_mark      # host clock → device clock
            ctx.host = [(name, a + shift, z + shift)
                        for name, a, z in spans]
            shutil.rmtree(trace_dir, ignore_errors=True)
            metrics = {}
            for m in cell.per_layer:
                v = load_reader(m["name"], cell.root)(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
            chip_ops = [ctx.devices.get(i, []) for i in range(cell.chips)]
            dev["busy_s"] = sum(TR.busy_ns(ops, ctx.lo, ctx.hi)
                                for ops in chip_ops) / len(chip_ops) / 1e9
            dev["window_s"] = (ctx.hi - ctx.lo) / 1e9
            worst = max(range(len(chip_ops)), key=lambda i: -TR.busy_ns(
                chip_ops[i], ctx.lo, ctx.hi))
            out["breakdown"] = {
                "device_ops": TR.top_ops(ctx.devices, ctx.lo, ctx.hi),
                "idle_gaps": TR.idle_gaps(chip_ops[worst], ctx.host,
                                          ctx.lo, ctx.hi)}
        else:
            values = {"site_samples_per_s": rate, "setup_s": setup_s}
            metrics = {m["name"]: {"value": float(values[m["name"]]),
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
        out["metrics"] = metrics
        out["device"] = dev
        out["plan"] = {"scheme": plan.scheme, "kernels": plan.kernels,
                       "micro_batch": plan.micro_batch,
                       "segment_len": plan.segment_len, "p1": plan.p1,
                       "p2": plan.p2}
        out["window_compiles"] = len(window_compiles)
        out["reference_s"] = check_s
        if control:
            out["control_gap"] = cgap
        out["checks"] = checks
        return out
    finally:
        if svc is not None:
            svc.close()
        if session is not None:
            session.close()
        if store is not None:
            store.close()
        shutil.rmtree(work, ignore_errors=True)


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    cell: Cell
    plan: object
    window_s: float
    rate: float
    batches: list
    memory: list
    setup: dict
    peaks: dict = dataclasses.field(default_factory=dict)
    devices: dict = dataclasses.field(default_factory=dict)
    host: list = dataclasses.field(default_factory=list)
    lo: int = 0
    hi: int = 0


def bench_window_marker(x):
    """A program of its own, run as the traced window opens and closes:
    its device events bound the window on the device clock."""
    return x + 1


def _marker():
    import jax
    return jax.jit(bench_window_marker)


def profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0          # see bench/trace.py
    return opts


def report(out: dict) -> None:
    """The checks as the last lines of stderr, the result as the last line
    of stdout."""
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)


def open_cell(workload: str) -> Cell:
    """Ready this process for a cell: the program on the path, the compile
    cache in the checkout, and the chips the cell asks for.  Raises
    ``SystemExit`` with a message when any is missing."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: no src/repro in {ROOT}: run from a "
                         f"checkout of the repository")
    sys.path.insert(0, src)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    try:
        cell = load_cell(workload)
    except (KeyError, OSError, ValueError) as e:
        raise SystemExit(f"bench: {e}") from None

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        raise SystemExit(f"bench: needs {cell.chips} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    log(f"compile cache: {enable_compile_cache()}")
    return cell


def main(argv, *, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = open_cell(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=t_start)
    except Exception:                   # report; never print a result
        traceback.print_exc()
        return 1
    report(out)
    return 0
