"""GBS sampling driver: the paper's workload end-to-end, fault-tolerant.

A thin shell over :class:`repro.api.SamplingSession`: argument parsing →
config construction → session calls.  The session composes every level —
DP×TP placement, micro batching, dynamic bond dimensions, segment
streaming, per-segment checkpoints — and the macro-batch
:class:`WorkQueue` (runtime/elastic.py) makes the run restart-exact: kill
it at any point and rerun, it resumes from the queue state (and, when
streaming, from the last mid-chain segment boundary) and produces
bit-identical samples (paper §4.1).

Usage:
  PYTHONPATH=src python -m repro.launch.sample --sites 64 --chi 64 \
      --samples 4096 --macro-batches 4 --scheme dp --out /tmp/gbs

Streaming mode (chains too big for device memory, paper §3.1/§3.3.2):
  PYTHONPATH=src python -m repro.launch.sample --sites 512 --chi 64 \
      --samples 4096 --stream --store /tmp/gbs_gamma --segment-len 64

Dynamic bond dimensions (§3.4.2) now compose with every mode:
  PYTHONPATH=src python -m repro.launch.sample --sites 512 --chi 64 \
      --samples 4096 --stream --dynamic-bond

Service mode (async job API, `repro.api.service`): the whole run is one
multi-batch job over elastic worker lanes — blocks persist and progress
prints as batches complete, with the same batch files (same seed schedule)
the synchronous path writes:
  PYTHONPATH=src python -m repro.launch.sample --sites 64 --chi 64 \
      --samples 4096 --macro-batches 8 --service --service-workers 2
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.core import dynamic_bond as DB
from repro.core import mps as M
from repro.data.gamma_store import MANIFEST_NAME, GammaStore
from repro.kernels import dispatch
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime.elastic import WorkQueue
from repro.runtime.faults import DeadLetter, FaultError


def main() -> None:
    try:
        _main()
    except FaultError as e:
        # the structured failure path: a verified-I/O / transport fault
        # (quarantined Γ site, dead-lettered poison batch, …) exits with a
        # machine-readable fault record instead of a stack trace — the
        # operator sees WHAT rotted and WHERE, and exit code 2
        # distinguishes "your data is bad" from "the driver crashed"
        record = {"fault": e.fault.to_dict(), "error": str(e)}
        if isinstance(e, DeadLetter):
            record["report"] = e.report.to_dict()
        print(json.dumps(record, indent=1), file=sys.stderr)
        raise SystemExit(2)


def _main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sites", type=int, default=64)
    ap.add_argument("--chi", type=int, default=64)
    ap.add_argument("--d", type=int, default=3)
    ap.add_argument("--samples", type=int, default=4096)
    ap.add_argument("--macro-batches", type=int, default=4)
    ap.add_argument("--scheme", default="dp",
                    choices=["auto", "seq", "dp", "tp_single", "tp_double",
                             "baseline19"])
    ap.add_argument("--runtime", default="auto",
                    choices=["auto", "local", "multihost", "remote"],
                    help="cluster runtime: where processes live and how Γ "
                         "bytes move (auto = local on one process)")
    ap.add_argument("--kernels", default="auto",
                    choices=["auto", "pallas", "xla"],
                    help="site-step kernel dispatch: fused Pallas pipeline, "
                         "XLA reference, or auto (pallas on TPU)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--clamp", default=None,
                    help="conditional sampling: 'site=outcome,...' forces "
                         "those sites and reports the per-sample conditional "
                         "log-probability (repro.workloads)")
    ap.add_argument("--dynamic-bond", action="store_true")
    ap.add_argument("--micro-batch", type=int, default=0,
                    help="N₂ per data shard (0 = whole batch)")
    ap.add_argument("--precision", default="fp64",
                    choices=["fp64", "fp32", "mxu_bf16"])
    ap.add_argument("--out", default="/tmp/fastmps_out")
    ap.add_argument("--service", action="store_true",
                    help="run through the async SamplingService: the whole "
                         "run is ONE multi-batch job, blocks stream back "
                         "with progress as they complete")
    ap.add_argument("--service-workers", type=int, default=1,
                    help="service submit lanes (elastic worker threads)")
    ap.add_argument("--service-fleet", action="store_true",
                    help="back every service lane with a persistent worker "
                         "PROCESS (framed-pipe RPC, repro.runtime.transport)"
                         " instead of an in-process thread")
    ap.add_argument("--stream", action="store_true",
                    help="segment-streamed engine (Γ from --store, §3.1)")
    ap.add_argument("--store", default=None,
                    help="GammaStore dir; built from the synthetic MPS if empty")
    ap.add_argument("--segment-len", type=int, default=0,
                    help="sites per streamed segment (0 = perfmodel planner)")
    args = ap.parse_args()
    print("compile cache:", enable_compile_cache())

    os.makedirs(args.out, exist_ok=True)
    # the runtime decides where devices live; the mesh is derived from it
    # (a remote runtime dispatches the whole request — no local mesh)
    runtime = api.resolve_runtime(args.runtime)
    mesh = (None if runtime.name == "remote" or args.service_fleet
            else runtime.mesh(args.model_parallel))
    print(f"runtime: {runtime.name} "
          f"(process {runtime.process_index}/{runtime.process_count})  "
          f"mesh: {dict(mesh.shape) if mesh else None}  "
          f"scheme: {args.scheme}")

    dtype = jnp.float64 if args.precision == "fp64" else jnp.float32
    compute = jnp.bfloat16 if args.precision == "mxu_bf16" else None

    def build_mps():
        return M.gbs_like_mps(jax.random.key(args.seed), args.sites,
                              args.chi, args.d,
                              dtype=jnp.float64).astype(dtype)

    # -- source: an in-memory MPS, or a Γ store the chain streams from ------
    # (streaming never materializes the full chain — that is its point)
    if args.stream:
        root = args.store or os.path.join(args.out, "gamma_store")
        store_dtype = jnp.float64 if args.precision == "fp64" else jnp.float32
        source = GammaStore(root, compute_dtype=store_dtype)
        if source.n_sites == 0:
            print(f"writing Γ store ({args.sites} sites) to {root}")
            source.write_mps(build_mps())
            source.write_digest_manifest()
        # verified Γ I/O (runtime/faults.py): with a digest manifest on
        # disk every site read is sha256-checked — a rotted file is
        # quarantined and the run exits 2 with a fault record instead of
        # emitting samples from bad bytes.  A zip-level CRC only covers
        # member payloads; the manifest covers the whole file.
        source.verify = os.path.exists(os.path.join(root, MANIFEST_NAME))
    else:
        source = build_mps()

    # -- config: every knob is a field; AUTO fields go to the planner -------
    chi_profile = None
    if args.dynamic_bond:
        prof = DB.area_law_profile(args.sites, args.chi, n_photon=1.0)
        buck = DB.bucketize(prof, sorted({max(args.model_parallel,
                                              args.chi // 4),
                                          args.chi // 2, args.chi}))
        chi_profile = tuple(int(c) for c in buck)
        print("table1:", DB.table1_metrics(prof, args.chi))

    clamp = None
    if args.clamp:
        from repro.workloads.clamp import parse_clamp_arg
        clamp = parse_clamp_arg(args.clamp)
        print(f"clamp: {clamp} (clamped walks skip chain checkpoints — "
              f"macro-batch idempotence is the restart story)")

    scheme = args.scheme
    if runtime.name == "remote" and scheme not in ("auto", "seq"):
        print(f"runtime=remote resolves placement on the worker — "
              f"overriding scheme {scheme!r} to auto")
        scheme = "auto"
    if args.service_fleet and scheme not in ("auto", "seq"):
        print(f"--service-fleet dispatches serialized job batches; workers "
              f"resolve their own placement — overriding scheme "
              f"{scheme!r} to auto")
        scheme = "auto"
    config = api.SamplerConfig(
        scheme=scheme,
        kernels=args.kernels,
        runtime=runtime,
        backend=("auto" if runtime.name == "remote"
                 else ("streamed" if args.stream else "inmem")),
        compute_dtype=compute,
        micro_batch=args.micro_batch or None,
        chi_profile=chi_profile,
        segment_len=args.segment_len or api.AUTO,
        checkpoint_every=1,
        clamp=clamp,
    )

    n1 = args.macro_batches
    assert args.samples % n1 == 0
    per_batch = args.samples // n1

    # resume: macro batches already on disk are done (idempotent by id)
    done = [b for b in range(n1)
            if os.path.exists(os.path.join(args.out, f"batch_{b:05d}.npy"))]
    queue = WorkQueue(n1, seed=args.seed)
    for b in done:
        queue.complete(b)
    print(f"pending macro batches: {queue.pending}")

    base = jax.random.key(args.seed + 1)
    t0 = time.perf_counter()
    with api.SamplingSession(source, config, mesh=mesh) as session:
        plan = session.plan(per_batch)
        print("plan:", plan)
        print("why:", session.explain(per_batch))
        print(f"kernel dispatch: requested={args.kernels!r} → resolved "
              f"{plan.kernels!r} (backend={jax.default_backend()}; "
              f"registered ops: {len(dispatch.registered_ops())})")

        lp_blocks: dict[int, np.ndarray] = {}

        def save_batch(b: int, out: np.ndarray) -> None:
            np.save(os.path.join(args.out, f"batch_{b:05d}.npy"),
                    np.asarray(out).astype(np.int8))
            if clamp is not None:
                lp = session.stats.get("log_prob")
                if lp is not None and len(lp) == out.shape[0]:
                    lp_blocks[b] = np.asarray(lp, dtype=np.float64)
            print(f"macro batch {b} done ({per_batch} samples)", flush=True)

        if args.service:
            # the async front door: ONE job, its macro batches fed through
            # the elastic WorkQueue across --service-workers lanes, blocks
            # streamed back (and persisted) as they complete.  The batch
            # files must be interchangeable with the synchronous mode's, so
            # the key schedule must match run_queue's fold_in(base, b) for
            # EVERY n1 — a 1-batch job passes its key through unfolded
            # (service.batch_key), so fold batch 0's key here.
            job_key = jax.random.fold_in(base, 0) if n1 == 1 else base
            # fleet lanes have no local chain walk — per-batch idempotence
            # (skip_batches from the files on disk) is the restart story
            ck_root = (None if args.service_fleet or clamp is not None
                       else os.path.join(args.out, "chain_ckpt"))
            with api.SamplingService(workers=args.service_workers,
                                     pool=args.service_fleet or None) as svc:
                handle = svc.submit(
                    session, n_samples=args.samples, key=job_key,
                    macro_batches=n1, skip_batches=done,
                    checkpoint_root=ck_root)
                for b, block in handle.stream():
                    save_batch(b, block)
                    p = handle.progress
                    st = svc.stats()
                    adm = st["admission"]
                    lanes = " ".join(
                        f"{n}:{c}"
                        for n, c in sorted(st["lane_batches"].items()))
                    print(f"[service] {p['done']}/{p['total']} batches "
                          f"(claims={p['claims']} requeues={p['requeues']} "
                          f"lanes={p['workers']}) queue_depth="
                          f"{st['queue_depth']} backpressure="
                          f"{'yes' if adm['backpressure'] else 'no'} "
                          f"(admitted={adm['admitted_jobs']} queued="
                          f"{adm['queued_jobs']}) per-lane: {lanes}",
                          flush=True)
                final = svc.stats()
                print("[service] final:", handle.status(), final)
                print(f"[service] per-lane batch counts: "
                      f"{final['lane_batches']}  stragglers: "
                      f"{final['stragglers']}" +
                      (f"  transport: {final['transport']}"
                       if args.service_fleet else ""), flush=True)
        else:
            session.run_queue(
                queue, per_batch, base, worker="driver",
                checkpoint_root=(None if clamp is not None else
                                 os.path.join(args.out, "chain_ckpt")),
                on_batch=save_batch)
        if session.stats:
            print("streaming stats:",
                  {k: (round(v, 4) if isinstance(v, float) else v)
                   for k, v in session.stats.items()
                   if k != "log_prob"})
        if clamp is not None and lp_blocks:
            # the conditional weights: ln P(clamped outcomes | earlier
            # sites) per sample — exp-mean estimates the clamp marginal
            lp = np.concatenate([lp_blocks[b] for b in sorted(lp_blocks)])
            w = np.exp(lp)
            print(f"clamp log_prob: n={lp.size} mean={lp.mean():.6f} "
                  f"min={lp.min():.6f} max={lp.max():.6f}  "
                  f"P(clamp) ≈ {w.mean():.6g}")
        # where the Γ bytes moved: disk I/O lives on the store counters,
        # interconnect/dispatch bytes on the runtime's
        print("runtime counters:", runtime.io_counters())
        # where the kernel block sizes came from (TPU: timed sweep entries;
        # elsewhere: heuristic table — either way cached per process)
        print("autotuner cache:", dispatch.autotune_cache_stats())
    if args.stream:
        source.close()

    # merge + stats
    allb = [np.load(os.path.join(args.out, f"batch_{b:05d}.npy"))
            for b in range(n1)]
    samples = np.concatenate(allb, axis=0)
    mean_photons = samples.mean(axis=0)
    stats = {"n_samples": int(samples.shape[0]), "sites": args.sites,
             "chi": args.chi, "walltime_s": time.perf_counter() - t0,
             "mean_photon_min": float(mean_photons.min()),
             "mean_photon_max": float(mean_photons.max())}
    with open(os.path.join(args.out, "stats.json"), "w") as f:
        json.dump(stats, f, indent=1)
    print(json.dumps(stats, indent=1))


if __name__ == "__main__":
    main()
