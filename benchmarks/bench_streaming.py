"""Streaming engine bench: compute/I-O overlap on chains beyond device memory.

The paper's §3.1 claim is that with a large enough macro batch, Γ I/O is
fully hidden behind contraction.  This bench builds a chain whose stacked Γ
*exceeds* a configurable device-memory budget, streams it through a
:class:`repro.api.SamplingSession` (streamed backend, double-buffered
GammaStore prefetch), and reports how much of the whole Γ fetch (read,
decode, stack, ``device_put``) was hidden behind compute:

  io_hidden_frac = clip(1 − io_wait_s / fetch_s, 0, 1)

Rows (see common.emit): total stream walltime with the derived column
carrying the paper-facing ratio.  ``--smoke`` shrinks shapes for CI.

Usage:
  PYTHONPATH=src python benchmarks/bench_streaming.py [--smoke]
"""
from __future__ import annotations

import argparse
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

import common
from repro import api
from repro.core import mps as M
from repro.data.gamma_store import GammaStore


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sites", type=int, default=0)
    ap.add_argument("--chi", type=int, default=0)
    ap.add_argument("--d", type=int, default=3)
    ap.add_argument("--samples", type=int, default=0)
    ap.add_argument("--segment-len", type=int, default=0)
    ap.add_argument("--json", default=None,
                    help="BENCH trajectory file to append the record to "
                         "('' disables; default: benchmarks/BENCH.json for "
                         "full runs, disabled for --smoke so CI never "
                         "mutates the tracked history)")
    args = ap.parse_args()
    json_path = (args.json if args.json is not None
                 else ("" if args.smoke else common.BENCH_JSON))

    sites = args.sites or (32 if args.smoke else 256)
    chi = args.chi or (8 if args.smoke else 64)
    n = args.samples or (256 if args.smoke else 4096)
    d = args.d

    # budget chosen so the stacked Γ does NOT fit: it covers the resident
    # environment + micro intermediate (Eq. 3) plus a quarter of the chain —
    # the in-memory path would need all of stacked_bytes, the session holds
    # only two segment buffers.
    stacked_bytes = sites * chi * chi * d * 8            # fp64 compute
    resident = (n * chi + n * chi * d) * 8
    budget = int(resident / 0.9) + stacked_bytes // 4
    mps = M.gbs_like_mps(jax.random.key(0), sites, chi, d)

    root = tempfile.mkdtemp(prefix="bench_gamma_")
    try:
        store = GammaStore(root, storage_dtype=jnp.bfloat16,
                           compute_dtype=jnp.float64)
        store.write_mps(mps)

        config = api.SamplerConfig(
            segment_len=args.segment_len or api.AUTO,
            device_budget=budget)
        key = jax.random.key(1)
        with api.SamplingSession(store, config) as session:
            plan = session.plan(n)
            info = session.explain(n)
            print(f"# chain {sites}x{chi} d={d}: stacked Γ "
                  f"{stacked_bytes/1e6:.1f} MB, budget {budget/1e6:.1f} MB "
                  f"→ segment_len {plan.segment_len} "
                  f"({info['device_resident_bytes']/1e6:.1f} MB resident)")
            assert 2 * plan.segment_len * chi * chi * d * 8 <= stacked_bytes, \
                "bench must exercise a chain larger than its device buffers"

            common.header()
            t = common.time_fn(session.sample, n, key, warmup=1,
                               iters=2 if args.smoke else 3)
            st = session.stats
            common.emit("stream_total", t,
                        f"io_hidden_frac={st['io_hidden_frac']:.3f}")
            common.emit("stream_compute", st["compute_s"] / st["segments"],
                        "per_segment")
            common.emit("stream_io_wait", st["io_wait_s"] / st["segments"],
                        "per_segment")
            common.emit("stream_raw_disk", st["store_io_s"],
                        f"bytes={st['io_bytes']}")
            assert st["max_live_segments"] <= 2, st["max_live_segments"]

        # reference: the in-memory backend at bench scale (it still fits
        # here — at paper scale it cannot; the ratio is the honest
        # comparison)
        with api.SamplingSession(mps) as session:
            t_mem = common.time_fn(
                lambda: session.sample(n, key), warmup=1,
                iters=2 if args.smoke else 3)
        common.emit("inmem_total", t_mem,
                    f"stream_overhead={t / t_mem - 1.0:+.2%}")
        print(f"# overlap: {st['io_hidden_frac']:.1%} of "
              f"{st['fetch_s']*1e3:.1f} ms fetch time hidden behind "
              f"compute (visible wait {st['io_wait_s']*1e3:.1f} ms)")
        common.append_bench_record(
            json_path, "streaming",
            {"sites": sites, "chi": chi, "d": d, "samples": n,
             "segment_len": plan.segment_len, "smoke": bool(args.smoke)},
            stream={"wall_s": t, "io_hidden_frac": st["io_hidden_frac"],
                    "io_wait_s": st["io_wait_s"],
                    "store_io_s": st["store_io_s"],
                    "io_bytes": int(st["io_bytes"])},
            inmem={"wall_s": t_mem},
            stream_overhead=t / t_mem - 1.0)
        store.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
