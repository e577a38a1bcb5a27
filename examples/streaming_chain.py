"""Streaming large chains: sample an MPS that never fully enters device memory.

    PYTHONPATH=src python examples/streaming_chain.py

Walks the paper's §3.1/§3.3.2 pipeline end-to-end at laptop scale through
the unified API: write Γ to a bf16 on-disk store, let the session's planner
pick segment sizes from the perf model, stream the chain with
double-buffered prefetch, a mid-run "crash", and an exact resume — all
behind ``SamplingSession.sample``.
"""
import os
import tempfile

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import api  # noqa: E402
from repro.core import mps as M  # noqa: E402
from repro.data.gamma_store import GammaStore  # noqa: E402


def main() -> None:
    # 1. a 96-site chain, written site-by-site to disk (bf16 storage halves
    # the I/O bytes, §3.3.2; fp32 upcast happens on read)
    sites, chi, d, n = 96, 16, 3, 2_000
    mps = M.gbs_like_mps(jax.random.key(0), sites, chi, d,
                         dtype=jnp.float64).astype(jnp.float32)
    root = os.path.join(tempfile.gettempdir(), "fastmps_stream_demo")
    store = GammaStore(root, storage_dtype=jnp.bfloat16,
                       compute_dtype=jnp.float32)
    if store.n_sites == 0:
        store.write_mps(mps)

    # 2. one config drives everything: a GammaStore source auto-selects the
    # streamed backend, and segment_len=AUTO asks the perf model for the
    # largest segment whose two buffers fit the device budget
    ckpt = os.path.join(root, "ckpt")
    config = api.SamplerConfig(
        segment_len=api.AUTO,
        device_budget=(n * chi * (1 + d) * 4) / 0.9 + sites * chi * chi * d,
        checkpoint_dir=ckpt, checkpoint_every=1)
    key = jax.random.key(1)

    # 3. stream the chain — at most two Γ segments are device-resident,
    # segment k+1 loads while segment k contracts
    with api.SamplingSession(store, config) as session:
        print("plan:", session.plan(n))
        print("why:", session.explain(n))
        out = session.sample(n, key)
        st = session.stats
        print(f"streamed {out.shape} samples over {st['segments']} segments; "
              f"{st['io_hidden_frac']:.0%} of fetch time hidden behind "
              f"compute; max {st['max_live_segments']} segments live")

    # 4. bit-identical to the all-in-memory scan over the same Γ (the
    # session's §4.1 contract; "same Γ" = after the bf16 storage roundtrip)
    g_rt, lam_rt = store.get_segment(0, sites)
    mps_rt = M.MPS(jnp.asarray(g_rt), jnp.asarray(lam_rt), "linear")
    with api.SamplingSession(mps_rt) as session:
        ref = session.sample(n, key)
    print("bit-identical to the in-memory backend:",
          bool(np.all(out == ref)))

    # 5. kill mid-chain, resume from the checkpoint — still bit-identical.
    # resume=True continues from the newest per-segment checkpoint; the
    # resumed run draws the exact randoms the uninterrupted one would have.
    crash_cfg = api.SamplerConfig(
        segment_len=16, checkpoint_dir=os.path.join(root, "ckpt_crash"),
        checkpoint_every=1)
    with api.SamplingSession(store, crash_cfg) as session:
        session.sample(n, key, stop_after_segments=2)    # "crash" at seg 2
        resumed = session.sample(n, key, resume=True)
    print("resumed run bit-identical:", bool(np.all(resumed == ref)))
    store.close()


if __name__ == "__main__":
    main()
