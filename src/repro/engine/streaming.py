"""Segment-streamed MPS sampling with compute/I-O overlap (paper §3.1, §3.3.2).

The in-memory sampler requires the entire stacked Γ as a device operand —
at 8,176 sites and χ=10⁴ that is impossible.  This engine splits the chain
into fixed-size site *segments* and, while the jitted scan contracts
segment k, a background thread reads segment k+1 from :class:`GammaStore`
(bf16 on disk → fp32 upcast) and moves it to the device, so Γ I/O is
hidden behind compute exactly as in the paper's data-parallel revival.  At
most **two** segments are ever device-resident (current + next); consumed
buffers are explicitly deleted.
On a multi-process :class:`~repro.api.runtime.ClusterRuntime`, the same
prefetch slot carries the paper's §3.1 collective instead: only the ROOT
process reads the store and broadcasts each segment in storage format —
see ``_fetch_via_runtime``.

Every level of the framework composes behind :meth:`StreamingEngine.sample`:

* ``inmem`` scheme — the single-process ``core/sampler`` scan; bit-identical
  to ``sampler.sample`` for the same seed (``micro_batch=None``) or to
  ``sampler.sample_batched`` (``micro_batch=N₂``).
* ``dp`` / ``tp_single`` / ``tp_double`` — the ``core/parallel`` segment
  runner (micro batching N₂ included, and the per-sample ``log_scale``
  diagnostic carried); bit-identical to the corresponding whole-chain
  segment-runner schedule (``parallel._multilevel_sample``).
* dynamic bond dimensions (§3.4.2): a bucketed per-site ``chi_profile``
  splits the walk into χ-stages; segments never cross a stage boundary and
  every segment of a bucket pads to one shape, so a staged chain costs one
  jit compilation *per bucket* (not per chain position).  Bit-identical to
  ``dynamic_bond.sample_staged`` for the inmem scheme.
* per-segment checkpointing through ``checkpoint/sampler_state`` — a killed
  run resumes mid-chain and emits bit-identical samples (paper §4.1).
* macro batches (paper N₁) as idempotent :class:`WorkQueue` work items —
  :meth:`StreamingEngine.run_queue`.

All same-shape segments run through ONE jit compilation: ``start_site`` is a
traced operand, and segment tails are padded to the segment length with
*identity sites* (Γ = I on outcome 0, Λ = 1) whose draws are discarded — an
identity site leaves the environment, its rescale factors, and every real
site's PRNG stream untouched.

Every step is a span (``repro.obs.trace``): ``engine.walk`` per walk, under
it ``engine.wait_gamma`` (the wait on the prefetch) and ``engine.segment``
(``engine.dispatch``, ``engine.samples_to_host``, ``engine.sync``); on the
pool thread ``engine.fetch`` (the store's ``store.parse``/``store.read``
per site and ``store.decode``, then ``engine.pad`` and
``engine.device_put``) caused by the walk that submitted it.  The per-walk
``stats`` counters ``io_wait_s``, ``compute_s``, ``fetch_s`` and ``put_s``
are the sums of those spans' durations, ``put_bytes`` the bytes handed to
``device_put``.

Applications should reach this engine through
:class:`repro.api.SamplingSession` (backend ``"streamed"``).
"""
from __future__ import annotations

import dataclasses
import os
import threading
from concurrent.futures import Future, ThreadPoolExecutor
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.runtime import ClusterRuntime, LocalRuntime
from repro.checkpoint.sampler_state import (load_sampler_state,
                                            newest_checkpoint_site,
                                            save_sampler_state)
from repro.core import parallel as PP
from repro.core import sampler as S
from repro.core.mps import MPS
from repro.core.precision import real_dtype_of
from repro.data import gamma_store as GS
from repro.obs import trace
from repro.runtime.faults import CorruptSegment, Fault


@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """How to walk the chain.  Produced by ``engine.planner.plan_stream``."""
    segment_len: int                    # sites per device-resident segment
    scheme: str = "inmem"               # "inmem" | "dp" | "tp_single" | "tp_double"
    micro_batch: Optional[int] = None   # N₂; composes with EVERY scheme
    checkpoint_every: int = 0           # segments between checkpoints; 0 = off


def fill_identity(g: np.ndarray, lam: np.ndarray) -> None:
    """Make every site of ``g`` (n, χ, χ, d) and ``lam`` (n, χ) a pad site
    that is an exact no-op for the chain walk: Γ[l,r,s] = δ_lr·δ_s0 keeps
    the environment fixed and puts all probability mass on outcome 0;
    Λ = 1 keeps born-semantics collapse factors at unity."""
    g.fill(0)
    diag = np.arange(g.shape[1])
    g[:, diag, diag, 0] = 1
    lam.fill(1)


def _device_copy(x: np.ndarray) -> jax.Array:
    """``x`` on the default device, in memory of its own.  The fetch slot
    is overwritten by the next fetch while this segment computes, and on
    the CPU backend ``device_put`` adopts an aligned host buffer instead of
    copying it, so there it gets a private copy (elsewhere the transfer is
    the copy)."""
    if jax.default_backend() == "cpu":
        x = np.array(x)
    return jax.device_put(x)


@partial(jax.jit, static_argnames=("config", "n_micro"))
def _micro_segment(mps: MPS, env, log_scale, base_key, start_site,
                   config: S.SamplerConfig, n_micro: int):
    """One segment under §3.1 micro-batching: chunk c carries key
    split(base, n_micro)[c] for the whole chain, matching
    ``sampler.sample_batched`` draw-for-draw."""
    n, chi = env.shape
    n2 = n // n_micro
    keys = jax.random.split(base_key, n_micro)

    def one(xs):
        k, e, ls = xs
        res = S.sample_chain(mps, S.SamplerState(e, k, ls), config,
                             start_site=start_site)
        return res.samples, res.state.env, res.state.log_scale

    samples, env2, ls2 = jax.lax.map(
        one, (keys, env.reshape(n_micro, n2, chi),
              log_scale.reshape(n_micro, n2)))
    samples = jnp.transpose(samples, (1, 0, 2)).reshape(-1, n)  # (L, N)
    return samples, env2.reshape(n, chi), ls2.reshape(n)


class StreamingEngine:
    """Drives a chain stored in a :class:`GammaStore` through any DP×TP
    placement, never holding more than two Γ segments on device."""

    def __init__(self, store, *, semantics: str = "linear",
                 config: S.SamplerConfig = S.SamplerConfig(),
                 plan: StreamPlan = StreamPlan(segment_len=64),
                 mesh=None, pconfig: Optional[PP.ParallelConfig] = None,
                 checkpoint_dir: Optional[str] = None,
                 chi_profile=None,
                 runtime: Optional[ClusterRuntime] = None,
                 shard=None, clamp=None):
        from repro.workloads.clamp import clamp_map
        # conditional sampling (repro.workloads): a normalized clamp spec
        # forces outcomes at a subset of sites; per-segment (mask, vals)
        # operands are built on the fly in _run_segment_clamped and the
        # walk carries a per-sample log_prob alongside log_scale, surfaced
        # through stats["log_prob"].  None = unclamped (unchanged paths).
        self.clamp_map = clamp_map(clamp)
        self.store = store
        self._source_store = store
        self._wrapped_store = None
        # where this engine's process lives and how Γ bytes reach it: on a
        # LocalRuntime every segment is a store read; on a multi-process
        # runtime only the ROOT touches the store and everyone else receives
        # the broadcast (paper §3.1) — see _fetch.  A `shard` map
        # (repro.shard.ShardMap) switches the multi-process plane from
        # broadcast to block-cyclic ownership: every process reads ONLY its
        # owned slice and the walk pipelines the (N, χ) env host-to-host
        # (ROADMAP item 3) — see _sample_sharded
        self.runtime = runtime or LocalRuntime()
        self.shard = shard
        self.n_sites = store.n_sites
        if self.n_sites == 0:
            raise ValueError(f"empty GammaStore at {store.root}")
        if shard is not None:
            from repro.shard.store import ShardedGammaStore
            if shard.n_sites != self.n_sites:
                raise ValueError(f"shard map covers {shard.n_sites} sites, "
                                 f"store holds {self.n_sites}")
            if shard.n_hosts != self.runtime.process_count:
                raise ValueError(
                    f"shard map spans {shard.n_hosts} hosts but the runtime "
                    f"has {self.runtime.process_count} processes")
            if shard.n_hosts > 1 and not isinstance(store, ShardedGammaStore):
                # shared-root deployment: wrap the caller's plain store in
                # this host's ownership-enforcing view (engine-owned; the
                # caller's store object stays untouched and shared)
                self.store = ShardedGammaStore(
                    store.root, shard, self.runtime.process_index,
                    storage_dtype=store.storage_dtype,
                    compute_dtype=store.compute_dtype, verify=True)
                self._wrapped_store = self.store
        # verified Γ I/O is ON by default whenever bytes cross process
        # boundaries (broadcast or sharded): a flipped bit must surface as
        # a structured CorruptSegment before any sample is emitted.  A
        # single-process run keeps the caller's choice — structural
        # corruption (a torn npz) is caught on every read regardless.
        if self.runtime.process_count > 1:
            self.store.verify = True
        shape = self.store.meta(0)        # header-only: no Γ payload read
        self.chi, self.d = shape[0], shape[2]
        self.gamma_dtype = np.dtype(self.store.compute_dtype)
        self.semantics = semantics
        self.config = config
        self.plan = plan
        if plan.scheme != "inmem" and mesh is None:
            raise ValueError(f"scheme {plan.scheme!r} needs a mesh")
        self.mesh = mesh
        self.pconfig = pconfig or PP.ParallelConfig(scheme=plan.scheme)
        if plan.scheme != "inmem" and plan.micro_batch is not None:
            # §3.1 micro batching composes with the DP/TP schemes through the
            # segment runner (N₂ per data shard, sample_batched key schedule)
            self.pconfig = dataclasses.replace(self.pconfig,
                                               micro_batch=plan.micro_batch)
        self.chi_profile = (None if chi_profile is None
                            else np.asarray(chi_profile, dtype=np.int64))
        if self.chi_profile is not None:
            if len(self.chi_profile) != self.n_sites:
                raise ValueError(f"chi_profile covers "
                                 f"{len(self.chi_profile)} of "
                                 f"{self.n_sites} sites")
            if int(self.chi_profile.max()) > self.chi:
                raise ValueError("chi_profile exceeds the stored χ "
                                 f"({int(self.chi_profile.max())} > {self.chi})")
        self.checkpoint_dir = checkpoint_dir
        if checkpoint_dir:
            os.makedirs(checkpoint_dir, exist_ok=True)
        self._pool = ThreadPoolExecutor(max_workers=1)
        # the host segment buffer each fetch lands Γ in, one per pool
        # thread and reused by every fetch on it: a fetch returns only once
        # its device copy is complete, so the next fetch on the same
        # thread may overwrite it
        self._host_segment = threading.local()
        # guards the live-segment count and the pool thread's fetch totals
        self._live_lock = threading.Lock()
        self._live = 0
        # Σ engine.fetch / engine.device_put seconds and the bytes handed to
        # device_put, since creation; each walk reports the growth since
        # the previous walk ended (_finish_walk), so a fetch that finishes
        # between walks is counted once
        self._fetched = {"fetch_s": 0.0, "put_s": 0.0, "put_bytes": 0}
        self._fetched0 = dict(self._fetched)
        # one walk at a time: the engine is cached per plan by the session
        # and service lanes may hand it consecutive macro batches
        self._walk_lock = threading.Lock()
        # gang-scheduling slot: ((start, stop, χ), Future) for the NEXT
        # walk's first segment, fetched behind this walk's tail compute
        self._warm: Optional[tuple] = None
        # store I/O is counted relative to engine creation so a shared
        # (session-owned) store can serve many engines without the hidden-
        # I/O ratio mixing scopes (self.store: the sharded view when one
        # was wrapped — its counters see owned traffic only)
        self._store0 = self._store_counters()
        # runtime counters are scoped the same way: deltas since engine
        # creation, so shared runtimes serve many engines cleanly
        self._runtime_io0 = dict(self.runtime.io_counters())
        self.stats = {"segments": 0, "io_wait_s": 0.0, "compute_s": 0.0,
                      "max_live_segments": 0, "io_hidden_frac": 0.0,
                      "fetch_s": 0.0, "put_s": 0.0, "put_bytes": 0,
                      "owned_segments": 0, "handoffs": 0,
                      "handoff_send_bytes": 0, "handoff_recv_bytes": 0,
                      "gather_bytes": 0,
                      **{k: 0 for k in self._STORE_COUNTERS}}
        for k in self._runtime_io0:
            self.stats[k] = 0
        # the shard algebra must hold for the REAL schedule (χ-stages can
        # split blocks in ways plan-time uniform checks miss): every
        # scheduled segment needs exactly one owner, checked here once
        self._seg_owners = (None if self.shard is None else
                            tuple(self.shard.segment_owner(s, e)
                                  for s, e, _ in self._segment_schedule()))

    # -- chain schedule ------------------------------------------------------
    def _segment_schedule(self) -> list[tuple[int, int, int]]:
        """[(start, stop, χ_stage)] — ``plan.segment_len``-sized chunks that
        never cross a χ-stage boundary.  With no profile this is the uniform
        fixed-χ split; with one, each §3.4.2 bucket walks its own segments
        (every segment of a bucket is padded to the same length, so a
        dynamic-χ chain costs ONE jit compilation per bucket)."""
        from repro.core import dynamic_bond as DB
        from repro.shard.shardmap import chain_segments

        if self.chi_profile is None:
            stages = [(0, self.n_sites, self.chi)]
        else:
            stages = [(st.start, st.stop, st.chi)
                      for st in DB.stages_from_profile(self.chi_profile)]
        for s0, s1, _ in stages:
            if self.pconfig.scheme == "tp_double" and (s0 % 2 or s1 % 2):
                raise ValueError(
                    "tp_double pairs sites (2j, 2j+1): χ-stage boundaries "
                    f"must be even (got stage [{s0}, {s1}))")
        # the chunking itself is shared with the planner's shard validation
        # (shardmap.chain_segments) so "every segment has one owner" is
        # proved against the very schedule this engine walks
        return chain_segments(self.n_sites, self.plan.segment_len, stages)

    #: per-walk stats read off the store: stats key → store attribute
    _STORE_COUNTERS = {"store_io_s": "io_seconds", "io_bytes": "io_bytes",
                       "payload_reads": "payload_reads",
                       "direct_reads": "direct_reads",
                       "quarantined_sites": "quarantined_sites",
                       "repaired_sites": "repaired_sites"}

    def _store_counters(self) -> dict:
        return {k: getattr(self.store, a)
                for k, a in self._STORE_COUNTERS.items()}

    # -- segment fetch (runs on the pool thread) ----------------------------
    def _fetch_via_runtime(self, start: int,
                           stop: int) -> tuple[np.ndarray, np.ndarray]:
        """Paper §3.1: process 0 reads the segment once and broadcasts it.

        Only the root runtime instance ever touches the GammaStore payload;
        the wire carries the store's *storage format* (bf16-packed when the
        store is bf16 — half the interconnect bytes), and every process —
        root included — decodes through ``gamma_store.decode_segment``, so
        the walk stays bit-identical to a LocalRuntime one.  Running on the
        prefetch pool thread, the broadcast of segment k+1 overlaps the
        contraction of segment k exactly like the local read does."""
        payload = None
        if self.runtime.is_root:
            try:
                payload = self.store.get_segment_raw(start, stop - start)
            except CorruptSegment as e:
                # the fault must cross the wire too: a root that raised
                # while its peers block in the collective would hang the
                # cluster — instead EVERY process receives the error frame
                # and fails this round with the same structured fault
                payload = {"start": start, "error": str(e),
                           "fault": e.fault.to_dict()}
        payload = self.runtime.broadcast_segment(payload)
        if payload.get("error") is not None:
            fd = dict(payload.get("fault") or {})
            raise CorruptSegment(Fault(
                kind=fd.get("kind", "corruption"),
                message=fd.get("message", str(payload["error"])),
                site=fd.get("site"), store=fd.get("store")))
        if payload["start"] != start:
            # a real error, not an assert: schedule desync across processes
            # must never silently sample the wrong segment (python -O)
            raise RuntimeError(
                f"broadcast schedule desync: this process expected segment "
                f"start {start} but received {payload['start']} — are all "
                f"processes walking the same plan?")
        return GS.decode_segment(payload, compute_dtype=self.gamma_dtype)

    def _land(self, start: int, stop: int) -> tuple[np.ndarray, np.ndarray]:
        """Sites [start, stop) read into this thread's host segment buffer
        (allocated at its first fetch), decoded in one call: compute-dtype
        (L, χ, χ, d) and (L, χ) arrays — views of the buffer when the store
        holds the compute dtype.  Slots past ``stop - start`` hold zeros or
        an earlier segment's sites."""
        buf = getattr(self._host_segment, "buf", None)
        if buf is None:
            buf = self._host_segment.buf = self.store.segment_buffer(
                self.plan.segment_len, start)
        raw, lam = buf
        gshape, two_byte = self.store.read_segment_into(start, stop, raw, lam)
        with trace.span("store.decode", start=start):
            return GS.decode_gamma(raw, gshape, two_byte,
                                   self.store.storage_dtype,
                                   self.gamma_dtype), lam

    def _fetch(self, start: int, stop: int, chi_s: int,
               cause: Optional[trace.Span] = None
               ) -> tuple[jax.Array, jax.Array, int]:
        """One segment read, padded and resident on the device.  Runs on
        the pool thread under an ``engine.fetch`` span whose cause is the
        walk that submitted it (``_submit``)."""
        L = self.plan.segment_len
        real = stop - start
        with trace.span("engine.fetch", parent=cause, start=start) as fetch:
            if self.shard is None and self.runtime.process_count > 1:
                g, lam = self._fetch_via_runtime(start, stop)
            else:
                # local read (the sharded plane included: the owner reads
                # its own slice, and Γ never crosses the interconnect)
                g, lam = self._land(start, stop)
            if chi_s < self.chi:          # §3.4.2: only the bucketed bond
                g = g[:, :chi_s, :chi_s, :]
                lam = lam[:, :chi_s]
            if real < L:                  # tail: pad with identity sites
                with trace.span("engine.pad"):
                    if len(g) < L:        # a broadcast payload: real sites
                        g = np.concatenate([g, np.empty(
                            (L - real,) + g.shape[1:], g.dtype)])
                        lam = np.concatenate([lam, np.empty(
                            (L - real,) + lam.shape[1:], lam.dtype)])
                    fill_identity(g[real:], lam[real:])
            # the walk's first kernel would wait for this transfer anyway:
            # waiting here keeps the wait inside the fetch that causes it
            with trace.span("engine.device_put") as put:
                gd, ld = _device_copy(g), _device_copy(lam)
                jax.block_until_ready((gd, ld))
        with self._live_lock:
            self._live += 1
            self.stats["max_live_segments"] = max(
                self.stats["max_live_segments"], self._live)
            self._fetched["fetch_s"] += fetch.seconds
            self._fetched["put_s"] += put.seconds
            self._fetched["put_bytes"] += g.nbytes + lam.nbytes
        return gd, ld, real

    def _submit(self, seg: tuple[int, int, int]) -> Future:
        """Fetch schedule entry ``seg`` on the pool thread, caused by the
        span open here (the walk)."""
        return self._pool.submit(self._fetch, *seg, cause=trace.current())

    def _wait_gamma(self, fut: Future) -> tuple[jax.Array, jax.Array, int]:
        """The walk's wait on the prefetch, counted in ``io_wait_s``."""
        with trace.span("engine.wait_gamma") as sp:
            out = fut.result()
        self.stats["io_wait_s"] += sp.seconds
        return out

    def _release(self, gd: jax.Array, ld: jax.Array) -> None:
        gd.delete()
        ld.delete()
        with self._live_lock:
            self._live -= 1

    def _compute_segment(self, gd, ld, real: int, chi_s: int, env, log_scale,
                         log_prob, key, start: int):
        """Walk one fetched segment and release its buffers; returns
        ``(samples (real, N), env', log_scale', log_prob')``.  Its
        ``engine.segment`` span is counted in ``compute_s``."""
        from repro.core.dynamic_bond import fit_env

        with trace.span("engine.segment", start=start) as sp:
            # the lock is a no-op except on the emulated cluster, where the
            # member "processes" share one XLA backend and concurrent
            # collective programs would interleave their rendezvous and
            # deadlock (block_until_ready stays inside: dispatch is async)
            with self.runtime.compute_lock():
                with trace.span("engine.dispatch"):
                    seg = MPS(gd, ld, self.semantics)
                    env = fit_env(env, chi_s)  # χ-stage transition
                    if self.clamp_map is None:
                        samples, env, log_scale = self._run_segment(
                            seg, env, log_scale, key, start)
                    else:
                        samples, env, log_scale, log_prob = \
                            self._run_segment_clamped(seg, env, log_scale,
                                                      log_prob, key, start)
                with trace.span("engine.samples_to_host"):
                    samples = np.asarray(samples[:real])  # drop pad sites
                with trace.span("engine.sync"):
                    jax.block_until_ready((env, log_scale))
        self.stats["compute_s"] += sp.seconds
        self._release(gd, ld)
        return samples, env, log_scale, log_prob

    # -- one segment of the data plane --------------------------------------
    def _run_segment(self, seg: MPS, env, log_scale, key, start: int):
        if self.plan.scheme == "inmem":
            if self.plan.micro_batch is not None:
                n_micro = env.shape[0] // self.plan.micro_batch
                return _micro_segment(seg, env, log_scale, key, start,
                                      self.config, n_micro)
            res = S.sample_chain(seg, S.SamplerState(env, key, log_scale),
                                 self.config, start_site=start)
            return res.samples, res.state.env, res.state.log_scale
        return PP.sample_segment(self.mesh, seg, env, key, start,
                                 self.pconfig, self.config,
                                 log_scale=log_scale)

    def _run_segment_clamped(self, seg: MPS, env, log_scale, log_prob, key,
                             start: int):
        """Clamped twin of ``_run_segment``: routes through the
        ``core.clamped`` walks with per-segment (mask, vals) built from the
        clamp spec.  Identity pad sites past the chain end are unclamped by
        construction, so they stay exact no-ops (outcome 0, zero weight).
        Returns ``(samples, env', log_scale', log_prob')``."""
        from repro.core import clamped as CL
        from repro.workloads.clamp import segment_clamp_arrays

        n = env.shape[0]
        mask, vals = segment_clamp_arrays(self.clamp_map, start,
                                          seg.n_sites, n)
        if self.plan.scheme == "inmem":
            return CL.clamped_segment(
                seg.gammas, seg.lambdas, env, key, start, mask, vals,
                self.config, log_scale=log_scale, log_prob=log_prob,
                micro_batch=self.plan.micro_batch)
        # tp schemes run the clamped dp walk over the non-model axes
        # (every schedule draws the same randoms per seed — §4.1)
        return CL.sample_segment_clamped(
            self.mesh, seg, env, key, start, mask, vals,
            CL.dp_equivalent_pconfig(self.pconfig), self.config,
            log_scale=log_scale, log_prob=log_prob)

    def _load_sample_blocks(self, up_to_site: int,
                            ckpt_dir: str) -> list[np.ndarray]:
        """Read back the per-segment sample blocks covering [0, up_to_site)."""
        blocks, cursor = [], 0
        names = sorted(f for f in os.listdir(ckpt_dir)
                       if f.startswith("samples_") and f.endswith(".npy"))
        for fn in names:
            offset = int(fn[len("samples_"):-len(".npy")])
            if offset >= up_to_site:
                break
            assert offset == cursor, (offset, cursor)   # contiguous prefix
            blk = np.load(os.path.join(ckpt_dir, fn))
            blocks.append(blk)
            cursor += blk.shape[0]
        assert cursor == up_to_site, (cursor, up_to_site)
        return blocks

    # -- per-walk bookkeeping ------------------------------------------------
    def _begin_walk(self) -> None:
        """Re-anchor the I/O deltas and zero the per-walk stats: a cached
        engine serves many macro batches, but ``stats`` always describes
        the most recent walk (the pre-cache contract)."""
        self._store0 = self._store_counters()
        self._runtime_io0 = dict(self.runtime.io_counters())
        with self._live_lock:
            live = self._live           # a warm prefetched segment counts
        self.stats.update(segments=0, io_wait_s=0.0, compute_s=0.0,
                          max_live_segments=live, io_hidden_frac=0.0,
                          fetch_s=0.0, put_s=0.0, put_bytes=0,
                          owned_segments=0, handoffs=0,
                          handoff_send_bytes=0, handoff_recv_bytes=0,
                          gather_bytes=0,
                          **{k: 0 for k in self._STORE_COUNTERS})
        for k in self._runtime_io0:
            self.stats[k] = 0
        self.stats.pop("log_prob", None)   # set per walk, clamped only

    def _take_warm(self, seg_key) -> Optional[Future]:
        """Claim the gang-scheduled first-segment fetch if it matches this
        walk's opening segment; release a stale one."""
        if self._warm is None:
            return None
        key, fut = self._warm
        self._warm = None
        if key == seg_key:
            return fut
        try:
            gd, ld, _ = fut.result()    # schedule changed (e.g. resume):
            self._release(gd, ld)       # drop the stale buffers
        except Exception:
            # a failed SPECULATIVE fetch must not fail a walk that never
            # needed it (the matched case above surfaces its error when
            # the walk consumes the future — that data was required)
            pass
        return None

    # -- driver --------------------------------------------------------------
    _UNSET = object()

    def sample(self, n_samples: int, key: jax.Array, *, resume: bool = False,
               stop_after_segments: Optional[int] = None,
               checkpoint_dir=_UNSET, pipeline: bool = False) -> np.ndarray:
        """Walk the whole chain; returns (N, M) int32 outcomes.

        ``resume=True`` continues from the newest checkpoint (bit-identical
        to the uninterrupted run); ``checkpoint_dir`` overrides the
        engine's per walk (a cached engine serves many macro batches, each
        with its own checkpoint subdirectory); ``stop_after_segments``
        simulates a mid-run kill for tests — the engine checkpoints the
        boundary state and returns the partial (N, sites_done) block.
        ``pipeline=True`` gang-schedules across walks: once this walk's
        last segment is fetched, the prefetch pool immediately fetches (or,
        multi-process, broadcasts) the *first* segment again, so the next
        macro batch's Γ I/O hides behind this batch's tail compute.
        """
        return self.sample_with_stats(
            n_samples, key, resume=resume,
            stop_after_segments=stop_after_segments,
            checkpoint_dir=checkpoint_dir, pipeline=pipeline)[0]

    def sample_with_stats(self, n_samples: int, key: jax.Array, *,
                          resume: bool = False,
                          stop_after_segments: Optional[int] = None,
                          checkpoint_dir=_UNSET, pipeline: bool = False
                          ) -> tuple[np.ndarray, dict]:
        """:meth:`sample` plus a stats snapshot taken under the walk lock —
        on a shared (session-cached) engine, reading ``self.stats`` after
        the lock drops races the next walk's reset."""
        with self._walk_lock, trace.span("engine.walk"):
            out = self._sample_locked(n_samples, key, resume=resume,
                                      stop_after_segments=stop_after_segments,
                                      checkpoint_dir=checkpoint_dir,
                                      pipeline=pipeline)
            return out, dict(self.stats)

    def _sample_locked(self, n_samples: int, key: jax.Array, *,
                       resume: bool, stop_after_segments: Optional[int],
                       checkpoint_dir, pipeline: bool) -> np.ndarray:
        ckpt_dir = (self.checkpoint_dir if checkpoint_dir is self._UNSET
                    else checkpoint_dir)
        if self.clamp_map is not None and (resume or ckpt_dir):
            # the checkpoint unit is SamplerState(env, key, log_scale) —
            # it has no log_prob slot, so a resumed clamped walk would
            # silently drop the conditional weights accumulated before the
            # kill.  Refuse loudly; clamped macro batches are idempotent
            # work items (run_queue) — rerun the batch instead.
            raise ValueError(
                "clamped walks do not checkpoint or resume (the sampler "
                "state has no log_prob slot) — drop checkpoint_dir/resume "
                "and rely on idempotent macro batches")
        if ckpt_dir:
            os.makedirs(ckpt_dir, exist_ok=True)
        if self.shard is not None and self.runtime.process_count > 1:
            return self._sample_sharded(n_samples, key, resume=resume,
                                        stop_after_segments=stop_after_segments,
                                        ckpt_dir=ckpt_dir, pipeline=pipeline)
        self._begin_walk()

        M_sites = self.n_sites
        if self.plan.micro_batch is not None:
            assert n_samples % self.plan.micro_batch == 0, \
                (n_samples, self.plan.micro_batch)
        if self.runtime.process_count > 1 and stop_after_segments is not None:
            raise ValueError(
                "stop_after_segments injects a single-process kill — "
                "on a multi-process runtime the peers would block on "
                "the broadcast")

        schedule = self._segment_schedule()
        boundaries = {s for s, _, _ in schedule} | {M_sites}
        idx = 0
        done: list[np.ndarray] = []       # site-major (L_i, N) blocks
        persisted = 0                     # blocks already written to disk
        env = PP.segment_env_init(n_samples, schedule[0][2], self.gamma_dtype)
        log_scale = jnp.zeros((n_samples,),
                              dtype=real_dtype_of(env.dtype))
        log_prob = (jnp.zeros((n_samples,), dtype=real_dtype_of(env.dtype))
                    if self.clamp_map is not None else None)
        if resume:
            if not ckpt_dir:
                raise ValueError("resume=True needs a checkpoint_dir")
            if self.runtime.process_count > 1:
                # cluster-synchronized resume: after an unclean stop the
                # processes' newest durable boundaries can differ, and
                # resuming from unequal indices would desync the broadcast
                # schedule.  Agree on min(newest) — the newest boundary
                # EVERY process holds (keep=0 checkpoints, see
                # newest_checkpoint_site) — and walk from there in
                # lockstep; 0 means someone lost everything: start fresh.
                agreed = self.runtime.allreduce_min(
                    newest_checkpoint_site(ckpt_dir))
                loaded = (load_sampler_state(ckpt_dir, site=agreed)
                          if agreed > 0 else None)
            else:
                loaded = load_sampler_state(ckpt_dir)
            if loaded is not None:
                site, state, _ = loaded
                # the engine only checkpoints segment boundaries (or end)
                assert site in boundaries, (site, sorted(boundaries))
                # a mismatched key would silently produce a chimera batch
                # (prefix from the checkpoint's seed, suffix from the
                # caller's)
                assert jnp.array_equal(jax.random.key_data(key),
                                       jax.random.key_data(state.key)), \
                    "resume key does not match the checkpointed run"
                env, key, log_scale = state.env, state.key, state.log_scale
                idx = next((i for i, (s, _, _) in enumerate(schedule)
                            if s == site), len(schedule))
                done = self._load_sample_blocks(site, ckpt_dir)
                persisted = len(done)

        if idx >= len(schedule):          # resumed from a finished run
            self._finish_walk()
            return np.concatenate(done, axis=0).T.astype(np.int32)

        fut: Optional[Future] = self._take_warm(schedule[idx])
        if fut is None:
            fut = self._submit(schedule[idx])
        seg_idx = 0
        while idx < len(schedule):
            start, _, chi_s = schedule[idx]
            gd, ld, real = self._wait_gamma(fut)
            if idx + 1 < len(schedule):   # double buffer: fetch k+1 now
                fut = self._submit(schedule[idx + 1])
            elif pipeline and stop_after_segments is None:
                # gang-scheduling (paper §3.1 across macro batches): the
                # pool is idle for the rest of this walk, so fetch — or on a
                # multi-process runtime, broadcast — the next batch's FIRST
                # segment now, behind this batch's tail compute
                self._warm = (schedule[0], self._submit(schedule[0]))

            samples, env, log_scale, log_prob = self._compute_segment(
                gd, ld, real, chi_s, env, log_scale, log_prob, key, start)
            done.append(samples)
            self.stats["segments"] += 1
            idx += 1
            seg_idx += 1
            site_done = start + real

            stopping = (stop_after_segments is not None
                        and seg_idx >= stop_after_segments
                        and idx < len(schedule))
            ckpt_due = (self.plan.checkpoint_every
                        and seg_idx % self.plan.checkpoint_every == 0)
            if ckpt_dir and (ckpt_due or stopping):
                # samples live in per-segment block files written exactly
                # once each — re-serializing the cumulative history every
                # segment would make total checkpoint I/O quadratic in M
                site_cursor = site_done - sum(b.shape[0]
                                              for b in done[persisted:])
                for blk in done[persisted:]:
                    np.save(os.path.join(ckpt_dir,
                                         f"samples_{site_cursor:06d}.npy"),
                            blk)
                    site_cursor += blk.shape[0]
                persisted = len(done)
                # multi-process walks keep the FULL boundary history
                # (keep=0): the cluster-min resume agreement must be able
                # to load any boundary a slower process is still at
                save_sampler_state(
                    ckpt_dir, site_done,
                    S.SamplerState(env, key, log_scale),
                    np.zeros((0, n_samples), dtype=np.int32),
                    keep=0 if self.runtime.process_count > 1 else 3)
            if stopping:
                if idx < len(schedule):   # drain the prefetch we no longer
                    gd, ld, _ = fut.result()   # need, or its buffers leak and
                    self._release(gd, ld)      # the ≤2-live bound breaks
                break

        if self.clamp_map is not None:
            self.stats["log_prob"] = np.asarray(log_prob)
        self._finish_walk()
        return np.concatenate(done, axis=0).T.astype(np.int32)

    def _verify_and_repair_sharded(self, me: int) -> None:
        """Pre-walk self-healing round (sharded plane): every host verifies
        its OWNED slice against the digest manifest, the union of corrupt
        sites is allgathered, and each corrupt site is re-materialized from
        the lowest-ranked peer holding a healthy copy over the existing
        tagged ``send``/``recv`` — block-cyclic replication (Adamski &
        Brown) means a peer often holds the very bytes a rotted slice
        needs.  With no healthy holder anywhere, EVERY process raises
        :class:`CorruptSegment` in the same round, so the collectives stay
        aligned and the job fails with a kind=corruption fault instead of
        hanging or sampling garbage."""
        if not getattr(self.store, "verify", False):
            return
        mine = self.store.verify_sites()
        rounds = self.runtime.allgather_payloads(
            {"corrupt": np.asarray(sorted(mine), dtype=np.int64)})
        bad = sorted({int(s) for pay in rounds
                      for s in np.asarray(pay["corrupt"]).ravel()})
        for site in bad:
            owner = self.shard.owner(site)
            healthy = int(me != owner and self.store.has_healthy_copy(site))
            votes = self.runtime.allgather_payloads(
                {"healthy": np.asarray([healthy], dtype=np.int64)})
            helpers = [r for r, pay in enumerate(votes)
                       if int(np.asarray(pay["healthy"]).ravel()[0])]
            if not helpers:
                raise CorruptSegment(Fault(
                    kind="corruption", site=site, store=self.store.root,
                    message=f"Γ site {site} (owner host {owner}) is corrupt "
                            f"and no peer holds a healthy copy — "
                            f"unrepairable; failing the job cleanly"))
            helper, tag = helpers[0], ("repair", site)
            if me == helper:
                data = self.store.read_repair_bytes(site)
                self.runtime.send(owner, {
                    "site": np.asarray(site, dtype=np.int64),
                    "data": np.frombuffer(data, dtype=np.uint8)}, tag=tag)
            elif me == owner:
                pay = self.runtime.recv(helper, tag=tag)
                if int(np.asarray(pay["site"])) != site:
                    raise RuntimeError(
                        f"repair desync: host {me} expected bytes for site "
                        f"{site} but received site "
                        f"{int(np.asarray(pay['site']))}")
                self.store.restore_site(
                    site, np.asarray(pay["data"], dtype=np.uint8).tobytes())
            else:
                self.runtime.observe_handoff(helper, tag=tag)

    def _sample_sharded(self, n_samples: int, key: jax.Array, *,
                        resume: bool, stop_after_segments: Optional[int],
                        ckpt_dir, pipeline: bool) -> np.ndarray:
        """Block-cyclic sharded walk (ROADMAP item 3, Adamski & Brown).

        Every process iterates the same segment schedule, but segment k's
        sites are contracted only by ``shard.segment_owner(k)``; at each
        ownership boundary the tiny (N, χ) environment — never Γ — crosses
        the wire (``runtime.send/recv``), and the next owner's Γ prefetch
        for its OWN slice runs behind the predecessor's compute, exactly as
        the broadcast plane overlaps its collective.  The walk ends with a
        barrier and one sample-block all-gather so every process returns
        the identical (N, M) batch: wire traffic is O(chain) env handoffs
        plus one outcome gather, not O(hosts × chain) Γ broadcast bytes.

        Crash consistency (the SIGKILL chaos test's contract): an owner
        persists a RECEIVED boundary before computing from it, and each
        computed block + post-compute boundary immediately after the
        compute — both with ``keep=0`` — so the cluster-min agreed site is
        always durable exactly where the resume needs it, with every owned
        block below it on disk.
        """
        from repro.shard import walk as SW

        if stop_after_segments is not None:
            raise ValueError(
                "stop_after_segments injects a single-process kill — on a "
                "sharded runtime the peers would block on the env handoff")
        self._begin_walk()
        if self.plan.micro_batch is not None:
            assert n_samples % self.plan.micro_batch == 0, \
                (n_samples, self.plan.micro_batch)

        schedule = self._segment_schedule()
        owners = list(self._seg_owners)
        me = self.runtime.process_index
        self._verify_and_repair_sharded(me)
        base_key_data = np.asarray(jax.random.key_data(key))

        idx0 = 0
        blocks: dict[int, np.ndarray] = {}     # start site → (L, N) block
        env = PP.segment_env_init(n_samples, schedule[0][2], self.gamma_dtype)
        log_scale = jnp.zeros((n_samples,), dtype=real_dtype_of(env.dtype))
        log_prob = (jnp.zeros((n_samples,), dtype=real_dtype_of(env.dtype))
                    if self.clamp_map is not None else None)

        if resume:
            if not ckpt_dir:
                raise ValueError("resume=True needs a checkpoint_dir")
            agreed = self.runtime.allreduce_min(
                newest_checkpoint_site(ckpt_dir))
            if agreed > 0:
                boundaries = {s for s, _, _ in schedule} | {self.n_sites}
                assert agreed in boundaries, (agreed, sorted(boundaries))
                idx0 = next((i for i, (s, _, _) in enumerate(schedule)
                             if s == agreed), len(schedule))
                for i in range(idx0):          # my durable blocks < agreed
                    if owners[i] == me:
                        s0 = schedule[i][0]
                        blocks[s0] = np.load(os.path.join(
                            ckpt_dir, f"samples_{s0:06d}.npy"))
                if idx0 < len(schedule) and owners[idx0] == me:
                    site, state, _ = load_sampler_state(ckpt_dir,
                                                        site=agreed)
                    assert jnp.array_equal(jax.random.key_data(key),
                                           jax.random.key_data(state.key)), \
                        "resume key does not match the checkpointed run"
                    env, key, log_scale = (state.env, state.key,
                                           state.log_scale)

        owned = [i for i in range(idx0, len(schedule)) if owners[i] == me]
        self.stats["owned_segments"] = len(owned)
        fut: Optional[Future] = None
        if owned:
            fut = self._take_warm(schedule[owned[0]])
            if fut is None:
                fut = self._submit(schedule[owned[0]])
        next_pos = 1                      # next entry of `owned` to prefetch

        for idx in range(idx0, len(schedule)):
            start, _, chi_s = schedule[idx]
            prev_owner = owners[idx - 1] if idx > idx0 else None
            incoming = prev_owner is not None and prev_owner != owners[idx]
            if owners[idx] != me:
                if incoming and prev_owner != me:
                    # neither endpoint: collective-backed transports still
                    # need this process in the transfer (no-op in-process)
                    self.runtime.observe_handoff(prev_owner, tag=start)
                continue

            if incoming:                  # I take over: receive the env
                with trace.span("engine.wait_handoff", start=start) as sp:
                    payload = self.runtime.recv(prev_owner, tag=start)
                self.stats["io_wait_s"] += sp.seconds
                env_h, ls_h, key_data, site = SW.decode_handoff(payload)
                if site != start:
                    raise RuntimeError(
                        f"handoff desync: host {me} expected the env at "
                        f"site {start} but received site {site} — are all "
                        f"processes walking the same plan?")
                if not np.array_equal(key_data, base_key_data):
                    raise RuntimeError(
                        "handoff key does not match this walk's base key — "
                        "the predecessor owner is sampling a different "
                        "(n_samples, key) job")
                env, log_scale = jnp.asarray(env_h), jnp.asarray(ls_h)
                if self.clamp_map is not None:
                    lp_h = SW.decode_handoff_log_prob(payload)
                    if lp_h is None:
                        raise RuntimeError(
                            "clamped walk received a handoff without the "
                            "log_prob carry — is the predecessor owner "
                            "running an unclamped plan?")
                    log_prob = jnp.asarray(lp_h)
                self.stats["handoffs"] += 1
                self.stats["handoff_recv_bytes"] += SW.payload_nbytes(payload)
                if ckpt_dir:              # durable BEFORE computing from it
                    save_sampler_state(
                        ckpt_dir, start, S.SamplerState(env, key, log_scale),
                        np.zeros((0, n_samples), dtype=np.int32), keep=0)

            gd, ld, real = self._wait_gamma(fut)
            if next_pos < len(owned):     # pipeline my NEXT owned segment
                fut = self._submit(schedule[owned[next_pos]])
                next_pos += 1
            else:
                fut = None
                if pipeline:              # gang-schedule the next walk
                    self._warm = (schedule[owned[0]],
                                  self._submit(schedule[owned[0]]))

            samples, env, log_scale, log_prob = self._compute_segment(
                gd, ld, real, chi_s, env, log_scale, log_prob, key, start)
            blocks[start] = samples
            self.stats["segments"] += 1
            site_done = start + real
            if ckpt_dir:
                np.save(os.path.join(ckpt_dir, f"samples_{start:06d}.npy"),
                        samples)
                save_sampler_state(
                    ckpt_dir, site_done,
                    S.SamplerState(env, key, log_scale),
                    np.zeros((0, n_samples), dtype=np.int32), keep=0)
            if idx + 1 < len(schedule) and owners[idx + 1] != me:
                payload = SW.encode_handoff(env, log_scale, key, site_done,
                                            log_prob=log_prob)
                self.runtime.send(owners[idx + 1], payload, tag=site_done)
                self.stats["handoffs"] += 1
                self.stats["handoff_send_bytes"] += SW.payload_nbytes(payload)

        # every process finishes its slice before the outcome gather
        self.runtime.barrier()
        merged: dict[int, np.ndarray] = {}
        for pay in self.runtime.allgather_payloads(SW.encode_blocks(blocks)):
            self.stats["gather_bytes"] += SW.payload_nbytes(pay)
            merged.update(SW.decode_blocks(pay))
        out = SW.assemble_blocks(merged, self.n_sites, n_samples)
        if self.clamp_map is not None:
            # the completed carry lives with the LAST segment's owner; one
            # extra tiny gather makes stats["log_prob"] identical on every
            # process, matching the sample-block contract
            mine = (np.asarray(log_prob) if owners[-1] == me
                    else np.zeros((0,), dtype=np.float64))
            for pay in self.runtime.allgather_payloads({"log_prob": mine}):
                arr = np.asarray(pay["log_prob"])
                if arr.size:
                    self.stats["log_prob"] = arr
        self._finish_walk()
        return out

    def _finish_walk(self) -> None:
        """Fold the store's and the runtime's I/O counters (deltas since
        engine creation) into ``stats`` and line the processes up — every
        process finishes macro batch b before any starts b+1."""
        for k, v in self._store_counters().items():
            self.stats[k] = v - self._store0[k]
        with self._live_lock:
            for k, v in self._fetched.items():
                self.stats[k] = v - self._fetched0[k]
            self._fetched0 = dict(self._fetched)
        # the share of the whole fetch (read, decode, pad, device_put)
        # that the walk did not wait for
        if self.stats["fetch_s"] > 0:
            self.stats["io_hidden_frac"] = min(1.0, max(
                0.0, 1.0 - self.stats["io_wait_s"] / self.stats["fetch_s"]))
        counters = self.runtime.io_counters()
        for k, v0 in self._runtime_io0.items():
            self.stats[k] = counters[k] - v0
        self.runtime.barrier()

    def run_queue(self, queue, per_batch: int, base_key: jax.Array,
                  worker: str = "engine") -> dict[int, np.ndarray]:
        """Macro batches (paper N₁) as engine work items: batch b is fully
        determined by fold_in(base_key, b), so the queue's elasticity /
        restart guarantees (runtime/elastic.py) hold verbatim — completed
        batches are never recomputed and results are owner-independent."""
        out: dict[int, np.ndarray] = {}
        while (b := queue.claim(worker)) is not None:
            # consecutive batches share the walk schedule — gang-schedule
            # the next batch's first segment behind this batch's tail
            # (pending includes b itself: the final batch must not pin a
            # speculative segment until close)
            out[b] = self.sample(per_batch, jax.random.fold_in(base_key, b),
                                 pipeline=len(queue.pending) > 1)
            queue.complete(b)
        return out

    def close(self, close_store: bool = True) -> None:
        """Join the prefetch thread (releasing any gang-scheduled segment
        still in its slot); ``close_store=False`` leaves the (possibly
        shared) GammaStore alive for further engines/sessions."""
        if self._warm is not None:
            _, fut = self._warm
            self._warm = None
            try:
                gd, ld, _ = fut.result()
                self._release(gd, ld)
            except Exception:           # fetch already failed — nothing live
                pass
        self._pool.shutdown(wait=True)
        if self._wrapped_store is not None:
            # the sharded view is ENGINE-owned (its prefetch thread must
            # not leak) even when the caller's underlying store is shared
            self._wrapped_store.close()
        if close_store:
            self._source_store.close()

    def __enter__(self) -> "StreamingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
