"""`SamplingSession` — one front door for every FastMPS sampling mode.

The session owns the source (an in-memory :class:`MPS`, an on-disk
:class:`GammaStore`, or a store path), resolves a :class:`SamplerConfig`
against it, and routes ``sample(n, key)`` to a registered backend.  Every
level of the paper's multi-level design composes behind that single call:

* macro batches N₁ as idempotent :class:`WorkQueue` items (``run_queue``),
* micro batches N₂ under every scheme (§3.1, Eq. 3),
* DP × TP placement over the session's mesh (§3.1–§3.2, Eq. 7 selector),
* dynamic bond dimensions via a bucketed χ-profile (§3.4.2),
* segment streaming with compute/I-O overlap (§3.1/§3.3.2),
* per-segment checkpoints + bit-exact mid-chain resume (§4.1).

Typical use::

    from repro import api

    with api.SamplingSession(mps) as session:           # in-memory
        samples = session.sample(4096, jax.random.key(0))

    cfg = api.SamplerConfig(backend="streamed", checkpoint_dir=ckpt)
    with api.SamplingSession(store, cfg, mesh=mesh) as session:
        print(session.explain(4096))                    # why this plan
        samples = session.sample(4096, key)             # streamed DP/TP
        resumed = session.sample(4096, key, resume=True)

``session.plan(n)`` returns the fully-resolved :class:`SessionPlan`;
``session.explain(n)`` adds the perfmodel's §3.1 overlap accounting.
"""
from __future__ import annotations

import os
import tempfile
import threading
from typing import Optional, Union

import jax
import numpy as np

from repro.api.backends import SampleRequest, get_backend
from repro.api.config import (SamplerConfig, SessionPlan, resolve_hardware,
                              resolve_plan)
from repro.api.runtime import ClusterRuntime, resolve_runtime
from repro.core.mps import MPS
from repro.data.gamma_store import GammaStore


class SamplingSession:
    """Facade over the (data plane × runtime) registries; see module
    docstring."""

    def __init__(self, source: Union[MPS, GammaStore, str, os.PathLike],
                 config: Optional[SamplerConfig] = None, *, mesh=None):
        self.config = config or SamplerConfig()
        self.mesh = mesh
        # the cluster runtime is session state (it may hold live transport
        # handles); plans record only its name.  A runtime resolved from a
        # name here is session-owned (its persistent workers are reaped on
        # close); an instance passed in stays the caller's
        self.runtime = resolve_runtime(self.config.runtime)
        self._owns_runtime = not isinstance(self.config.runtime,
                                            ClusterRuntime)
        self._mps: Optional[MPS] = None
        self._store: Optional[GammaStore] = None
        self._owns_store = False
        self._tmp_store_root: Optional[str] = None
        self._plans: dict[int, SessionPlan] = {}
        self.stats: dict = {}           # last sample()'s engine statistics
        # service workers drive the session concurrently: plan resolution /
        # source materialization must be race-free
        self._state_lock = threading.RLock()
        # streamed engines, cached per plan so repeated batches of one job
        # reuse ONE compilation and the prefetch pool can gang-schedule
        # across batch boundaries (closed with the session)
        self._engines: dict = {}
        self._service = None            # lazy one-lane service behind sample()

        if isinstance(source, (str, os.PathLike)):
            source = GammaStore(str(source))
            self._owns_store = True
        if isinstance(source, GammaStore):
            self._store = source
            if source.n_sites == 0:
                raise ValueError(f"empty GammaStore at {source.root}")
            shape = source.meta(0)      # header-only probe
            self.n_sites, self.chi, self.d = (source.n_sites, shape[0],
                                              shape[2])
            self._source_semantics = None
            self._backend_hint = "streamed"
            self._elt_bytes = np.dtype(source.compute_dtype).itemsize
        elif isinstance(source, MPS):
            self._mps = source
            self.n_sites, self.chi, self.d = (source.n_sites, source.chi,
                                              source.phys_dim)
            self._source_semantics = source.semantics
            self._backend_hint = "inmem"
            self._elt_bytes = np.dtype(source.gammas.dtype).itemsize
        else:
            raise TypeError(f"source must be an MPS, a GammaStore, or a "
                            f"store path — got {type(source).__name__}")

    # -- planning ------------------------------------------------------------
    def plan(self, n_samples: int) -> SessionPlan:
        """The fully-resolved execution plan for ``sample(n_samples, ...)``."""
        with self._state_lock:
            if n_samples not in self._plans:
                self._plans[n_samples] = resolve_plan(
                    self.config, n_samples=n_samples, n_sites=self.n_sites,
                    chi=self.chi, d=self.d, mesh=self.mesh,
                    source_semantics=self._source_semantics,
                    backend_hint=self._backend_hint,
                    elt_bytes=self._elt_bytes, runtime=self.runtime)
            return self._plans[n_samples]

    def explain(self, n_samples: int) -> dict:
        """``plan()`` plus the perfmodel accounting behind the AUTO choices."""
        plan = self.plan(n_samples)
        stages = plan.stages or ((0, self.n_sites, self.chi),)
        info = {
            "backend": plan.backend, "runtime": plan.runtime,
            "processes": self.runtime.process_count,
            "scheme": plan.scheme, "kernels": plan.kernels,
            "semantics": plan.semantics, "p1": plan.p1, "p2": plan.p2,
            "micro_batch": plan.micro_batch,
            "n_stages": len(stages),
            "chi_buckets": sorted({chi_s for _, _, chi_s in stages}),
        }
        if plan.backend == "streamed":
            from repro.core.perfmodel import Workload
            from repro.engine.planner import explain_plan
            from repro.engine.streaming import StreamPlan
            w = Workload(n_samples=n_samples, n_sites=self.n_sites,
                         chi=self.chi, d=self.d, macro_batch=n_samples,
                         micro_batch=(plan.micro_batch or n_samples))
            engine_info = explain_plan(
                StreamPlan(segment_len=plan.segment_len,
                           scheme=("inmem" if plan.scheme == "seq"
                                   else plan.scheme),
                           micro_batch=plan.micro_batch),
                w, resolve_hardware(self.config),
                compute_bytes=self._elt_bytes)
            engine_info.pop("scheme", None)      # keep the session-level name
            info.update(engine_info)
            if plan.shard_block:
                from repro.core.perfmodel import shard_wire_bytes
                info["shard"] = {
                    "block": plan.shard_block,
                    "hosts": self.runtime.process_count,
                    **shard_wire_bytes(w, self.runtime.process_count,
                                       block=plan.shard_block),
                }
        return info

    # -- source materialization (lazy; at most once per session) -------------
    def _ensure_mps(self) -> MPS:
        with self._state_lock:
            if self._mps is None:
                import jax.numpy as jnp
                g, lam = self._store.get_segment(0, self.n_sites)
                semantics = (self.config.semantics
                             if self.config.semantics != "auto" else "linear")
                self._mps = MPS(jnp.asarray(g), jnp.asarray(lam), semantics)
            return self._mps

    def _ensure_store(self) -> GammaStore:
        with self._state_lock:
            if self._store is None:
                root = self.config.store_root
                if root is None:
                    root = tempfile.mkdtemp(prefix="fastmps_session_")
                    self._tmp_store_root = root
                # identity storage dtype: a session-materialized store must
                # not round Γ, or the streamed backend would diverge bit-wise
                # from the in-memory one (callers wanting bf16 storage build
                # the GammaStore themselves)
                dt = self._mps.gammas.dtype
                self._store = GammaStore(root, storage_dtype=dt,
                                         compute_dtype=dt)
                if self._store.n_sites == 0:
                    self._store.write_mps(self._mps)
                self._owns_store = True
            return self._store

    # -- execution -----------------------------------------------------------
    def _default_service(self):
        """The session's private one-lane :class:`SamplingService` —
        ``sample()``/``run_queue()`` are synchronous wrappers over it, so
        there is exactly ONE execution path (the service's batch runner)."""
        with self._state_lock:
            if self._service is None:
                from repro.api.service import SamplingService
                self._service = SamplingService(workers=1)
            return self._service

    def sample(self, n_samples: int, key: jax.Array, *, resume: bool = False,
               checkpoint_dir: Optional[str] = None,
               stop_after_segments: Optional[int] = None) -> np.ndarray:
        """Draw ``n_samples`` chains; returns (N, M) int32 outcomes.

        A thin synchronous wrapper: the call is a single-macro-batch job on
        the session's private :class:`~repro.api.service.SamplingService`
        (same key, so bit-identity with pre-service releases holds — see
        ``service.batch_key``); multi-batch/async callers use a service
        directly.  ``resume=True`` continues a killed streamed run from its
        newest checkpoint (bit-identical to the uninterrupted run, paper
        §4.1).  ``checkpoint_dir`` overrides the config's (e.g. one dir per
        macro batch); ``stop_after_segments`` is the failure-injection hook
        tests use to simulate a mid-chain kill.
        """
        handle = self._default_service().submit(
            self, n_samples=n_samples, key=key, macro_batches=1,
            resume=resume, checkpoint_dir=checkpoint_dir,
            stop_after_segments=stop_after_segments)
        return handle.result()

    def _execute_batch(self, n_samples: int, key: jax.Array, *, job=None,
                       resume: bool = False,
                       checkpoint_dir: Optional[str] = None,
                       stop_after_segments: Optional[int] = None,
                       pipeline: bool = False) -> tuple[np.ndarray, dict]:
        """Run ONE macro batch on the data plane — the service's batch
        runner, and the only place a backend is invoked.  ``key`` is the
        *job* key: the local schedule folds it per :func:`service.batch_key`;
        the remote data plane ships it unfolded with the ``job`` identity so
        the worker side folds identically (the job batch, not the whole run,
        is the dispatch unit).  Returns ``(samples, stats)`` — stats by
        value, so concurrent lanes never read another batch's numbers off
        the shared ``self.stats`` attribute (kept for the synchronous
        facade)."""
        from repro.api.service import batch_key

        plan = self.plan(n_samples)
        if job is not None and plan.backend != "remote":
            key = batch_key(key, job.batch_id, job.n_batches)
        # the config-level checkpoint_dir names ONE chain walk's directory —
        # a multi-batch job must not fall back to it, or every batch would
        # overwrite the same site_*/samples_* files (use checkpoint_root,
        # which the scheduler expands to per-batch subdirs)
        if checkpoint_dir is None and (job is None or job.n_batches == 1):
            checkpoint_dir = self.config.checkpoint_dir
        req = SampleRequest(
            plan=plan, n_samples=n_samples, key=key, mesh=self.mesh,
            mps=self._ensure_mps, store=self._ensure_store,
            runtime=self.runtime, config=self.config, resume=resume,
            checkpoint_dir=checkpoint_dir,
            stop_after_segments=stop_after_segments,
            job=job, pipeline=pipeline, engines=self._engines)
        out = get_backend(plan.backend).sample(req)
        self.stats = req.stats
        return out, dict(req.stats)

    def run_queue(self, queue, per_batch: int, base_key: jax.Array, *,
                  worker: str = "session", checkpoint_root: Optional[str] = None,
                  on_batch=None) -> dict[int, np.ndarray]:
        """Macro batches (paper N₁) as idempotent work items.

        A thin synchronous wrapper over the service execution path: each
        batch claimed from the *caller's* queue (whose state is the restart
        unit — two sessions sharing one queue split the work) runs as a
        single-batch service job via :meth:`sample`.  Callers that don't
        need an external queue should submit one multi-batch job to a
        :class:`~repro.api.service.SamplingService` instead and stream it.

        Batch b is fully determined by ``fold_in(base_key, b)``, so the
        :class:`WorkQueue`'s elasticity/restart guarantees hold verbatim:
        completed batches are never recomputed and results are
        owner-independent.  With ``checkpoint_root``, each batch checkpoints
        into its own subdirectory and a mid-batch kill resumes from the last
        segment boundary (streamed backend).  ``on_batch(b, samples)`` is
        called per finished batch (e.g. to persist it); without it the
        samples are collected and returned.
        """
        import shutil

        from repro.api.service import (batch_checkpoint_dir,
                                       has_chain_checkpoint)

        streamed = self.plan(per_batch).backend == "streamed"
        out: dict[int, np.ndarray] = {}
        while (b := queue.claim(worker)) is not None:
            ck, resume = None, False
            if checkpoint_root and streamed:
                ck = batch_checkpoint_dir(checkpoint_root, b)
                os.makedirs(ck, exist_ok=True)
                resume = has_chain_checkpoint(ck)
            res = self.sample(per_batch, jax.random.fold_in(base_key, b),
                              resume=resume, checkpoint_dir=ck)
            if on_batch is not None:
                on_batch(b, res)
            else:
                out[b] = res
            if ck:
                shutil.rmtree(ck, ignore_errors=True)  # batch output durable
            queue.complete(b)
        return out

    # -- lifecycle -----------------------------------------------------------
    def close(self) -> None:
        """Release session-owned resources (the private service lane, the
        cached streamed engines, the materialized store's prefetch thread
        and temp directory); stores passed in by the caller stay open."""
        if self._service is not None:
            self._service.close()       # joins the lane — no walk in flight
            self._service = None
        for eng in self._engines.values():
            eng.close(close_store=False)
        self._engines.clear()
        if self._owns_store and self._store is not None:
            self._store.close()
            self._store = None
            self._owns_store = False
        if self._tmp_store_root is not None:
            import shutil
            shutil.rmtree(self._tmp_store_root, ignore_errors=True)
            self._tmp_store_root = None
        if self._owns_runtime:
            self.runtime.close()        # reap persistent transport workers

    def __enter__(self) -> "SamplingSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
