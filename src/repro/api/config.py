"""Config schema + resolution for the unified sampling front door.

:class:`SamplerConfig` is the *session-level* schema: one frozen dataclass
describing workload semantics, placement scheme, precision, χ-profile, micro
batching, and streaming/checkpoint options.  Fields set to :data:`AUTO` are
resolved against the perfmodel planner (``engine/planner`` + ``core/perfmodel``)
and the session's source/mesh into a concrete :class:`SessionPlan` — the
fully-resolved record a backend executes and ``session.plan()`` returns.

(The identically-named ``repro.core.sampler.SamplerConfig`` is the *kernel*
config — semantics/scaling/compute dtype of one chain scan.  Resolution
builds it from this schema; applications only touch the session-level one.)

Schema summary (see also examples/README.md):

======================  =====================================================
field                   meaning
======================  =====================================================
``semantics``           "linear" | "born" | AUTO (taken from the source MPS)
``scheme``              "seq" | "dp" | "tp_single" | "tp_double" |
                        "baseline19" | AUTO (planner: Eq. 7 TP selector over
                        the mesh's p₁×p₂)
``backend``             the *data plane*: "inmem" | "streamed" | "remote" |
                        AUTO (streamed iff the source is a ``GammaStore`` /
                        store path; remote iff the runtime is remote)
``runtime``             the *cluster runtime*: "local" | "multihost" |
                        "remote" | a ``ClusterRuntime`` instance | AUTO
                        (local on one process).  Orthogonal to ``backend``:
                        ``streamed × multihost`` is the paper's §3.1
                        process-0-reads-then-broadcasts cell
``scaling``             §3.3 environment rescale: "none"|"global"|"per_sample"
``kernels``             site-step kernel dispatch: "pallas" (fused VMEM-
                        resident pipeline, ``kernels/dispatch.py``) | "xla" |
                        AUTO (pallas on a TPU backend, xla elsewhere)
``compute_dtype``       mixed-precision GEMM inputs (e.g. ``jnp.bfloat16``)
``wire_dtype``          §3.3.2-on-the-wire cast for TP collectives
``measure_first``       tp-3 measure-first reformulation (linear semantics)
``micro_batch``         N₂ *per data shard* (int), AUTO (memory-model pick),
                        or None (whole batch in one chunk)
``chi_profile``         per-site bucketed χ tuple (§3.4.2) or None (fixed χ)
``segment_len``         streamed-backend sites per device segment, or AUTO
                        (largest L whose two buffers fit the device budget)
``shard``               chain sharding (``repro.shard``): None (off — the
                        §3.1 broadcast plane), an int block size in sites
                        (block-cyclic site→host ownership; must be a whole
                        number of segments), or AUTO (one segment per
                        block).  Streamed backend only; composes with
                        DP-over-samples and dynamic χ
``clamp``               conditional sampling (``repro.workloads``): a
                        ``{site: outcome}`` / ``{site: per-sample array}``
                        mapping fixing outcomes at a subset of sites; the
                        walk forces those outcomes into the collapse path
                        and returns the clamped branch's Born weight as a
                        per-sample ``log_prob`` in ``session.stats``.
                        ``None``/``{}`` = unclamped (bit-identical to the
                        plain sampler)
``store_root``          where a streamed session materializes Γ when built
                        from an in-memory MPS (default: temp dir)
``checkpoint_dir``      per-segment checkpoint directory (streamed backend)
``checkpoint_every``    segments between checkpoints (0 = off)
``hardware``            perfmodel :class:`Hardware` the AUTO fields plan for;
                        None = the ``perfmodel.PEAKS`` row of this
                        process's device kind
``device_budget``       device memory budget override in bytes
======================  =====================================================
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Union

import numpy as np

from repro.api.runtime import ClusterRuntime, resolve_runtime
from repro.core.dynamic_bond import stages_from_profile
from repro.core.parallel import ParallelConfig
from repro.core.perfmodel import (Hardware, Workload, choose_tp_scheme,
                                  hardware_for)
from repro.core.sampler import SamplerConfig as CoreSamplerConfig
from repro.workloads.clamp import normalize_clamp, validate_clamp

AUTO = "auto"

_SCHEMES = ("seq", "dp", "tp_single", "tp_double", "baseline19")


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    """Session-level sampling configuration (see module docstring)."""
    # workload semantics / numerics
    semantics: str = AUTO
    scaling: str = "per_sample"
    kernels: str = AUTO                # site-step dispatch: pallas | xla
    compute_dtype: Optional[Any] = None
    wire_dtype: Optional[Any] = None
    measure_first: bool = False
    # placement: data plane (backend) × cluster runtime — orthogonal axes
    scheme: str = AUTO
    backend: str = AUTO
    runtime: Union[str, ClusterRuntime] = AUTO
    # batching (paper N₂; per data shard)
    micro_batch: Union[int, str, None] = None
    # dynamic bond dimensions (paper §3.4.2): bucketed per-site χ
    chi_profile: Optional[tuple[int, ...]] = None
    # streaming backend
    segment_len: Union[int, str] = AUTO
    # chain sharding (block-cyclic Γ distribution, repro.shard): None = the
    # §3.1 broadcast plane; int = sites per ownership block; AUTO = one
    # segment per block
    shard: Union[int, str, None] = None
    # conditional sampling (repro.workloads): {site: outcome} or
    # {site: per-sample outcomes}; normalized at construction to the
    # canonical hashable spec (service coalescing cells and streamed
    # engine keys contain this config).  None/{} = unclamped.
    clamp: Optional[Any] = None
    store_root: Optional[str] = None
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 1
    # planner inputs for the AUTO fields
    hardware: Optional[Hardware] = None
    device_budget: Optional[float] = None

    def __post_init__(self):
        # malformed specs raise ValueError here — the gateway turns that
        # into a clean 400 ("invalid config: ...") via config_from_dict
        object.__setattr__(self, "clamp", normalize_clamp(self.clamp))


@dataclasses.dataclass(frozen=True)
class SessionPlan:
    """Fully-resolved execution record for one ``session.sample(n, key)``."""
    backend: str                       # data plane: "inmem" | "streamed" | ...
    runtime: str                       # cluster runtime name: "local" | ...
    scheme: str                        # "seq" | "dp" | "tp_single" | ...
    semantics: str
    kernels: str                       # resolved dispatch: "pallas" | "xla"
    n_samples: int
    p1: int                            # data-parallel shards
    p2: int                            # tensor-parallel workers per group
    micro_batch: Optional[int]         # N₂ per data shard (resolved)
    segment_len: Optional[int]         # streamed backend only
    chi_profile: Optional[tuple[int, ...]]
    stages: Optional[tuple[tuple[int, int, int], ...]]   # (start, stop, χ)
    checkpoint_every: int
    sampler_config: CoreSamplerConfig  # the kernel-level config
    pconfig: Optional[ParallelConfig]  # dp/tp placement, None for seq
    # chain sharding: sites per block-cyclic ownership block (repro.shard),
    # None for the broadcast plane.  The host count is the RUNTIME's
    # process count at execution time, so the same plan serializes cleanly
    # to a remote worker (which runs the degenerate 1-host shard).
    shard_block: Optional[int] = None
    # conditional sampling: the normalized clamp spec (repro.workloads),
    # range-validated against this plan's chain/batch; None = unclamped —
    # a None-clamp plan executes the UNCHANGED unclamped code paths, so
    # empty-clamp bit-identity holds by construction.
    clamp: Optional[tuple] = None

    @property
    def cell(self) -> tuple[str, str, str, str, str]:
        """The plan's config-cell identity (backend × runtime × scheme ×
        semantics × kernels) — what the service layer coalesces jobs on:
        two plans in one cell share compilation given equal shapes."""
        return (self.backend, self.runtime, self.scheme, self.semantics,
                self.kernels)


def resolve_hardware(config: SamplerConfig) -> Hardware:
    """``config.hardware``, or the peak-table row of this process's device
    kind (an unknown kind raises — see ``perfmodel.hardware_for``)."""
    if config.hardware is not None:
        return config.hardware
    import jax
    return hardware_for(jax.devices()[0].device_kind)


def _mesh_sizes(mesh) -> tuple[int, int]:
    if mesh is None:
        return 1, 1
    shape = dict(mesh.shape)
    p2 = shape.get("model", 1)
    p1 = 1
    for ax, size in shape.items():
        if ax != "model":
            p1 *= size
    return p1, p2


def _auto_micro_batch(n_local: int, chi: int, d: int, budget: float,
                      bytes_per_elt: int = 8) -> Optional[int]:
    """Eq. 3 memory-model pick: the largest divisor of the local batch whose
    unmeasured (N₂, χ, d) intermediate stays under ~10% of the budget."""
    target = max(1, int(0.1 * budget // (chi * d * bytes_per_elt)))
    if target >= n_local:
        return None                     # the whole shard fits — no chunking
    for k in range(target, 0, -1):
        if n_local % k == 0:
            return k
    return None


def resolve_plan(config: SamplerConfig, *, n_samples: int, n_sites: int,
                 chi: int, d: int, mesh=None, source_semantics=None,
                 backend_hint: str = "inmem", elt_bytes: int = 8,
                 runtime: Optional[ClusterRuntime] = None) -> SessionPlan:
    """Resolve every AUTO field of ``config`` into a :class:`SessionPlan`.

    Raises ``ValueError`` for contradictory requests (a parallel scheme with
    no mesh, a χ bucket that does not divide over p₂, an unsupported
    runtime × data-plane cell, ...) — the session surfaces these before any
    compilation happens.  ``runtime`` is the session's already-resolved
    :class:`ClusterRuntime`; ``None`` resolves ``config.runtime`` here.
    """
    from repro.api.backends import available_backends

    if runtime is None:
        runtime = resolve_runtime(config.runtime)
    backend = config.backend
    if backend == AUTO:
        # a remote runtime can only execute a dispatched payload — the
        # worker picks the data plane on its side
        backend = "remote" if runtime.name == "remote" else backend_hint
    if backend not in available_backends():
        raise ValueError(f"unknown backend {backend!r}; have "
                         f"{available_backends()} "
                         f"(registry: repro.api.register_backend)")

    # -- runtime × data-plane cell validation -------------------------------
    if runtime.process_count > 1 and backend != "streamed":
        raise ValueError(
            f"runtime {runtime.name!r} spans {runtime.process_count} "
            f"processes — the §3.1 Γ broadcast needs the 'streamed' data "
            f"plane (got backend={backend!r})")
    if runtime.name == "remote" and backend != "remote":
        raise ValueError(
            f"a remote runtime dispatches serialized configs — use "
            f"backend='remote' (or AUTO), not {backend!r}")
    if backend == "remote":
        if config.scheme not in (AUTO, "seq"):
            raise ValueError(
                f"backend='remote' resolves placement on the worker — "
                f"scheme must stay AUTO/'seq' on the dispatching side "
                f"(got {config.scheme!r})")
        if mesh is not None:
            raise ValueError("backend='remote' takes no local mesh — the "
                             "worker builds its own from its runtime")
        if config.checkpoint_dir is not None:
            raise ValueError(
                "backend='remote' does not ship checkpoint_dir — the "
                "worker's checkpoints would be local to it and resume "
                "could not find them; rely on idempotent macro batches "
                "(run_queue) for remote fault tolerance")

    semantics = (config.semantics if config.semantics != AUTO
                 else (source_semantics or "linear"))

    # -- kernel dispatch (AUTO → pallas on TPU, xla elsewhere) --------------
    from repro.kernels.dispatch import resolve_kernels
    kernels = resolve_kernels(config.kernels)   # raises on unknown modes

    p1, p2 = _mesh_sizes(mesh)
    hw = resolve_hardware(config)
    budget = config.device_budget if config.device_budget else hw.mem_capacity

    # -- scheme (Eq. 7 TP selector when the mesh has a model axis) ----------
    scheme = config.scheme
    w_probe = Workload(n_samples=n_samples, n_sites=n_sites, chi=chi, d=d,
                       macro_batch=n_samples,
                       micro_batch=max(1, n_samples // p1))
    if scheme == AUTO:
        if mesh is None or (p1 == 1 and p2 == 1):
            scheme = "seq"
        elif p2 > 1:
            scheme = "tp_" + choose_tp_scheme(w_probe, hw, p2)
        else:
            scheme = "dp"
    if scheme not in _SCHEMES:
        raise ValueError(f"unknown scheme {scheme!r}; have {_SCHEMES}")
    if scheme in ("dp", "tp_single", "tp_double", "baseline19") and mesh is None:
        raise ValueError(f"scheme {scheme!r} needs a mesh")
    if scheme == "baseline19" and backend != "inmem":
        raise ValueError("the [19] pipeline exists for comparison only and "
                         "has no streamed backend")
    if scheme in ("dp", "tp_single", "tp_double") and n_samples % p1 != 0:
        raise ValueError(f"n_samples={n_samples} must divide over the "
                         f"p₁={p1} data shards")
    if scheme in ("tp_single", "tp_double") and chi % p2 != 0:
        raise ValueError(f"χ={chi} does not divide over p₂={p2} "
                         f"tensor-parallel workers")
    n_local = n_samples // (p1 if scheme != "seq" else 1)

    # -- dynamic bond dimensions (§3.4.2) -----------------------------------
    chi_profile = config.chi_profile
    stages = None
    if chi_profile is not None:
        chi_profile = tuple(int(c) for c in chi_profile)
        if len(chi_profile) != n_sites:
            raise ValueError(f"chi_profile covers {len(chi_profile)} of "
                             f"{n_sites} sites")
        if max(chi_profile) > chi:
            raise ValueError(f"chi_profile exceeds the chain's χ "
                             f"({max(chi_profile)} > {chi})")
        if scheme == "baseline19":
            raise ValueError("dynamic χ does not compose with the [19] "
                             "pipeline baseline")
        stages = tuple((st.start, st.stop, st.chi) for st in
                       stages_from_profile(np.asarray(chi_profile)))
        if scheme in ("tp_single", "tp_double"):
            for s0, s1, chi_s in stages:
                if chi_s % p2 != 0:
                    raise ValueError(f"χ bucket {chi_s} does not divide over "
                                     f"p₂={p2} tensor-parallel workers")
        if scheme == "tp_double":
            for s0, s1, _ in stages:
                if s0 % 2 or s1 % 2:
                    raise ValueError(
                        "tp_double pairs sites (2j, 2j+1): χ-stage "
                        f"boundaries must be even (got [{s0}, {s1}))")

    # -- micro batching N₂ (per data shard) ---------------------------------
    micro = config.micro_batch
    micro_was_auto = micro == AUTO
    if micro_was_auto:
        micro = _auto_micro_batch(n_local, chi, d, budget,
                                  bytes_per_elt=elt_bytes)
        # AUTO must resolve to a *supported* value: the [19] pipeline is the
        # one cell micro batching does not compose with
        if scheme == "baseline19":
            micro = None
    if micro is not None:
        micro = int(micro)
        if micro <= 0 or n_local % micro != 0:
            raise ValueError(f"micro_batch={micro} must divide the local "
                             f"batch {n_local}")
        if micro == n_local and micro_was_auto:
            micro = None
    if micro is not None and scheme == "baseline19":
        raise ValueError("micro batching does not compose with the [19] "
                         "pipeline baseline")

    # -- streamed-backend segment length ------------------------------------
    segment_len = None
    if backend == "streamed":
        if config.segment_len == AUTO:
            from repro.engine.planner import plan_stream
            w = Workload(n_samples=n_samples, n_sites=n_sites, chi=chi, d=d,
                         macro_batch=n_samples,
                         micro_batch=(micro * p1 if micro else n_samples))
            segment_len = plan_stream(
                w, hw, p1=p1, p2=p2, compute_bytes=elt_bytes,
                device_budget=config.device_budget).segment_len
        else:
            segment_len = int(config.segment_len)
            if segment_len < 1:
                raise ValueError(f"segment_len must be ≥ 1, got {segment_len}")
        if scheme == "tp_double" and segment_len % 2:
            segment_len += 1            # pairs never straddle segments

    # -- chain sharding (block-cyclic Γ distribution, repro.shard) ----------
    shard_block = None
    if config.shard is not None:
        if backend == "remote":
            # rides the serialized config untouched; the WORKER resolves it
            # against its own runtime (a single worker runs the degenerate
            # 1-host shard, bit-identical by construction)
            pass
        elif backend != "streamed":
            # also covers the [19] pipeline baseline, which is inmem-only
            raise ValueError(
                f"chain sharding distributes the streamed Γ walk — it needs "
                f"backend='streamed', got {backend!r}")
        else:
            shard_block = (segment_len if config.shard == AUTO
                           else int(config.shard))
            if shard_block < 1:
                raise ValueError(f"shard block must be ≥ 1 site, got "
                                 f"{shard_block}")
            if shard_block % segment_len != 0:
                raise ValueError(
                    f"shard block ({shard_block} sites) must be a whole "
                    f"number of segments (segment_len={segment_len}) — a "
                    f"segment contracted on one host cannot straddle two "
                    f"owners")
            # prove single-ownership against the engine's REAL schedule
            # (χ-stages can split blocks in ways the uniform check misses)
            from repro.shard.shardmap import ShardMap, chain_segments
            smap = ShardMap(n_sites=n_sites,
                            n_hosts=max(1, runtime.process_count),
                            block=shard_block)
            smap.owners_for(chain_segments(n_sites, segment_len, stages))

    # -- conditional sampling (repro.workloads clamp) -----------------------
    clamp = config.clamp                # already normalized by __post_init__
    if clamp is not None:
        if backend == "remote":
            # rides the serialized config; the WORKER validates against the
            # store it opens (chain length / d are not known here)
            pass
        else:
            validate_clamp(clamp, n_sites=n_sites, d=d, n_samples=n_samples)
        if scheme == "baseline19":
            raise ValueError("clamped sampling does not compose with the "
                             "[19] pipeline baseline")

    pconfig = None
    if scheme in ("dp", "tp_single", "tp_double"):
        # shard the batch over EVERY non-model mesh axis ("pod" folds into
        # data parallel on multi-pod meshes) — must agree with the p₁ the
        # plan validated n_samples/micro_batch against
        data_axes = tuple(ax for ax in mesh.axis_names if ax != "model")
        pconfig = ParallelConfig(scheme=scheme, data_axes=data_axes,
                                 wire_dtype=config.wire_dtype,
                                 measure_first=config.measure_first,
                                 micro_batch=micro)
    sampler_config = CoreSamplerConfig(semantics=semantics,
                                       scaling=config.scaling,
                                       compute_dtype=config.compute_dtype,
                                       kernels=kernels)
    return SessionPlan(backend=backend, runtime=runtime.name, scheme=scheme,
                       semantics=semantics, kernels=kernels,
                       n_samples=n_samples, p1=p1, p2=p2, micro_batch=micro,
                       segment_len=segment_len, chi_profile=chi_profile,
                       stages=stages,
                       checkpoint_every=config.checkpoint_every,
                       sampler_config=sampler_config, pconfig=pconfig,
                       shard_block=shard_block, clamp=clamp)
