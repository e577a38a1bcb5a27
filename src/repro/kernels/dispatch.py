"""Kernel dispatch layer: one registry for every site-step stage.

The sampling data planes never call a Pallas kernel (or its XLA fallback)
directly — they ask this registry for the implementation of a *stage*:

=================  ==========================================================
stage              semantics of the op
=================  ==========================================================
``site_step``      the fully fused contract → measure → draw → collapse →
                   rescale pipeline (``kernels/site_step.py``); temp stays
                   VMEM-resident, only (N, χ) + two (N,) vectors hit HBM
``contract_measure``  contract + measure emitting (temp, probs) — the TP
                   schedules that must ship the unmeasured temp through a
                   collective use this (``kernels/contract_measure.py``)
``measure``        the tp-3 measure-first partial-probs GEMM env @ W
``collapse``       the sample-selected collapse GEMM env·Γ[:, :, sₙ]
                   (``kernels/collapse_select.py``)
=================  ==========================================================

Implementations register under ``(stage, semantics, backend)`` where
``backend`` is ``"pallas"`` or ``"xla"``.  Lookup order for
``backend="pallas"`` is ``(stage, semantics, "pallas")`` then the XLA entry
— a cell with no Pallas kernel (e.g. Born split-K TP, whose collective
forces the temp to HBM anyway) silently keeps its XLA implementation, so
``kernels="pallas"`` is always safe to request globally.

``SamplerConfig.kernels ∈ {"auto", "pallas", "xla"}`` is resolved by the
session planner through :func:`resolve_kernels`: AUTO means Pallas on a
real TPU backend and XLA elsewhere (tests force ``"pallas"`` explicitly
and the kernels run under ``interpret=True``).

The **autotuner** picks Pallas block sizes per shape: on TPU a timed sweep
over (8, 128)-legal candidates (cached per process), elsewhere a
deterministic heuristic table (largest legal tiles under a lane-padded VMEM
model) — interpret-mode numerics do not depend on the block choice, so CI
exercises the same code path the TPU runs.  ``autotune_cache_stats()`` and
``autotune_report()`` report cache behaviour, the chosen blocks and the
candidates the compiler rejected (surfaced by ``launch/sample.py
--kernels``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional

import jax

from repro.obs import trace

STAGES = ("site_step", "contract_measure", "measure", "collapse")
KERNEL_MODES = ("auto", "pallas", "xla")

_REGISTRY: dict[tuple[str, str, str], Callable] = {}


def register_site_op(stage: str, semantics: str, backend: str):
    """Decorator: register ``fn`` as the ``backend`` implementation of
    ``stage`` under ``semantics`` ("linear" | "born" | "*" for both)."""
    assert stage in STAGES, stage

    def deco(fn: Callable) -> Callable:
        sems = ("linear", "born") if semantics == "*" else (semantics,)
        for s in sems:
            _REGISTRY[(stage, s, backend)] = fn
        return fn
    return deco


def get_site_op(stage: str, semantics: str, backend: str) -> Callable:
    """The implementation for a stage; Pallas requests fall back to XLA
    when the cell has no kernel (see module docstring)."""
    if backend == "auto":
        backend = resolve_kernels("auto")
    if backend == "pallas":
        impl = _REGISTRY.get((stage, semantics, "pallas"))
        if impl is not None:
            return impl
        backend = "xla"
    try:
        return _REGISTRY[(stage, semantics, backend)]
    except KeyError:
        raise ValueError(
            f"no implementation for stage={stage!r} semantics={semantics!r} "
            f"backend={backend!r}; registered: {sorted(_REGISTRY)}") from None


def registered_ops() -> list[tuple[str, str, str]]:
    return sorted(_REGISTRY)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_kernels(requested: str) -> str:
    """``"auto" | "pallas" | "xla"`` → a concrete backend name."""
    if requested not in KERNEL_MODES:
        raise ValueError(f"kernels must be one of {KERNEL_MODES}, "
                         f"got {requested!r}")
    if requested == "auto":
        return "pallas" if on_tpu() else "xla"
    return requested


# ---------------------------------------------------------------------------
# Block-size autotuner
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """Pallas tile sizes for one (stage, shape) cell."""
    bn: int
    br: int
    bl: int


# working-set budget the block choice must fit: below the compiled kernels'
# scoped VMEM limit (site_step.VMEM_LIMIT_BYTES), leaving room for the
# compiler's own temporaries of the epilogue's full (BN, χr) rows
_VMEM_BUDGET_BYTES = 64 * 2 ** 20

# TPU block rule: the last two block dims are multiples of (8, 128) or the
# whole array dims.  BN is a sublane dim, BR/BL lane dims.
_SUBLANE, _LANE = 8, 128

_cache: dict[tuple, BlockConfig] = {}
_stats = {"hits": 0, "misses": 0, "swept": 0, "rejected": 0}
_report: dict[tuple, dict] = {}


def autotune_cache_stats() -> dict:
    """Cache behaviour counters + current entries (per process)."""
    return {"entries": len(_cache), **_stats}


def autotune_report() -> list[dict]:
    """One record per tuned cell: the chosen blocks, how many candidates
    were timed and how many the compiler rejected (with the first error)."""
    return [dict(r) for r in _report.values()]


def clear_autotune_cache() -> None:
    _cache.clear()
    _report.clear()
    _stats.update(hits=0, misses=0, swept=0, rejected=0)


def _legal_tile(size: int, pref: int, align: int) -> int:
    """Largest divisor of ``size`` that is ≤ ``pref`` and a multiple of
    ``align``; the whole dimension when there is none (always a legal
    block — and the only one for a χ like 10⁴ that no multiple of 128
    divides, which the VMEM model then rejects)."""
    for t in range(min(pref, size) // align * align, 0, -align):
        if size % t == 0:
            return t
    return size


def _padded_bytes(shape: tuple, elt: int) -> int:
    """VMEM bytes of one buffer under the (sublane, 128) tiling: the minor
    dim pads to 128 lanes and the next to a whole sublane tile (8 rows of
    4 bytes, 16 of 2) — a (BN, 1) column costs BN·128 words, not BN."""
    *lead, rows, lanes = (1,) + tuple(shape) if len(shape) == 1 else shape
    sub = max(1, 32 // elt)
    out = (-(-rows // sub) * sub) * (-(-lanes // _LANE) * _LANE) * elt
    for x in lead:
        out *= x
    return out


def _working_set_bytes(stage: str, cfg: BlockConfig, chi_r: int, d: int,
                       elt: int, planes: int) -> int:
    """VMEM model of a block choice: pipelined operand/result blocks count
    twice (double buffering), scratch once, all lane/sublane-padded."""
    bn, br, bl = cfg.bn, cfg.br, cfg.bl

    def b(*shape):
        return _padded_bytes(shape, elt)

    env_in, gam_in = 2 * b(bn, bl), 2 * bl * b(d, br)
    if stage == "site_step":
        # per plane: env + Γ blocks, split-K acc, the resident temp slab,
        # the env' row block (×2) and two epilogue full-row temporaries
        per_plane = (env_in + gam_in + d * b(bn, br) + d * b(bn, chi_r)
                     + 4 * b(bn, chi_r))
        vectors = 2 * b(1, br) + 6 * b(bn, 1) + d * b(bn, 1)
        return planes * per_plane + vectors
    if stage == "contract_measure":
        return (env_in + gam_in + 2 * b(1, br) + 2 * bn * b(d, br)
                + 2 * d * b(bn, 1) + d * b(bn, br))
    if stage == "collapse":
        return env_in + gam_in + 2 * b(bn, 1) + 3 * b(bn, br)
    if stage == "measure":
        return env_in + 2 * b(bl, d) + 3 * b(bn, d)
    raise ValueError(stage)


def _heuristic(stage: str, n: int, chi_l: int, chi_r: int, d: int,
               elt: int, planes: int) -> BlockConfig:
    """Deterministic block choice: the largest legal tiles under the MXU
    preferences, then shrink BN (the only axis the site_step slab scales
    with), BR and BL until the VMEM model fits.  Correctness never depends
    on the choice — any divisors work in interpret mode — but a choice
    that cannot fit raises here rather than in the TPU compiler."""
    cfg = BlockConfig(bn=_legal_tile(n, 256, _SUBLANE),
                      br=_legal_tile(chi_r, 512, _LANE),
                      bl=_legal_tile(chi_l, 512, _LANE))
    while (_working_set_bytes(stage, cfg, chi_r, d, elt, planes)
           > _VMEM_BUDGET_BYTES):
        for field, size, align in (("bn", n, _SUBLANE), ("br", chi_r, _LANE),
                                   ("bl", chi_l, _LANE)):
            cur = getattr(cfg, field)
            smaller = _legal_tile(size, cur // 2, align)
            if smaller < cur:
                cfg = dataclasses.replace(cfg, **{field: smaller})
                break
        else:
            raise ValueError(
                f"no block choice for stage {stage!r} at N={n}, "
                f"χ=({chi_l}, {chi_r}), d={d} fits the "
                f"{_VMEM_BUDGET_BYTES >> 20} MiB VMEM budget under the "
                f"(8, 128) tiling rule — pad the bond to a multiple of 128 "
                f"once at store time (repro.core.mps.pad_bond)")
    return cfg


def _sweep_candidates(stage: str, n: int, chi_l: int, chi_r: int, d: int,
                      elt: int, planes: int) -> list[BlockConfig]:
    """Legal, budget-filtered candidate grid for the timed TPU sweep; the
    heuristic's own choice always leads."""
    first = _heuristic(stage, n, chi_l, chi_r, d, elt, planes)
    seen, out = {first}, [first]
    for pn in (256, 128, 64):
        for pr in (512, 256):
            for plb in (1024, 512):
                cfg = BlockConfig(bn=_legal_tile(n, pn, _SUBLANE),
                                  br=_legal_tile(chi_r, pr, _LANE),
                                  bl=_legal_tile(chi_l, plb, _LANE))
                if cfg in seen:
                    continue
                seen.add(cfg)
                if (_working_set_bytes(stage, cfg, chi_r, d, elt, planes)
                        <= _VMEM_BUDGET_BYTES):
                    out.append(cfg)
    return out


def _time_call(fn: Callable, *args, iters: int = 3) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def autotune(stage: str, *, n: int, chi_l: int, chi_r: int, d: int,
             dtype, planes: int = 1,
             probe: Optional[Callable[[BlockConfig], Callable]] = None
             ) -> BlockConfig:
    """Block sizes for one (stage, shape, dtype) cell, cached per process.

    Off-TPU (and whenever no ``probe`` is supplied) the heuristic table
    answers immediately.  On TPU, ``probe(cfg)`` must return a zero-arg
    thunk running the kernel at ``cfg``; the fastest candidate wins and is
    cached, so a production sampler pays the sweep once per distinct
    (χ-bucket, N₂) shape.  Candidates the compiler rejects are counted
    (``autotune_report``); when every one is rejected this raises.  Each
    tuned cell (each cache miss) is one ``dispatch.autotune`` span.
    """
    elt = jax.numpy.dtype(dtype).itemsize
    key = (stage, n, chi_l, chi_r, d, str(jax.numpy.dtype(dtype)), planes,
           on_tpu())
    hit = _cache.get(key)
    if hit is not None:
        _stats["hits"] += 1
        return hit
    _stats["misses"] += 1
    with trace.span("dispatch.autotune", stage=stage, n=n, chi_l=chi_l,
                    chi_r=chi_r, d=d):
        cfg = _tune(key, stage, n, chi_l, chi_r, d, elt, planes, probe)
    _cache[key] = cfg
    return cfg


def _tune(key: tuple, stage: str, n: int, chi_l: int, chi_r: int, d: int,
          elt: int, planes: int, probe) -> BlockConfig:
    """The sweep (or heuristic) behind one cache miss of
    :func:`autotune`; records the cell in ``autotune_report``."""
    rec = {"stage": stage, "n": n, "chi_l": chi_l, "chi_r": chi_r, "d": d,
           "dtype": key[5], "candidates": 1, "rejected": 0, "error": None}
    if probe is not None and on_tpu():
        best_cfg, best_t = None, float("inf")
        cands = _sweep_candidates(stage, n, chi_l, chi_r, d, elt, planes)
        rec["candidates"] = len(cands)
        for cfg in cands:
            _stats["swept"] += 1
            try:
                t = _time_call(probe(cfg))
            except Exception as e:  # the compiler refused this candidate
                _stats["rejected"] += 1
                rec["rejected"] += 1
                rec["error"] = rec["error"] or f"{cfg}: {e}"[:500]
                continue
            if t < best_t:
                best_cfg, best_t = cfg, t
        if best_cfg is None:
            raise RuntimeError(
                f"the TPU compiler rejected all {len(cands)} block "
                f"candidates for {stage} at N={n}, χ=({chi_l}, {chi_r}), "
                f"d={d}; first error: {rec['error']}")
        cfg = best_cfg
        rec["best_s"] = best_t
    else:
        cfg = _heuristic(stage, n, chi_l, chi_r, d, elt, planes)
    rec["blocks"] = dataclasses.asdict(cfg)
    _report[key] = rec
    return cfg


# ---------------------------------------------------------------------------
# Implementations (imported last so the registry decorators see the helpers)
# ---------------------------------------------------------------------------

from repro.kernels import site_impls  # noqa: E402,F401  (registers the ops)
