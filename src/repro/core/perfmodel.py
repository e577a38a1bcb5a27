"""Executable performance models (paper Eqs. 1, 2, 3, 4, 7).

These drive (a) the scheme selector (data-parallel vs. the [19] site
pipeline; single- vs. double-site TP), (b) macro/micro batch sizing against
memory and overlap thresholds, and (c) the benchmark harness's derived
columns.  All times in seconds, sizes in bytes, rates in units/s.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Hardware:
    """Per-chip capabilities.  The defaults repeat the v5e row of
    :data:`PEAKS` so tests can build variants field by field; a running
    process plans with :func:`hardware_for` its own device kind."""
    peak_flops: float = 197e12          # bf16 MXU
    hbm_bw: float = 819e9               # bytes/s
    ici_bw: float = 50e9                # bytes/s per link
    io_bw: float = 5e9                  # storage read (paper's NVMe figure)
    mem_capacity: float = 16e9          # HBM bytes
    allreduce_bw: float | None = None   # measured override (Eq. 7 selector)
    reducescatter_bw: float | None = None

    @property
    def b_allreduce(self) -> float:
        return self.allreduce_bw or self.ici_bw

    @property
    def b_reducescatter(self) -> float:
        return self.reducescatter_bw or self.ici_bw


A100 = Hardware(peak_flops=156e12, hbm_bw=2039e9, ici_bw=300e9, io_bw=5e9,
                mem_capacity=80e9)

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (four links, 50 GB/s
# each).  io_bw is the paper's NVMe read figure, not the chip's.
TPU_V5E = Hardware(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9, io_bw=5e9,
                   mem_capacity=16e9)

#: Per-chip peaks keyed by ``jax.devices()[0].device_kind``.  A kind that
#: is not here is an error (:func:`hardware_for`), never a default.
PEAKS: dict[str, Hardware] = {
    "TPU v5 lite": TPU_V5E,
    # The CPU backend (tests, interpret-mode kernels) has no published
    # peaks.  It plans with the v5e figures so a CPU run resolves the same
    # schedules a v5e would; no CPU time is ever reported against them.
    "cpu": TPU_V5E,
}


def hardware_for(device_kind: str) -> Hardware:
    """The :data:`PEAKS` row for a ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak-table row for device kind {device_kind!r} (have "
            f"{sorted(PEAKS)}) — add the chip's published peaks to "
            f"repro.core.perfmodel.PEAKS") from None


@dataclasses.dataclass(frozen=True)
class Workload:
    n_samples: int          # N
    n_sites: int            # M
    chi: int                # bond dimension
    d: int = 3              # physical dimension
    macro_batch: int = 20_000   # N₁
    micro_batch: int = 5_000    # N₂
    bytes_per_elt: int = 8      # fp64 real / complex64; paper uses 16 for c128

    @property
    def n_macro(self) -> int:           # n₁
        return max(1, self.n_samples // self.macro_batch)


def t_site_compute(w: Workload, hw: Hardware, n: int | None = None,
                   efficiency: float = 0.5) -> float:
    """T_{i,N}: one site's contraction+measure for an n-sample batch.

    2·N·χ²·d FLOPs (GEMM) + 2·N·χ·d (measure), at `efficiency`×peak.
    """
    n = w.macro_batch if n is None else n
    flops = 2.0 * n * w.chi * w.chi * w.d + 2.0 * n * w.chi * w.d
    return flops / (hw.peak_flops * efficiency)


def t_gamma_io(w: Workload, hw: Hardware, storage_bytes: int | None = None) -> float:
    """Read one Γ (χ²·d elements) from storage."""
    b = storage_bytes if storage_bytes is not None else w.bytes_per_elt
    return (w.chi * w.chi * w.d * b) / hw.io_bw


def eq1_model_parallel(w: Workload, hw: Hardware, efficiency: float = 0.5,
                       imbalance: float = 0.1) -> float:
    """Eq. 1 — the [19] pipeline: p = M processes, one site each.

    T = T_read + n₁·max_i T_{i,N₁} + Σ_i (T_{i,N₁} + T_comm).
    `imbalance` models max_i/mean_i − 1 (startup/straggler spread).
    """
    t_comp = t_site_compute(w, hw, w.macro_batch, efficiency)
    t_comm = (w.macro_batch * w.chi * w.bytes_per_elt) / hw.ici_bw
    t_read = t_gamma_io(w, hw)
    return (t_read + w.n_macro * t_comp * (1 + imbalance)
            + w.n_sites * (t_comp + t_comm))


def eq2_data_parallel(w: Workload, hw: Hardware, p: int,
                      efficiency: float = 0.5,
                      overlapped: bool = True,
                      storage_bytes: int | None = None) -> float:
    """Eq. 2 — FastMPS data parallel with I/O+bcast overlapped behind compute.

    T = T_read + T_bcast + (n₁/p)·Σ_i T_{i,N₁}   (ideal, overlap holds when
    T_comp > T_IO per site; otherwise I/O leaks into the critical path).
    """
    t_comp = t_site_compute(w, hw, w.macro_batch, efficiency)
    t_io = t_gamma_io(w, hw, storage_bytes)
    t_bcast = (w.chi * w.chi * w.d * (storage_bytes or w.bytes_per_elt)) / hw.ici_bw
    per_site = t_comp if (overlapped and t_comp >= t_io) else t_comp + (t_io - t_comp if overlapped else t_io)
    # continuous rounds (the paper's ideal n₁/p; in practice n₁ ≫ p and the
    # work queue balances the remainder — runtime/elastic.py)
    n_rounds = max(1.0, w.n_macro / p)
    return t_io + t_bcast + n_rounds * w.n_sites * per_site


def eq3_memory(w: Workload, bytes_per_elt: int | None = None) -> float:
    """Eq. 3 — resident bytes: left env (N₁·χ·d… reduced to N₁·χ by micro
    batching) + Γ (χ²·d).  Paper counts the unmeasured micro intermediate
    separately; with N₁ ≫ N₂·d it is negligible."""
    b = bytes_per_elt or w.bytes_per_elt
    return (w.macro_batch * w.chi + w.chi * w.chi * w.d
            + w.micro_batch * w.chi * w.d) * b


def site_hbm_bytes(n: int, chi: int, d: int, bytes_per_elt: int = 8,
                   fused: bool = False) -> float:
    """Modeled per-site HBM traffic of the sampling hot loop (§Roofline).

    *Unfused* (separate XLA ops): the unmeasured ``temp[N, χ, d]`` makes
    three HBM trips — written by the contraction GEMM, read back by the
    measurement, read again by the collapse — on top of the operands
    (env, Γ) and results (probs, env').

    *Fused* (``kernels/site_step.py``): temp lives in VMEM for the whole
    pipeline; HBM carries only env + Γ + u in and env' + samples + dlog
    out.  The 3·N·χ·d term — the dominant one for d ≥ 2 — vanishes, which
    is the ≥ 2× byte reduction ``bench_site_step.py`` records.
    """
    operands = n * chi + chi * chi * d            # env read + Γ read
    env_out = n * chi                             # env' write
    if fused:
        # + uniforms in, samples (int32≈elt) + dlog out
        return (operands + env_out + 3 * n) * bytes_per_elt
    temp = 3 * n * chi * d                        # write + 2 reads
    probs = 2 * n * d                             # write + read for the draw
    return (operands + env_out + temp + probs) * bytes_per_elt


def site_fusion_byte_reduction(n: int, chi: int, d: int,
                               bytes_per_elt: int = 8) -> float:
    """HBM bytes(unfused) / bytes(fused) for one site — the paper-facing
    derived column of the site-step bench."""
    return (site_hbm_bytes(n, chi, d, bytes_per_elt, fused=False)
            / site_hbm_bytes(n, chi, d, bytes_per_elt, fused=True))


def eq4_tp_site(w: Workload, hw: Hardware, p2: int, scheme: str,
                efficiency: float = 0.5, t_measure: float | None = None) -> float:
    """Eq. 4 — one TP site step: GEMM + measure + comm_volume/bandwidth."""
    n2 = w.micro_batch
    gemm_flops = 2.0 * n2 * w.chi * (w.chi / p2) * w.d
    t_gemm = gemm_flops / (hw.peak_flops * efficiency)
    t_meas = t_measure if t_measure is not None else (
        2.0 * n2 * w.chi * w.d) / (hw.hbm_bw)      # bandwidth-bound reduction
    if scheme == "single":
        vol = n2 * (w.chi / p2) * (p2 - 1) / p2 * w.bytes_per_elt * p2  # RS of (N₂,χ)
        t_comm = vol / hw.b_reducescatter
        t_meas = t_meas * p2                        # replicated measurement η=p₂… no:
        # single-site measures partial probs then collapses locally; the paper's
        # η=p₂ refers to the *non-distributed* measurement overhead.
    elif scheme == "double":
        vol = 2 * n2 * w.chi * w.d * (p2 - 1) / p2 * w.bytes_per_elt    # AR of (N₂,χ,d) every 2 sites
        t_comm = vol / hw.b_allreduce / 2.0         # amortized per site
    else:
        raise ValueError(scheme)
    return t_gemm + t_meas + t_comm


def eq7_tp_overhead(w: Workload, hw: Hardware, p2: int, scheme: str,
                    efficiency: float = 0.5) -> float:
    """Eq. 7 — Overhead = (CommVolume/B + η·T_measure) / T_{i,N₂}.

    single: ships the *measured* (N₂, χ) env (d× smaller — §3.2's
            measure-before-communicate) via ReduceScatter; η = p₂
            (non-distributed measurement).
    double: ships the unmeasured (N₂, χ, d) via AllReduce every *two*
            sites (per-site volume N₂χd/2); η = 1.
    """
    n2 = w.micro_batch
    t_meas = (2.0 * n2 * w.chi * w.d) / hw.hbm_bw
    if scheme == "double":
        eta = 1.0
        comm = (n2 * w.chi * w.d * w.bytes_per_elt / 2.0) / hw.b_allreduce
    else:
        eta = float(p2)
        comm = (n2 * w.chi * w.bytes_per_elt) / hw.b_reducescatter
    t_site = t_site_compute(w, hw, n2, efficiency) / p2
    return (comm + eta * t_meas) / t_site


def choose_tp_scheme(w: Workload, hw: Hardware, p2: int) -> str:
    """Paper §4.3: pick the scheme with the lower Eq. 7 overhead."""
    od = eq7_tp_overhead(w, hw, p2, "double")
    os_ = eq7_tp_overhead(w, hw, p2, "single")
    return "double" if od <= os_ else "single"


def min_macro_batch_for_overlap(w: Workload, hw: Hardware,
                                efficiency: float = 0.5,
                                storage_bytes: int | None = None) -> int:
    """Smallest N₁ with T_comp ≥ T_IO (§3.1's computation-I/O ratio = N₁)."""
    t_io = t_gamma_io(w, hw, storage_bytes)
    per_sample_flops = 2.0 * w.chi * w.chi * w.d
    per_sample_t = per_sample_flops / (hw.peak_flops * efficiency)
    return int(t_io / per_sample_t) + 1


def shard_wire_bytes(w: Workload, hosts: int, *, block: int,
                     storage_bytes: int = 2, env_bytes: int = 8,
                     sample_bytes: int = 4) -> dict:
    """Interconnect bytes of a full chain walk: §3.1 broadcast vs the
    chain-sharded data plane (block-cyclic Γ, pipelined env handoff).

    broadcast ships every Γ segment from the root to hosts−1 peers —
    O(hosts × chain).  Sharded ships NO Γ at all (each host reads only the
    blocks it owns) and instead hands the tiny (N, χ) env across each of
    the n_blocks−1 block boundaries, plus one final sample allgather —
    O(chain-boundaries × N·χ), independent of per-site Γ size.  The
    crossover is immediate for χ² ≫ N, which is exactly the large-χ regime
    the paper targets."""
    gamma_site = w.chi * w.chi * w.d * storage_bytes
    broadcast = (hosts - 1) * w.n_sites * gamma_site
    n_blocks = -(-w.n_sites // block)
    boundaries = n_blocks - 1 if hosts > 1 else 0
    handoff = boundaries * w.n_samples * w.chi * env_bytes
    gather = ((hosts - 1) * w.n_samples * w.n_sites * sample_bytes
              if hosts > 1 else 0)
    return {
        "broadcast_bytes": broadcast,
        "handoff_bytes": handoff,
        "gather_bytes": gather,
        "sharded_bytes": handoff + gather,
    }


def job_admission_cost(w: Workload, hw: Hardware, n_batches: int = 1,
                       efficiency: float = 0.5) -> dict:
    """Modeled footprint of one service job, for admission control.

    ``resident_bytes`` is Eq. 3 for ONE active macro batch — what the job
    pins on a device while any of its batches runs; batches of one job run
    one-at-a-time per lane, so concurrency across *jobs*, not batches, is
    what the admission budget must bound.  ``compute_s`` is the modeled
    chain-walk time summed over the job's live batches — the scheduler
    surfaces it so queued-job backpressure is interpretable (seconds of
    modeled work waiting, not just a count)."""
    return {
        "resident_bytes": eq3_memory(w),
        "compute_s": n_batches * w.n_sites * t_site_compute(
            w, hw, w.macro_batch, efficiency),
    }
