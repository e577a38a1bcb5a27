"""Executable performance models (paper Eqs. 1, 2, 3, 4, 7)."""
import pytest

from repro.core import perfmodel as PM


W = PM.Workload(n_samples=10_000_000, n_sites=288, chi=10_000, d=4,
                macro_batch=20_000, micro_batch=5_000, bytes_per_elt=8)


def test_eq2_beats_eq1_with_equal_resources():
    """The paper's §3.1 claim: with p = M processes AND the macro batch
    sized to the overlap threshold (T_comp ≥ T_IO), data parallel beats the
    [19] site pipeline (no pipeline fill, no imbalance).  At too-small N₁
    the DP scheme is I/O-bound — exactly the paper's §2.2 failure mode —
    and eq1 can win; both regimes are asserted."""
    import dataclasses
    hw = PM.A100
    n1 = max(W.macro_batch, PM.min_macro_batch_for_overlap(W, hw))
    w_ok = dataclasses.replace(W, macro_batch=n1)
    t_dp = PM.eq2_data_parallel(w_ok, hw, p=W.n_sites)
    t_mp = PM.eq1_model_parallel(w_ok, hw)
    assert t_dp < t_mp
    # undersized N₁ → I/O leaks into the DP critical path (paper §3.1)
    w_small = dataclasses.replace(W, macro_batch=2_000)
    t_dp_small = PM.eq2_data_parallel(w_small, hw, p=W.n_sites)
    assert t_dp_small > t_dp * 0.99


def test_eq3_memory_accounting():
    mem = PM.eq3_memory(W)
    manual = (W.macro_batch * W.chi + W.chi * W.chi * W.d
              + W.micro_batch * W.chi * W.d) * W.bytes_per_elt
    assert mem == manual
    # χ=20 000, d=3 Γ alone ≈ 19.2 GB in fp64 16B complex (paper §3.2)
    w2 = PM.Workload(1, 1, 20_000, 3, bytes_per_elt=16)
    assert PM.eq3_memory(w2) > 19e9


def test_overlap_threshold_scales_with_hardware():
    """§3.1: N₁ must exceed the compute/IO break-even; faster chips need
    bigger macro batches."""
    n_gpu = PM.min_macro_batch_for_overlap(W, PM.A100)
    slow = PM.Hardware(peak_flops=2e12, hbm_bw=100e9, io_bw=5e9)
    n_cpu = PM.min_macro_batch_for_overlap(W, slow)
    assert n_cpu < n_gpu
    # paper: safe N₁ ~ 1e5-1e6 on A100-class hardware at χ=1e4
    assert 1e4 < n_gpu < 5e6


def test_eq4_single_vs_double_bandwidth_regimes():
    """Fast AllReduce, slow ReduceScatter (the paper's NVLink numbers) →
    double-site wins; symmetric bandwidths → single-site's d× smaller wire
    volume wins."""
    nv = PM.Hardware(allreduce_bw=401e9, reducescatter_bw=46e9,
                     peak_flops=156e12, hbm_bw=2039e9)
    assert PM.choose_tp_scheme(W, nv, p2=4) == "double"

    sym = PM.Hardware(allreduce_bw=50e9, reducescatter_bw=50e9)
    assert PM.choose_tp_scheme(W, sym, p2=4) == "single"


def test_eq7_overhead_monotone_in_p2():
    hw = PM.TPU_V5E
    o2 = PM.eq7_tp_overhead(W, hw, 2, "single")
    o8 = PM.eq7_tp_overhead(W, hw, 8, "single")
    assert o8 > o2                     # replicated measurement η=p₂ bites


def test_t_site_compute_linear_in_n():
    hw = PM.TPU_V5E
    assert PM.t_site_compute(W, hw, 2000) == pytest.approx(
        2 * PM.t_site_compute(W, hw, 1000), rel=1e-9)


def test_macro_batch_count():
    assert W.n_macro == 500


def test_peaks_keyed_by_device_kind():
    """The planner's peaks come from one table keyed by device_kind; a
    kind that is not in it raises instead of defaulting to v5e."""
    assert PM.hardware_for("TPU v5 lite") is PM.TPU_V5E
    assert PM.TPU_V5E.peak_flops == 197e12 and PM.TPU_V5E.hbm_bw == 819e9
    assert PM.hardware_for("cpu").mem_capacity > 0
    with pytest.raises(ValueError, match="no peak-table row"):
        PM.hardware_for("TPU v99")


def test_plan_resolves_hardware_from_device(monkeypatch):
    from repro.api import config as C
    cfg = C.SamplerConfig()
    assert cfg.hardware is None
    assert C.resolve_hardware(cfg) is PM.hardware_for("cpu")
    pinned = PM.Hardware(mem_capacity=1e9)
    assert C.resolve_hardware(C.SamplerConfig(hardware=pinned)) is pinned
    monkeypatch.delitem(PM.PEAKS, "cpu")
    with pytest.raises(ValueError, match="cpu"):
        C.resolve_hardware(cfg)
