"""Operation and byte counts at the published width, against hand
arithmetic, and the peak table."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import roofline as R  # noqa: E402

CHI = 10_000


def test_site_step_counts_at_m8176_width():
    n, d = 16384, 3
    # 2·N·χ²·d + 2·N·χ·d
    assert R.site_step_ops(n, CHI, d) == 2 * 16384 * 10**8 * 3 \
        + 2 * 16384 * 10**4 * 3 == 9_831_383_040_000
    # Γ bf16 + env f32 in and out + Λ f32 + (u f32, dlog f32, sample i32)
    want = (10**8 * 3 * 2) + 2 * 16384 * 10**4 * 4 + 10**4 * 4 \
        + 16384 * 12
    assert want == 1_910_956_608
    assert R.site_step_bytes(n, CHI, d, gamma_dtype="bfloat16",
                             env_dtype="float32") == want
    assert R.site_sample_ops(CHI, d) == 600_060_000


def test_tp_stage_counts_at_jiuzhang2_width():
    n, d, p2 = 16384, 4, 4
    assert R.tp_stage_ops(n, CHI, d, p2) == \
        2 * 16384 * 10**4 * 2500 * 4 + 2 * 16384 * 10**4 * 4 == \
        3_278_110_720_000
    # Γ slice bf16 + env slice bf16 + Λ f32 + temp (N, χ, d) f32 + probs
    want = 2500 * 10**4 * 4 * 2 + 16384 * 2500 * 2 + 10**4 * 4 \
        + 16384 * 10**4 * 4 * 4 + 16384 * 4 * 4
    assert want == 2_903_662_144
    assert R.tp_stage_bytes(n, CHI, d, p2, gamma_dtype="bfloat16",
                            gemm_dtype="bfloat16",
                            env_dtype="float32") == want


def test_least_time_names_its_bound():
    v5e = R.peaks("TPU v5 lite")
    t, bound = R.least_time(197e12, 1.0, v5e)
    assert (t, bound) == (1.0, "compute")
    t, bound = R.least_time(1.0, 819e9, v5e)
    assert (t, bound) == (1.0, "memory")
    # the m8176 site step sits far right of the ridge (~240 FLOP/B)
    ops = R.site_step_ops(16384, CHI, 3)
    assert R.least_time(ops, R.site_step_bytes(
        16384, CHI, 3, gamma_dtype="bfloat16", env_dtype="float32"),
        v5e)[1] == "compute"


def test_peak_table_has_no_fallback():
    assert set(R.PEAKS) == {"TPU v5 lite"}
    assert R.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(ValueError):
            R.peaks(kind)
