"""Peak table and the operation and byte counts of the sampler's kernels.

The counts are the benchmark's yardstick, kept apart from the program's own
``perfmodel`` (whose ``site_hbm_bytes`` takes one element size for every
operand).  They are taken at the *published* bond dimension and at the
dtypes the configuration states, so the same work reads the same whatever
implements it: padding χ to a tiling multiple is work the chip does on top,
and it counts against a kernel's roofline share, not in its favour.
"""
from __future__ import annotations

#: Published peaks per ``jax.Device.device_kind``.  Source: Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int32": 4,
               "float64": 8}


def peaks(device_kind: str) -> dict:
    """The peak row for ``device_kind``; a kind not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def _b(dtype: str) -> int:
    return DTYPE_BYTES[dtype]


def site_sample_ops(chi: int, d: int) -> int:
    """Operations one sample needs at one site: the contraction
    env·Γ (2χ²d) and the measurement temp·Λ (2χd)."""
    return 2 * chi * chi * d + 2 * chi * d


def site_step_ops(n: int, chi: int, d: int) -> int:
    """One fused site-step call over ``n`` samples."""
    return n * site_sample_ops(chi, d)


def site_step_bytes(n: int, chi: int, d: int, *, gamma_dtype: str,
                    env_dtype: str, sample_dtype: str = "int32") -> int:
    """HBM bytes one fused site-step call must move at least: Γ once, the
    environment in and out, Λ, and per sample its uniform, its outcome and
    its rescale factor."""
    return (chi * chi * d * _b(gamma_dtype)
            + 2 * n * chi * _b(env_dtype)
            + chi * _b(env_dtype)
            + n * (2 * _b(env_dtype) + _b(sample_dtype)))


def tp_stage_ops(n: int, chi: int, d: int, p2: int) -> int:
    """One chip's contract-and-measure call under tensor parallelism: a
    split-K GEMM over its χ/p₂ slice of the left bond, and the partial
    measurement of the (n, χ, d) product."""
    return 2 * n * chi * (chi // p2) * d + 2 * n * chi * d


def tp_stage_bytes(n: int, chi: int, d: int, p2: int, *, gamma_dtype: str,
                   gemm_dtype: str, env_dtype: str) -> int:
    """HBM bytes of that call: the chip's Γ slice, its environment slice in
    the GEMM input dtype, Λ, the partial (n, χ, d) product and the partial
    (n, d) probabilities out in the environment dtype."""
    k = chi // p2
    return (k * chi * d * _b(gamma_dtype)
            + n * k * _b(gemm_dtype)
            + chi * _b(env_dtype)
            + n * chi * d * _b(env_dtype)
            + n * d * _b(env_dtype))


def least_time(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / peak["bf16_flops"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
