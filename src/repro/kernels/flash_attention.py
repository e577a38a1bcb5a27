"""Flash attention (forward) — Pallas TPU kernel (§Perf iteration attn-1).

The prefill/train attention cells are memory-bound because the naive path
materializes the (B, H, S, T) score matrix in HBM several times per layer
(qwen1.5-4b prefill_32k: 85 GB/layer/device, t_memory = 52 s vs t_compute
= 5.3 s).  This kernel runs the online-softmax recurrence with all
intermediates in VMEM: HBM traffic is Q + K + V + O only.

TPU mapping:
  * grid = (B·H, S/BQ, T/BK), key-block innermost (sequential on TPU, so
    the running max/denominator/accumulator live in VMEM scratch);
  * Q/O blocks are (BQ, Dh); K/V blocks (BK, Dh) — all MXU-aligned;
  * GQA: the KV block index is the query-head block index divided by the
    group size (no KV duplication in HBM);
  * causal masking by absolute indices; fully-masked key blocks skip their
    MXU work under ``pl.when`` (the paper's "only the region under the
    profile is computed" idea, applied to the causal triangle).

Backward is intentionally not provided: the serving path (prefill/decode)
is forward-only; training keeps the XLA path (see DESIGN.md §Perf notes).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, n_k: int, bq: int, bk: int, scale: float, causal: bool):
    i = pl.program_id(1)          # query block
    j = pl.program_id(2)          # key block (sequential reduction)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * bq
    k_start = j * bk
    # skip key blocks entirely above the causal diagonal
    live = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(live)
    def _update():
        q = q_ref[0]                                  # (BQ, Dh)
        k = k_ref[0]                                  # (BK, Dh)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (BQ, BK)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)               # (BQ,)
        p = jnp.exp(s - m_new[:, None])               # (BQ, BK)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1)
        acc_ref[...] = (alpha[:, None] * acc_ref[...]
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _emit():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...]
                    / jnp.where(l > 0, l, 1.0)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention(q: Array, k: Array, v: Array, causal: bool = True,
                    bq: int = 512, bk: int = 512,
                    interpret: bool = False) -> Array:
    """q (B, S, H, Dh), k/v (B, T, KVH, Dh) → out (B, S, H, Dh).

    H must be a multiple of KVH (GQA group broadcast happens via the KV
    BlockSpec index map — KV is never duplicated in HBM).
    """
    b, s, h, dh = q.shape
    t, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0
    g = h // kvh
    bq = min(bq, s)
    bk = min(bk, t)
    assert s % bq == 0 and t % bk == 0, (s, t, bq, bk)
    grid = (b * h, s // bq, t // bk)
    scale = 1.0 / math.sqrt(dh)

    qr = q.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kvh, t, dh)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kvh, t, dh)

    kern = functools.partial(_kernel, n_k=grid[2], bq=bq, bk=bk,
                             scale=scale, causal=causal)
    out = pl.pallas_call(
        kern,
        name="flash_attention",  # the HLO and trace op name
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda bh, i, j, g=g: (bh // g, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda bh, i, j, g=g: (bh // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # running max
            pltpu.VMEM((bq,), jnp.float32),       # running denominator
            pltpu.VMEM((bq, dh), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
