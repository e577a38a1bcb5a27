"""Optimizers, schedules, gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import compression as C
from repro.optim import optimizers as O
from repro.optim import schedule


@pytest.mark.parametrize("make", [O.adamw, O.adafactor])
def test_optimizer_decreases_quadratic(make):
    opt = make(1e-1)
    target = jnp.array([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3), "b": jnp.zeros((3, 4))}

    def loss_fn(p):
        return jnp.sum((p["w"] - target) ** 2) + jnp.sum(p["b"] ** 2)

    state = opt.init(params)
    l0 = float(loss_fn(params))
    for _ in range(60):
        grads = jax.grad(loss_fn)(params)
        params, state = opt.update(grads, state, params)
    assert float(loss_fn(params)) < 0.2 * l0


def test_adafactor_state_is_factored():
    opt = O.adafactor()
    params = {"w": jnp.zeros((64, 32)), "s": jnp.zeros((16,)),
              "stacked": jnp.zeros((4, 8, 12))}
    st = opt.init(params)
    assert st.inner["w"]["vr"].shape == (64,)
    assert st.inner["w"]["vc"].shape == (32,)
    assert st.inner["stacked"]["vr"].shape == (4, 8)
    assert st.inner["stacked"]["vc"].shape == (4, 12)
    assert st.inner["s"]["v"].shape == (16,)   # 1-D not factored


def test_optimizer_policy():
    from repro import configs
    small = configs.get_config("qwen1.5-4b")
    big = configs.get_config("deepseek-v3-671b")
    assert O.optimizer_for(small).name == "adamw"
    assert O.optimizer_for(big).name == "adafactor"


def test_schedule_warmup_cosine():
    fn = schedule.cosine_schedule(1e-3, warmup=10, total=100, min_frac=0.05)
    assert float(fn(0)) == pytest.approx(0.0, abs=1e-9)
    assert float(fn(10)) == pytest.approx(1e-3, rel=1e-2)
    assert float(fn(100)) == pytest.approx(0.05e-3, rel=1e-2)
    # monotone decay after warmup
    vals = [float(fn(s)) for s in range(10, 101, 10)]
    assert all(a >= b for a, b in zip(vals, vals[1:]))


def test_int8_compression_roundtrip_error():
    x = jax.random.normal(jax.random.key(0), (1000,), jnp.float32) * 3.0
    q, scale = C.int8_compress(x)
    y = C.int8_decompress(q, scale, x.shape, x.dtype)
    # per-block max-abs quantization: |err| <= scale/2 per element
    blocks = jnp.pad(x, (0, (-x.size) % C.BLOCK)).reshape(-1, C.BLOCK)
    bound = jnp.repeat(scale / 2, C.BLOCK)[: x.size] + 1e-7
    assert bool(jnp.all(jnp.abs(y - x) <= bound))


def test_int8_compression_zero_block():
    x = jnp.zeros((512,), jnp.float32)
    q, scale = C.int8_compress(x)
    y = C.int8_decompress(q, scale, x.shape, x.dtype)
    assert bool(jnp.all(y == 0))


def test_compressed_psum_single_axis():
    """On a 1-device mesh axis the compressed all-reduce must be ≈identity."""
    mesh = jax.make_mesh((1,), ("pod",))
    x = jax.random.normal(jax.random.key(1), (300,), jnp.float32)

    def f(v):
        return C.compressed_psum(v, "pod")

    out = jax.shard_map(f, mesh=mesh,
                        in_specs=jax.sharding.PartitionSpec(),
                        out_specs=jax.sharding.PartitionSpec(),
                        check_vma=False)(x)
    assert float(jnp.max(jnp.abs(out - x))) < 0.05 * float(jnp.max(jnp.abs(x)))
