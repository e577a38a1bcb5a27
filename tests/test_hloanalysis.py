"""The dry-run profiler: loop-corrected HLO cost analysis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hloanalysis as H


def _compiled(fn, *sds):
    return jax.jit(fn).lower(*sds).compile()


def test_plain_dot_flops():
    c = _compiled(lambda a, b: a @ b,
                  jax.ShapeDtypeStruct((64, 128), jnp.float32),
                  jax.ShapeDtypeStruct((128, 32), jnp.float32))
    cost = H.analyze(c.as_text())
    assert cost.flops == pytest.approx(2 * 64 * 128 * 32, rel=0.01)


def test_scan_trip_count_correction():
    def f(x, ws):
        def body(c, w):
            return c @ w, None
        return jax.lax.scan(body, x, ws)[0]
    c = _compiled(f, jax.ShapeDtypeStruct((128, 128), jnp.float32),
                  jax.ShapeDtypeStruct((17, 128, 128), jnp.float32))
    cost = H.analyze(c.as_text())
    assert cost.flops == pytest.approx(17 * 2 * 128 ** 3, rel=0.01)


def test_nested_scan_multiplies():
    def f(x, ws):
        def outer(c, w):
            def inner(ci, _):
                return ci @ w, None
            return jax.lax.scan(inner, c, None, length=3)[0], None
        return jax.lax.scan(outer, x, ws)[0]
    c = _compiled(f, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                  jax.ShapeDtypeStruct((5, 64, 64), jnp.float32))
    cost = H.analyze(c.as_text())
    assert cost.flops == pytest.approx(15 * 2 * 64 ** 3, rel=0.01)


def test_collective_wire_bytes():
    import os
    import subprocess
    import sys
    import textwrap
    child = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import hloanalysis as H
        mesh = jax.make_mesh((4,), ("x",))
        def f(v):
            return jax.lax.psum(v, "x")
        g = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                          check_vma=False)
        c = jax.jit(g).lower(jax.ShapeDtypeStruct((1024,), jnp.float32)).compile()
        cost = H.analyze(c.as_text())
        # ring all-reduce of 4 KiB over 4 ranks: 2*4096*(3/4) = 6144 B
        assert abs(cost.collective_wire_bytes - 6144) < 1, cost.collective_wire_bytes
        assert cost.n_collectives.get("all-reduce") == 1
        print("OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr[-2000:]


def test_parse_tuple_types_with_index_comments():
    text = """
ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %t = (f32[8,8]{1,0}, s32[], /*index=2*/f32[4]{0}) tuple(%a, %a, %a)
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%t), index=0
}
"""
    comps = H.parse_hlo(text)
    assert "main" in comps
    ops = [i.opcode for i in comps["main"].instrs]
    assert ops == ["parameter", "tuple", "get-tuple-element"]


def test_roofline_terms():
    cost = H.HLOCost(flops=197e12, memory_bytes=819e9,
                     collective_wire_bytes=50e9, collective_raw_bytes=0,
                     per_collective={}, n_collectives={})
    rf = H.roofline(cost, n_chips=4, model_flops=4 * 197e12)
    assert rf.t_compute == pytest.approx(1.0)
    assert rf.t_memory == pytest.approx(1.0)
    assert rf.t_collective == pytest.approx(1.0)
    assert rf.useful_ratio == pytest.approx(1.0)


def test_complex_dot_flop_multiplier():
    c = _compiled(lambda a, b: a @ b,
                  jax.ShapeDtypeStruct((32, 32), jnp.complex64),
                  jax.ShapeDtypeStruct((32, 32), jnp.complex64))
    cost = H.analyze(c.as_text())
    if cost.flops:                       # CPU may lower c64 dot to custom-call
        assert cost.flops >= 4 * 2 * 32 ** 3 * 0.9
