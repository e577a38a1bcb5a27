"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

Nothing here runs on a chip: each test lowers and compiles for a
*described* ``v5e:2x2`` topology, which raises whatever the TPU compiler
(Mosaic included) would refuse — block shapes off the (8, 128) tiling,
layouts XLA and Mosaic disagree on, more VMEM than a core has.  Shapes are
the ``jiuzhang2`` width (χ = 10⁴ padded to 10240 by ``core.mps.pad_bond``)
at N = 4096, with d = 4 and d = 3, plus the χ/4 shard widths the
tensor-parallel stages see on four chips.  Blocks come from the autotuner's
heuristic, so its choices are what is compiled.  Each kernel must reach
the HLO as a custom call named after its own ``pallas_call(name=...)``:
the benchmark finds kernels in the device trace by that name.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every xdist worker imports
this module.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.core import parallel as PP
from repro.core import sampler as S
from repro.core.mps import MPS
from repro.kernels import collapse_select as CS
from repro.kernels import contract_measure as CM
from repro.kernels import dispatch, site_impls
from repro.kernels import site_step as SS
from repro.launch.mesh import make_mesh

N, CHI = 4096, 10240


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-topology compile cannot be read back from the
    persistent cache without a chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """Steer the dispatch layer to its TPU branch (compiled kernels, no
    interpret mode) while tracing for the described chip."""
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setattr(site_impls, "on_tpu", lambda: True)


def kernel_names(text: str) -> set[str]:
    """Instruction names (numeric suffix dropped) of the Pallas custom
    calls in compiled HLO text."""
    return set(re.findall(r"%([A-Za-z_][\w-]*?)(?:\.\d+)? = [^\n]*"
                          r"custom_call_target=\"tpu_custom_call\"", text))


def _compile(fn, *args, kernel: str):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert kernel in kernel_names(text), kernel_names(text)
    return compiled


def _blocks(stage, chi_l, chi_r, d, planes=1):
    return dispatch._heuristic(stage, N, chi_l, chi_r, d, 4, planes)


@pytest.mark.parametrize("d", [4, 3])
def test_site_step_linear_compiles(one_chip, no_persistent_cache, d):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    cfg = _blocks("site_step", CHI, CHI, d)
    compiled = _compile(
        lambda e, g, lam, u: SS.site_step_linear(
            e, g, lam, u, bn=cfg.bn, br=cfg.br, bl=cfg.bl,
            compute_dtype=jnp.bfloat16),
        sds((N, CHI), jnp.float32), sds((CHI, CHI, d), jnp.bfloat16),
        sds((CHI,), jnp.float32), sds((N,), jnp.float32),
        kernel="site_step_linear")
    if d == 4:
        # Γ reaches the kernel as a bitcast of its HBM layout: no copy
        assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("d", [4, 3])
def test_site_step_born_compiles(one_chip, no_persistent_cache, d):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    cfg = _blocks("site_step", CHI, CHI, d, planes=2)
    _compile(
        lambda e, g, lam, u: SS.site_step_born(e, g, lam, u, bn=cfg.bn,
                                               br=cfg.br, bl=cfg.bl),
        sds((N, CHI), jnp.complex64), sds((CHI, CHI, d), jnp.complex64),
        sds((CHI,), jnp.float32), sds((N,), jnp.float32),
        kernel="site_step_born")


# (χl, χr): the full bond, and the two tensor-parallel shard shapes
_TP_SHAPES = [(CHI, CHI), (CHI // 4, CHI), (CHI, CHI // 4)]


@pytest.mark.parametrize("d", [4, 3])
@pytest.mark.parametrize("chi_l,chi_r", _TP_SHAPES)
def test_contract_measure_compiles(one_chip, no_persistent_cache, d, chi_l,
                                   chi_r):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    cfg = _blocks("contract_measure", chi_l, chi_r, d)
    _compile(
        lambda e, g, lam: CM.contract_measure(e, g, lam, bn=cfg.bn,
                                              br=cfg.br, bl=cfg.bl),
        sds((N, chi_l), jnp.bfloat16), sds((chi_l, chi_r, d), jnp.bfloat16),
        sds((chi_r,), jnp.float32), kernel="contract_measure")


@pytest.mark.parametrize("d", [4, 3])
@pytest.mark.parametrize("chi_l,chi_r", _TP_SHAPES)
def test_collapse_compiles(one_chip, no_persistent_cache, d, chi_l, chi_r):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    cfg = _blocks("collapse", chi_l, chi_r, d)
    _compile(
        lambda e, g, s: CS.collapse_select(e, g, s, bn=cfg.bn, br=cfg.br,
                                           bl=cfg.bl),
        sds((N, chi_l), jnp.bfloat16), sds((chi_l, chi_r, d), jnp.bfloat16),
        sds((N,), jnp.int32), kernel="collapse_select")


@pytest.mark.parametrize("d", [4, 3])
@pytest.mark.parametrize("chi_l", [CHI, CHI // 4])
def test_measure_compiles(one_chip, no_persistent_cache, d, chi_l):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    cfg = _blocks("measure", chi_l, chi_l, d)
    _compile(
        lambda e, w: SS.measure_probs(e, w, bn=cfg.bn, bl=cfg.bl,
                                      compute_dtype=jnp.bfloat16),
        sds((N, chi_l), jnp.float32), sds((chi_l, d), jnp.float32),
        kernel="measure_probs")


def test_streamed_segment_scan_compiles(one_chip, no_persistent_cache,
                                        as_tpu):
    """The seq walk's whole segment program (two sites, as the streamed
    engine runs it) with the fused kernel inside the scan."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    mps = MPS(sds((2, CHI, CHI, 4), jnp.bfloat16), sds((2, CHI), jnp.float32))
    state = S.SamplerState(sds((N, CHI), jnp.float32),
                           sds((), jax.random.key(0).dtype),
                           sds((N,), jnp.float32))
    cfg = S.SamplerConfig(compute_dtype=jnp.bfloat16, kernels="pallas")
    compiled = jax.jit(S.sample_chain, static_argnames=("config",)).lower(
        mps, state, config=cfg, start_site=sds((), jnp.int32)).compile()
    assert kernel_names(compiled.as_text()) == {"site_step_linear"}


@pytest.mark.parametrize("scheme", ["tp_single", "tp_double"])
def test_tp_segment_compiles_on_2x2(topo, no_persistent_cache, as_tpu,
                                    scheme):
    """The tensor-parallel segment programs on a (1, 4) mesh of the four
    described chips: Γ sharded over the bond, kernels inside shard_map."""
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices)

    def sds(shape, dt, spec):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))
    da = ("data",)
    cfg = S.SamplerConfig(compute_dtype=jnp.bfloat16, kernels="pallas")
    f = PP._segment_callable(mesh, PP.ParallelConfig(scheme, data_axes=da),
                             cfg)
    head = (sds((1, 2), jnp.uint32, P(da)),
            sds((N, CHI), jnp.float32, P(da, "model")),
            sds((N,), jnp.float32, P(da)))
    by_left = sds((1, CHI, CHI, 4), jnp.bfloat16, P(None, "model"))
    lam = sds((1, CHI), jnp.float32, P())
    start = sds((), jnp.int32, P())
    if scheme == "tp_single":
        args = head + (by_left, lam, start)
    else:
        by_right = sds((1, CHI, CHI, 4), jnp.bfloat16,
                       P(None, None, "model"))
        args = head + (by_left, lam, by_right, lam, start)
    compiled = f.lower(*args).compile()
    assert "contract_measure" in kernel_names(compiled.as_text())
