"""Γ store: low-precision storage + double-buffered prefetch (paper §3.3.2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mps as M
from repro.data import gamma_store as GS
from repro.data.gamma_store import GammaStore
from repro.data.tokens import synthetic_token_stream


def test_roundtrip_bf16_storage(tmp_path):
    store = GammaStore(str(tmp_path), storage_dtype=jnp.bfloat16,
                       compute_dtype=jnp.float32)
    mps = M.random_linear_mps(jax.random.key(0), 4, 8, 3, dtype=jnp.float32)
    store.write_mps(mps)
    g0, lam0 = store.get(0)
    assert g0.shape == (8, 8, 3) and g0.dtype == np.float32
    # bf16 storage: ~3 decimal digits
    np.testing.assert_allclose(g0, np.asarray(mps.gammas[0]), rtol=2e-2,
                               atol=1e-4)
    np.testing.assert_allclose(lam0, np.asarray(mps.lambdas[0]), rtol=1e-6)
    store.close()


def test_fp16_storage_halves_io(tmp_path):
    a = GammaStore(str(tmp_path / "bf16"), storage_dtype=jnp.bfloat16)
    b = GammaStore(str(tmp_path / "fp32"), storage_dtype=jnp.float32)
    mps = M.random_linear_mps(jax.random.key(1), 2, 16, 3, dtype=jnp.float32)
    a.write_mps(mps)
    b.write_mps(mps)
    a.get(0, prefetch_next=False)
    b.get(0, prefetch_next=False)
    # §3.3.2: Γ wire/IO bytes halve with 2-byte storage
    assert a.io_bytes < 0.6 * b.io_bytes
    a.close()
    b.close()


def test_prefetch_chain(tmp_path):
    store = GammaStore(str(tmp_path))
    mps = M.random_linear_mps(jax.random.key(2), 6, 4, 2, dtype=jnp.float32)
    store.write_mps(mps)
    for i in range(6):                      # sequential walk hits the prefetch
        g, lam = store.get(i)
        assert g.shape == (4, 4, 2)
    store.close()


def test_prefetch_reads_each_site_exactly_once(tmp_path):
    """Regression: an in-flight prefetch must be awaited, not re-read — a
    sequential walk costs exactly one disk read per site."""
    store = GammaStore(str(tmp_path), storage_dtype=jnp.float32)
    mps = M.random_linear_mps(jax.random.key(4), 8, 4, 2, dtype=jnp.float32)
    store.write_mps(mps)
    per_site = int(mps.gammas[0].size * 4 + mps.lambdas[0].size * 4)
    for i in range(8):
        store.get(i)
    assert store.io_bytes == 8 * per_site, (store.io_bytes, per_site)
    # nothing leaked into the buffer besides the final scheduled site
    assert set(store._prefetched) <= {8}
    store.close()
    assert not store._thread.is_alive()


def test_segment_reads_and_device_handoff(tmp_path):
    store = GammaStore(str(tmp_path), storage_dtype=jnp.bfloat16,
                       compute_dtype=jnp.float32)
    mps = M.random_linear_mps(jax.random.key(5), 10, 4, 3, dtype=jnp.float32)
    store.write_mps(mps)
    assert store.n_sites == 10
    g, lam = store.get_segment(0, 4)
    assert g.shape == (4, 4, 4, 3) and lam.shape == (4, 4)
    gd, ld = store.get_segment_on_device(4, 4)
    assert gd.shape == (4, 4, 4, 3) and ld.shape == (4, 4)
    # tail segment is clipped to the chain end
    g2, _ = store.get_segment(8, 4)
    assert g2.shape[0] == 2
    # every site read exactly once across the three segment calls:
    # bf16 gamma (4·4·3·2 B) + f32 lambda (4·4 B) per site
    assert store.io_bytes == 10 * (4 * 4 * 3 * 2 + 4 * 4)
    store.close()


@pytest.fixture(scope="module")
def stores(tmp_path_factory):
    """The same 10-site χ=6 chain in a bf16, a float32 and a float64
    store."""
    mps = M.random_linear_mps(jax.random.key(6), 10, 6, 3, dtype=jnp.float32)
    roots = {}
    for name in ("bfloat16", "float32", "float64"):
        roots[name] = str(tmp_path_factory.mktemp(name))
        with GammaStore(roots[name], storage_dtype=getattr(jnp, name),
                        compute_dtype=jnp.float32) as st:
            st.write_mps(mps)
    return roots


def _open(stores, storage):
    return GammaStore(stores[storage], storage_dtype=getattr(jnp, storage),
                      compute_dtype=jnp.float32)


def _per_site(store, start, stop):
    """The per-site ``_read_raw`` path: raw and decoded Γ, Λ, layout."""
    sites = [store._read_raw(i) for i in range(start, stop)]
    raw = np.stack([r for r, _, _, _ in sites])
    lam = np.stack([lm for _, lm, _, _ in sites])
    _, _, gshape, two_byte = sites[-1]
    dec = np.stack([GS.decode_gamma(r, gshape, two_byte, store.storage_dtype,
                                    store.compute_dtype)
                    for r, _, _, _ in sites])
    return raw, dec, lam, gshape, two_byte


def _same_bits(a, b):
    return a.dtype == b.dtype and a.shape == b.shape \
        and a.tobytes() == b.tobytes()


@pytest.mark.parametrize("segment", ["full", "tail", "chi_sliced"])
@pytest.mark.parametrize("storage", ["bfloat16", "float32", "float64"])
def test_segment_landing_matches_per_site_reads(stores, storage, segment):
    """``read_segment_into`` (slot by slot, in the storage format) and
    ``get_segment`` give the per-site ``_read_raw`` path's bits: a full
    segment, a tail of 2 sites in a 4-site buffer, and the χ-sliced view
    of dynamic bonds."""
    start, stop = {"full": (0, 4), "tail": (8, 10),
                   "chi_sliced": (4, 8)}[segment]
    chi_s = 4 if segment == "chi_sliced" else 6
    with _open(stores, storage) as store:
        raw, dec, lam, gshape, two_byte = _per_site(store, start, stop)
        g_out, l_out = store.segment_buffer(4, start)
        assert g_out.dtype == raw.dtype and l_out.dtype == lam.dtype
        assert store.read_segment_into(start, stop, g_out, l_out) == (
            gshape, two_byte)
        n = stop - start
        assert _same_bits(g_out[:n, :chi_s, :chi_s].copy(),
                          raw[:, :chi_s, :chi_s].copy())
        assert _same_bits(l_out[:n, :chi_s].copy(), lam[:, :chi_s].copy())
        g, lm = store.get_segment(start, 4)
        assert _same_bits(g, dec) and _same_bits(lm, lam)
        assert store.direct_reads == 2 * n
        assert store.payload_reads == 3 * n      # _read_raw counts too


def test_direct_reads_fall_back_on_compressed_members(stores, tmp_path):
    """Stored members land directly; a site file rewritten with
    ``np.savez_compressed`` takes the ``_read_raw`` path into its slot,
    gives the same arrays and does not count as direct."""
    import shutil
    root = str(tmp_path / "mixed")
    shutil.copytree(stores["bfloat16"], root)
    path = f"{root}/{GS.site_filename(1)}"
    with np.load(path) as z:
        members = {k: z[k] for k in z.files}
    np.savez_compressed(path, **members)
    with GammaStore(root, storage_dtype=jnp.bfloat16,
                    compute_dtype=jnp.float32) as store, \
            _open(stores, "bfloat16") as ref:
        g_out, l_out = store.segment_buffer(3)
        store.read_segment_into(0, 3, g_out, l_out)
        assert store.payload_reads == 3 and store.direct_reads == 2
        raw, _, lam, _, _ = _per_site(ref, 0, 3)
        assert _same_bits(g_out, raw) and _same_bits(l_out, lam)


@pytest.mark.parametrize("storage", ["bfloat16", "float64"])
def test_segment_raw_payload_is_the_stacked_site_bytes(stores, storage):
    """The broadcast payload carries the stacked per-site storage bytes
    and the CRC32 over them, as before sites were landed in place."""
    with _open(stores, storage) as store:
        raw, _, lam, gshape, two_byte = _per_site(store, 6, 10)
        payload = store.get_segment_raw(6, 4)
        assert _same_bits(payload["gamma"], raw)
        assert _same_bits(payload["lam"], lam)
        assert payload["gshape"] == gshape and payload["two_byte"] == two_byte
        assert int(payload["crc"]) == GS.segment_checksum(raw, lam)
        g, lm = GS.decode_segment(payload)
        assert _same_bits(g, store.get_segment(6, 4)[0])


def test_token_stream_restart_exact():
    bat = synthetic_token_stream(seed=3, vocab=100, batch=4, seq=16)
    a = bat(10)
    b = bat(10)
    c = bat(11)
    assert jnp.all(a["tokens"] == b["tokens"])       # idempotent by (seed, step)
    assert not jnp.all(a["tokens"] == c["tokens"])
    assert jnp.all(a["labels"][:, :-1] == a["tokens"][:, 1:])
