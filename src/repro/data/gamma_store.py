"""Γ tensor store with double-buffered background prefetch (paper §3.1/§3.3.2).

The paper's data-parallel revival hinges on hiding Γ I/O behind compute:
process 0 reads Γᵢ₊₁ from disk while every process contracts Γᵢ.  Here the
store owns an on-disk directory of per-site tensors (written in bf16 — the
paper's FP16-storage trick, halving I/O and broadcast bytes) and a one-slot
prefetch thread; ``get(i)`` returns site i (upcast to the compute dtype) and
immediately schedules site i+1.

Segments are read by :meth:`read_segment_into`, which lands each site's
Γ payload straight from its file into a slot of a buffer the caller owns,
in the storage format — no intermediate copy, no stack.  Three consumers
build on it:

* the streaming engine (``repro.engine``) keeps one such segment buffer per
  fetch thread and reuses it for every segment it streams to the device;
* :meth:`get_segment` lands a segment in a fresh buffer and decodes it
  (the all-in-memory sampler stacks the whole chain this way);
* the multihost runtime (``repro.api.runtime``) broadcasts Γ in the
  **storage format**: :meth:`get_segment_raw` returns a wire payload of the
  packed on-disk bytes (bf16 when the store is bf16 — the same §3.3.2 trick
  that halves disk I/O halves the broadcast), and the module-level
  :func:`decode_segment` turns a payload back into compute-dtype arrays.
  Every read path decodes through the *same* function, so a
  broadcast-received segment is bit-identical to a locally-read one.

``get(i)`` never re-reads a site whose prefetch is already in flight: it
blocks on the worker's result queue instead (the old fall-back issued a
duplicate synchronous read and leaked the prefetched copy into
``_prefetched`` forever — asserted against in tests/test_gamma_store.py).
"""
from __future__ import annotations

import hashlib
import io
import json
import os
import queue
import struct
import threading
import time
import zipfile
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from numpy.lib import format as npy_format

from repro.obs import trace
from repro.runtime.faults import CorruptSegment, Fault

#: per-store digest manifest (``write_digest_manifest``): maps each site
#: file name to its leaf digest so a host holding only a *slice* of the
#: chain (repro.shard) can still reproduce the whole store's digest — the
#: key the serving gateway's ResultCache addresses results by.  The name
#: deliberately does not match the ``site_*.npz`` glob.
MANIFEST_NAME = "digests.json"

#: the zip member that holds Γ, and the zip local file header before it
#: (signature, …, file name length and extra field length at offset 26)
GAMMA_MEMBER = "gamma.npy"
_LOCAL_HEADER = struct.Struct("<4s22xHH")
#: bytes per ``readinto`` of a landing payload (hashed as they pass)
_CHUNK = 16 << 20


def site_filename(i: int) -> str:
    """Canonical site-file name — shared with repro.shard so a sliced store
    and a whole store agree on the Merkle leaf set."""
    return f"site_{i:06d}.npz"


def leaf_digest(fname: str, data: bytes) -> str:
    """Merkle leaf: sha256 over the site file's name + bytes (the name binds
    the leaf to its chain position; bytes alone would let two permuted
    stores collide)."""
    h = hashlib.sha256()
    h.update(fname.encode())
    h.update(data)
    return h.hexdigest()


def merkle_root(leaves: dict[str, str]) -> str:
    """Combine per-site leaf digests into the store digest: sha256 over the
    sorted ``name:leaf`` lines.  Computable from the leaves alone — which is
    the point: a sharded store hashes only the files it holds and takes the
    rest from the manifest."""
    h = hashlib.sha256()
    for f in sorted(leaves):
        h.update(f"{f}:{leaves[f]}\n".encode())
    return h.hexdigest()


def _npy_header(fp) -> Optional[tuple[int, tuple[int, ...], bool, np.dtype]]:
    """(header length, shape, fortran_order, dtype) of the ``.npy`` array
    at ``fp``'s position, leaving ``fp`` at its payload; None for a header
    version other than 1.0 or 2.0 (:meth:`GammaStore.put` writes 1.0)."""
    at = fp.tell()
    read = {(1, 0): npy_format.read_array_header_1_0,
            (2, 0): npy_format.read_array_header_2_0}.get(
                npy_format.read_magic(fp))
    if read is None:
        return None
    shape, fortran, dtype = read(fp)
    return fp.tell() - at, shape, fortran, dtype


def _small_member(zf: zipfile.ZipFile, name: str) -> np.ndarray:
    """A small member (Λ, gshape, two_byte) read whole; ``zipfile`` checks
    its CRC."""
    return npy_format.read_array(io.BytesIO(zf.read(name)),
                                 allow_pickle=False)


def decode_gamma(raw: np.ndarray, gshape: tuple[int, ...], two_byte: bool,
                 storage_dtype, compute_dtype) -> np.ndarray:
    """Storage-format Γ bytes → a compute-dtype host array.

    THE decode path: the store's local reads and the multihost broadcast
    receive both go through here, so the two are bit-identical by
    construction.  ``raw`` may carry a leading stack axis (a whole segment
    decodes in one call)."""
    lead = raw.shape[:max(0, raw.ndim - len(gshape))]
    g = raw
    if two_byte:
        # host-side: ml_dtypes gives numpy the 2-byte float types, so the
        # decode never round-trips through the accelerator
        g = raw.view(np.uint16).view(storage_dtype).reshape(
            lead + tuple(gshape))
    return g.astype(compute_dtype, copy=False)


def segment_checksum(gamma: np.ndarray, lam: np.ndarray) -> int:
    """CRC32 over a segment payload's packed Γ + Λ bytes — stamped by
    :meth:`GammaStore.get_segment_raw`, verified by :func:`decode_segment`,
    so a corrupt broadcast/RPC payload is rejected at decode instead of
    sampled from."""
    return zlib.crc32(np.ascontiguousarray(lam).tobytes(),
                      zlib.crc32(np.ascontiguousarray(gamma).tobytes()))


def decode_segment(payload: dict, compute_dtype=None
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Wire payload (see :meth:`GammaStore.get_segment_raw`) → stacked
    (gammas (L, χ, χ, d), lambdas (L, χ)) compute-dtype host arrays.

    Payloads stamped with a ``crc`` (every ``get_segment_raw`` payload)
    are verified here; a mismatch raises :class:`CorruptSegment` —
    kind=corruption, carrying the segment start site."""
    if payload.get("crc") is not None:
        want = int(np.asarray(payload["crc"]))
        got = segment_checksum(payload["gamma"], payload["lam"])
        if got != want:
            start = int(np.asarray(payload.get("start", -1)))
            raise CorruptSegment(Fault(
                kind="corruption", site=start,
                message=f"segment payload at site {start} failed its wire "
                        f"checksum (crc {got:#010x} != {want:#010x}) — "
                        f"rejected at decode, not sampled from"))
    compute = payload["compute_dtype"] if compute_dtype is None \
        else compute_dtype
    g = decode_gamma(payload["gamma"], tuple(payload["gshape"]),
                     bool(payload["two_byte"]), payload["storage_dtype"],
                     compute)
    return g, payload["lam"]


class GammaStore:
    def __init__(self, root: str, storage_dtype=jnp.bfloat16,
                 compute_dtype=jnp.float32, verify: bool = False):
        self.root = root
        self.storage_dtype = storage_dtype
        self.compute_dtype = compute_dtype
        #: verify every payload read against the digest manifest
        #: (digests.json) when one is present.  The streaming engine turns
        #: this on automatically for multi-host / sharded runs; structural
        #: corruption (a torn npz) is caught on every read regardless.
        self.verify = verify
        os.makedirs(root, exist_ok=True)
        self._prefetched: dict[int, np.ndarray] = {}
        self._inflight: set[int] = set()
        self._lock = threading.Lock()
        # (site, the span that scheduled its read) — the worker's read spans
        # take that span as their cause
        self._queue: "queue.Queue[Optional[tuple]]" = queue.Queue()
        self._results: "queue.Queue[tuple[int, np.ndarray]]" = queue.Queue()
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()
        self.io_bytes = 0          # instrumentation for the benches
        self.io_seconds = 0.0      # worker+sync read wall time
        self.payload_reads = 0     # Γ payload reads (meta() probes excluded)
        self.direct_reads = 0      # of those, landed straight in a slot
        self.verified_reads = 0    # payload reads digest-checked vs manifest
        self.quarantined_sites = 0
        self.repaired_sites = 0
        self.repair_read_bytes = 0  # bytes served to peers for repair
        self._digest: Optional[str] = None
        # per-file leaf cache keyed by (st_mtime_ns, st_size, st_ino): an
        # unchanged file never re-hashes, a rewritten/rotted one always does
        self._sigleaves: dict[str, tuple[tuple, str]] = {}
        self._manifest: Optional[tuple[tuple, dict]] = None
        self._n_sites = sum(1 for f in os.listdir(root)
                            if f.startswith("site_") and f.endswith(".npz"))

    # -- write path ---------------------------------------------------------
    def put(self, i: int, gamma: np.ndarray, lam: np.ndarray) -> None:
        fresh = not os.path.exists(self._path(i))
        g16 = np.asarray(jnp.asarray(gamma).astype(self.storage_dtype))
        np.savez(self._path(i), gamma=g16.view(np.uint16)
                 if g16.dtype.itemsize == 2 else g16,
                 gshape=np.array(gamma.shape), lam=np.asarray(lam),
                 two_byte=np.array(g16.dtype.itemsize == 2))
        if fresh:
            self._n_sites += 1
        self._digest = None            # content changed: recompute lazily
        self._sigleaves.pop(site_filename(i), None)

    def write_mps(self, mps) -> None:
        for i in range(mps.n_sites):
            self.put(i, np.asarray(mps.gammas[i]), np.asarray(mps.lambdas[i]))

    # -- read path ----------------------------------------------------------
    def _path(self, i: int) -> str:
        return os.path.join(self.root, site_filename(i))

    @property
    def n_sites(self) -> int:
        """Cached count (kept current by put()) — a listdir per call would be
        O(M) filenames on every segment walk of an M-site chain."""
        return self._n_sites

    def _site_files(self) -> list[str]:
        return sorted(f for f in os.listdir(self.root)
                      if f.startswith("site_") and f.endswith(".npz"))

    def _stat_sig(self, path: str) -> tuple:
        st = os.stat(path)
        return (st.st_mtime_ns, st.st_size, st.st_ino)

    def _leaf_for(self, f: str) -> str:
        """Leaf digest of one site file, cached per stat signature — the
        same ``(st_mtime_ns, st_size, st_ino)`` scheme the gateway's store
        identity cache uses.  Repeated ``digest()`` calls and per-read
        verification hash each file once until it changes on disk."""
        path = os.path.join(self.root, f)
        sig = self._stat_sig(path)
        cached = self._sigleaves.get(f)
        if cached is not None and cached[0] == sig:
            return cached[1]
        with open(path, "rb") as fh:
            leaf = leaf_digest(f, fh.read())
        self._sigleaves[f] = (sig, leaf)
        return leaf

    def site_digests(self) -> dict[str, str]:
        """Per-site Merkle leaves (``{file name: leaf_digest}``) for every
        site file this store holds.  Leaves are cached per file stat
        signature (see :meth:`_leaf_for`), so only changed files re-hash."""
        return {f: self._leaf_for(f) for f in self._site_files()}

    def digest(self) -> str:
        """Content digest of the materialized store: the Merkle root
        (:func:`merkle_root`) over the per-site leaf digests.  This
        identifies *these tensor files* — npz archives embed zip
        timestamps, so re-writing identical tensors yields a new digest;
        that is conservative in the right direction for result caching (a
        stale hit is impossible, a spurious miss just recomputes).  The
        tree shape is what lets a *sharded* store (repro.shard) reproduce
        the same digest from its owned leaves plus the manifest's.
        Cached; invalidated by :meth:`put`."""
        if self._digest is None:
            self._digest = merkle_root(self.site_digests())
        return self._digest

    def write_digest_manifest(self) -> str:
        """Persist the per-site leaves as ``digests.json`` in the store
        root (atomic).  A sharded slice carries this file so each host can
        answer for the GLOBAL digest while holding only its own sites."""
        path = os.path.join(self.root, MANIFEST_NAME)
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(self.site_digests(), fh, indent=0, sort_keys=True)
        os.replace(tmp, path)
        self._manifest = None
        return path

    def manifest_leaves(self) -> dict[str, str]:
        """The digest manifest's leaves (``{}`` when no ``digests.json``),
        cached per manifest file signature.  These are what verified reads
        compare against — the manifest is the store's ground truth."""
        path = os.path.join(self.root, MANIFEST_NAME)
        try:
            sig = self._stat_sig(path)
        except OSError:
            self._manifest = None
            return {}
        if self._manifest is not None and self._manifest[0] == sig:
            return self._manifest[1]
        with open(path) as fh:
            data = json.load(fh)
        self._manifest = (sig, data)
        return data

    def _probe_site(self, i: int) -> int:
        """The site whose file a header probe of site i reads."""
        return i

    def meta(self, i: int = 0) -> tuple[int, ...]:
        """Γ shape of site i from the npz header — no tensor payload read."""
        with np.load(self._path(self._probe_site(i))) as z:
            return tuple(int(x) for x in z["gshape"])

    def segment_buffer(self, length: int, i: int = 0
                       ) -> tuple[np.ndarray, np.ndarray]:
        """Zeroed host buffers for ``length`` sites in site i's
        storage format, for :meth:`read_segment_into`: Γ (length, χ, χ, d)
        in the stored dtype (``uint16`` when two_byte) and Λ (length, χ).
        Reads the two members' ``.npy`` headers, not their payloads; a site
        whose headers are damaged is a corrupt site, as in a read."""
        i = self._probe_site(i)
        fault = None
        for _attempt in range(2):
            with open(self._path(i), "rb") as fh:   # FileNotFoundError
                try:                                # propagates
                    with zipfile.ZipFile(fh) as zf:
                        (gshape, gdt), (lshape, ldt) = [
                            self._member_layout(zf, name)
                            for name in (GAMMA_MEMBER, "lam.npy")]
                    return (np.zeros((length,) + gshape, gdt),
                            np.zeros((length,) + lshape, ldt))
                except (zipfile.BadZipFile, ValueError, KeyError, EOFError,
                        OSError) as e:
                    fault = self._structural_fault(i, e)
        self.quarantine_site(i)
        raise CorruptSegment(fault)

    @staticmethod
    def _member_layout(zf: zipfile.ZipFile, name: str
                       ) -> tuple[tuple[int, ...], np.dtype]:
        with zf.open(name) as fp:
            header = _npy_header(fp)
        if header is None:
            raise ValueError(f"{name}: not an .npy array of version 1.0 "
                             f"or 2.0")
        return tuple(header[1]), header[3]

    def _structural_fault(self, i: int, e: Exception) -> Fault:
        return Fault(kind="corruption", site=i, store=self.root,
                     message=f"Γ site {i} is structurally corrupt "
                             f"({type(e).__name__}: {e})")

    def quarantine_site(self, i: int) -> Optional[str]:
        """Move a corrupt site file aside (rename to ``*.quarantine``) so
        no later read can consume the bad bytes; returns the quarantine
        path (None when the file is already gone)."""
        path = self._path(i)
        qpath = path + ".quarantine"
        try:
            os.replace(path, qpath)
        except OSError:
            return None
        with self._lock:
            self.quarantined_sites += 1
        self._sigleaves.pop(site_filename(i), None)
        self._digest = None
        return qpath

    def _read_raw(self, i: int, cause: Optional[trace.Span] = None
                  ) -> tuple[np.ndarray, np.ndarray, tuple[int, ...], bool]:
        """One site's storage-format payload: (packed Γ, Λ, gshape, two_byte).
        With :meth:`read_segment_into` one of the two places Γ payload
        bytes leave the disk — the I/O counters of both are what the
        only-root-reads contract asserts on.  This one reads the file into
        memory and parses it with ``np.load``: the per-site :meth:`get`
        path, and members :meth:`read_segment_into` cannot land directly.

        Verification happens here, at the choke point: when :attr:`verify`
        is on and the manifest carries a leaf for site i, the file bytes
        are digest-checked before decode; a torn/truncated npz is caught
        structurally on every read regardless.  Bad bytes get one bounded
        re-read (a transient torn read heals; real rot fails twice), then
        the file is quarantined and :class:`CorruptSegment` raised — no
        caller ever sees garbage tensors.

        Spans ``store.read`` (the file read) and ``store.parse``
        (``np.load``) cover each attempt; ``cause`` is their parent when
        the read runs on the prefetch worker."""
        t0 = time.perf_counter()
        path = self._path(i)
        fname = site_filename(i)
        fault = None
        checked = False
        raw = lam = gshape = two_byte = None
        for _attempt in range(2):
            fault = None
            with trace.span("store.read", parent=cause, site=i), \
                    open(path, "rb") as fh:  # FileNotFoundError propagates
                data = fh.read()
            if self.verify:
                expected = self.manifest_leaves().get(fname)
                if expected is not None:
                    checked = True
                    if leaf_digest(fname, data) != expected:
                        fault = Fault(
                            kind="corruption", site=i, store=self.root,
                            message=f"Γ site {i} failed digest verification "
                                    f"against {MANIFEST_NAME} in {self.root}")
                        continue
            try:
                with trace.span("store.parse", parent=cause, site=i), \
                        np.load(io.BytesIO(data)) as z:
                    raw, lam = z["gamma"], z["lam"]
                    gshape = tuple(int(x) for x in z["gshape"])
                    two_byte = bool(z["two_byte"])
            except (zipfile.BadZipFile, ValueError, KeyError, EOFError,
                    OSError) as e:
                fault = self._structural_fault(i, e)
                continue
            break
        if fault is not None:
            self.quarantine_site(i)
            raise CorruptSegment(fault)
        # the worker thread and a caller's synchronous fall-back read can
        # race here — unsynchronized += would lose counts
        with self._lock:
            self.io_bytes += raw.nbytes + lam.nbytes
            self.io_seconds += time.perf_counter() - t0
            self.payload_reads += 1
            if checked:
                self.verified_reads += 1
        return raw, lam, gshape, two_byte

    def read_segment_into(self, start: int, stop: int,
                          out_gamma: np.ndarray, out_lam: np.ndarray,
                          cause: Optional[trace.Span] = None
                          ) -> tuple[tuple[int, ...], bool]:
        """Land sites [start, stop) in ``out_gamma[k]`` and ``out_lam[k]``
        (site start + k), in the storage format (the ``uint16`` view when
        two_byte; :meth:`segment_buffer` makes such buffers), and return
        the sites' (gshape, two_byte).

        A Γ member stored uncompressed in C order — what :meth:`put`
        writes — is read straight from the file into its slot and checked
        there against the member's CRC32; with :attr:`verify` on, the
        manifest leaf is hashed from the same bytes as they pass.  Any
        other member goes through :meth:`_read_raw` and is copied in.
        Either way a site counts once in ``payload_reads`` (the direct
        ones in ``direct_reads``), and bad bytes get the one bounded
        re-read, quarantine and :class:`CorruptSegment` of
        :meth:`_read_raw`.

        Spans per site: ``store.parse`` (zip and ``.npy`` headers, Λ),
        ``store.read`` (the payload), ``store.parse`` (the CRC)."""
        n = stop - start
        if len(out_gamma) < n or len(out_lam) < n \
                or not out_gamma.flags.c_contiguous:
            raise ValueError(f"segment buffers {out_gamma.shape}/"
                             f"{out_lam.shape} cannot hold sites "
                             f"[{start}, {stop}) contiguously")
        layout = None
        for k in range(n):
            layout = self._land_site(start + k, out_gamma[k], out_lam[k],
                                     cause)
        return layout

    def _land_site(self, i: int, g_out: np.ndarray, l_out: np.ndarray,
                   cause: Optional[trace.Span]) -> tuple[tuple[int, ...],
                                                        bool]:
        """One site of :meth:`read_segment_into`, with its fault handling
        and counters."""
        t0 = time.perf_counter()
        path = self._path(i)
        fname = site_filename(i)
        fault = None
        checked = False
        for _attempt in range(2):
            expected = (self.manifest_leaves().get(fname) if self.verify
                        else None)
            with open(path, "rb", buffering=0) as fh:  # FileNotFoundError
                try:                                   # propagates
                    landed = self._land(fh, i, g_out, expected is not None,
                                        cause)
                except (zipfile.BadZipFile, ValueError, KeyError, EOFError,
                        OSError) as e:
                    fault = self._structural_fault(i, e)
                    continue
            if landed is None:       # compressed or Fortran-order member
                raw, lam, gshape, two_byte = self._read_raw(i, cause)
                np.copyto(g_out, raw, casting="no")
                np.copyto(l_out, lam, casting="no")
                return gshape, two_byte
            lam, gshape, two_byte, leaf = landed
            checked = expected is not None
            if checked and leaf != expected:
                fault = Fault(
                    kind="corruption", site=i, store=self.root,
                    message=f"Γ site {i} failed digest verification "
                            f"against {MANIFEST_NAME} in {self.root}")
                continue
            fault = None
            break
        if fault is not None:
            self.quarantine_site(i)
            raise CorruptSegment(fault)
        np.copyto(l_out, lam, casting="no")
        with self._lock:
            self.io_bytes += g_out.nbytes + lam.nbytes
            self.io_seconds += time.perf_counter() - t0
            self.payload_reads += 1
            self.direct_reads += 1
            if checked:
                self.verified_reads += 1
        return gshape, two_byte

    def _land(self, fh, i: int, g_out: np.ndarray, hash_leaf: bool,
              cause: Optional[trace.Span]):
        """Read site i's Γ payload from ``fh`` (unbuffered) into ``g_out``
        and check its CRC32.  Returns (Λ, gshape, two_byte, leaf digest or
        None), or None when the member is not stored uncompressed in C
        order with ``g_out``'s dtype and shape.  Structural damage raises
        ``zipfile.BadZipFile``, ``ValueError``, ``EOFError`` or
        ``OSError``."""
        with trace.span("store.parse", parent=cause, site=i):
            with zipfile.ZipFile(fh) as zf:       # the central directory
                info = zf.getinfo(GAMMA_MEMBER)
                if info.compress_type != zipfile.ZIP_STORED:
                    return None
                fh.seek(info.header_offset)
                local = fh.read(_LOCAL_HEADER.size)
                if len(local) != _LOCAL_HEADER.size:
                    raise EOFError(f"{GAMMA_MEMBER}: local header cut short")
                sig, n_name, n_extra = _LOCAL_HEADER.unpack(local)
                if sig != b"PK\x03\x04" or \
                        fh.read(n_name) != GAMMA_MEMBER.encode():
                    raise zipfile.BadZipFile(
                        f"{GAMMA_MEMBER}: bad local file header")
                start = info.header_offset + _LOCAL_HEADER.size + n_name \
                    + n_extra
                fh.seek(start)
                header = _npy_header(fh)
                if header is None:
                    return None
                n_head, shape, fortran, dtype = header
                if fortran or dtype != g_out.dtype \
                        or tuple(shape) != g_out.shape:
                    return None
                if info.file_size != n_head + g_out.nbytes:
                    raise zipfile.BadZipFile(
                        f"{GAMMA_MEMBER}: {info.file_size} bytes, expected "
                        f"{n_head + g_out.nbytes}")
                fh.seek(start)
                head = fh.read(n_head)
                lam = _small_member(zf, "lam.npy")
                gshape = tuple(int(x) for x in _small_member(zf,
                                                             "gshape.npy"))
                two_byte = bool(_small_member(zf, "two_byte.npy"))
        offset = start + n_head
        payload = memoryview(g_out.reshape(-1).view(np.uint8))
        h = None
        with trace.span("store.read", parent=cause, site=i):
            if hash_leaf:            # leaf_digest over the whole file
                h = hashlib.sha256(site_filename(i).encode())
                fh.seek(0)
                h.update(fh.read(offset))
            fh.seek(offset)
            pos = 0
            while pos < len(payload):
                got = fh.readinto(payload[pos:pos + _CHUNK])
                if not got:
                    raise EOFError(f"{GAMMA_MEMBER}: payload cut short by "
                                   f"{len(payload) - pos} bytes")
                if h is not None:
                    h.update(payload[pos:pos + got])
                pos += got
            if h is not None:
                h.update(fh.read())
        with trace.span("store.parse", parent=cause, site=i):
            crc = zlib.crc32(payload, zlib.crc32(head))
        if crc != info.CRC:
            raise zipfile.BadZipFile(f"Bad CRC-32 for file {GAMMA_MEMBER!r}")
        return lam, gshape, two_byte, None if h is None else h.hexdigest()

    def verify_sites(self, sites=None) -> list[int]:
        """Verify site files against the digest manifest; quarantine any
        that fail and return their indices.  Cheap on a healthy store —
        leaves are cached per stat signature, so unchanged files hash
        once.  Sites with no file or no manifest entry are skipped
        (nothing to verify against)."""
        manifest = self.manifest_leaves()
        if sites is None:
            sites = [int(f[len("site_"):-len(".npz")])
                     for f in self._site_files()]
        bad = []
        for i in sites:
            f = site_filename(i)
            expected = manifest.get(f)
            if expected is None or not os.path.exists(
                    os.path.join(self.root, f)):
                continue
            try:
                ok = self._leaf_for(f) == expected
            except OSError:
                ok = False
            if not ok:
                self.quarantine_site(i)
                bad.append(i)
        return bad

    def has_healthy_copy(self, i: int) -> bool:
        """Does this root hold site i's file with bytes matching the
        manifest?  The peer-repair eligibility probe — a metadata read,
        never a Γ payload read."""
        f = site_filename(i)
        if not os.path.exists(os.path.join(self.root, f)):
            return False
        expected = self.manifest_leaves().get(f)
        if expected is None:
            return False
        try:
            return self._leaf_for(f) == expected
        except OSError:
            return False

    def read_repair_bytes(self, i: int) -> bytes:
        """Raw file bytes of site i for serving a peer repair, verified
        against the manifest before leaving this host — never ship rot to
        a peer.  This is the recovery path: it deliberately bypasses shard
        ownership enforcement (a healthy replica of a *foreign* site is
        exactly what repair needs) and is counted separately from payload
        reads (:attr:`repair_read_bytes`)."""
        f = site_filename(i)
        with open(os.path.join(self.root, f), "rb") as fh:
            data = fh.read()
        expected = self.manifest_leaves().get(f)
        if expected is not None and leaf_digest(f, data) != expected:
            raise CorruptSegment(Fault(
                kind="corruption", site=i, store=self.root,
                message=f"repair source for Γ site {i} is itself corrupt"))
        with self._lock:
            self.repair_read_bytes += len(data)
        return data

    def restore_site(self, i: int, data: bytes) -> None:
        """Atomically re-materialize site i from repair bytes (verified
        against the manifest when one is present) and clear any
        quarantined copy — the receiving end of a peer repair."""
        f = site_filename(i)
        expected = self.manifest_leaves().get(f)
        if expected is not None and leaf_digest(f, data) != expected:
            raise CorruptSegment(Fault(
                kind="corruption", site=i, store=self.root,
                message=f"repair payload for Γ site {i} failed "
                        f"verification — refusing to install it"))
        path = self._path(i)
        tmp = path + ".repair_tmp"
        with open(tmp, "wb") as fh:
            fh.write(data)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
        try:
            os.unlink(path + ".quarantine")
        except OSError:
            pass
        self._sigleaves.pop(f, None)
        self._digest = None
        with self._lock:
            self.repaired_sites += 1

    def _read(self, i: int, cause: Optional[trace.Span] = None):
        raw, lam, gshape, two_byte = self._read_raw(i, cause)
        with trace.span("store.decode", parent=cause, site=i):
            return decode_gamma(raw, gshape, two_byte, self.storage_dtype,
                                self.compute_dtype), lam

    def _worker(self):
        while True:
            item = self._queue.get()
            if item is None:
                return
            i, cause = item
            try:
                self._results.put((i, self._read(i, cause)))
            except Exception as e:          # surfaced on the consumer side
                self._results.put((i, e))

    def prefetch(self, i: int) -> None:
        with self._lock:
            if i in self._inflight or i in self._prefetched:
                return
            self._inflight.add(i)
        self._queue.put((i, trace.current()))

    def _drain(self, block: bool) -> bool:
        """Move one worker result into ``_prefetched``; True if one arrived."""
        try:
            j, payload = self._results.get(block=block,
                                           timeout=60.0 if block else None)
        except queue.Empty:
            if block:
                raise TimeoutError("prefetch worker stalled >60s")
            return False
        with self._lock:
            self._inflight.discard(j)
            self._prefetched[j] = payload
        return True

    def get(self, i: int, prefetch_next: bool = True):
        """Blocking read of site i (served from the prefetch buffer when the
        background thread already has it); schedules i+1.

        If a prefetch for i is *in flight*, block on the worker's result
        instead of issuing a duplicate synchronous read — each site is read
        from disk exactly once along a sequential walk.
        """
        while True:
            with self._lock:
                hit = self._prefetched.pop(i, None)
                wait = i in self._inflight
            if hit is not None:
                break
            if wait:
                self._drain(block=True)
                continue
            if not self._drain(block=False):
                hit = self._read(i)
                break
        if isinstance(hit, Exception):
            raise hit
        if prefetch_next and os.path.exists(self._path(i + 1)):
            self.prefetch(i + 1)
        return hit

    def get_segment(self, start: int, length: int
                    ) -> tuple[np.ndarray, np.ndarray]:
        """Blocking read of sites [start, start+length), clipped to the
        chain end: compute-dtype (gammas (L, χ, χ, d), lambdas (L, χ))
        host arrays, landed in a fresh buffer by
        :meth:`read_segment_into`."""
        stop = min(start + length, self.n_sites)
        raw, lam = self.segment_buffer(stop - start, start)
        gshape, two_byte = self.read_segment_into(start, stop, raw, lam)
        with trace.span("store.decode", start=start):
            return decode_gamma(raw, gshape, two_byte, self.storage_dtype,
                                self.compute_dtype), lam

    def get_segment_on_device(self, start: int, length: int, device=None):
        """Segment read + device hand-off: the returned jax arrays are already
        on (or being transferred to) the accelerator.  ``device_put`` is
        asynchronous, so callers can overlap this transfer with compute on the
        previous segment simply by calling this from a background thread."""
        g, lam = self.get_segment(start, length)
        return jax.device_put(g, device), jax.device_put(lam, device)

    def get_segment_raw(self, start: int, length: int) -> dict:
        """Storage-format wire payload for sites [start, start+length).

        This is what the multihost runtime broadcasts (paper §3.1): the
        packed on-disk bytes — bf16 when the store is bf16, so the §3.3.2
        compression that halves disk I/O halves the interconnect bytes too —
        plus the metadata a receiver needs to :func:`decode_segment` them.
        Reads synchronously on the caller's thread (the streaming engine
        calls this from its prefetch pool, which already overlaps the read
        and the broadcast with compute on the previous segment)."""
        stop = min(start + length, self.n_sites)
        gamma, lam = self.segment_buffer(stop - start, start)
        gshape, two_byte = self.read_segment_into(start, stop, gamma, lam)
        return {"start": start, "gamma": gamma, "lam": lam, "gshape": gshape,
                "two_byte": two_byte, "storage_dtype": self.storage_dtype,
                "compute_dtype": self.compute_dtype,
                "crc": np.uint32(segment_checksum(gamma, lam))}

    def close(self):
        self._queue.put(None)
        self._thread.join()

    # context-manager support: sessions and tests that open a store inline
    # can never leak the prefetch thread
    def __enter__(self) -> "GammaStore":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
