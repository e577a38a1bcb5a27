"""A GammaStore view that *enforces* block-cyclic site ownership.

The acceptance contract for the sharded data plane is "no host read or
received a Γ segment it does not own".  Rather than asserting that after
the fact, the store refuses up front: :meth:`ShardedGammaStore._read_raw`
and :meth:`ShardedGammaStore.read_segment_into` — the two ways a Γ
payload byte leaves disk — raise :class:`ShardViolation` for a foreign site
*before* touching the file.  The engine's sharded walk therefore cannot silently fall back to
reading a neighbour's sites, and the per-engine ``io_bytes``/
``payload_reads`` counters count owned traffic only, by construction.

Two deployment shapes share the class:

* **shared root** (tests, single-filer clusters): every site file is
  visible to every host; the view only *restricts* what this host may
  read.  The streaming engine wraps a plain session store in this view
  automatically when a shard map is active.
* **materialized slice** (:func:`materialize_shard`): each host's root
  holds only its owned files (store capacity scales with hosts) plus the
  digest manifest, so :meth:`digest` still reproduces the whole store's
  Merkle root — the key the serving gateway's ResultCache addresses
  results by.
"""
from __future__ import annotations

import json
import os
import shutil

import jax.numpy as jnp

from repro.data.gamma_store import (MANIFEST_NAME, GammaStore, leaf_digest,
                                    merkle_root, site_filename)
from repro.shard.shardmap import ShardMap


class ShardViolation(RuntimeError):
    """A host touched (read, prefetched-with-force, wrote) a foreign site."""


class ShardedGammaStore(GammaStore):
    """One host's ownership-scoped view of a (possibly sliced) store."""

    def __init__(self, root: str, shard: ShardMap, host: int,
                 storage_dtype=jnp.bfloat16, compute_dtype=jnp.float32,
                 verify: bool = False):
        if not 0 <= host < shard.n_hosts:
            raise ValueError(f"host {host} outside the shard map's "
                             f"[0, {shard.n_hosts}) hosts")
        self.shard = shard
        self.host = int(host)
        super().__init__(root, storage_dtype=storage_dtype,
                         compute_dtype=compute_dtype, verify=verify)
        # n_sites is the GLOBAL chain length: schedules, identity padding
        # and digests are all chain-wide notions even when this root holds
        # only a slice of the files
        self._n_sites = int(shard.n_sites)

    # -- ownership enforcement ----------------------------------------------
    def _check_read(self, i: int) -> None:
        if not self.shard.owns(self.host, i):
            raise ShardViolation(
                f"host {self.host} tried to read Γ site {i}, owned by host "
                f"{self.shard.owner(i)} (block={self.shard.block}, "
                f"hosts={self.shard.n_hosts}) — only the (N, χ) env crosses "
                f"hosts, never Γ")

    def _read_raw(self, i: int, cause=None):
        self._check_read(i)
        return super()._read_raw(i, cause)

    def read_segment_into(self, start: int, stop: int, out_gamma, out_lam,
                          cause=None):
        # every site is checked before the first byte of any is read
        for i in range(start, stop):
            self._check_read(i)
        return super().read_segment_into(start, stop, out_gamma, out_lam,
                                         cause)

    def prefetch(self, i: int) -> None:
        # advisory, not a violation: get(i) schedules i + 1, which may lie
        # across an ownership boundary
        if self.shard.owns(self.host, i):
            super().prefetch(i)

    def put(self, i: int, gamma, lam) -> None:
        if not self.shard.owns(self.host, i):
            raise ShardViolation(
                f"host {self.host} tried to write Γ site {i}, owned by host "
                f"{self.shard.owner(i)}")
        super().put(i, gamma, lam)
        self._n_sites = int(self.shard.n_sites)   # global, not file count

    def _probe_site(self, i: int) -> int:
        """Header probes (:meth:`meta`, :meth:`segment_buffer`) of a
        foreign site read this host's first owned site instead — chains
        stream through one fixed (χ, χ, d) site format, which is what
        callers probe for."""
        if self.shard.owns(self.host, i):
            return i
        owned = self.shard.owned_sites(self.host)
        if not owned:
            raise ShardViolation(
                f"host {self.host} owns no sites of the "
                f"{self.shard.n_sites}-site chain "
                f"(block={self.shard.block} × {self.shard.n_hosts} "
                f"hosts) and cannot probe a site shape")
        return owned[0]

    # -- global digest from a slice -----------------------------------------
    def digest(self) -> str:
        """The WHOLE store's Merkle root, computed from this host's owned
        leaves plus the manifest's (or, on a shared root with no manifest,
        by hashing the present foreign files directly — a metadata read,
        not a Γ payload read; payload reads are what the ownership check
        guards)."""
        if self._digest is None:
            owned_leaves = self.site_digests()
            manifest = {}
            mpath = os.path.join(self.root, MANIFEST_NAME)
            if os.path.exists(mpath):
                with open(mpath) as fh:
                    manifest = json.load(fh)
            leaves = {}
            for i in range(self.shard.n_sites):
                f = site_filename(i)
                if f in owned_leaves:
                    leaves[f] = owned_leaves[f]
                elif f in manifest:
                    leaves[f] = manifest[f]
                elif os.path.exists(os.path.join(self.root, f)):
                    leaves[f] = self._leaf_for(f)
                else:
                    raise FileNotFoundError(
                        f"sharded digest needs {MANIFEST_NAME} covering "
                        f"foreign site {i} (host {self.host} does not hold "
                        f"{f}) — materialize_shard writes the manifest")
            self._digest = merkle_root(leaves)
        return self._digest

    def site_digests(self) -> dict[str, str]:
        """Leaves for this host's OWNED files only (foreign files on a
        shared root are not this host's to answer for — and hashing them
        would defeat the capacity-scaling story).  Leaves are cached per
        file stat signature (see :meth:`GammaStore._leaf_for`)."""
        leaves = {}
        for f in self._site_files():
            i = int(f[len("site_"):-len(".npz")])
            if self.shard.owns(self.host, i):
                leaves[f] = self._leaf_for(f)
        return leaves

    def verify_sites(self, sites=None) -> list[int]:
        """Pre-walk verification of this host's OWNED slice only — the
        engine's repair round calls this before the lockstep walk so a
        rotted site surfaces while a healthy peer can still serve it."""
        if sites is None:
            sites = list(self.shard.owned_sites(self.host))
        return super().verify_sites(sites)


def materialize_shard(src_root: str, dst_root: str, shard: ShardMap,
                      host: int, link: bool = True) -> str:
    """Pack host ``host``'s slice of the store at ``src_root`` into
    ``dst_root``: only the owned site files (hard-linked when the
    filesystem allows, else copied) plus the full digest manifest, so the
    slice still reproduces the global :meth:`GammaStore.digest`.  Per-host
    disk is O(chain / hosts) — the capacity axis the broadcast plane does
    not have."""
    os.makedirs(dst_root, exist_ok=True)
    leaves = {}
    for i in range(shard.n_sites):
        f = site_filename(i)
        src = os.path.join(src_root, f)
        with open(src, "rb") as fh:
            leaves[f] = leaf_digest(f, fh.read())
        if shard.owns(host, i):
            dst = os.path.join(dst_root, f)
            if os.path.exists(dst):
                os.remove(dst)
            if link:
                try:
                    os.link(src, dst)
                    continue
                except OSError:       # cross-device / unsupported: copy
                    pass
            shutil.copyfile(src, dst)
    mpath = os.path.join(dst_root, MANIFEST_NAME)
    tmp = mpath + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(leaves, fh, indent=0, sort_keys=True)
    os.replace(tmp, mpath)
    return dst_root


__all__ = ["ShardViolation", "ShardedGammaStore", "materialize_shard"]
