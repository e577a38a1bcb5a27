"""Remote dispatch: serialize a `SamplerConfig`, ship it through a runtime.

The ``remote`` backend does not walk the chain itself — it packages the
session's request (config + store location + batch size + PRNG key) into a
JSON-serializable *payload* and hands it to
:meth:`repro.api.runtime.ClusterRuntime.submit`:

* :class:`~repro.api.runtime.LocalRuntime` executes the payload in-process
  (the loopback transport — zero infrastructure, same serialization
  boundary, so the dispatch path is exercised by every tier-1 run);
* :class:`RemoteRuntime` (registered as ``runtime="remote"``) dispatches
  to a **persistent worker interpreter** over the framed-pipe RPC of
  ``repro.runtime.transport``: the worker is spawned once, stays alive
  across submits (warm jit cache, cached worker-side sessions), streams
  each batch result back, and is reaped when the runtime closes.  Nothing
  but the payload crosses — the same isolation a real RPC/queue transport
  to another machine would give.  ``RemoteRuntime(persistent=False)``
  keeps the old one-subprocess-per-batch behaviour as a measurable
  baseline (``benchmarks/bench_fleet.py``).

Either way the worker resolves the *inner* config against its own
local runtime (``runtime="local"``, ``backend=AUTO`` → streamed from the
store path), so remote samples are bit-identical to a local streamed walk
for the same seed — the §4.1 contract extends across the dispatch
boundary and is asserted in ``tests/test_api.py``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import tempfile
from typing import Optional

import numpy as np

from repro.api.runtime import ClusterRuntime, register_runtime

_DTYPE_FIELDS = ("compute_dtype", "wire_dtype")


def _dtype_name(dt) -> Optional[str]:
    return None if dt is None else np.dtype(dt).name


def _dtype_from_name(name: Optional[str]):
    # by-name lookup through jnp attributes: numpy's registry does not know
    # 'bfloat16' but jnp.bfloat16 (ml_dtypes) does
    import jax.numpy as jnp
    return None if name is None else getattr(jnp, name)


def config_to_dict(config) -> dict:
    """``SamplerConfig`` → a JSON-serializable dict (dtypes by name, the
    perfmodel ``Hardware`` by its fields, runtime by name).

    Field-by-field rather than ``dataclasses.asdict`` — the runtime field
    may hold a live :class:`ClusterRuntime` whose locks/queues must not be
    deep-copied."""
    out = {f.name: getattr(config, f.name)
           for f in dataclasses.fields(config)}
    for f in _DTYPE_FIELDS:
        out[f] = _dtype_name(out[f])
    rt = out.get("runtime")
    out["runtime"] = rt if isinstance(rt, (str, type(None))) else rt.name
    if config.hardware is not None:
        out["hardware"] = dataclasses.asdict(config.hardware)
    if out.get("chi_profile") is not None:
        out["chi_profile"] = [int(c) for c in out["chi_profile"]]
    if out.get("clamp") is not None:
        # canonical pair-list form: json would coerce the tuples anyway,
        # but an explicit shape keeps payload_cell's sorted dump stable
        # (the worker-side SamplerConfig re-normalizes on construction)
        out["clamp"] = [[int(s), list(o) if isinstance(o, tuple) else int(o)]
                        for s, o in out["clamp"]]
    return out


def config_from_dict(d: dict):
    """Inverse of :func:`config_to_dict`."""
    from repro.api.config import SamplerConfig
    from repro.core.perfmodel import Hardware
    d = dict(d)
    for f in _DTYPE_FIELDS:
        d[f] = _dtype_from_name(d.get(f))
    if d.get("hardware") is not None:
        d["hardware"] = Hardware(**d["hardware"])
    if d.get("chi_profile") is not None:
        d["chi_profile"] = tuple(int(c) for c in d["chi_profile"])
    return SamplerConfig(**d)


def build_payload(config, store, n_samples: int, key, job=None) -> dict:
    """The unit of dispatch: one JOB BATCH, as plain JSON.

    Everything a worker needs to reproduce one macro batch bit-exactly:
    the session config, the store location, the batch size, the *job base
    key*, and (``job`` — a ``service.JobBatch``) the batch's identity
    within its job.  The worker derives the batch key itself via
    ``service.batch_key(key, batch_id, n_batches)`` — identical arithmetic
    to the local path, so a service may scatter one job's batches over
    many workers and reassemble a bit-identical result.  ``job=None``
    degrades to the v1 whole-run payload (a 1-batch job in disguise).

    The inner config re-resolves on the worker: ``backend=AUTO`` picks the
    streamed data plane from the store path, ``runtime="local"`` because
    the worker IS the remote process.  Γ itself never rides the payload —
    the store location does (shared filesystem / object store in a real
    deployment).
    """
    import jax

    from repro.api.runtime import AUTO
    inner = dataclasses.replace(config, backend=AUTO, runtime="local",
                                store_root=None, checkpoint_dir=None)
    out = {
        "version": 2,
        "config": config_to_dict(inner),
        "store_root": str(store.root),
        "storage_dtype": np.dtype(store.storage_dtype).name,
        "compute_dtype": np.dtype(store.compute_dtype).name,
        "n_samples": int(n_samples),
        "key_data": np.asarray(jax.random.key_data(key)).tolist(),
        "enable_x64": bool(jax.config.jax_enable_x64),
    }
    if job is not None:
        out["job"] = {"job_id": int(job.job_id),
                      "batch_id": int(job.batch_id),
                      "n_batches": int(job.n_batches)}
    return out


class _CachedSession:
    """A worker-held (store, session) pair — one per payload cell, kept
    open across batches so repeated batches of a job reuse one engine and
    jit cache (the point of a persistent worker)."""

    def __init__(self, store, session):
        self.store = store
        self.session = session

    def close(self) -> None:
        self.session.close()
        self.store.close()


def payload_cell(payload: dict) -> tuple:
    """The worker-side session-coalescing identity of a payload — the
    mirror of ``SamplingService._coalesce_session``'s (source, config)
    cell, in serialized form."""
    return (payload["store_root"], payload["storage_dtype"],
            payload["compute_dtype"],
            json.dumps(payload["config"], sort_keys=True))


def execute_payload(payload: dict, cache: Optional[dict] = None
                    ) -> np.ndarray:
    """Run one payload to completion — the worker half of the dispatch.

    Called in-process by ``LocalRuntime.submit``, as ``__main__`` by the
    one-shot baseline worker, and per batch frame by the persistent
    ``repro.runtime.transport`` worker loop — the latter passes ``cache``
    (a dict it owns and closes on shutdown) so sessions persist across
    batches.  Accepts v1 (whole-run) and v2 (job-batch) payloads; a v2
    payload's ``job`` entry selects the batch key exactly as the local
    scheduler would."""
    import jax

    version = int(payload.get("version", 1))
    if version not in (1, 2):
        raise ValueError(f"unknown payload version {version}")
    if payload.get("enable_x64"):
        jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from repro.api.service import batch_key
    from repro.api.session import SamplingSession
    from repro.data.gamma_store import GammaStore

    config = config_from_dict(payload["config"])
    key = jax.random.wrap_key_data(
        jnp.asarray(payload["key_data"], dtype=jnp.uint32))
    job = payload.get("job")
    if job is not None:
        key = batch_key(key, int(job["batch_id"]), int(job["n_batches"]))
    if cache is None:
        with GammaStore(
                payload["store_root"],
                storage_dtype=_dtype_from_name(payload["storage_dtype"]),
                compute_dtype=_dtype_from_name(payload["compute_dtype"])
                ) as store:
            with SamplingSession(store, config) as session:
                return session.sample(payload["n_samples"], key)
    tok = payload_cell(payload)
    entry = cache.get(tok)
    if entry is None:
        store = GammaStore(
            payload["store_root"],
            storage_dtype=_dtype_from_name(payload["storage_dtype"]),
            compute_dtype=_dtype_from_name(payload["compute_dtype"]))
        entry = cache[tok] = _CachedSession(store,
                                            SamplingSession(store, config))
    return entry.session.sample(payload["n_samples"], key)


@register_runtime("remote")
class RemoteRuntime(ClusterRuntime):
    """Dispatch payloads to worker interpreters on this machine.

    ``persistent=True`` (the default): one long-lived worker process
    (``repro.runtime.transport``) is spawned on first :meth:`submit`, kept
    alive across submits — its jit cache and worker-side sessions stay
    warm, so batch k pays dispatch + compute, not interpreter + jax import
    + recompile — and reaped by :meth:`close` (sessions close runtimes
    they resolved themselves).  A worker that died is respawned
    transparently on the next submit; the failed submit raises
    ``transport.TransportError`` so callers requeue the (idempotent)
    batch.

    ``persistent=False`` is PR 5's behaviour — one fresh
    ``python -m repro.api.remote`` per submit — kept as the measurable
    baseline for ``benchmarks/bench_fleet.py``.

    Either way the worker is a child interpreter, which a process holding
    a TPU refuses to start (``transport.refuse_spawn_on_chip``).  The
    subprocess boundary enforces that only the serialized
    payload crosses, exactly what an RPC transport to another machine
    would guarantee.  Point :attr:`python` / :attr:`env` at a container or
    remote-exec shim to move the worker off-host; neither the payload
    schema nor the frame protocol changes.
    """
    name = "remote"

    def __init__(self, python: Optional[str] = None,
                 env: Optional[dict] = None, timeout: float = 600.0,
                 persistent: bool = True):
        self.python = python or sys.executable
        self.env = env
        self.timeout = timeout
        self.persistent = persistent
        self._worker = None
        self._dispatch_bytes = 0
        self._dispatches = 0

    def io_counters(self) -> dict:
        out = super().io_counters()
        out.update(dispatch_bytes=self._dispatch_bytes,
                   dispatches=self._dispatches,
                   persistent_worker=bool(self._worker is not None
                                          and self._worker.alive))
        return out

    def submit(self, payload: dict) -> np.ndarray:
        blob = json.dumps(payload).encode()
        self._dispatch_bytes += len(blob)
        self._dispatches += 1
        if not self.persistent:
            return self._submit_oneshot(blob)
        from repro.runtime.transport import WorkerProcess
        if self._worker is None or not self._worker.alive:
            self._worker = WorkerProcess("remote-0", python=self.python,
                                         env=self.env, timeout=self.timeout)
        return self._worker.call(payload)

    def close(self) -> None:
        if self._worker is not None:
            self._worker.close()
            self._worker = None

    def _submit_oneshot(self, blob: bytes) -> np.ndarray:
        """The PR 5 baseline: a fresh interpreter per batch, serially."""
        from repro.runtime.transport import refuse_spawn_on_chip
        refuse_spawn_on_chip("RemoteRuntime")
        env = dict(os.environ if self.env is None else self.env)
        src_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = src_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        with tempfile.TemporaryDirectory(prefix="fastmps_remote_") as tmp:
            payload_path = os.path.join(tmp, "payload.json")
            out_path = os.path.join(tmp, "samples.npy")
            with open(payload_path, "wb") as f:
                f.write(blob)
            proc = subprocess.run(
                [self.python, "-m", "repro.api.remote", payload_path,
                 out_path],
                env=env, capture_output=True, text=True,
                timeout=self.timeout)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"remote worker failed (rc={proc.returncode}):\n"
                    f"{proc.stderr[-2000:]}")
            return np.load(out_path)


def _worker_main(argv: list[str]) -> int:
    payload_path, out_path = argv
    with open(payload_path) as f:
        payload = json.load(f)
    np.save(out_path, execute_payload(payload))
    return 0


if __name__ == "__main__":
    sys.exit(_worker_main(sys.argv[1:]))
