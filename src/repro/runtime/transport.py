"""Persistent worker-process RPC transport for fleet-scale dispatch.

PR 5's ``RemoteRuntime.submit`` shelled out one fresh interpreter per
macro batch, serially — every batch paid a full jax import and a cold jit
cache.  This module is the replacement: **worker processes stay alive**
and stream job-batch results back over a framed pipe protocol, so one
worker amortizes its startup and compilation across every batch it runs
(the FastMPS premise: a batch is an independent, restart-exact unit, so a
fleet of long-lived workers can claim batches in any order).

Layers, bottom up:

* **frames** — length-prefixed messages on a byte stream: an 8-byte
  big-endian length plus a 4-byte CRC32 of the body, then the body (a
  corrupt frame is rejected at decode as a lane fault, never parsed into
  garbage).  A request is one JSON frame; a
  response is a JSON header frame (``{"kind": "result" | "error", ...}``)
  followed, for results, by one raw ``.npy`` frame.  Deliberately dumb:
  any queue/RPC system (gRPC, ZMQ, a Redis list) can carry the same
  payloads — the schema is ``repro.api.remote``'s v2 job batch, unchanged.
* :class:`WorkerProcess` — one spawned ``python -m repro.runtime.transport``
  child, driven synchronously: ``call(payload)`` writes the request and
  blocks (with a deadline) for the streamed-back result.  The worker loop
  on the far side caches :class:`~repro.api.session.SamplingSession`
  objects per (store, config) cell, so repeated batches of one job hit a
  warm engine and jit cache — the whole point of staying alive.
* :class:`WorkerPool` — named workers spawned/reaped on demand (the
  elastic-lane membership operations), with **chaos injectors**: test
  hooks observing/perturbing every dispatch and result (delay a batch,
  drop a result, deliver a payload twice, kill a worker mid-call) so the
  fault-tolerance claims are *exercised*, not assumed
  (``tests/chaos.py``).

Failure model: any transport fault — worker death, dropped result,
deadline overrun — raises :class:`TransportError`.  Callers (the service's
fleet lanes) treat it as a lane fault, NOT a job fault: the batch requeues
on the :class:`~repro.runtime.elastic.WorkQueue` and the worker respawns;
because batch = f(seed, id), the recomputation is bit-identical.
"""
from __future__ import annotations

import io
import json
import os
import random
import select
import struct
import subprocess
import sys
import tempfile
import time
from typing import Optional

import numpy as np

import zlib

#: frame header: 8-byte big-endian body length + 4-byte CRC32 of the body.
#: The checksum means a corrupt frame is rejected at decode (a
#: :class:`TransportError` — lane fault, batch requeues) instead of parsed
#: into garbage a worker would faithfully compute on.
_HDR = struct.Struct(">QI")
_LEN = struct.Struct(">Q")     # legacy alias: header length parsing in tests
SHUTDOWN = {"kind": "shutdown"}


class TransportError(RuntimeError):
    """A transport-level fault (worker death, drop, deadline, corrupt
    frame).  The batch is NOT lost — callers requeue it and recompute
    bit-identically."""


class WorkerDied(TransportError):
    """The worker process exited (or was killed) mid-conversation."""


# ---------------------------------------------------------------------------
# frames
# ---------------------------------------------------------------------------

def write_frame(stream, body: bytes) -> None:
    stream.write(_HDR.pack(len(body), zlib.crc32(body)))
    stream.write(body)
    stream.flush()


def read_frame(stream) -> bytes:
    """Blocking read of one frame; raises :class:`WorkerDied` on EOF and
    :class:`TransportError` on a checksum mismatch."""
    head = stream.read(_HDR.size)
    if len(head) != _HDR.size:
        raise WorkerDied("stream closed mid-frame")
    n, crc = _HDR.unpack(head)
    body = b""
    while len(body) < n:
        chunk = stream.read(n - len(body))
        if not chunk:
            raise WorkerDied("stream closed mid-frame")
        body += chunk
    if zlib.crc32(body) != crc:
        raise TransportError(
            f"frame checksum mismatch ({zlib.crc32(body):#010x} != "
            f"{crc:#010x}) — corrupt frame rejected at decode")
    return body


def write_json(stream, obj: dict) -> None:
    write_frame(stream, json.dumps(obj).encode())


def read_json(stream) -> dict:
    return json.loads(read_frame(stream).decode())


def array_to_frame(arr: np.ndarray) -> bytes:
    bio = io.BytesIO()
    np.save(bio, np.asarray(arr), allow_pickle=False)
    return bio.getvalue()


def array_from_frame(body: bytes) -> np.ndarray:
    return np.load(io.BytesIO(body), allow_pickle=False)


# ---------------------------------------------------------------------------
# the client side: one persistent worker
# ---------------------------------------------------------------------------

def refuse_spawn_on_chip(what: str) -> None:
    """One process per chip: raise instead of starting a child interpreter
    when this process already holds a TPU.  A chip belongs to one process
    at a time, so a child that needs it would fail on libtpu's lock or
    hang waiting for it.  A parent that never initialized JAX holds
    nothing and may spawn freely."""
    if "jax" not in sys.modules:
        return
    import jax
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what}: this process holds the TPU, and a chip belongs to one "
            f"process at a time — a child interpreter would fail or hang "
            f"waiting for it.  Sample in this process (runtime='local', no "
            f"service fleet), or start the workers from a parent that "
            f"never initializes JAX.")


def _src_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


class WorkerProcess:
    """One long-lived ``python -m repro.runtime.transport`` child.

    Synchronous request/response: one in-flight call at a time (a service
    lane drives exactly one worker, so this is the natural shape; a real
    RPC stack would multiplex).  ``call`` enforces ``timeout`` with a
    select() deadline on the response pipe and kills the worker on
    overrun — a hung worker must not wedge its lane.
    """

    def __init__(self, name: str, python: Optional[str] = None,
                 env: Optional[dict] = None, timeout: float = 600.0):
        refuse_spawn_on_chip(f"worker {name!r}")
        self.name = name
        self.timeout = timeout
        self.batches = 0                  # results streamed back
        self.dispatch_bytes = 0
        env = dict(os.environ if env is None else env)
        env["PYTHONPATH"] = _src_root() + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        # stderr goes to a file, never a pipe: a chatty worker (jax
        # warnings, tracebacks) must not fill a 64K pipe buffer and wedge
        # itself mid-batch; the tail is read back on fault for diagnostics
        fd, self._stderr_path = tempfile.mkstemp(
            prefix=f"fastmps_worker_{name}_", suffix=".log")
        self._proc = subprocess.Popen(
            [python or sys.executable, "-m", "repro.runtime.transport"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=fd, env=env)
        os.close(fd)

    @property
    def pid(self) -> int:
        return self._proc.pid

    @property
    def alive(self) -> bool:
        return self._proc.poll() is None

    def _drain_stderr(self) -> str:
        try:
            with open(self._stderr_path, "rb") as f:
                return f.read()[-2000:].decode(errors="replace")
        except OSError:
            return ""

    def _read_frame_deadline(self, deadline: float) -> bytes:
        """``read_frame`` with a wall deadline enforced via select()."""
        fd = self._proc.stdout.fileno()
        buf = b""
        need = _HDR.size
        body_len = None
        body_crc = None
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                # the response stream is now desynced (a late frame would be
                # misread as the NEXT call's response) — the worker dies here
                pid = self.pid
                self.kill()
                raise TransportError(
                    f"worker {self.name!r} (pid {pid}) exceeded the "
                    f"{self.timeout}s deadline")
            ready, _, _ = select.select([fd], [], [], min(remaining, 1.0))
            if not ready:
                if not self.alive:
                    raise WorkerDied(
                        f"worker {self.name!r} died (rc="
                        f"{self._proc.returncode}):\n{self._drain_stderr()}")
                continue
            chunk = os.read(fd, need - len(buf))
            if not chunk:
                raise WorkerDied(
                    f"worker {self.name!r} closed its pipe (rc="
                    f"{self._proc.poll()}):\n{self._drain_stderr()}")
            buf += chunk
            if len(buf) == need:
                if body_len is None:
                    body_len, body_crc = _HDR.unpack(buf)
                    buf, need = b"", body_len
                    if body_len == 0:
                        body = b""
                    else:
                        continue
                else:
                    body = buf
                if zlib.crc32(body) != body_crc:
                    raise TransportError(
                        f"worker {self.name!r} sent a corrupt frame "
                        f"(crc {zlib.crc32(body):#010x} != "
                        f"{body_crc:#010x}) — rejected at decode")
                return body

    def call(self, payload: dict) -> np.ndarray:
        """Dispatch one job-batch payload; block for its streamed result."""
        if not self.alive:
            raise WorkerDied(f"worker {self.name!r} is not running (rc="
                             f"{self._proc.returncode})")
        blob = json.dumps({"kind": "batch", "payload": payload}).encode()
        self.dispatch_bytes += len(blob)
        try:
            write_frame(self._proc.stdin, blob)
        except (BrokenPipeError, OSError) as e:
            raise WorkerDied(f"worker {self.name!r} pipe broke on dispatch: "
                             f"{e}\n{self._drain_stderr()}") from None
        deadline = time.monotonic() + self.timeout
        head = json.loads(self._read_frame_deadline(deadline).decode())
        if head.get("kind") == "error":
            # the *payload* failed on a healthy worker: a job error, not a
            # transport fault — re-raise as the job-visible exception type
            raise RuntimeError(
                f"worker {self.name!r} batch failed: {head.get('error')}")
        if head.get("kind") != "result":
            raise TransportError(f"worker {self.name!r} sent unknown frame "
                                 f"{head.get('kind')!r}")
        out = array_from_frame(self._read_frame_deadline(deadline))
        self.batches += 1
        return out

    def kill(self) -> None:
        """Hard-kill (chaos / deadline path) — no shutdown handshake."""
        if self.alive:
            self._proc.kill()
        self._close_pipes()
        self._proc.wait(timeout=30)

    def close(self, timeout: float = 30.0) -> None:
        """Graceful shutdown: handshake, then wait; kill on overrun."""
        if self.alive:
            try:
                write_json(self._proc.stdin, SHUTDOWN)
                self._proc.stdin.close()
                self._proc.wait(timeout=timeout)
            except (BrokenPipeError, OSError, subprocess.TimeoutExpired):
                self._proc.kill()
                self._proc.wait(timeout=30)
        self._close_pipes()

    def _close_pipes(self) -> None:
        for pipe in (self._proc.stdin, self._proc.stdout):
            try:
                if pipe is not None:
                    pipe.close()
            except OSError:
                pass
        try:
            os.unlink(self._stderr_path)
        except OSError:
            pass


# ---------------------------------------------------------------------------
# the pool: elastic membership + chaos injection points
# ---------------------------------------------------------------------------

class LaneHealth:
    """Per-lane fault accounting: exponential respawn backoff with jitter,
    and a sliding fault window that turns a crash-looping lane into a
    :class:`~repro.runtime.faults.CrashLoopLane` instead of a hot respawn.

    Shared by :class:`WorkerPool` and any in-process pool stand-in (the
    fault-injection tests), so the quarantine policy is one implementation
    everywhere."""

    def __init__(self, backoff_base: float = 0.05, backoff_max: float = 2.0,
                 fault_window_s: float = 30.0,
                 max_faults_per_window: int = 5):
        self.backoff_base = backoff_base
        self.backoff_max = backoff_max
        self.fault_window_s = fault_window_s
        self.max_faults_per_window = max_faults_per_window
        self._faults: dict[str, list[float]] = {}
        self._streak: dict[str, int] = {}     # consecutive respawns per lane
        self.backoff_seconds = 0.0            # total backoff slept (telemetry)

    def record_fault(self, name: str, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        self._faults.setdefault(name, []).append(now)

    def record_success(self, name: str) -> None:
        self._streak.pop(name, None)

    def forgive(self, name: str) -> None:
        """Clear a lane's fault window and streak — called when the lane is
        quarantined (the cooldown IS the penalty; readmit starts clean)."""
        self._faults.pop(name, None)
        self._streak.pop(name, None)

    def window_faults(self, name: str, now: Optional[float] = None) -> int:
        now = time.monotonic() if now is None else now
        kept = [t for t in self._faults.get(name, ())
                if now - t <= self.fault_window_s]
        if kept:
            self._faults[name] = kept
        else:
            self._faults.pop(name, None)
        return len(kept)

    def check_respawn(self, name: str, now: Optional[float] = None) -> float:
        """Gate one respawn of ``name``: raises
        :class:`~repro.runtime.faults.CrashLoopLane` when the lane's fault
        window is exhausted, else returns the backoff delay (exponential
        in the consecutive-respawn streak, ±50% jitter) the caller should
        sleep before spawning."""
        from repro.runtime.faults import CrashLoopLane, Fault
        n_window = self.window_faults(name, now)
        if n_window >= self.max_faults_per_window:
            raise CrashLoopLane(Fault(
                kind="transport", lane=name,
                message=f"lane {name!r} crash-looping: {n_window} faults "
                        f"inside {self.fault_window_s}s — quarantine it "
                        f"(cooldown readmit) instead of respawning hot"))
        streak = self._streak.get(name, 0)
        self._streak[name] = streak + 1
        if streak == 0:
            return 0.0
        delay = min(self.backoff_base * (2 ** (streak - 1)), self.backoff_max)
        delay *= 0.5 + random.random()        # jitter: ±50%, decorrelates
        self.backoff_seconds += delay
        return delay

    def stats(self) -> dict:
        now = time.monotonic()
        return {"lane_window_faults": {n: self.window_faults(n, now)
                                       for n in sorted(self._faults)},
                "backoff_seconds": self.backoff_seconds}


class WorkerPool:
    """Named persistent workers, spawned/reaped on demand (never from a
    process that holds a TPU — see :func:`refuse_spawn_on_chip`).

    The service's fleet lanes map 1:1 onto pool workers: ``add_worker`` →
    :meth:`spawn`, ``remove_worker`` → :meth:`reap`, one ``call`` per
    claimed batch.  ``injectors`` is the chaos seam: every entry may
    implement ``before(worker, payload) -> None | "drop" | "duplicate"``
    and/or ``after(worker, payload, result) -> None | "drop"`` — sleeps
    inside model delay, ``"drop"`` raises :class:`TransportError` (before:
    without executing; after: discarding a computed result), and
    ``"duplicate"`` delivers the payload twice (the worker executes both;
    results must agree bit-for-bit — idempotence, checked here).
    """

    def __init__(self, python: Optional[str] = None,
                 env: Optional[dict] = None, timeout: float = 600.0,
                 observer=None, health: Optional[LaneHealth] = None):
        self.python = python
        self.env = env
        self.timeout = timeout
        self.workers: dict[str, WorkerProcess] = {}
        self.injectors: list = []
        self.spawned = 0
        self.reaped = 0
        self.faults = 0               # TransportErrors surfaced to callers
        self.health = LaneHealth() if health is None else health
        # telemetry seam (repro.obs.metrics): optional callable invoked as
        # observer(event, ...) for transport_{spawn,reap,fault,dispatch,
        # result}; errors swallowed — telemetry never perturbs dispatch
        self.observer = observer

    def _emit(self, event: str, **fields) -> None:
        if self.observer is not None:
            try:
                self.observer(event, **fields)
            except Exception:          # noqa: BLE001 — see __init__
                pass

    def spawn(self, name: str) -> WorkerProcess:
        if name in self.workers and self.workers[name].alive:
            raise ValueError(f"worker {name!r} already running")
        w = WorkerProcess(name, python=self.python, env=self.env,
                          timeout=self.timeout)
        self.workers[name] = w
        self.spawned += 1
        self._emit("transport_spawn", worker=name)
        return w

    def reap(self, name: str, kill: bool = False) -> None:
        w = self.workers.pop(name, None)
        if w is None:
            return
        (w.kill if kill else w.close)()
        self.reaped += 1
        self._emit("transport_reap", worker=name)

    def respawn(self, name: str) -> WorkerProcess:
        """Replace a dead/hung worker under its stable lane name.

        Gated by :class:`LaneHealth`: consecutive respawns back off
        exponentially (with jitter) so a flapping lane doesn't hot-loop
        fork(), and a lane whose fault window is exhausted raises
        :class:`~repro.runtime.faults.CrashLoopLane` — the caller
        quarantines it (cooldown readmit) instead of respawning."""
        delay = self.health.check_respawn(name)   # may raise CrashLoopLane
        if delay > 0:
            time.sleep(delay)
        self.reap(name, kill=True)
        return self.spawn(name)

    def call(self, name: str, payload: dict) -> np.ndarray:
        w = self.workers.get(name)
        if w is None:
            raise WorkerDied(f"no worker {name!r} in the pool")
        try:
            actions = [inj.before(name, payload) for inj in self.injectors
                       if hasattr(inj, "before")]
            if "drop" in actions:
                raise TransportError(
                    f"payload to {name!r} dropped by injector")
            self._emit("transport_dispatch", worker=name,
                       nbytes=len(json.dumps(payload)))
            out = w.call(payload)
            if "duplicate" in actions:          # delivered twice: idempotent?
                again = w.call(payload)
                if not np.array_equal(out, again):
                    raise TransportError(
                        f"worker {name!r} is not idempotent: duplicate "
                        f"delivery produced different bits")
            for inj in self.injectors:
                if hasattr(inj, "after"):
                    if inj.after(name, payload, out) == "drop":
                        raise TransportError(
                            f"result from {name!r} dropped by injector")
            self._emit("transport_result", worker=name, nbytes=out.nbytes)
            self.health.record_success(name)
            return out
        except TransportError:
            self.faults += 1
            self.health.record_fault(name)
            self._emit("transport_fault", worker=name)
            raise

    def stats(self) -> dict:
        out = {"workers": len(self.workers),
               "spawned": self.spawned, "reaped": self.reaped,
               "faults": self.faults,
               "batches": {n: w.batches for n, w in self.workers.items()},
               "dispatch_bytes": sum(w.dispatch_bytes
                                     for w in self.workers.values())}
        out.update(self.health.stats())
        return out

    def close(self) -> None:
        for name in list(self.workers):
            self.reap(name)

    def __enter__(self) -> "WorkerPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ---------------------------------------------------------------------------
# the worker side (``python -m repro.runtime.transport``)
# ---------------------------------------------------------------------------

def serve(stdin, stdout) -> int:
    """The worker loop: frames in, results out, until shutdown/EOF.

    Sessions are cached per (store, config) cell across batches — the
    second batch of a job reuses the first's engine, prefetch pool, and
    jit cache, which is exactly what subprocess-per-batch could never do.
    """
    from repro.api.remote import execute_payload

    cache: dict = {}
    try:
        while True:
            try:
                msg = read_json(stdin)
            except WorkerDied:            # parent went away: clean exit
                return 0
            kind = msg.get("kind")
            if kind == "shutdown":
                return 0
            if kind != "batch":
                write_json(stdout, {"kind": "error",
                                    "error": f"unknown frame {kind!r}"})
                continue
            try:
                out = execute_payload(msg["payload"], cache=cache)
            except BaseException as e:    # noqa: BLE001 — shipped to caller
                write_json(stdout, {"kind": "error",
                                    "error": f"{type(e).__name__}: {e}"})
                continue
            write_json(stdout, {"kind": "result"})
            write_frame(stdout, array_to_frame(out))
    finally:
        for sess in cache.values():
            try:
                sess.close()
            except Exception:             # noqa: BLE001 — shutdown path
                pass


def _main() -> int:
    # claim the protocol stream BEFORE anything else can print: the real
    # stdout becomes ours exclusively, and fd 1 (plus sys.stdout writes
    # from imported libraries) is re-pointed at stderr so stray prints can
    # never corrupt a frame
    protocol_out = os.fdopen(os.dup(sys.stdout.fileno()), "wb")
    os.dup2(sys.stderr.fileno(), sys.stdout.fileno())
    return serve(sys.stdin.buffer, protocol_out)


if __name__ == "__main__":
    sys.exit(_main())
