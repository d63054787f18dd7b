"""Read replicas: local posterior windows answering queries.

A :class:`ReplicaEnsemble` is the read-side half of a fleet shard: it holds
a delta-streamed copy of its writer's rolling window and serves posterior
functionals from that copy through the same
:class:`repro.serving.resident.SnapshotEvaluator` the writer uses — no
forked query path, so a replica's answers are bit-for-bit what the writer
would serve from the same version (regression-tested).

:class:`ReplicaProcess` hosts one ReplicaEnsemble in its own OS process —
the fleet's "process group" transport. Deltas and query batches travel
over a pipe (pickled; :func:`repro.fleet.delta.wire_bytes` is literally
what crosses), and because each replica process owns a private Python
interpreter and XLA client, replicas serve genuinely in parallel on
multi-core hosts — the replica-scaling axis ``benchmarks/fleet_bench.py``
measures. The worker rebuilds its workload's query specs from the serving
registry by name (specs hold closures, which don't pickle across a spawn).
"""
from __future__ import annotations

import multiprocessing as mp
import pickle
import threading
import time
from typing import Any

import jax
import numpy as np

from ..obs.trace import new_span_id, span_close, span_open
from ..serving.resident import QuerySpec, Snapshot, SnapshotEvaluator
from .delta import SnapshotDelta, apply_delta, wire_bytes

Params = Any


class ReplicaDeadError(ConnectionError):
    """The replica itself (not the request) failed: its process died, its
    pipe broke, or it was killed. Callers treat this differently from a
    request-level error — the fleet sync loop skips the replica and keeps
    broadcasting, and the router marks the lane dead and reroutes the batch
    to the surviving lanes instead of failing it."""


class ReplicaEnsemble:
    """An in-process read replica: local window copy + shared evaluator.

    Thread-safe like the resident: ``apply_delta`` replaces (never mutates)
    the window under a lock; snapshots are immutable once taken.
    """

    def __init__(self, name: str, *, micro_batch: int = 64):
        self.name = name
        self.version = 0  # writer steps_done our window mirrors
        self._draws = None
        self._summary: dict = {}
        self._base_staleness = 0.0  # writer-side staleness at last sync
        self._last_update: float | None = None
        self._evaluator = SnapshotEvaluator(micro_batch)
        self._lock = threading.RLock()
        self._dead = False
        self.deltas_applied = 0
        self.full_syncs = 0
        self.bytes_received = 0

    def apply_delta(self, delta: SnapshotDelta, *, nbytes: int | None = None) -> int:
        """Fold a writer delta into the local window; returns the version.

        An incremental delta whose ``base_version`` doesn't match raises —
        the caller (the fleet sync loop) then re-emits a full resync.
        """
        with self._lock:
            if self._dead:
                raise ReplicaDeadError(f"replica {self.name!r} is down (killed)")
            if not delta.full and delta.draws is not None \
                    and delta.base_version != self.version:
                raise ValueError(
                    f"replica {self.name!r} at version {self.version} cannot "
                    f"apply incremental delta from base {delta.base_version}; "
                    "full resync required"
                )
            self._draws = apply_delta(self._draws, delta)
            self.version = delta.version
            self._summary = delta.summary
            self._base_staleness = delta.staleness_s
            self._last_update = time.monotonic()
            self.deltas_applied += 1
            self.full_syncs += int(delta.full)
            self.bytes_received += int(
                nbytes if nbytes is not None else wire_bytes(delta)
            )
            if delta.draws is not None:
                # The window changed under the same (steps_done, num_draws)
                # key only on resync-after-restore; invalidating is cheap
                # and always safe.
                self._evaluator.invalidate()
            return self.version

    def reset(self) -> None:
        """Forget the local copy (forces the next sync to be full)."""
        with self._lock:
            self._draws = None
            self.version = 0
            self._summary = {}
            self._base_staleness = 0.0
            self._last_update = None
            self._evaluator.invalidate()

    def snapshot(self) -> Snapshot:
        """The replica's local view. Staleness compounds the writer-side
        staleness at emission with the time since the delta arrived — a
        replica never under-reports how old its draws are."""
        with self._lock:
            now = time.monotonic()
            staleness = (
                float("inf") if self._last_update is None
                else self._base_staleness + (now - self._last_update)
            )
            num = 0
            if self._draws is not None:
                lead = jax.tree.leaves(self._draws)[0].shape
                num = int(lead[0] * lead[1])
            return Snapshot(
                draws=self._draws,
                num_draws=num,
                steps_done=self.version,
                staleness_s=staleness,
                summary=self._summary,
                created_at=now,
            )

    def query(
        self,
        spec: QuerySpec,
        xs,
        *,
        snapshot: Snapshot | None = None,
        span_sink: list | None = None,
    ) -> tuple[np.ndarray, Snapshot]:
        if self._dead:
            raise ReplicaDeadError(f"replica {self.name!r} is down (killed)")
        snap = snapshot if snapshot is not None else self.snapshot()
        if snap.draws is None:
            raise RuntimeError(
                f"replica {self.name!r} has no window yet; sync a delta first"
            )
        return self._evaluator.evaluate(spec, snap, xs, span_sink=span_sink), snap

    def serve(self, spec: QuerySpec, query_class: str, xs, trace=None):
        """The router-facing entry: returns ``(values, staleness_s)``, or —
        when the router passes ``trace=(trace_id, parent_span_id)`` —
        ``(values, staleness_s, spans)`` with the replica's own
        ``replica_serve`` span and its ``device_eval`` child, already keyed
        to the caller's trace. ``query_class`` is unused in-process (the
        spec is passed directly); the process transport resolves it
        registry-side instead."""
        del query_class
        if trace is None:
            values, snap = self.query(spec, xs)
            return values, snap.staleness_s
        values, snap, spans = _traced_query(self, spec, xs, trace)
        return values, snap.staleness_s, spans

    def window(self, known_version: int = -1) -> tuple[int, Snapshot | None]:
        """The replica's current window for combine-at-query: returns
        ``(version, snapshot)``, or ``(version, None)`` when the caller
        already holds ``known_version`` — the router's per-lane window
        cache then skips re-fetching an unchanged window (which, for the
        process transport, is a full-window pickle)."""
        with self._lock:
            if self._dead:
                raise ReplicaDeadError(f"replica {self.name!r} is down (killed)")
            if self.version == known_version and self._draws is not None:
                return self.version, None
            return self.version, self.snapshot()

    def stats(self) -> dict:
        with self._lock:
            return {
                "name": self.name,
                "version": self.version,
                "alive": not self._dead,
                "deltas_applied": self.deltas_applied,
                "full_syncs": self.full_syncs,
                "bytes_received": self.bytes_received,
            }

    # -- chaos / fault-injection surface (parity with ReplicaProcess) ------

    @property
    def alive(self) -> bool:
        return not self._dead

    def ping(self) -> bool:
        return not self._dead

    def kill(self) -> None:
        """Simulated crash for the in-process transport: every subsequent
        ``apply_delta``/``query`` raises :class:`ReplicaDeadError` until
        :meth:`restart` — what lets the chaos tests exercise the router's
        failover deterministically without spawning processes."""
        with self._lock:
            self._dead = True

    def restart(self) -> None:
        """Come back empty (a restarted replica has no window; the next
        sync is a full resync)."""
        with self._lock:
            self._dead = False
        self.reset()

    def close(self) -> None:  # interface parity with ReplicaProcess
        pass


def _traced_query(replica: ReplicaEnsemble, spec: QuerySpec, xs, trace):
    """Run a replica query under a ``replica_serve`` span with its
    ``device_eval`` child, both keyed to ``trace = (trace_id,
    parent_span_id)``. Returns ``(values, snap, spans)`` — closed, fully
    linked span dicts ready to :meth:`Tracer.emit` (for the process
    transport they pickle back over the pipe first)."""
    trace_id, parent_id = trace
    serve_span = span_open(trace_id, f"replica_serve:{replica.name}",
                           "replica_serve", parent_id=parent_id,
                           replica=replica.name)
    sink: list = []
    values, snap = replica.query(spec, xs, span_sink=sink)
    span_close(serve_span, version=replica.version)
    spans = [serve_span]
    for raw in sink:
        raw = dict(raw)
        raw["trace_id"] = trace_id
        if raw.get("span_id") is None:
            raw["span_id"] = new_span_id()
        if raw.get("parent_id") is None:  # children keep their parent
            raw["parent_id"] = serve_span["span_id"]
        spans.append(raw)
    return values, snap, spans


# ---------------------------------------------------------------------------
# Process-group transport
# ---------------------------------------------------------------------------


def _replica_worker(conn, name: str, workload_name: str, build_kw: dict,
                    micro_batch: int, threads: int | None) -> None:
    """Replica process main loop: build the workload's query specs from the
    registry, then answer pickled (cmd, ...) frames until ``stop``."""
    import os

    if threads:
        # Cap this replica's XLA intra-op pool BEFORE the backend
        # initializes (module import is fine; the first op is not). One
        # compute thread per replica is what makes N replicas scale on an
        # M-core host instead of thrashing one shared pool.
        flags = os.environ.get("XLA_FLAGS", "")
        if "intra_op_parallelism_threads" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_cpu_multi_thread_eigen=false "
                f"intra_op_parallelism_threads={threads}"
            ).strip()
    from ..serving.workloads import build_serving_workload

    try:
        workload = build_serving_workload(workload_name, **build_kw)
        replica = ReplicaEnsemble(name, micro_batch=micro_batch)
        conn.send_bytes(pickle.dumps(("ready", name)))
    except Exception as e:  # noqa: BLE001 — report the failure, then exit
        conn.send_bytes(pickle.dumps(("err", f"{type(e).__name__}: {e}")))
        return
    while True:
        try:
            msg = pickle.loads(conn.recv_bytes())
        except EOFError:
            return
        cmd = msg[0]
        if cmd == "stop":
            conn.send_bytes(pickle.dumps(("ok",)))
            return
        try:
            if cmd == "delta":
                version = replica.apply_delta(msg[1], nbytes=msg[2])
                out = ("ok", version)
            elif cmd == "query":
                # 3-tuple = untraced (the wire format predating tracing);
                # a 4th element carries (trace_id, parent_span_id) and asks
                # for this replica's spans back in a 5-tuple reply.
                _, query_class, xs, *rest = msg
                trace = rest[0] if rest else None
                spec = workload.query_specs[query_class]
                if trace is None:
                    values, snap = replica.query(spec, xs)
                    out = ("ok", values, snap.staleness_s, replica.version)
                else:
                    values, snap, spans = _traced_query(replica, spec, xs, trace)
                    out = ("ok", values, snap.staleness_s, replica.version, spans)
            elif cmd == "window":
                version, snap = replica.window(msg[1])
                out = ("ok", version, snap)
            elif cmd == "reset":
                replica.reset()
                out = ("ok", replica.version)
            elif cmd == "stats":
                out = ("ok", replica.stats())
            elif cmd == "ping":
                out = ("ok",)
            else:
                out = ("err", f"unknown command {cmd!r}")
        except Exception as e:  # noqa: BLE001 — fail the request, not the loop
            out = ("err", f"{type(e).__name__}: {e}")
        conn.send_bytes(pickle.dumps(out))


class ReplicaProcess:
    """A read replica hosted in its own OS process.

    Same duck-typed surface as :class:`ReplicaEnsemble` (``apply_delta`` /
    ``serve`` / ``stats`` / ``version``), but every call is an RPC over a
    spawn-context pipe, and ``bytes_sent`` counts the real serialized
    payload. One RPC runs at a time per replica (the pipe is the queue);
    parallelism comes from running several replicas.

    Spawn-context caveat: scripts that create ReplicaProcess (directly or
    via ``FleetConfig(transport="proc")``) must do so under an
    ``if __name__ == "__main__":`` guard — the standard multiprocessing
    requirement, since the child re-imports the main module.
    """

    def __init__(
        self,
        name: str,
        workload_name: str,
        build_kw: dict | None = None,
        *,
        micro_batch: int = 64,
        threads: int | None = 1,
        start_timeout_s: float = 120.0,
    ):
        self.name = name
        self.version = 0
        self.bytes_sent = 0
        # Re-entrant: restart() holds it across close() + _spawn() (close
        # acquires it again for the stop handshake) so no concurrent _rpc
        # can interleave with the fresh pipe's "ready" handshake.
        self._lock = threading.RLock()
        self._workload_name = workload_name
        self._build_kw = dict(build_kw or {})
        self._micro_batch = micro_batch
        self._threads = threads
        self._start_timeout_s = start_timeout_s
        self._proc = None
        self._conn = None
        self._spawn()

    def _spawn(self) -> None:
        if jax.default_backend() == "tpu":
            # The parent holds the chip; a spawned child cannot load the TPU
            # runtime, and JAX would then serve it from the CPU unannounced.
            raise RuntimeError(
                f"replica process {self.name!r} refused: this process holds "
                "the TPU, so a spawned replica would silently run on the CPU. "
                "Use in-process replicas (FleetConfig(transport='inproc'))."
            )
        ctx = mp.get_context("spawn")
        self._conn, child = ctx.Pipe()
        self._proc = ctx.Process(
            target=_replica_worker,
            args=(child, self.name, self._workload_name, dict(self._build_kw),
                  self._micro_batch, self._threads),
            name=f"replica-{self.name}",
            daemon=True,
        )
        self._proc.start()
        child.close()
        if not self._conn.poll(self._start_timeout_s):
            self.close()
            raise TimeoutError(f"replica process {self.name!r} did not start")
        first = pickle.loads(self._conn.recv_bytes())
        if first[0] != "ready":
            self.close()
            raise RuntimeError(f"replica process {self.name!r} failed: {first[1]}")

    def _rpc(self, *msg):
        payload = pickle.dumps(msg, protocol=pickle.HIGHEST_PROTOCOL)
        try:
            with self._lock:
                if self._proc is None or not self._proc.is_alive():
                    raise ReplicaDeadError(
                        f"replica {self.name!r} process is down"
                    )
                self.bytes_sent += len(payload)
                self._conn.send_bytes(payload)
                out = pickle.loads(self._conn.recv_bytes())
        except ReplicaDeadError:
            raise
        except (EOFError, BrokenPipeError, ConnectionResetError, OSError) as e:
            # The transport (not the request) failed — a killed process
            # shows up as EOF on the pipe. Distinct from the worker's
            # ("err", ...) replies, which stay RuntimeError below.
            raise ReplicaDeadError(
                f"replica {self.name!r} transport failed: "
                f"{type(e).__name__}: {e}"
            ) from e
        if out[0] == "err":
            raise RuntimeError(f"replica {self.name!r}: {out[1]}")
        return out

    def apply_delta(self, delta: SnapshotDelta, *, nbytes: int | None = None) -> int:
        nb = nbytes if nbytes is not None else wire_bytes(delta)
        out = self._rpc("delta", delta, nb)
        self.version = out[1]
        return self.version

    def reset(self) -> None:
        out = self._rpc("reset")
        self.version = out[1]

    def serve(self, spec, query_class: str, xs, trace=None):
        """Same contract as :meth:`ReplicaEnsemble.serve`: 2-tuple
        ``(values, staleness_s)``, or a 3-tuple with the worker's spans
        when ``trace`` is passed (the spans are built in the worker
        process — their ``pid`` is the replica's — and ride back inside
        the query reply)."""
        del spec  # resolved registry-side in the worker
        if trace is None:
            out = self._rpc("query", query_class, np.asarray(xs))
            self.version = out[3]
            return out[1], out[2]
        out = self._rpc("query", query_class, np.asarray(xs), tuple(trace))
        self.version = out[3]
        return out[1], out[2], out[4]

    def window(self, known_version: int = -1) -> tuple[int, Snapshot | None]:
        """RPC counterpart of :meth:`ReplicaEnsemble.window`: the snapshot
        crosses the pipe only when ``known_version`` is out of date (numpy
        windows pickle directly)."""
        out = self._rpc("window", known_version)
        self.version = out[1]
        return out[1], out[2]

    def stats(self) -> dict:
        stats = self._rpc("stats")[1]
        stats["bytes_sent"] = self.bytes_sent
        return stats

    # -- chaos / fault-injection surface ------------------------------------

    @property
    def alive(self) -> bool:
        return self._proc is not None and self._proc.is_alive()

    def ping(self) -> bool:
        """True when the worker process answers; False on a dead transport
        (never raises — this is the router's revive() probe)."""
        try:
            self._rpc("ping")
            return True
        except ReplicaDeadError:
            return False

    def kill(self, timeout_s: float = 10.0) -> None:
        """SIGKILL the worker process — the chaos harness's crash. No
        handshake, no cleanup: in-flight RPCs surface ReplicaDeadError."""
        proc = self._proc
        if proc is not None and proc.is_alive():
            proc.kill()
            proc.join(timeout=timeout_s)

    def restart(self) -> None:
        """Respawn the worker in place (fresh interpreter, empty window at
        version 0 — the next sync full-resyncs it). The surrounding lane /
        fleet objects keep their references valid across the bounce.

        Holds the RPC lock for the whole bounce: otherwise a concurrent
        ``_rpc`` (e.g. the fleet's background delta-sync thread) can grab
        the *new* pipe between ``_spawn`` assigning ``self._conn`` and the
        handshake read, consume the worker's ``("ready", ...)`` message,
        and leave its own reply for the handshake to misread. A caller
        blocked in ``_rpc`` on the old pipe fails fast (EOF on the killed
        process -> ReplicaDeadError) and releases the lock, so this cannot
        deadlock."""
        with self._lock:
            self.close(timeout_s=1.0)
            self.version = 0
            self._spawn()

    def close(self, timeout_s: float = 10.0) -> None:
        proc, conn = self._proc, self._conn
        if proc is None:
            return
        try:
            if proc.is_alive():
                try:
                    with self._lock:
                        conn.send_bytes(pickle.dumps(("stop",)))
                        if conn.poll(timeout_s):
                            conn.recv_bytes()
                except (BrokenPipeError, OSError):
                    pass
            proc.join(timeout=timeout_s)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=timeout_s)
        finally:
            conn.close()
            self._proc = None
