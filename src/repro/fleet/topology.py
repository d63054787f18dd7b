"""Fleet topology: workload shards, writers, replica pools, and sync.

The sharded serving fleet splits the two halves of posterior serving that
PR 4's single pool fused (the parallel-transition vs replicated-serving
split of Angelino et al., *Patterns of Scalable Bayesian Inference*):

    Fleet
      └─ shard "bayeslr@0"   writer ResidentEnsemble  (advances chains,
      │                       optionally on a 2-d chains x data mesh)
      │     ├─ replica #r0   ReplicaEnsemble | ReplicaProcess
      │     └─ replica #r1     (serve queries from a delta-streamed
      │                          copy of the writer's window)
      └─ shard "bayeslr@1"   ...

Each registered workload gets ``shards`` independent writers — same data,
independent chain keys (``fold_in(seed_key, shard)``), so the fleet's
aggregate posterior capacity scales with shard count — and each writer
broadcasts :mod:`repro.fleet.delta` snapshot deltas to ``replicas`` read
replicas. Writers live in one :class:`repro.serving.EnsemblePool`, so the
freshness policy, warm checkpointing, and background refresh of the
serving layer apply unchanged; replicas resync (a full-window delta) after
a restore and then ride incremental deltas again.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, NamedTuple

import jax

from ..partition.combine import METHODS as COMBINE_METHODS
from ..partition.partitioner import (
    partition_append_indices,
    partition_target,
    take_sections,
)
from ..serving.pool import EnsemblePool, ServingConfig
from ..serving.resident import QuerySpec, ResidentEnsemble
from ..serving.workloads import ServingWorkload, build_serving_workload
from .delta import make_delta, payload_nbytes, wire_bytes
from .replica import ReplicaDeadError, ReplicaEnsemble, ReplicaProcess


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Static fleet shape.

    ``replicas``: read replicas per shard; ``shards``: independent writers
    per workload; ``mesh``: forwarded to every writer's
    ``ChainEnsemble(shard=...)`` (e.g. ``("chains", "data")`` for the 2-d
    fan-out — a no-op on one device); ``transport``: ``"inproc"`` replicas
    share the process (deterministic, cheap — tests/smoke), ``"proc"``
    replicas each get an OS process (the scaling configuration);
    ``sync_interval_s``: pause between background refresh+broadcast rounds;
    ``subposterior``: data-parallel partition count P — each workload's
    observation pool is split into P disjoint stride shards, every writer
    runs against its local slice under the ``p(theta)^(1/P)`` tempered
    prior, and the router recombines the per-partition windows at query
    time with the ``combine`` rule (:mod:`repro.partition`). P=1 is
    bit-for-bit the unpartitioned fleet.
    """

    replicas: int = 2
    shards: int = 1
    serving: ServingConfig = ServingConfig()
    mesh: Any = "auto"
    transport: str = "inproc"  # "inproc" | "proc"
    sync_interval_s: float = 0.0
    # Per-replica XLA intra-op thread cap for the "proc" transport (None =
    # backend default). One thread per replica is what lets N replicas scale
    # across an M-core host instead of contending for one shared pool.
    replica_threads: int | None = 1
    subposterior: int = 1  # data partitions P per workload
    combine: str = "consensus"  # "consensus" | "product" draw combination

    def __post_init__(self):
        if self.replicas < 1 or self.shards < 1:
            raise ValueError("replicas and shards must be >= 1")
        if self.transport not in ("inproc", "proc"):
            raise ValueError(f"unknown transport {self.transport!r}")
        if self.subposterior < 1:
            raise ValueError(
                f"subposterior must be >= 1, got {self.subposterior}"
            )
        if self.combine not in COMBINE_METHODS:
            raise ValueError(
                f"unknown combine method {self.combine!r}; "
                f"known: {COMBINE_METHODS}"
            )


class FleetShard(NamedTuple):
    """One workload shard: a writer and its read replicas."""

    name: str  # "<workload>@<index>" or "<workload>@p<partition>@<index>"
    workload: str
    writer: ResidentEnsemble
    replicas: tuple
    partition: int = 0  # data partition this shard's writer samples


class Fleet:
    """Writers + replicas + delta streams behind one management surface."""

    def __init__(self, config: FleetConfig | None = None):
        self.config = config or FleetConfig()
        # Writers live in the pool, so ``pool.tracer`` (if set) times their
        # refresh blocks.
        self.pool = EnsemblePool(self.config.serving)
        self._workloads: dict[str, ServingWorkload] = {}
        self._shards: dict[str, list[FleetShard]] = {}
        self._partitions: dict[str, int] = {}  # workload -> P
        self._data_sizes: dict[str, int] = {}  # workload -> total sections
        # Replica construction inputs, kept for runtime scale-out: the
        # workload builder kwargs add_replica re-plays, and a per-shard
        # monotonic name counter so a retired replica's name is never
        # reused (lane/trace history stays unambiguous).
        self._build_kw: dict[str, dict] = {}
        self._replica_seq: dict[str, int] = {}
        self._sync_lock = threading.Lock()
        self.sync_stats = {
            "syncs": 0,
            "delta_wire_bytes": 0,
            "full_wire_bytes": 0,  # what full-snapshot streaming would cost
            "delta_payload_bytes": 0,
            "full_payload_bytes": 0,
            "full_deltas": 0,  # syncs that were full-window resyncs
            "skipped_dead": 0,  # replicas skipped because their transport was down
        }
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        # Last background refresh+broadcast error per shard (cleared on the
        # next success) — surfaced in report() so a dying replica shows up
        # instead of silently freezing the shard's delta stream.
        self._shard_errors: dict[str, str] = {}

    # -- registration ------------------------------------------------------

    def add_workload(self, name: str, **build_kw) -> list[FleetShard]:
        """Register ``shards`` writers + ``replicas`` replicas for a
        registry workload. ``build_kw`` reaches the workload builder
        (every shard gets the same data; chain keys differ per shard).

        With ``config.subposterior = P > 1`` the workload's observation pool
        is partitioned first and each of the P partitions gets its own
        ``shards`` writers (P × shards writers total), named
        ``"<workload>@p<partition>@<index>"``. The P=1 path is untouched —
        same shard names, same keys, same targets as an unpartitioned
        fleet.
        """
        if name in self._shards:
            raise ValueError(f"workload {name!r} already in this fleet")
        cfg = self.config
        scfg = cfg.serving
        build_kw.setdefault("num_chains", scfg.num_chains)
        build_kw.setdefault("seed", scfg.seed)
        base = build_serving_workload(name, **build_kw)
        self._workloads[name] = base
        self._build_kw[name] = dict(build_kw)
        if cfg.subposterior > 1:
            return self._add_partitioned(name, base, build_kw)
        shards: list[FleetShard] = []
        for i in range(cfg.shards):
            shard_name = f"{name}@{i}"  # "@": shard names double as checkpoint file stems
            ensemble = base.ensemble
            if cfg.mesh != "auto":
                ensemble = dataclasses.replace(ensemble, shard=cfg.mesh)
            shard_wl = dataclasses.replace(
                base, name=shard_name, ensemble=ensemble
            )
            writer = self.pool.add_workload(
                shard_wl, key=jax.random.fold_in(jax.random.key(scfg.seed), i)
            )
            replicas = tuple(
                self._make_replica(f"{shard_name}#r{j}", name, build_kw)
                for j in range(cfg.replicas)
            )
            self._replica_seq[shard_name] = cfg.replicas
            shards.append(FleetShard(shard_name, name, writer, replicas))
        self._shards[name] = shards
        self._partitions[name] = 1
        if base.ensemble.target is not None:
            self._data_sizes[name] = int(base.ensemble.target.num_sections)
        return shards

    def _add_partitioned(
        self, name: str, base: ServingWorkload, build_kw: dict
    ) -> list[FleetShard]:
        """The subposterior fan-out: P tempered slice targets, each with its
        own writer group. Raises for workloads whose target carries no
        :class:`~repro.core.target_builder.TargetSpec` recipe (composite /
        latent-variable transitions cannot be data-partitioned)."""
        cfg = self.config
        scfg = cfg.serving
        num_p = cfg.subposterior
        if base.ensemble.target is None:
            raise ValueError(
                f"workload {name!r} runs a composite transition with no "
                "single target; subposterior partitioning needs a "
                "builder-constructed target"
            )
        sub_targets = partition_target(base.ensemble.target, num_p)
        shards: list[FleetShard] = []
        for p in range(num_p):
            for i in range(cfg.shards):
                shard_name = f"{name}@p{p}@{i}"
                ensemble = dataclasses.replace(
                    base.ensemble, target=sub_targets[p]
                )
                if cfg.mesh != "auto":
                    ensemble = dataclasses.replace(ensemble, shard=cfg.mesh)
                shard_wl = dataclasses.replace(
                    base, name=shard_name, ensemble=ensemble
                )
                # Independent chain trajectories per (partition, shard):
                # fold the partition in first so partition p shard i never
                # collides with partition i shard p.
                key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.key(scfg.seed), p), i
                )
                writer = self.pool.add_workload(shard_wl, key=key)
                replicas = tuple(
                    self._make_replica(f"{shard_name}#r{j}", name, build_kw)
                    for j in range(cfg.replicas)
                )
                self._replica_seq[shard_name] = cfg.replicas
                shards.append(
                    FleetShard(shard_name, name, writer, replicas, p)
                )
        self._shards[name] = shards
        self._partitions[name] = num_p
        self._data_sizes[name] = int(base.ensemble.target.num_sections)
        return shards

    def _make_replica(self, replica_name: str, workload: str, build_kw: dict):
        if self.config.transport == "proc":
            return ReplicaProcess(
                replica_name, workload, build_kw,
                micro_batch=self.config.serving.micro_batch,
                threads=self.config.replica_threads,
            )
        return ReplicaEnsemble(
            replica_name, micro_batch=self.config.serving.micro_batch
        )

    # -- lookups -----------------------------------------------------------

    def workloads(self) -> tuple[str, ...]:
        return tuple(sorted(self._shards))

    def shards(self, workload: str) -> list[FleetShard]:
        return self._shards[workload]

    def workload(self, name: str) -> ServingWorkload:
        return self._workloads[name]

    def spec(self, workload: str, query_class: str) -> QuerySpec:
        return self._workloads[workload].query_specs[query_class]

    def num_partitions(self, workload: str) -> int:
        """Data partitions P the workload was registered with (1 when the
        fleet is unpartitioned)."""
        return self._partitions.get(workload, 1)

    def replica_count(self, workload: str) -> int:
        """Live replica total across the workload's shards."""
        return sum(len(s.replicas) for s in self._shards[workload])

    # -- runtime scaling ---------------------------------------------------

    def add_replica(self, workload: str, shard_index: int = 0):
        """Spawn one more read replica on a running shard (what the
        autoscaler actuates through).

        The replica is built exactly like its launch-time siblings (same
        transport, same builder kwargs, the shard's next never-reused
        ``#rN`` name), the shard entry is swapped for one whose ``replicas``
        tuple includes it, and one :meth:`sync_shard` round seeds it — a
        version-0 replica receives the full window, so it serves bit-exact
        with the writer before this method returns. The background sync
        loop re-reads its shard every round, so subsequent deltas reach the
        newcomer without a restart. Returns ``(shard, replica)`` with the
        updated shard — hand both to
        :meth:`repro.fleet.FleetRouter.attach_lane` to start routing to it.
        """
        shards = self._shards[workload]
        shard = shards[shard_index]
        seq = self._replica_seq.get(shard.name, len(shard.replicas))
        self._replica_seq[shard.name] = seq + 1
        replica = self._make_replica(
            f"{shard.name}#r{seq}", workload, self._build_kw.get(workload, {})
        )
        new_shard = shard._replace(replicas=shard.replicas + (replica,))
        shards[shard_index] = new_shard
        self.sync_shard(new_shard)  # join resync: version 0 -> full window
        return new_shard, replica

    def remove_replica(self, workload: str, replica_name: str | None = None):
        """Retire one replica (the scale-down actuation): drop it from its
        shard's broadcast set, then close its transport.

        Detach its router lane **first** (:meth:`FleetRouter.detach_lane`
        reroutes the backlog and waits out the in-flight batch) — this
        method closes the replica immediately after unlinking it. With no
        ``replica_name`` the newest replica of the first shard is retired.
        Each shard keeps at least one replica. Returns the retired
        replica's name."""
        shards = self._shards[workload]
        if replica_name is None:
            shard_index, shard = 0, shards[0]
            replica = shard.replicas[-1]
        else:
            for shard_index, shard in enumerate(shards):
                replica = next(
                    (r for r in shard.replicas if r.name == replica_name),
                    None,
                )
                if replica is not None:
                    break
            else:
                raise KeyError(
                    f"no replica {replica_name!r} in workload {workload!r}"
                )
        if len(shard.replicas) <= 1:
            raise ValueError(
                f"cannot remove the last replica of shard {shard.name!r}"
            )
        remaining = tuple(r for r in shard.replicas if r is not replica)
        with self._sync_lock:  # never yank a replica mid-broadcast
            shards[shard_index] = shard._replace(replicas=remaining)
        self._shard_errors.pop(f"{shard.name}/{replica.name}", None)
        replica.close()
        return replica.name

    # -- streaming append --------------------------------------------------

    def append_observations(self, workload: str, new_data) -> int:
        """Fold a freshly appended observation chunk into every running
        writer of ``workload`` (the streaming append-only target mode).

        Unpartitioned (P=1): every shard's writer sees the full chunk —
        shards sample the same grown posterior. Partitioned: the chunk is
        routed with :func:`~repro.partition.partitioner.partition_append_indices`,
        so each partition's slice grows exactly as if the concatenated pool
        had been stride-partitioned from scratch (no repartitioning, chains
        keep running). Writers that receive rows reset their staleness
        clock (:meth:`~repro.serving.resident.ResidentEnsemble.append`), so
        pre-append windows stop serving as fresh. Returns the number of
        appended sections.
        """
        shards = self._shards[workload]
        num_p = self._partitions.get(workload, 1)
        leaves = jax.tree.leaves(new_data)
        if not leaves:
            raise ValueError("empty append chunk (no array leaves)")
        n_new = int(leaves[0].shape[0])
        if n_new == 0:
            return 0
        if num_p == 1:
            for shard in shards:
                shard.writer.append(new_data)
        else:
            parts = partition_append_indices(
                self._data_sizes[workload], n_new, num_p
            )
            for shard in shards:
                idx = parts[shard.partition]
                if idx.shape[0]:
                    shard.writer.append(take_sections(new_data, idx))
        self._data_sizes[workload] = self._data_sizes.get(workload, 0) + n_new
        return n_new

    # -- delta streaming ---------------------------------------------------

    def sync_shard(self, shard: FleetShard) -> int:
        """Broadcast the writer's snapshot to every replica as deltas;
        returns total wire bytes sent. Also accounts what streaming the full
        window instead would have cost (the bench's comparison)."""
        snap = shard.writer.snapshot()
        window = shard.writer.window
        sent = 0
        with self._sync_lock:
            for replica in shard.replicas:
                try:
                    delta = make_delta(snap, replica.version, window, shard.name)
                    nbytes = wire_bytes(delta)
                    try:
                        replica.apply_delta(delta, nbytes=nbytes)
                    except (ValueError, RuntimeError):
                        # Version drift (e.g. a replica reset raced the
                        # snapshot): fall back to a full resync. ReplicaProcess
                        # surfaces the worker's ValueError as RuntimeError, so
                        # both are resync triggers; a genuinely broken replica
                        # raises again below and propagates.
                        delta = make_delta(snap, 0, window, shard.name)
                        nbytes = wire_bytes(delta)
                        replica.apply_delta(delta, nbytes=nbytes)
                except ReplicaDeadError as e:
                    # A crashed replica must not stall the broadcast to its
                    # healthy peers: skip it (the router routes around the
                    # dead lane) and keep the error visible until a later
                    # sync — after restart() — reaches it again.
                    self.sync_stats["skipped_dead"] += 1
                    self._shard_errors[f"{shard.name}/{replica.name}"] = (
                        f"{type(e).__name__}: {e}"
                    )
                    continue
                self._shard_errors.pop(f"{shard.name}/{replica.name}", None)
                delta_payload = payload_nbytes(delta.draws)
                if delta.full:
                    full_wire, full_payload = nbytes, delta_payload
                else:
                    # The full-snapshot baseline without serializing the
                    # whole window every sync just for accounting: the
                    # pickle frame (name, summary, ints) is shared between
                    # the delta and its full-window counterpart, so the
                    # full wire cost is the delta's plus the payload
                    # difference. Exact for the raw-array part, which is
                    # what dominates.
                    full_payload = payload_nbytes(snap.draws)
                    full_wire = nbytes + (full_payload - delta_payload)
                self.sync_stats["syncs"] += 1
                self.sync_stats["full_deltas"] += int(delta.full)
                self.sync_stats["delta_wire_bytes"] += nbytes
                self.sync_stats["delta_payload_bytes"] += delta_payload
                self.sync_stats["full_wire_bytes"] += full_wire
                self.sync_stats["full_payload_bytes"] += full_payload
                sent += nbytes
        return sent

    def sync_all(self) -> int:
        return sum(
            self.sync_shard(s) for shards in self._shards.values() for s in shards
        )

    def pump(self, workload: str | None = None) -> None:
        """One refresh+broadcast round (synchronous — what tests and the
        smoke path drive; ``start`` moves the same loop onto threads)."""
        names = [workload] if workload else list(self._shards)
        for name in names:
            for shard in self._shards[name]:
                shard.writer.refresh()
                self.sync_shard(shard)

    # -- lifecycle ---------------------------------------------------------

    def warm(self) -> None:
        """Bring every writer to a servable snapshot, then seed every
        replica with its first (full) delta."""
        self.pool.warm()
        self.sync_all()

    def start(self) -> None:
        """Background refresh+broadcast, one thread per shard."""
        if self._threads:
            return
        self._stop.clear()
        for name, shards in self._shards.items():
            for idx, shard in enumerate(shards):
                def loop(name=name, idx=idx):
                    while not self._stop.is_set():
                        # Re-read the shard entry every round: add_replica /
                        # remove_replica swap it for one with an updated
                        # replicas tuple, and a loop pinned to the launch-
                        # time NamedTuple would never broadcast to a
                        # runtime-attached replica.
                        shard = self._shards[name][idx]
                        try:
                            shard.writer.refresh(cause="background")
                            self.sync_shard(shard)
                            self._shard_errors.pop(shard.name, None)
                        except Exception as e:  # noqa: BLE001 — a dead
                            # replica must not silently kill the shard's
                            # refresh loop; record, back off, retry (the
                            # error stays visible in report() until a sync
                            # succeeds).
                            self._shard_errors[shard.name] = (
                                f"{type(e).__name__}: {e}"
                            )
                            self._stop.wait(0.5)
                            continue
                        if self.config.sync_interval_s:
                            self._stop.wait(self.config.sync_interval_s)

                t = threading.Thread(
                    target=loop, name=f"fleet-{shard.name}", daemon=True
                )
                t.start()
                self._threads.append(t)

    def stop(self, timeout_s: float = 30.0) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=timeout_s)
        self._threads = []

    def close(self) -> None:
        """Stop background sync and tear down replica processes."""
        self.stop()
        for shards in self._shards.values():
            for shard in shards:
                for replica in shard.replicas:
                    replica.close()

    # -- persistence -------------------------------------------------------

    def save(self, ckpt_dir: str, keep: int = 3) -> str:
        """Persist every writer (replicas are derived state: they resync)."""
        return self.pool.save(ckpt_dir, keep=keep)

    def restore(self, ckpt_dir: str, step: int | None = None) -> int:
        """Restore writers warm, then full-resync every replica — the
        restored key schedule continues exactly (writer contract), and the
        replicas mirror the restored windows."""
        step = self.pool.restore(ckpt_dir, step=step)
        for shards in self._shards.values():
            for shard in shards:
                for replica in shard.replicas:
                    replica.reset()
                self.sync_shard(shard)
        return step

    # -- reporting ---------------------------------------------------------

    def report(self) -> dict:
        out = {"sync": dict(self.sync_stats), "shards": {},
               "errors": dict(self._shard_errors)}
        for name, shards in sorted(self._shards.items()):
            for shard in shards:
                out["shards"][shard.name] = {
                    "writer_steps": shard.writer.steps_done,
                    "replica_versions": [r.version for r in shard.replicas],
                    "replicas": [self._replica_stats(r) for r in shard.replicas],
                }
        return out

    @staticmethod
    def _replica_stats(replica) -> dict:
        try:
            return replica.stats()
        except ReplicaDeadError:
            return {"name": replica.name, "alive": False}
