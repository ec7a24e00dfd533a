"""repro.api — the public partitioning-service surface.

Everything callers need to serve a partitioned knowledge graph:

* strategies: :class:`Partitioner` protocol with :class:`HashPartitioner`,
  :class:`WawPartitioner`, :class:`AWAPartitioner`;
* :class:`PartitionedKG` — shard-view facade with incremental delta updates
  and the per-``(query, store)`` plan cache;
* :class:`KGService` — the Fig.-6 session loop (``bootstrap / query /
  query_batch / observe / maybe_adapt / step / drain / reset_baseline``);
* :class:`MigrationSession` — chunked online application of an accepted
  migration (``repro.migrate``), throttled by the service's
  ``migration_budget`` knob;
* :class:`ReplicaMap` — workload-aware read replication of hot features
  (``repro.replicate``), budgeted by the service's ``replica_budget`` knob;
* :class:`WriteBatch` / :class:`WriteReport` — the live write path
  (``repro.write``): ``svc.insert(...)`` / ``svc.delete(...)`` served
  concurrently with queries, replication, and an in-flight drain;
* :class:`StreamService` / :class:`LatencyRecorder` — continuous
  admission (``repro.stream``): ``svc.stream()`` serves submitted
  queries/writes in pipelined windows, byte-identical to ``query_batch``
  over the same admission order, with p50/p95/p99 tail telemetry on
  ``svc.stats()``;
* executors: :class:`Executor` protocol with :class:`NumpyExecutor`
  (reference) and :class:`JaxExecutor` (batched; ``pallas=True`` — the
  ``executor="jax-pallas"`` knob — probes joins through the
  ``repro.kernels.join`` Pallas kernel family), re-exported from
  ``repro.query.exec``;
* observability: :class:`Tracer` / :class:`MetricsRegistry`
  (``repro.obs``) — the program's spans measure real time where the work
  happens (serving window, planning, scans, joins and their stages,
  federation, adaptation round and its phases, migration chunks, writes)
  and show on a ``jax.profiler`` capture; ``KGService(trace=True)`` also
  keeps them, with parent and request id, on the wall clock
  (``svc.tracer().export("out.json")`` is Perfetto-loadable), and every
  service folds its metrics snapshot, span totals included, into
  ``stats()["metrics"]``.

See ``docs/api.md`` for the quickstart.
"""
from repro.api.facade import PartitionedKG
from repro.api.partitioners import (AWAPartitioner, HashPartitioner,
                                    Partitioner, WawPartitioner)
from repro.api.service import KGService
from repro.migrate import MigrationSession
from repro.obs import MetricsRegistry, Tracer
from repro.query.exec import Executor, JaxExecutor, NumpyExecutor
from repro.replicate import ReplicaMap
from repro.stream import LatencyRecorder, StreamService
from repro.write import WriteBatch, WriteLog, WriteReport

__all__ = [
    "AWAPartitioner",
    "Executor",
    "HashPartitioner",
    "JaxExecutor",
    "KGService",
    "LatencyRecorder",
    "MetricsRegistry",
    "MigrationSession",
    "NumpyExecutor",
    "PartitionedKG",
    "Partitioner",
    "ReplicaMap",
    "StreamService",
    "Tracer",
    "WawPartitioner",
    "WriteBatch",
    "WriteLog",
    "WriteReport",
]
