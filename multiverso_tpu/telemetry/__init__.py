"""Telemetry — typed metrics, span tracing, and export for the runtime.

The reference's only host-side instrument was the Dashboard monitor
(count + mean per named region, utils/dashboard.py); production traffic
needs latency *distributions*, byte accounting, and a way to follow one
verb across the actor mailboxes. This package provides the three layers
(docs/DESIGN.md §6):

* ``metrics`` — a thread-safe registry of typed instruments (Counter,
  Gauge, log-bucketed Histogram with p50/p90/p99) that merges across
  hosts over the same union-of-names allreduce the Dashboard uses,
  extended to fixed bucket vectors so every rank agrees on collective
  shape.
* ``trace`` — Dapper-style span trees carried on ``Message`` across the
  worker -> mailbox -> server-window hops, exported as Chrome
  trace-event JSON (Perfetto-loadable), with
  ``jax.profiler.TraceAnnotation`` bridging so host spans line up with
  the xplane device traces ``MV_StartProfiler`` produces.
* ``export`` — the ``-stats_interval_s`` periodic reporter plus the
  snapshot/dump helpers behind ``MV_MetricsSnapshot`` /
  ``MV_DumpTrace``.

The ops plane (round 9) adds three more:

* ``flight`` — the always-on flight recorder: a bounded,
  allocation-cheap ring of structured events (windows with exchange
  SEQ, fence causes, barriers, CRC retries, dedup hits, snapshot
  publish/evict, serving dispatch/shed, actor poison), dumped as JSONL
  by ``MV_DumpFlightRecorder`` and automatically on failure paths
  under ``-mv_diag_dir``.
* ``forensics`` — aligns several ranks' flight dumps by exchange SEQ
  and pinpoints the first diverging stream position (``python -m
  multiverso_tpu.telemetry.forensics``). An offline tool with no
  flags, so it is NOT eagerly imported — import it when correlating.
* ``ops`` — the ``-mv_ops_port`` HTTP endpoint: ``/metrics``
  (Prometheus text), ``/healthz`` (poison-aware liveness),
  ``/flight`` (recent events). Local snapshots only — the handler
  never issues collectives.

The watchdog plane (round 13) adds two more:

* ``accounting`` — the process memory/capacity ledger: pull-probed
  ``mem.*`` byte gauges (per-table device/mirror/host placement,
  snapshot retention, flight/dedup/buffer footprints, shm rings) and
  the ``/memory`` ops endpoint.
* ``watchdog`` — ``-mv_watchdog_s`` typed online alert rules with
  fire/clear hysteresis over LOCAL instruments only (shard imbalance,
  shm backpressure, apply-pool saturation, mailbox/memory growth,
  snapshot staleness, the straggler proxy), surfaced at ``/alerts``,
  in ``alert.<rule>`` counters + flight events, and as the /healthz
  ``warn`` status.

The fleet plane (round 22) adds one more:

* ``fleet`` — mergeable-digest rollups piggybacked on the lease
  heartbeats that already flow (``replica_hb`` for readers, the
  elastic member heartbeat for trainer ranks), folded coordinator-side
  into the ``/fleet`` ops document (per-member QPS/p50/p99, staleness,
  "slowest member by p99"), three fleet watchdog rules, and the
  ``python -m multiverso_tpu.telemetry.fleet --trace`` multi-dump
  trace merge CLI. Zero new connections, zero collectives.

The start-up ledger (PR 52) adds one more, on the same registry and
spans and with no flag of its own:

* ``startup`` — set-up by phase (``mv.import_s``, ``mv.init_s``, ...)
  and every program JAX traced, lowered, compiled or loaded, by name
  (``jit.*``), from JAX's own monitoring events. Imported where it is
  used (the package's lazy import is its first phase).

Importing this package registers every telemetry flag (``-telemetry``,
``-trace``, ``-stats_interval_s``, ``-mv_flight_events``,
``-mv_diag_dir``, ``-mv_ops_port``, ``-mv_watchdog_s``,
``-mv_fleet_stale_s``, ``-mv_fleet_p99_s``) so ``MV_Init`` argv
parsing claims them.
"""

from multiverso_tpu.telemetry import (export, flight,  # noqa: F401
                                      metrics, ops, trace)
from multiverso_tpu.telemetry import accounting, watchdog  # noqa: F401,E402
from multiverso_tpu.telemetry import fleet  # noqa: F401,E402
