"""The Broker: assembly root tying config, metrics, hooks, registry, retain
store, message store, sessions, and background services together.

Plays the role of the reference's supervision root
(``vmq_server_sup.erl:43-58`` boot order: config → msg store → queues →
registry → cluster → metrics → listeners) — in asyncio there is no
supervision tree, so this object owns construction order and shutdown.
"""

from __future__ import annotations

import asyncio
import inspect
import logging
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..protocol import topic as T
from ..protocol.types import Will
from ..robustness import faults
from ..storage.msg_store import FileMsgStore, MemoryMsgStore, MsgStore
from .config import Config
from .egress import Outbox
from .message import Msg, SubscriberId
from .metrics import Metrics
from .plugins import HookError, HookRegistry
from .queue import SubscriberQueue
from .reg import Registry
from .retain import RetainStore

log = logging.getLogger("vernemq_tpu.broker")


def _log_hook_task_error(task: "asyncio.Task") -> None:
    if not task.cancelled() and task.exception() is not None:
        log.error("async hook handler failed", exc_info=task.exception())


class Broker:
    def __init__(self, config: Optional[Config] = None, node_name: str = "node1"):
        self.config = config or Config()
        self.node_name = node_name
        self._resolve_base_dirs()
        self.metrics = Metrics()
        # a loop turn's socket writes and egress counters (broker/egress.py)
        self.outbox = Outbox(self.metrics)
        self.hooks = HookRegistry()
        from ..plugins import PluginManager

        self.plugins = PluginManager(self)
        # replicated metadata store (vmq_metadata facade,
        # vmq_metadata.erl:24-28): ``metadata_plugin`` picks the backend the
        # way metadata_impl selects vmq_plumtree or vmq_swc — "lww" is the
        # plumtree-flavored LWW store, "swc" the server-wide-clock store
        persist_dir = (self.config.metadata_dir
                       if self.config.get("metadata_persistence", False)
                       else None)
        if self.config.get("metadata_plugin", "lww") == "swc":
            from ..cluster.swc_store import SWCMetadata

            self.metadata = SWCMetadata(
                node_name, persist_dir=persist_dir,
                n_groups=self.config.get("swc_replication_groups", 8),
                sync_interval=self.config.get("swc_sync_interval", 2.0),
                db_backend=self.config.get("swc_db_backend", "kvstore"))
        else:
            from ..cluster.metadata import MetadataStore

            self.metadata = MetadataStore(node_name, persist_dir=persist_dir)
        self.cluster: Optional[Any] = None  # set by cluster.Cluster
        # stall watchdog (robustness/watchdog.py): monitored-operation
        # registry + sacrificial dispatch for every cross-boundary wait
        # (device dispatch, rebuild threads, delta scatter, store
        # writes, cluster ack progress). Created unconditionally so the
        # gauges always exist; the monitor thread starts in start()
        # when watchdog_enabled.
        from ..robustness.watchdog import StallWatchdog

        self.watchdog = StallWatchdog(
            tick_s=self.config.get("watchdog_tick_ms", 100) / 1e3)
        self.retain = RetainStore(on_dirty=self._retain_dirty)
        # device-resident retained index (vernemq_tpu/retained/): created
        # lazily on the first replay once the tpu reg view is live; the
        # retain dirty hook write-throughs deltas into it
        self._retained_engine: Optional[Any] = None
        self._retained_collector: Optional[Any] = None
        self.metadata.subscribe("retain", self._on_retain_event)
        self.registry = Registry(self)
        # payload filtering & windowed aggregation (vernemq_tpu/filters/,
        # MQTT+): per-mountpoint schemas replicate through the metadata
        # plane like the mesh slice map; the engine runs the predicate
        # phase behind topic match. Disabled ⇒ both stay None and every
        # hook is one attribute test — byte-identical to the pre-filter
        # broker.
        self.schema_registry: Optional[Any] = None
        self.filter_engine: Optional[Any] = None
        if self.config.get("payload_filters_enabled", True):
            from ..filters.engine import FilterEngine
            from ..filters.schema_registry import SchemaRegistry

            self.schema_registry = SchemaRegistry(self.metadata, node_name)
            self.schema_registry.boot_install(
                self.config.get("payload_schemas", []))
            cfg = self.config
            self.filter_engine = FilterEngine(
                self.schema_registry, metrics=self.metrics,
                breaker_enabled=cfg.get("tpu_breaker_enabled", True),
                breaker_failure_threshold=cfg.get(
                    "tpu_breaker_failure_threshold", 3),
                breaker_backoff_initial=cfg.get(
                    "tpu_breaker_backoff_initial_ms", 200) / 1e3,
                breaker_backoff_max=cfg.get(
                    "tpu_breaker_backoff_max_ms", 10_000) / 1e3,
                host_threshold=cfg.get("predicate_host_threshold", 16),
                max_pairs=cfg.get("predicate_max_pairs", 65536),
                window_initial=cfg.get("aggregate_initial_windows", 256),
                window_cap=cfg.get("aggregate_max_windows", 4096),
                tick_ms=cfg.get("aggregate_tick_ms", 250),
                # the device phase runs only where the device lives:
                # never in SO_REUSEPORT workers (the match service owns
                # JAX; rows come back over the rings and the worker's
                # exact host evaluator filters them), and only while
                # the tpu view actually serves
                device_gate=lambda: (
                    self.match_client is None
                    and self.registry.batched_view_active()),
            )
            self.filter_engine.emit = self._deliver_aggregate
            self.filter_engine.share_rows = self.registry.share_member_rows
        # mesh slice map (cluster/mesh_map.py): slice→node ownership in
        # the replicated metadata plane, gossiped like the netsplit
        # CAPs. Created whenever a tpu_mesh is configured — single-node
        # deployments claim every slice at start; cluster membership
        # changes re-run the deterministic round-robin claim.
        self.mesh_map: Optional[Any] = None
        n_slices = self._mesh_slice_count()
        if n_slices:
            from ..cluster.mesh_map import MeshSliceMap

            self.mesh_map = MeshSliceMap(
                self.metadata, node_name, n_slices,
                on_adopt=self._on_mesh_adopt, metrics=self.metrics)
        fsync = bool(self.config.get("msg_store_fsync", False))
        # fsync group-commit: one fsync per write burst at the flush-tick
        # boundary instead of per record (msg_store_fsync_coalesced)
        gc_on = bool(self.config.get("msg_store_group_commit", True))
        seg_max = int(self.config.get("store_segment_max_bytes",
                                      8 * 1024 * 1024))
        ckpt_every = int(self.config.get("store_checkpoint_every_bytes",
                                         32 * 1024 * 1024))
        if self.config.message_store == "file":
            from ..storage.msg_store import SegmentMsgStore

            store_dir = self.config.message_store_dir
            if os.path.exists(os.path.join(store_dir, "msgstore.log")):
                # a legacy flat-log store already lives here — honour
                # its data rather than silently orphaning it
                log.warning("legacy flat-log msg store found in %s; "
                            "serving it (new dirs open the segment "
                            "engine)", store_dir)
                self.msg_store: MsgStore = FileMsgStore(
                    store_dir, fsync=fsync, group_commit=gc_on)
            else:
                # the pure-Python half of the unified segment engine
                # (storage/segment.py): checkpointed recovery, budgeted
                # broker-driven compaction — the same engine layer the
                # cluster spool journals through
                self.msg_store = SegmentMsgStore(
                    store_dir, fsync=fsync, group_commit=gc_on,
                    segment_max_bytes=seg_max,
                    checkpoint_every_bytes=ckpt_every)
        elif self.config.message_store == "native":
            from ..storage.msg_store import BucketedMsgStore, NativeMsgStore

            try:
                n = int(self.config.get("msg_store_instances", 1))
                store_dir = self.config.message_store_dir
                if n > 1 and os.path.exists(
                        os.path.join(store_dir, "msgstore.kv")):
                    # a flat single-instance store already lives here —
                    # honour it rather than silently orphaning its data
                    log.warning("legacy single-instance msg store found in "
                                "%s; ignoring msg_store_instances=%d",
                                store_dir, n)
                    n = 1
                # N engines hashed by msg-ref (vmq_lvldb_store_sup.erl:47-54)
                self.msg_store = (BucketedMsgStore(store_dir, n, fsync=fsync,
                                                   group_commit=gc_on)
                                  if n > 1
                                  else NativeMsgStore(store_dir, fsync=fsync,
                                                      group_commit=gc_on))
            except Exception as e:  # no toolchain → segment-log twin
                from ..storage.msg_store import SegmentMsgStore

                log.warning("native msg store unavailable (%s); "
                            "falling back to the segment-log engine", e)
                self.msg_store = SegmentMsgStore(
                    self.config.message_store_dir, fsync=fsync,
                    group_commit=gc_on, segment_max_bytes=seg_max,
                    checkpoint_every_bytes=ckpt_every)
        else:
            self.msg_store = MemoryMsgStore()
        # batched reconnect-storm resumption (storage/resume.py): built
        # lazily on the first deferrable recover when the store supports
        # off-loop batched reads; the store breaker + compaction driver
        # state lives here so the gauges always exist
        self._resume_collector: Optional[Any] = None
        self._store_commit_scheduled = False
        from ..robustness.breaker import CircuitBreaker

        self.store_breaker = CircuitBreaker(
            failure_threshold=self.config.get(
                "tpu_breaker_failure_threshold", 3),
            backoff_initial=self.config.get(
                "tpu_breaker_backoff_initial_ms", 200) / 1e3,
            backoff_max=self.config.get(
                "tpu_breaker_backoff_max_ms", 10_000) / 1e3,
            name="store")
        self.store_compactions = 0
        self.store_compacted_bytes = 0
        self.store_compact_paused = 0
        self.store_compact_errors = 0
        # last-drained (hits, misses) snapshot of the bucketed store's
        # probe counters (the maintenance tick moves deltas into $SYS)
        self._probe_drained = (0, 0)
        # corrupt records skipped by the store's recovery scan are
        # surfaced, not silent (the old behavior discarded the tail) —
        # and so is a checkpoint-discarding full-scan fallback
        skipped = getattr(self.msg_store, "recover_skipped", 0)
        if skipped:
            self.metrics.incr("msg_store_recover_skipped", skipped)
        fallbacks = sum(
            getattr(getattr(st, "engine", None), "recover_fallbacks", 0)
            for st in (getattr(self.msg_store, "instances", None)
                       or [self.msg_store]))
        if fallbacks:
            self.metrics.incr("store_recover_fallbacks", fallbacks)
        # live sessions: sid -> Session (the reference reaches sessions via
        # queue pids; a direct map is equivalent single-node)
        self.sessions: Dict[SubscriberId, Any] = {}
        # live queue-migration state, surfaced via `vmq-admin cluster
        # migrations` (the reference surfaces drain progress via queue
        # status / cluster show): sid -> {target, pending, retries, state}
        self.migrations: Dict[SubscriberId, Dict[str, Any]] = {}
        # live-handoff engine (cluster/handoff.py): the reusable
        # freeze->drain->fence->adopt FSM behind `vmq-admin handoff
        # drain|rebalance` and `cluster drain-node`; its breaker gates
        # admission so repeated rollbacks stop new moves piling onto a
        # broken successor
        from ..cluster.handoff import HandoffManager

        self.handoff = HandoffManager(self)
        self._delayed_wills: Dict[SubscriberId, asyncio.Task] = {}
        self.tracer: Optional[Any] = None  # single active session tracer
        # hot-path flight recorder (observability/recorder.py): the
        # 1-in-N publish sample decision is made once at admission
        # (session._handle_publish) and the trace rides the fold
        # envelope; `vmq-admin timeline show|dump` read the ring. The
        # dispatch profiler is process-global (observability/profiler)
        # — the matcher records into it without a broker handle.
        from ..observability import FlightRecorder

        self.recorder = FlightRecorder(
            sample_n=int(self.config.get("flight_recorder_sample_n", 32)),
            capacity=int(self.config.get("flight_recorder_capacity",
                                         4096)),
            node=node_name)
        # canary SLO probe (observability/canary.py): built at start()
        # when canary_enabled — the loopback subscription must not
        # exist unless the operator asked for the probe
        self.canary: Optional[Any] = None
        # multi-process session front end (broker/workers.py): when this
        # broker is one of N SO_REUSEPORT workers, the parent hands it a
        # shared stats slot (fused overload pressure, `vmq-admin workers
        # show`) and optionally a ring pair to the device-match service.
        # Both stay None in the classic single-process boot — the
        # workers=1 byte-identical guarantee.
        self.worker_index = int(self.config.get("worker_index", 0) or 0)
        self.worker_stats: Optional[Any] = None
        self.match_client: Optional[Any] = None
        self.sysmon: Optional[Any] = None
        self.overload: Optional[Any] = None  # adaptive overload governor
        self.supervisor: Optional[Any] = None  # crash-restart supervision
        self.crl_refresher: Optional[Any] = None
        self.http: Optional[Any] = None
        self.graphite: Optional[Any] = None
        self.listeners: Optional[Any] = None  # ListenerManager (transports)
        self._servers: List[Any] = []
        self._bg_tasks: List[asyncio.Task] = []
        self._started = time.time()
        self._cluster_ready = True  # single-node; cluster layer overrides
        self.metrics.register_gauges(self._gauges, {
            "router_subscriptions": "Subscriptions in the routing table.",
            "router_memory": "Approximate routing table memory (bytes).",
            "queue_processes": "Live subscriber queues.",
            "retain_messages": "Retained messages.",
            "retain_memory": "Approximate bytes used for storing "
                             "retained messages.",
            "active_sessions": "Currently connected sessions.",
            "uptime_seconds": "Broker uptime.",
            "tpu_hybrid_host_pubs": "Small flushes served by the host "
                                    "trie (hybrid dispatch).",
            "tpu_overload_shed_pubs": "Publishes shed to the trie at "
                                      "collector overload.",
            "tpu_rebuild_shed_pubs": "Publishes the trie served during "
                                     "a device table rebuild.",
            "tpu_busy_shed_pubs": "Publishes the trie served past the "
                                  "matcher-lock/cold-compile bound.",
            "tpu_saturated_merges": "Flushes merged into a later batch "
                                    "(both pipeline slots busy).",
            "tpu_async_rebuilds": "Background device-table rebuilds.",
            "tpu_wide_publishes": "Publishes past the flat match "
                                  "result's caps (tpu_max_fanout a part, "
                                  "flat capacity a batch) that the device "
                                  "answered whole with the wide pass.",
            "tpu_wide_dispatches": "Wide-pass device dispatches.",
            "tpu_wide_topics": "Distinct topics the wide-pass dispatches "
                               "matched (identical topics of a dispatch "
                               "are matched once).",
            "tpu_wide_rows": "Matched rows the wide pass brought back, "
                             "summed over its publishes.",
            "tpu_wide_failures": "Publishes whose wide answer fell short "
                                 "of the flat form's own count "
                                 "(host-matched instead).",
            "tpu_phase_dispatches": "Windowed match programs executed "
                                    "for live traffic (single or super "
                                    "dispatch).",
            "tpu_phase_runs": "Match phases compiled into those programs, "
                              "summed (1-3 each: the dense pass over "
                              "region 0 and probe B are left out while "
                              "their rows hold no live subscription).",
            "tpu_phase_dense_dispatches": "Of those programs, the ones "
                                          "that held the dense pass over "
                                          "region 0 (a live "
                                          "wildcard-first subscription).",
            "tpu_release_rows": "Matched rows (at least one a "
                                "submission) the collector's release "
                                "queue released.",
            # degraded-mode observability (robustness tentpole): breaker
            # state + fallback/fault counters, published to $SYS like
            # every other metric by the systree reporter
            # adaptive overload governor (robustness/overload.py):
            # current level + composite pressure, per-level cumulative
            # seconds and entry counts, hysteresis extends, plus the
            # sysmon hysteresis counters the governor builds on
            "overload_level": "Current overload governor level (0 ok, "
                              "1 throttle, 2 shed, 3 refuse).",
            "overload_pressure": "Composite overload pressure score "
                                 "(max of the fused signal severities, "
                                 "0..1).",
            "overload_level_pinned": "Manually pinned overload level "
                                     "(-1 = automatic).",
            "overload_level_extends": "Overload hysteresis windows "
                                      "re-armed by boundary pressure.",
            "overload_l1_seconds": "Cumulative seconds spent at "
                                   "overload level 1.",
            "overload_l2_seconds": "Cumulative seconds spent at "
                                   "overload level 2.",
            "overload_l3_seconds": "Cumulative seconds spent at "
                                   "overload level 3.",
            "overload_level_enters_l1": "Transitions into overload "
                                        "level 1.",
            "overload_level_enters_l2": "Transitions into overload "
                                        "level 2.",
            "overload_level_enters_l3": "Transitions into overload "
                                        "level 3.",
            "sysmon_overload_extends": "Sysmon overload cooldowns "
                                       "re-armed by boundary lag "
                                       "(hysteresis extends).",
            "sysmon_last_loop_lag_seconds": "Most recent event-loop "
                                            "lag sample.",
            "loop_cpu_s": "CPU seconds the event loop's thread has "
                          "consumed (time.thread_time(), sampled on "
                          "sysmon's tick); its rate is the share of a "
                          "second the loop is busy.",
            "sysmon_stalls": "Stalls the sysmon witness recorded: "
                             "stretches in which the loop's 50 ms "
                             "heartbeat ran later than "
                             "sysmon_lag_threshold.",
            "sysmon_stall_seconds_loop": "Seconds of stalls in which the "
                                         "loop's own callbacks ran long "
                                         "(no device program "
                                         "compiling).",
            "sysmon_stall_seconds_gil": "Seconds of stalls in which "
                                        "native code in another thread "
                                        "held the interpreter lock (no "
                                        "device program compiling).",
            "sysmon_stall_seconds_host": "Seconds of stalls in which no "
                                         "thread of the process ran and "
                                         "it used almost no CPU: the host "
                                         "took the cores away (no device "
                                         "program compiling).",
            "sysmon_stall_seconds_gc": "Seconds of stalls at least half "
                                       "covered by a full collection (no "
                                       "device program compiling).",
            "sysmon_stall_seconds_cold": "Seconds of stalls made while a "
                                         "device program compiled, "
                                         "whatever their cause.",
            "tpu_breaker_state": "Device circuit breaker state "
                                 "(0 closed, 1 half-open, 2 open; worst "
                                 "across mountpoints).",
            "tpu_breaker_opens": "Breaker open transitions (device path "
                                 "degraded to the host trie).",
            "tpu_breaker_closes": "Breaker close transitions (device "
                                  "path recovered).",
            "tpu_breaker_time_degraded_seconds":
                "Cumulative seconds the device path spent degraded.",
            "tpu_device_failures": "Device dispatch/upload failures fed "
                                   "to the breaker.",
            "tpu_degraded_sheds": "Match calls refused while the "
                                  "breaker was open.",
            "tpu_degraded_host_pubs": "Publishes the host trie served "
                                      "while the breaker was open.",
            "tpu_delta_shapes_warmed": "Delta-scatter shapes "
                                       "pre-compiled at startup.",
            "fault_plan_active": "1 while a fault-injection plan is "
                                 "installed.",
            "faults_injected": "Faults raised by the active plan.",
            "faults_delayed": "Latency/hang faults applied by the "
                              "active plan.",
            # wire plane (protocol/fastpath.py + native/codec.cc)
            "wire_native_active": "1 while the native wire codec is "
                                  "serving batch parse/encode (built, "
                                  "enabled, breaker closed).",
            "wire_native_batches": "Recv buffers batch-parsed by the "
                                   "native frame-table builder.",
            "wire_pure_batches": "Recv buffers batch-parsed by the "
                                 "bit-identical pure-Python twin.",
            "wire_native_errors": "Native codec calls that failed and "
                                  "fed the wire breaker (the batch was "
                                  "re-served by the pure codec).",
            "wire_degraded_batches": "Batches served pure-Python while "
                                     "the wire breaker was open.",
            "wire_fastpath_pubs": "QoS0 publishes admitted through the "
                                  "object-free wire fast path (no "
                                  "frame/Msg objects materialised).",
            "wire_fastpath_pubs_qos": "QoS1/2 publishes admitted "
                                      "through the wire fast path (pid "
                                      "stamped from the frame-table "
                                      "span, no inbound frame object).",
            "wire_classic_pubs_qos": "QoS1/2 publishes that took the "
                                     "classic handler (retained, dup, "
                                     "a hook, a tracer, a rate limit, "
                                     "a raised governor, a protocol "
                                     "edge): with wire_fastpath_pubs_"
                                     "qos, the share the wire plane "
                                     "admitted.",
            "wire_fastpath_acks": "Ack-family frames (PUBACK/PUBREC/"
                                  "PUBREL/PUBCOMP) resolved straight "
                                  "from the frame table with no frame "
                                  "object.",
            "wire_inline_chunks": "Recv chunks of a protocol-level "
                                  "(mqtt/mqtts) listener that held only "
                                  "wire-plane records and were parsed, "
                                  "admitted and acknowledged by the "
                                  "connection's protocol (the listener's "
                                  "per-turn callback): no stream reader, "
                                  "no task step.",
            "wire_task_chunks": "Recv chunks, or the remainder of one "
                                "from its first record on, that such a "
                                "listener handed to the connection's "
                                "parked task: gate closed, a classic "
                                "record, a run bound. With "
                                "wire_inline_chunks, how often the "
                                "inline run engages.",
            "wire_egress_flushes": "Outbox flushes (broker/egress.py): "
                                   "one per loop turn in which any "
                                   "stream transport was written or an "
                                   "egress counter moved.",
            "wire_egress_writes": "Transports those flushes wrote: one "
                                  "socket write each, whatever the "
                                  "number of frames queued on it in "
                                  "the turn.",
            "wire_egress_publishes": "PUBLISH frames those writes "
                                     "carried: over wire_egress_writes, "
                                     "the frames a socket write took out "
                                     "(a fan-out puts several of one "
                                     "turn on one socket).",
            "wire_egress_joined": "Of those, flushes of several chunks "
                                  "(a delivery's header + payload, a "
                                  "run of acks) small enough to leave "
                                  "as ONE joined write (a plain send).",
            "wire_egress_scattered": "Of those, flushes of several "
                                     "chunks over the join bound, sent "
                                     "through writelines so a shared "
                                     "payload is not copied per "
                                     "recipient.",
            "wire_egress_offload_writes": "Of wire_egress_writes, the "
                                          "transports a flush handed to "
                                          "the native writer thread "
                                          "(plain sockets) instead of "
                                          "writing on the loop.",
            "wire_egress_offload_sent": "Hand-offs the writer thread "
                                        "finished sending.",
            "wire_egress_offload_lag_us": "Sum over those of the time "
                                          "from hand-off to the last "
                                          "byte sent, in microseconds: "
                                          "over wire_egress_offload_sent, "
                                          "whether the writer keeps up.",
            "wire_egress_offload_dropped": "Backlogs the writer dropped "
                                           "because their connection "
                                           "was lost.",
            "wire_fanout_batches": "One-call batched fanout header "
                                   "encodes (publish_headers_batch): "
                                   "each emitted N per-recipient "
                                   "pid/alias-patched headers into one "
                                   "arena.",
            "wire_fanout_pass_pubs": "Wire-plane publishes whose every "
                                     "matched row the one-pass fan-out "
                                     "served.",
            "wire_fanout_classic_pubs": "Wire-plane publishes a complex "
                                        "row sent to the classic "
                                        "route_rows path.",
            "wire_breaker_state": "Wire-codec breaker state (0 closed, "
                                  "1 half-open, 2 open).",
            # shared subscriptions (broker/shared.py): one member drawn
            # from a group's own classes a publish
            "share_picks": "Deliveries made to a member drawn from a "
                           "shared subscription (one a publish and "
                           "shared subscription, ShareName and "
                           "filter).",
            "share_wire_picks": "Of those, the ones the wire plane's "
                                "fanout wrote (a lone online session "
                                "with fast options).",
            "share_stale_picks": "Draws whose member's queue was online "
                                 "no more: the group's online list was "
                                 "repaired and the draw made again.",
            "share_offline_picks": "Deliveries to an offline member's "
                                   "queue because no member of the "
                                   "group was online.",
            "wire_share_split_picks": "Of the share_picks, draws for a "
                                      "ShareName already drawn for under "
                                      "another filter in the same "
                                      "publish.",
            # cluster delivery spool (cluster/spool.py): depth +
            # outstanding-ack gauges, published to $SYS/Prometheus
            "cluster_spool_depth_frames": "QoS>=1 cluster frames "
                                          "journaled awaiting acks.",
            "cluster_spool_depth_bytes": "Bytes journaled in the "
                                         "cluster delivery spool.",
            "cluster_spool_outstanding_acks": "Peers with spooled "
                                              "frames awaiting a "
                                              "cumulative ack.",
            "cluster_spool_peers_blocked": "Peers whose spooled stream "
                                           "is paused pending replay "
                                           "resync.",
            # device retained index (vernemq_tpu/retained/): monotonic
            # counts exposed like the tpu_breaker_* family
            "retained_index_rows": "Retained messages mirrored in the "
                                   "device reverse-match index.",
            "retained_index_rebuilds": "Full device retained-table "
                                       "(re)builds.",
            "retained_match_dispatches": "Batched retained reverse-match "
                                         "device dispatches.",
            "retained_match_queries": "Subscription filters served by "
                                      "the retained device path.",
            "retained_host_fallback_queries": "Filters the device could "
                                              "not serve exactly "
                                              "(host-resolved).",
            "retained_device_failures": "Retained dispatch/upload "
                                        "failures fed to the breaker.",
            "retained_degraded_sheds": "Retained match calls refused "
                                       "while the breaker was open.",
            "retained_breaker_state": "Retained device breaker state "
                                      "(0 closed, 1 half-open, 2 open; "
                                      "worst across mountpoints).",
            "retained_replay_deferred_flushes": "Replay flushes deferred "
                                                "by the overload "
                                                "governor (level 2+).",
            "retained_replay_device_batches": "Replay flushes served by "
                                              "the device path.",
            "retained_replay_device_filters": "Replay filters that rode "
                                              "a device dispatch.",
            "retained_replay_host_filters": "Small replay flushes served "
                                            "by the host walk (hybrid "
                                            "dispatch).",
            "retained_replay_degraded_filters": "Replay filters the host "
                                                "walk served while the "
                                                "breaker was open.",
            "retained_replay_rebuild_filters": "Replay filters the host "
                                               "walk served during a "
                                               "table rebuild.",
            "retained_replay_fallback_filters": "Per-filter device "
                                                "escapes resolved "
                                                "against the host store.",
            "retained_replay_stalled_filters": "Replay filters the host "
                                               "walk served after a "
                                               "dispatch deadline "
                                               "abandonment.",
            "retained_replay_expired_filters": "Queued replay filters "
                                               "host-served past their "
                                               "collector expiry.",
            # storage tier (storage/segment.py + storage/resume.py):
            # the unified segment engine's health + the batched
            # reconnect-storm resumption counters
            "store_breaker_state": "Store compaction breaker state "
                                   "(0 closed, 1 half-open, 2 open; "
                                   "open = append-only degraded mode).",
            "store_live_bytes": "Live record bytes across every "
                                "segment/kv engine (msg store + "
                                "cluster spool).",
            "store_garbage_bytes": "Dead record bytes awaiting "
                                   "budgeted compaction across every "
                                   "engine.",
            "store_segments": "On-disk segment files across every "
                              "segment-log engine.",
            "resume_batched_sessions": "Reconnecting sessions whose "
                                       "offline replay rode a batched "
                                       "off-loop store read.",
            "resume_batched_reads": "Batched off-loop read_many calls "
                                    "issued by the resume collector.",
            "resume_host_sessions": "Small resume flushes served by "
                                    "the per-session read on the loop "
                                    "(hybrid dispatch).",
            "resume_expired_sessions": "Queued resumes served by the "
                                       "exact per-session fallback "
                                       "past their expiry.",
            "resume_fallback_sessions": "Sessions served per-session "
                                        "after a batched read failed.",
            "resume_deferred_flushes": "Resume flushes deferred by the "
                                       "overload governor (level 2+).",
            "resume_pending_sessions": "Reconnect resumes queued in "
                                       "the collector window.",
            "retained_dispatch_stalls": "Retained dispatches abandoned "
                                        "at the watchdog deadline (fed "
                                        "to the breaker).",
            "retained_rebuild_abandons": "Wedged retained rebuilds "
                                         "abandoned by the watchdog.",
            # stall watchdog (robustness/watchdog.py): the silent-stall
            # observability family — every cross-boundary wait registers
            # here, overdue ops are counted/abandoned, late results of
            # abandoned ops are discarded (never delivered)
            "watchdog_stalls": "Monitored operations observed past "
                               "their deadline.",
            "watchdog_abandoned": "Stalled operations abandoned "
                                  "(waiters released to the host "
                                  "fallback; breaker fed).",
            "watchdog_late_discarded": "Abandoned operations that "
                                       "completed late; their results "
                                       "were discarded, never "
                                       "delivered.",
            "watchdog_cluster_stalls": "Cluster channels cycled by "
                                       "ack-progress stall detection.",
            "watchdog_inflight_ops": "Monitored operations currently "
                                     "in flight.",
            "watchdog_inflight_age_max": "Age (seconds) of the oldest "
                                         "in-flight monitored "
                                         "operation.",
            "watchdog_sacrificed_threads": "Executor workers lost to "
                                           "abandoned (wedged) "
                                           "dispatches; the pool "
                                           "spawned around each.",
            "faults_wedged_now": "Injection points currently blocked "
                                 "in a wedge fault.",
            "faults_wedge_releases": "Wedge faults released (watchdog "
                                     "abandonment or `vmq-admin fault "
                                     "release`).",
            "tpu_stalled_host_pubs": "Publishes the host trie served "
                                     "after a dispatch deadline "
                                     "abandonment.",
            "tpu_expired_host_pubs": "Queued publishes host-served "
                                     "past their collector expiry.",
            "tpu_dispatch_stalls": "Device dispatches abandoned at the "
                                   "watchdog deadline (fed to the "
                                   "breaker).",
            "tpu_rebuild_abandons": "Wedged device-table rebuilds "
                                    "abandoned by the watchdog.",
            # multi-process front end (broker/workers.py +
            # broker/match_service.py): per-worker counters aggregated
            # at the scrape/$SYS point from the shared stats block,
            # plus the worker's own match-service client stats
            "workers_total": "Worker slots in the shared stats block "
                             "(the SO_REUSEPORT group size).",
            "workers_alive": "Workers with a fresh heartbeat in the "
                             "shared stats block.",
            "workers_sessions_total": "Connected sessions summed "
                                      "across live workers.",
            "workers_admitted_pubs_total": "PUBLISHes admitted summed "
                                           "across live workers.",
            "workers_level_max": "Highest overload level any live "
                                 "worker reports (the fused L2/L3 "
                                 "shedding gate).",
            "workers_pressure_max": "Highest local overload pressure "
                                    "any live worker reports.",
            "overload_peer_pressure": "Peer-worker pressure fused into "
                                      "this governor (0 outside "
                                      "multi-process mode).",
            "match_client_folds": "Fold batches this worker shipped to "
                                  "the match service.",
            "match_client_fold_pubs": "Publishes that rode a "
                                      "match-service fold batch.",
            "match_client_timeouts": "Match-service folds abandoned at "
                                     "the reply deadline (local trie "
                                     "served).",
            "match_client_stalls": "Match-service folds abandoned by "
                                   "the stall watchdog (local trie "
                                   "served).",
            "match_client_degraded": "Folds refused while the "
                                     "match-service breaker was open "
                                     "(local trie served).",
            "match_client_held": "Folds served locally while an op "
                                 "backlog/resync was still in flight "
                                 "(ordering fence).",
            "match_client_ops_sent": "Subscription write ops forwarded "
                                     "to the match service.",
            "match_client_ops_dropped": "Subscription ops dropped on "
                                        "backlog overflow (a full "
                                        "resync replaces them).",
            "match_client_resyncs": "Owned-row replays after a "
                                    "match-service (re)start.",
            "match_client_breaker_state": "Match-service client breaker "
                                          "state (0 closed, 1 "
                                          "half-open, 2 open).",
            "match_client_op_backlog": "Subscription ops buffered "
                                       "while the request ring is "
                                       "full.",
            # flight recorder (observability/recorder.py)
            "flight_sampled": "Publishes sampled by the flight "
                              "recorder (1-in-N at admission).",
            "flight_records": "Stage-stamped publish records currently "
                              "in the flight-recorder ring.",
            "flight_sample_n": "Flight-recorder sampling divisor "
                               "(every Nth admitted publish records).",
            "flight_resumed": "Flight-recorder traces resumed from a "
                              "cluster peer's propagated context "
                              "(cross-node publishes).",
            # mesh-native matcher (parallel/mesh_match.py) + slice map
            # (cluster/mesh_map.py): slice residency and delta-routing
            # effectiveness — all zero outside mesh mode
            "mesh_slices_total": "Mesh matcher slices in the slice map "
                                 "(the 'sub' axis size; 0 when no mesh "
                                 "is configured).",
            "mesh_slices_local": "Mesh slices owned by this node per "
                                 "the gossiped slice map.",
            "mesh_rows_resident": "Active subscription rows resident "
                                  "across the local mesh slices.",
            "mesh_dispatches": "Mesh-native match dispatches pulled.",
            "mesh_delta_flushes": "Slice-routed delta flushes applied "
                                  "to the mesh table.",
            "mesh_delta_dirty_slices": "Dirty slices scattered across "
                                       "all delta flushes (flushes x "
                                       "slices touched; the routing "
                                       "numerator).",
            "mesh_delta_gzone_flushes": "Delta flushes that also "
                                        "touched the replicated dense "
                                        "g-zone mirrors (replication "
                                        "cost, not a routing miss).",
            "mesh_delta_rows": "Subscription rows shipped by "
                               "slice-routed delta flushes.",
            "mesh_full_scatters": "Full-table mesh placements (builds "
                                  "and growth re-partitions — never a "
                                  "delta path).",
            "mesh_slice_adoptions": "Slice-map adoptions replayed into "
                                    "the device table (exactly once "
                                    "per epoch).",
            # shared-memory ring publish ordering (parallel/shm_ring.py)
            "shm_ring_fence": "1 when the native release fence backs "
                              "ShmRing tail publishes, 0 on the "
                              "pure-Python x86-TSO fallback.",
            # payload filtering & aggregation (vernemq_tpu/filters/):
            # predicate-phase + window-table health, the tpu_breaker_*
            # pattern extended to the third device path
            "predicate_compiled": "Distinct compiled predicate rows "
                                  "resident in the device predicate "
                                  "tables.",
            "predicate_dispatches_total": "Predicate-phase device "
                                          "dispatches completed.",
            "predicate_host_batches": "Predicate batches served by the "
                                      "exact host evaluator (degraded/"
                                      "small/forced-host).",
            "predicate_rows_filtered_total": "Matched fanout rows "
                                             "removed by payload "
                                             "predicates.",
            "predicate_degraded_sheds_total": "Predicate dispatches "
                                              "refused while the "
                                              "breaker was open (host "
                                              "evaluator served).",
            "predicate_device_failures_total": "Predicate device "
                                               "failures fed to the "
                                               "breaker.",
            "predicate_dispatch_stalls": "Predicate dispatches "
                                         "abandoned at the watchdog "
                                         "deadline (fed to the "
                                         "breaker).",
            "predicate_fail_open_errors": "Predicate phase internal "
                                          "errors that delivered the "
                                          "batch unfiltered (fail-"
                                          "open, loud).",
            "predicate_breaker_state": "Predicate device breaker state "
                                       "(0 closed, 1 half-open, 2 "
                                       "open).",
            "predicate_breaker_opens": "Predicate breaker open "
                                       "transitions (device phase "
                                       "degraded to the host "
                                       "evaluator).",
            "aggregate_windows_open": "Aggregation windows currently "
                                      "accumulating.",
            "aggregate_window_capacity": "Aggregation accumulator-"
                                         "table capacity (grows in "
                                         "doublings to the cap).",
            "aggregate_window_overflows": "Aggregation subscriptions "
                                          "degraded to raw delivery "
                                          "because the window table "
                                          "was full.",
            "aggregate_emissions_total": "Synthesized aggregate "
                                         "PUBLISHes emitted by closed "
                                         "windows.",
            # membership health plane (cluster/health.py): detector
            # verdicts + this node's gossiped load, published like the
            # breaker/governor families
            "cluster_health_suspect_peers": "Peers the accrual failure "
                                            "detector currently marks "
                                            "suspect.",
            "cluster_health_down_peers": "Peers the accrual failure "
                                         "detector currently declares "
                                         "down.",
            "cluster_health_quorum": "1 while this node sees a "
                                     "majority of the joined "
                                     "membership (automatic rebalance "
                                     "admissible).",
            "cluster_load_score": "This node's gossiped load score "
                                  "(queue depth + loop-lag p99 + "
                                  "governor pressure; order matters, "
                                  "not units).",
            "rebalance_cycles": "Automatic planner cycles that passed "
                                "every safety rail and acted.",
        })
        from ..observability import events as _events
        from ..observability.canary import GAUGE_HELP as _canary_help

        self.metrics.register_gauges(self._observability_gauges,
                                     {**_events.gauge_help(),
                                      **_canary_help})

    # ------------------------------------------------------------ plumbing

    def _mesh_slice_count(self) -> int:
        """'sub'-axis size from the ``tpu_mesh`` spec via the ONE
        shared (jax-free) parser — the slice map must exist before
        (and regardless of whether) a backend initialises."""
        if not bool(self.config.get("tpu_mesh_native", True)):
            return 0
        from ..cluster.mesh_map import parse_mesh_spec

        parsed = parse_mesh_spec(self.config.get("tpu_mesh", ""))
        return parsed[1] if parsed else 0

    def _on_mesh_adopt(self, slice_ids, epoch: int) -> None:
        """Slice-map adoption: replay the newly-owned slices' rows into
        the mesh matcher exactly once per epoch. Touches only an
        ALREADY-BUILT tpu view — adoption before the view exists is a
        no-op because the first build ships every owned row anyway.
        The replay takes the matcher lock, which a device flush can
        hold for a long time — and this fires from metadata gossip
        callbacks on the event-loop thread, so it is pushed to an
        executor (the exactly-once guard lives inside adopt_slices,
        so deferred execution stays idempotent)."""
        view = self.registry.reg_views.get("tpu")
        fn = getattr(view, "adopt_slices", None)
        if fn is None:
            return

        def _adopt() -> None:
            try:
                fn(slice_ids, epoch)
            except Exception:
                log.exception("mesh slice adoption failed for %s",
                              slice_ids)

        try:
            asyncio.get_running_loop().run_in_executor(None, _adopt)
        except RuntimeError:
            _adopt()  # no loop (sync/unit-test use): inline is safe

    def _mesh_gauges(self) -> Dict[str, float]:
        out = {
            "mesh_slices_total": 0.0, "mesh_slices_local": 0.0,
            "mesh_rows_resident": 0.0, "mesh_dispatches": 0.0,
            "mesh_delta_flushes": 0.0, "mesh_delta_dirty_slices": 0.0,
            "mesh_delta_gzone_flushes": 0.0, "mesh_delta_rows": 0.0,
            "mesh_full_scatters": 0.0, "mesh_slice_adoptions": 0.0,
        }
        mm = self.mesh_map
        if mm is not None:
            out["mesh_slices_total"] = float(mm.n_slices)
            out["mesh_slices_local"] = float(len(mm.local_slices()))
        view = self.registry.reg_views.get("tpu")
        st = getattr(view, "mesh_status", None)
        st = st() if st is not None else None
        if st:
            out["mesh_slices_total"] = max(out["mesh_slices_total"],
                                           float(st["slices"]))
            out["mesh_rows_resident"] = float(sum(st["rows_per_slice"]))
            out["mesh_dispatches"] = float(st["mesh_dispatches"])
            out["mesh_delta_flushes"] = float(st["route_flushes"])
            out["mesh_delta_dirty_slices"] = float(
                st["route_dirty_slices"])
            out["mesh_delta_gzone_flushes"] = float(
                st["route_gzone_flushes"])
            out["mesh_delta_rows"] = float(st["route_rows"])
            out["mesh_full_scatters"] = float(st["full_scatters"])
            out["mesh_slice_adoptions"] = float(st["slice_adoptions"])
        return out

    def _gauges(self) -> Dict[str, float]:
        out = dict(self.registry.stats())
        out["retain_messages"] = len(self.retain)
        out["retain_memory"] = self.retain.memory()
        out["active_sessions"] = len(self.sessions)
        out["uptime_seconds"] = time.time() - self._started
        if self.overload is not None:
            out.update(self.overload.stats())
        if self.sysmon is not None:
            st = self.sysmon
            out["sysmon_overload_extends"] = float(st.overload_extends)
            out["sysmon_last_loop_lag_seconds"] = round(st.last_lag, 4)
            out["loop_cpu_s"] = round(st.loop_cpu_s, 4)
            from . import sysmon as sm

            out["sysmon_stalls"] = float(sm.stall_count)
            for cause in ("loop", "gil", "host", "gc", "cold"):
                out["sysmon_stall_seconds_" + cause] = getattr(
                    sm, "stall_us_" + cause) / 1e6
        spool = getattr(self.cluster, "spool", None)
        if spool is not None:
            out.update(spool.stats())
        if self.worker_stats is not None:
            # scrape-point aggregation: every worker writes only its own
            # slot; any worker's scrape (and the parent's reads)
            # fuse the block into one node-level view
            try:
                slots = self.worker_stats.read_all()
                live = [s for s in slots
                        if s["heartbeat_age_s"] is not None
                        and s["heartbeat_age_s"] < 5.0]
                out["workers_total"] = float(self.worker_stats.n_workers)
                out["workers_alive"] = float(len(live))
                out["workers_sessions_total"] = float(
                    sum(s["sessions"] for s in live))
                out["workers_admitted_pubs_total"] = float(
                    sum(s["admitted_pubs"] for s in live))
                out["workers_level_max"] = float(
                    max((s["level"] for s in live), default=0))
                out["workers_pressure_max"] = round(
                    max((s["pressure"] for s in live), default=0.0), 4)
            except Exception:
                pass  # a torn attach must never break the scrape
        if self.match_client is not None:
            out.update(self.match_client.stats_dict())
        if self._retained_engine is not None:
            out.update(self._retained_engine.stats())
        if self._retained_collector is not None:
            out.update(self._retained_collector.stats())
        if self.filter_engine is not None:
            out.update(self.filter_engine.stats())
        # storage tier (unified segment engine + batched resumption)
        out["store_breaker_state"] = float(self.store_breaker.state)
        live = garbage = segs = 0.0
        for eng in self._store_engines():
            try:
                est = eng.stats()
            except Exception:
                continue
            live += float(est.get("live_bytes", 0))
            garbage += float(est.get("garbage_bytes", 0))
            segs += float(est.get("segments", 0))
        out["store_live_bytes"] = live
        out["store_garbage_bytes"] = garbage
        out["store_segments"] = segs
        if self._resume_collector is not None:
            out.update(self._resume_collector.stats())
        out.update(self.watchdog.stats())
        out.update(self.recorder.stats())
        out.update(self._mesh_gauges())
        health = getattr(self.cluster, "health", None)
        if health is not None:
            from ..cluster.health import DOWN, SUSPECT, local_load_score

            states = [p.state for p in health.peers.values()]
            out["cluster_health_suspect_peers"] = float(
                states.count(SUSPECT))
            out["cluster_health_down_peers"] = float(states.count(DOWN))
            out["cluster_health_quorum"] = 1.0 if health.quorum_ok() \
                else 0.0
            out["cluster_load_score"] = local_load_score(self)
            planner = getattr(self.cluster, "planner", None)
            if planner is not None:
                out["rebalance_cycles"] = float(planner.cycles)
        from ..parallel.shm_ring import fence_active

        out["shm_ring_fence"] = 1.0 if fence_active() else 0.0
        return out

    def _observability_gauges(self) -> Dict[str, float]:
        """Event-journal counters (process-global ring) plus the canary
        probe's counters — split from _gauges so the HELP text comes
        from the registries themselves (events.KNOWN_EVENTS / canary
        GAUGE_HELP), never a drifting literal."""
        from ..observability import events as _events

        out = _events.journal().stats()
        if self.canary is not None:
            out.update(self.canary.stats())
        return out

    def _peer_histograms(self):
        """Merged stage-histogram blocks of every OTHER live worker
        (heartbeat-fresh slots only — a dead worker's frozen block must
        not pin the tail forever). Wired as ``metrics.histogram_extra``
        in worker mode."""
        from ..observability import histogram as _hist

        ws = self.worker_stats
        out = {}
        if ws is None:
            return out
        for i in range(ws.n_workers):
            if i == self.worker_index:
                continue
            slot = ws.read_slot(i)
            hb = slot.get("heartbeat_age_s")
            if hb is None or hb > 5.0:
                continue
            for name, snap in _hist.unpack_flat(ws.read_hist(i)).items():
                cur = out.get(name)
                out[name] = _hist.merge(cur, snap) if cur else snap
        # the match service's block carries the device-side seams
        # (dispatch/delta/rebuild run in ITS process) — merged when the
        # service is live and a DIFFERENT process (an in-process service
        # shares this worker's registry; merging its block would double
        # count every observation)
        try:
            svc = ws.service_info()
            if (svc.get("pid") and svc["pid"] != os.getpid()
                    and svc.get("heartbeat_age_s") is not None
                    and svc["heartbeat_age_s"] < 5.0):
                for name, snap in _hist.unpack_flat(
                        ws.read_service_hist()).items():
                    cur = out.get(name)
                    out[name] = _hist.merge(cur, snap) if cur else snap
        except Exception:
            pass
        return out

    def merged_journal_events(self, merge: bool = False):
        """The control-plane event stream for this node: the local
        journal (full detail), plus — with ``merge`` in worker mode —
        every OTHER live worker's packed slot events and the match
        service's, interleaved by monotonic stamp into ONE list
        (`vmq-admin events dump --merge` / `timeline dump --merge`; the
        on-hardware capture item scrapes one worker instead of N)."""
        from ..observability import events as _events

        out = _events.journal().snapshot()
        ws = self.worker_stats
        if not merge or ws is None:
            return out
        my_pid = os.getpid()
        for i in range(ws.n_workers):
            if i == self.worker_index:
                continue
            slot = ws.read_slot(i)
            hb = slot.get("heartbeat_age_s")
            if hb is None or hb > 5.0:
                continue
            out.extend(_events.unpack(ws.read_events(i),
                                      pid=slot.get("pid", 0)))
        try:
            svc = ws.service_info()
            if (svc.get("pid") and svc["pid"] != my_pid
                    and svc.get("heartbeat_age_s") is not None
                    and svc["heartbeat_age_s"] < 5.0):
                out.extend(_events.unpack(ws.read_service_events(),
                                          pid=svc["pid"]))
        except Exception:
            pass  # an old-layout block (no event region) stays healthy
        # a peer's packed ring may overlap what we read last time;
        # dedup on the (stamp, code, pid) identity, then one timeline
        seen = set()
        uniq = []
        for e in sorted(out, key=lambda e: e["t"]):
            key = (round(e["t"], 6), e["code"], e.get("pid", 0))
            if key in seen:
                continue
            seen.add(key)
            uniq.append(e)
        return uniq

    def cluster_ready(self) -> bool:
        """is_ready consistency gate (vmq_cluster.erl:67-92)."""
        if self.cluster is not None:
            return self.cluster.is_ready()
        return self._cluster_ready

    # ------------------------------------------------- retain replication

    def _retain_dirty(self, mountpoint: str, topic, value) -> None:
        """Write-behind from the retain cache into the replicated metadata
        store (vmq_retain_srv.erl:186-191 persist + broadcast)."""
        term = None
        if value is not None:
            term = {"payload": value.payload, "props": value.properties,
                    "qos": value.qos, "exp": value.expiry_ts}
        self.metadata.put("retain", (mountpoint,) + tuple(topic), term)
        if self._retained_engine is not None:
            # delta-scatter write-through into the device retained index
            self._retained_engine.on_retain(mountpoint, tuple(topic), value)

    @staticmethod
    def _retain_term(value):
        """Replicated retain term → RetainedMsg (None passes through)."""
        if value is None:
            return None
        from .reg import RetainedMsg

        return RetainedMsg(value["payload"], dict(value.get("props") or {}),
                           value.get("qos", 0), value.get("exp"))

    def _on_retain_event(self, key, old, new, origin) -> None:
        if origin == self.node_name:
            return  # local writes already applied write-through
        mountpoint, topic = key[0], tuple(key[1:])
        value = self._retain_term(new)
        self.retain.apply_remote(mountpoint, topic, value)
        if self._retained_engine is not None:
            # replicated retain changes bypass the dirty hook; the
            # device index must still see them
            self._retained_engine.on_retain(mountpoint, topic, value)

    # -------------------------------------------------- queue migration

    def on_subscriber_moved(self, sid: SubscriberId, new_node: str) -> None:
        """A persistent subscriber's record now points at another node:
        hand off our queue — close any live session (cross-node takeover),
        drain the offline backlog over the acked cluster channel, drop
        local state (vmq_reg_mgr.erl:155-243 + vmq_queue migrate/drain)."""
        queue = self.registry.queues.get(sid)
        if queue is None:
            return
        cur = self.migrations.get(sid)
        if cur is not None and cur.get("state") == "handoff":
            # the live-handoff FSM is already moving this queue — its
            # own fence phase wrote the record that fired this hook;
            # a second concurrent drain task would double-ship
            return
        # register the migration BEFORE the task first runs: callers (the
        # graceful-leave wait loop) poll this map right after the record
        # rewrite, and a not-yet-scheduled task must already count.
        # Retarget bookkeeping (a leave retrying a dead target) survives
        # the re-registration so each peer is tried at most once.
        prev = self.migrations.get(sid) or {}
        self.migrations[sid] = {"target": new_node,
                                "pending": len(queue.offline),
                                "retries": 0, "state": "draining",
                                **{k: prev[k] for k in ("tried",)
                                   if k in prev}}
        task = asyncio.get_event_loop().create_task(
            self._migrate_queue(sid, queue, new_node))
        self._bg_tasks.append(task)

    async def _migrate_queue(self, sid: SubscriberId, queue, new_node: str) -> None:
        session = self.sessions.get(sid)
        if session is not None:
            await session.takeover_close()
        try:
            backlog = queue.start_drain()
        except Exception:
            # the stored backlog could not be read (start_drain restored
            # the queue untouched — state, parked publishes, in-store
            # marker): fail the migration so the retarget/retry machinery
            # owns recovery; nothing was shipped, nothing may be deleted
            st = self.migrations.get(sid)
            if st is not None:
                st["state"] = "failed"
            self.metrics.incr("queue_drain_failed")
            log.exception("queue drain %s -> %s could not load the "
                          "stored backlog; migration failed, local "
                          "state intact", sid, new_node)
            return
        step = self.config.max_msgs_per_drain_step
        # retry/settle delay between drain steps (vmq_server.schema
        # max_drain_time, ms): the reference re-arms drain_start after
        # DrainTimeout on a failed step (vmq_queue.erl:365-368); the ack
        # timeout itself stays remote_enqueue_timeout
        drain_retry_delay = self.config.get("max_drain_time", 500) / 1000.0
        max_retries = self.config.get("migrate_drain_retries", 60)
        state = self.migrations.setdefault(
            sid, {"target": new_node, "retries": 0, "state": "draining"})
        state["pending"] = len(backlog)
        while True:
            sent = 0
            ok = self.cluster is not None
            if backlog and ok:
                for i in range(0, len(backlog), step):
                    try:
                        ok = await self.cluster.remote_enqueue(
                            new_node, sid, backlog[i:i + step])
                    except (ConnectionError, asyncio.TimeoutError):
                        ok = False
                    if not ok:
                        break
                    sent = i + step
                    state["pending"] = len(backlog) - sent
            if ok:
                # messages that raced in mid-drain follow the migration
                # (drain({enqueue,..}) re-fires drain_start,
                # vmq_queue.erl:383-390): keep pulling until dry
                more = queue.drain_pending()
                if more:
                    backlog = more
                    state["pending"] = len(backlog)
                    continue
                self.delete_offline(sid)
                self.metrics.incr("queue_migrated")
                # clean_session stays False: queue_terminated must NOT delete
                # the subscriber record — the new owner just rewrote it
                queue.terminate("migrated")
                self.migrations.pop(sid, None)
                return
            # drain failed mid-way: keep the unsent tail (an unacked chunk
            # may have landed — at-least-once, like any QoS1 redelivery) and
            # retry while the record still points away (block_until_migrated
            # retry loop, vmq_reg.erl:225-244) — bounded: a peer that never
            # acks must not pin a drain task forever
            backlog = backlog[sent:]
            state["pending"] = len(backlog)
            state["retries"] += 1
            self.metrics.incr("queue_drain_retry")
            log.warning("queue drain %s -> %s failed, %d msgs pending "
                        "(retry %d/%d)", sid, new_node, len(backlog),
                        state["retries"], max_retries)
            if state["retries"] >= max_retries:
                from .queue import OFFLINE

                queue.offline.extend(backlog)
                queue.state = OFFLINE
                queue._arm_expiry()  # start_drain cancelled the clock
                state["state"] = "failed"
                self.metrics.incr("queue_drain_failed")
                log.error("queue drain %s -> %s abandoned after %d retries; "
                          "%d msgs restored to the local offline queue",
                          sid, new_node, max_retries, len(backlog))
                return
            await asyncio.sleep(drain_retry_delay)
            rec = self.registry.db.read(sid)
            if rec is None or rec.node == self.node_name:
                # moved back / cleaned up: restore what's left locally
                from .queue import OFFLINE

                queue.offline.extend(backlog)
                queue.state = OFFLINE
                queue._arm_expiry()  # start_drain cancelled the clock
                self.migrations.pop(sid, None)
                return

    def hooks_fire_all(self, name: str, *args: Any) -> None:
        """Fire-and-forget lifecycle hooks (on_register/on_publish/...).
        Sync handlers run inline on the hot path; async handlers are
        scheduled (the reference calls these synchronously in-process)."""
        for fn in self.hooks.handlers(name):
            try:
                res = fn(*args)
                if inspect.isawaitable(res):
                    task = asyncio.ensure_future(res)
                    task.add_done_callback(_log_hook_task_error)
            except Exception:
                log.exception("hook %s handler %r failed", name, fn)

    async def auth_publish(
        self,
        sid: SubscriberId,
        username: Optional[str],
        topic: Tuple[str, ...],
        payload: bytes,
        qos: int,
        retain: bool,
        proto_ver: int,
        properties: Optional[dict] = None,
    ) -> Dict[str, Any]:
        """auth_on_publish(_m5) chain; returns modifier dict (may rewrite
        topic/payload), raises HookError on deny
        (vmq_mqtt_fsm.erl:681-746)."""
        hook = "auth_on_publish_m5" if proto_ver == 5 else "auth_on_publish"
        try:
            res = await self.hooks.all_till_ok(
                hook, username, sid, qos, topic, payload, retain
            )
        except HookError as e:
            if e.reason == "no_matching_hook_found":
                # no plugin answered: allowed unless default-deny is active
                # (vmq_auth.erl:3-8 registers deny hooks when
                # allow_anonymous=off)
                if self.config.allow_anonymous:
                    return {}
                raise HookError("not_authorized") from None
            raise
        if isinstance(res, tuple):
            return res[1]
        return {}

    # ----------------------------------------------------- session support

    async def takeover(self, sid: SubscriberId, new_session: Any) -> None:
        """Duplicate ClientId: disconnect the live session
        (vmq_connect_SUITE takeover semantics)."""
        old = self.sessions.get(sid)
        if old is not None and old is not new_session:
            await old.takeover_close()

    def schedule_will(self, sid: SubscriberId, will: Will, mountpoint: str,
                      proto_ver: int, session_expiry: int) -> None:
        """Publish the LWT, possibly after the v5 will-delay interval
        (vmq_mqtt5_fsm set_delayed_will; vmq_queue.erl:932-942). The will is
        cancelled if the client reconnects before the delay elapses."""
        delay = will.properties.get("will_delay_interval", 0)
        cap = self.config.max_last_will_delay
        if cap:
            delay = min(delay, cap)
        if session_expiry:
            delay = min(delay, session_expiry)

        def _publish_will() -> None:
            try:
                words = tuple(T.validate_topic("publish", will.topic))
            except T.TopicError:
                return
            props = {
                k: v for k, v in will.properties.items()
                if k in ("payload_format_indicator", "message_expiry_interval",
                         "content_type", "response_topic", "correlation_data",
                         "user_property")
            }
            msg = Msg(topic=words, payload=will.payload, qos=will.qos,
                      retain=will.retain, mountpoint=mountpoint, properties=props)
            expiry = props.get("message_expiry_interval")
            if expiry:
                msg.expires_at = time.monotonic() + expiry
            try:
                self.registry.publish(msg)
            except RuntimeError:
                pass

        if delay <= 0:
            _publish_will()
            return

        async def _delayed() -> None:
            await asyncio.sleep(delay)
            self._delayed_wills.pop(sid, None)
            _publish_will()

        self.cancel_delayed_will(sid)
        self._delayed_wills[sid] = asyncio.get_event_loop().create_task(_delayed())

    def cancel_delayed_will(self, sid: SubscriberId) -> None:
        t = self._delayed_wills.pop(sid, None)
        if t is not None:
            t.cancel()

    # ------------------------------------------------------ offline storage

    def store_offline(self, sid: SubscriberId, msg: Msg) -> None:
        try:
            # loop-side synchronous seam: injected latency models a slow
            # disk blocking the loop exactly like the real store would,
            # but capped so a hang drill stays a stall, not an outage.
            # Registered with the stall watchdog for visibility — a
            # synchronous loop-side write cannot be abandoned, but a
            # stall here shows up in watchdog_stalls / `watchdog show`
            # instead of reading as unexplained loop lag.
            with self.watchdog.monitored("store.write", 2.0,
                                         label=f"{sid[0]}/{sid[1]}"):
                faults.inject("store.write", max_delay_s=1.0)
                t0 = time.monotonic()
                self.msg_store.write(sid, msg)
                self.metrics.observe("stage_store_append_ms",
                                     (time.monotonic() - t0) * 1e3)
        except Exception:
            # degraded, not fatal: the in-memory queue still holds the
            # message, so live delivery is unaffected — only the
            # crash-restart durability of THIS message is lost. A failed
            # write must never fail the enqueue (the reference's store
            # is likewise fire-and-forget from the queue's view).
            self.metrics.incr("msg_store_write_errors")
            log.exception("offline store write failed for %s "
                          "(message kept in memory only)", sid)
            return
        self.metrics.incr("msg_store_ops_write")
        if self.msg_store.needs_commit() and not self._store_commit_scheduled:
            # fsync group-commit: the burst's records are flushed; ONE
            # fsync lands at the flush-tick boundary for all of them
            try:
                loop = asyncio.get_running_loop()
            except RuntimeError:
                self._commit_msg_store()  # no loop (tests): sync now
            else:
                self._store_commit_scheduled = True
                loop.call_soon(self._commit_msg_store)

    def _commit_msg_store(self) -> None:
        self._store_commit_scheduled = False
        try:
            coalesced = self.msg_store.commit()
        except Exception:
            self.metrics.incr("msg_store_write_errors")
            log.exception("msg store group commit failed")
            return
        if coalesced:
            self.metrics.incr("msg_store_fsync_coalesced", coalesced)

    def resume_collector(self):
        """Lazy batched-resume collector (storage/resume.py), or None
        when disabled or the store cannot serve off-loop batched reads
        (memory / legacy flat-log stores) — reconnects then recover on
        the synchronous per-session path, unchanged."""
        if (not self.config.get("resume_batched", True)
                or not getattr(self.msg_store, "supports_batched_read",
                               False)):
            return None
        if self._resume_collector is None:
            from ..storage.resume import ResumeCollector

            cfg = self.config
            self._resume_collector = ResumeCollector(
                self.msg_store,
                window_us=cfg.get("resume_window_us", 500),
                max_batch=cfg.get("resume_max_batch", 512),
                host_threshold=cfg.get("resume_host_threshold", 4),
                item_expiry_ms=float(cfg.get("resume_expiry_ms",
                                             30_000)),
                metrics=self.metrics)
            if self.overload is not None:
                # L2 response: resume storms defer behind live publishes
                # exactly like retained replays
                self._resume_collector.defer_gate = \
                    self.overload.defer_replay
        return self._resume_collector

    def recover_offline(self, sid: SubscriberId, queue: SubscriberQueue,
                        may_defer: bool = False,
                        lazy: bool = False) -> None:
        """Rebuild the offline backlog from storage on queue re-creation
        (vmq_queue offline(init_offline_queue), vmq_lvldb_store.erl:396-416).

        ``lazy`` marks boot/remap recovery of a DETACHED persistent
        queue: with a batched-read store the backlog stays parked in
        storage (``queue.offline_in_store``) and loads on first attach
        (through the collector) or at drain — a million parked sessions
        boot without a million read_alls. ``may_defer`` marks the
        reconnect path (a session is attaching right now): the replay
        rides the ResumeCollector — one batched off-loop read per storm
        window instead of one loop-side ``read_all`` per session — with
        the queue parking live publishes until the stored backlog
        lands."""
        if (lazy and self.config.get("resume_batched", True)
                and getattr(self.msg_store, "supports_batched_read",
                            False)):
            queue.offline_in_store = True
            return
        coll = self.resume_collector() if may_defer else None
        if coll is not None:
            try:
                asyncio.get_running_loop()
            except RuntimeError:
                coll = None  # no loop (tests/boot): sync path below
        if coll is None:
            msgs = self.msg_store.read_all(sid)
            if msgs:
                # merge, not extend: on the lazy path the deque may
                # already hold a suffix of the store content (a publish
                # that arrived while parked lands in both)
                queue.merge_recovered(msgs)
                self.metrics.incr("queue_initialized_from_storage")
            return
        queue.begin_resume()
        fut = coll.submit(sid)

        def _done(f: "asyncio.Future") -> None:
            exc = None if f.cancelled() else f.exception()
            if f.cancelled() or exc is not None:
                # batched AND fallback read failed (or the future was
                # cancelled): serve the exact per-session read inline —
                # never leave the queue wedged in the resuming state
                if exc is not None:
                    log.warning("offline resume for %s failed: %s",
                                sid, exc)
                try:
                    msgs = self.msg_store.read_all(sid)
                except Exception:
                    self.metrics.incr("msg_store_read_errors")
                    log.exception("per-session resume fallback read "
                                  "failed for %s", sid)
                    msgs = []
            else:
                msgs = f.result()
            queue.finish_resume(msgs)

        fut.add_done_callback(_done)

    def delete_offline(self, sid: SubscriberId) -> None:
        self.msg_store.delete_all(sid)
        self.metrics.incr("msg_store_ops_delete")

    def offline_delivered(self, sid: SubscriberId, msg: Msg) -> None:
        self.msg_store.delete(sid, msg.msg_ref)

    # ------------------------------------------------- store maintenance

    def _store_engines(self) -> List[Any]:
        """Every compactable engine this broker owns: the msg store's
        (one per bucket instance) plus the cluster spool's journal —
        they share the engine layer, so ONE budgeted driver maintains
        both."""
        engines: List[Any] = []
        ms = self.msg_store
        for st in (getattr(ms, "instances", None) or [ms]):
            eng = getattr(st, "engine", None)
            if eng is not None and hasattr(eng, "compact_step"):
                engines.append(eng)
        spool = getattr(self.cluster, "spool", None) \
            if self.cluster is not None else None
        eng = getattr(spool, "engine", None) if spool is not None else None
        if eng is not None and hasattr(eng, "compact_step"):
            engines.append(eng)
        return engines

    async def store_maintain_once(self, budget: Optional[int] = None) -> int:
        """One budgeted compaction/checkpoint pass over every engine,
        off the event loop on the watchdog's sacrificial executor.
        ``store.compact`` is the drill seam: injected (or real) failures
        feed the store breaker — open, compaction PAUSES and the store
        degrades to append-only (counted) while writes/reads/delivery
        continue untouched; the half-open probe resumes it."""
        from ..robustness.watchdog import StallAbandoned

        if budget is None:
            budget = int(self.config.get("store_compact_budget_bytes",
                                         4 * 1024 * 1024))
        reclaimed = 0
        for eng in self._store_engines():
            if not self.store_breaker.allow():
                self.store_compact_paused += 1
                self.metrics.incr("store_compact_paused")
                break

            def _step(e=eng):
                faults.inject("store.compact", max_delay_s=5.0)
                return e.compact_step(budget)

            label = getattr(eng, "directory", None) \
                or getattr(eng, "path", "") or type(eng).__name__
            try:
                deadline = self._dispatch_deadline_ms() / 1e3
                if deadline > 0:
                    n = await self.watchdog.dispatch_async(
                        "store.compact", _step, deadline, label=label)
                else:
                    n = await asyncio.get_event_loop().run_in_executor(
                        None, _step)
            except StallAbandoned:
                self.store_breaker.record_failure()
                self.store_compact_errors += 1
                self.metrics.incr("store_compact_errors")
                continue
            except Exception:
                opened = self.store_breaker.record_failure()
                self.store_compact_errors += 1
                self.metrics.incr("store_compact_errors")
                if opened:
                    log.warning("store compaction breaker OPEN: the "
                                "store runs append-only until the "
                                "half-open probe succeeds")
                continue
            self.store_breaker.record_success()
            if n:
                self.store_compactions += 1
                self.store_compacted_bytes += int(n)
                self.metrics.incr("store_compactions")
                self.metrics.incr("store_compacted_bytes", int(n))
                reclaimed += int(n)
        # the TTL sweep of expired parked messages rides the same tick,
        # budgeted like compaction and gated by the same breaker (it is
        # store maintenance: a failing engine must not be hammered)
        ms = self.msg_store
        sweep = getattr(ms, "sweep_expired", None)
        if sweep is not None and self.store_breaker.allow():
            sweep_budget = int(self.config.get(
                "store_expire_sweep_budget", 256))
            try:
                n = await asyncio.get_event_loop().run_in_executor(
                    None, sweep, sweep_budget)
            except Exception:
                if self.store_breaker.record_failure():
                    log.warning("store TTL sweep failed; store "
                                "maintenance breaker OPEN")
                self.store_compact_errors += 1
                self.metrics.incr("store_compact_errors")
            else:
                # no record_success here: the compaction steps own the
                # breaker's success/probe accounting — a healthy sweep
                # must not mask an accumulating compaction failure run
                if n:
                    self.metrics.incr("msg_store_expired_swept", n)
        # bucket-probe telemetry: move the bucketed store's counter
        # deltas into $SYS (the store layer holds no metrics handle)
        hits = getattr(ms, "probe_hits", 0)
        misses = getattr(ms, "probe_misses", 0)
        dh, dm = self._probe_drained
        if hits - dh or misses - dm:
            if hits - dh:
                self.metrics.incr("store_bucket_probe_hits", hits - dh)
            if misses - dm:
                self.metrics.incr("store_bucket_probe_misses",
                                  misses - dm)
            self._probe_drained = (hits, misses)
        return reclaimed

    async def _store_maintenance_loop(self) -> None:
        interval = max(0.05, float(self.config.get(
            "store_compact_interval_ms", 1000)) / 1e3)
        while True:
            await asyncio.sleep(interval)
            try:
                await self.store_maintain_once()
            except asyncio.CancelledError:
                raise
            except Exception:
                # the maintenance tick must never die: the next tick
                # retries (a persistent failure shows in the breaker)
                log.exception("store maintenance tick failed")

    def store_status(self) -> Dict[str, Any]:
        """`vmq-admin store show` introspection."""
        engines = []
        for eng in self._store_engines():
            st = {"kind": getattr(eng, "kind", "?")}
            try:
                st.update(eng.stats())
            except Exception:
                pass
            engines.append(st)
        out: Dict[str, Any] = {
            "engine_kind": getattr(self.msg_store, "engine_kind",
                                   "memory"),
            "engines": engines,
            "breaker": self.store_breaker.status(),
            "compactions": self.store_compactions,
            "compacted_bytes": self.store_compacted_bytes,
            "compact_paused": self.store_compact_paused,
            "compact_errors": self.store_compact_errors,
        }
        if self._resume_collector is not None:
            out["resume"] = self._resume_collector.stats()
        if hasattr(self.msg_store, "stats"):
            out["msg_store"] = self.msg_store.stats()
        return out

    # ------------------------------------------------------------ lifecycle

    def batch_collector(self):
        """Lazy publish batch collector for the TPU reg view (µs-scale
        coalescing, SURVEY.md §5.8 host↔TPU batching layer)."""
        if getattr(self, "_collector", None) is None:
            from ..models.tpu_matcher import BatchCollector

            self._collector = BatchCollector(
                self.registry.reg_view("tpu"),
                window_us=self.config.tpu_batch_window_us,
                host_threshold=self.config.tpu_host_batch_threshold,
                lock_busy_shed_ms=self.config.tpu_lock_busy_shed_ms,
                super_batch_k=self.config.tpu_super_batch_k,
                latency_budget_ms=self.config.get(
                    "overload_dispatch_budget_ms", 50.0),
                watchdog=self.watchdog,
                dispatch_deadline_ms=self._dispatch_deadline_ms(),
                item_expiry_ms=self._collector_expiry_ms(),
                filter_engine=self.filter_engine,
                after_release=self.outbox.flush,
            )
        return self._collector

    def _deliver_aggregate(self, mountpoint: str, sub_key, opts,
                           topic_words, payload: bytes) -> None:
        """A closed aggregation window emits ONE synthesized PUBLISH to
        its subscriber (the telemetry-downsampling delivery): topic =
        the concrete aggregated topic, payload = the JSON aggregate.
        Runs on the event loop (the engine marshals emissions here);
        the subscriber's queue applies the normal delivery transform."""
        sid = sub_key[2] if (isinstance(sub_key, tuple) and len(sub_key) == 3
                             and sub_key[0] == "$g") else sub_key
        queue = self.registry.queues.get(sid)
        if queue is None:
            return  # subscriber gone between fold and close: drop
        msg = Msg(topic=tuple(topic_words), payload=payload,
                  qos=getattr(opts, "qos", 0), mountpoint=mountpoint)
        self.registry._enqueue_to(sid, msg, opts)
        self.metrics.incr("aggregate_publishes_delivered")

    def _dispatch_deadline_ms(self) -> float:
        """Device-dispatch abandon deadline (0 when the watchdog is
        off: the pre-watchdog unbounded wait)."""
        if not self.config.get("watchdog_enabled", True):
            return 0.0
        return float(self.config.get("watchdog_dispatch_deadline_ms",
                                     5000))

    def _collector_expiry_ms(self) -> float:
        """Queued-item expiry: derived from the overload dispatch
        budget so the bounded-tail guarantee tracks the same knob the
        governor judges dispatch latency against."""
        if not self.config.get("watchdog_enabled", True):
            return 0.0
        budgets = float(self.config.get(
            "watchdog_collector_expiry_budgets", 4))
        return budgets * float(self.config.get(
            "overload_dispatch_budget_ms", 50.0))

    def retained_engine(self):
        """Lazy per-mountpoint device retained index (the reverse-match
        engine, vernemq_tpu/retained/). Shares the tpu_breaker_* knob
        family with the publish matcher's breaker."""
        if self._retained_engine is None:
            from ..retained.index import RetainedEngine

            cfg = self.config
            self._retained_engine = RetainedEngine(
                self.retain,
                initial_capacity=cfg.get("tpu_retained_initial_capacity",
                                         2048),
                max_fanout=cfg.get("tpu_retained_max_fanout", 256),
                breaker_enabled=cfg.get("tpu_breaker_enabled", True),
                breaker_failure_threshold=cfg.get(
                    "tpu_breaker_failure_threshold", 3),
                breaker_backoff_initial=cfg.get(
                    "tpu_breaker_backoff_initial_ms", 200) / 1e3,
                breaker_backoff_max=cfg.get(
                    "tpu_breaker_backoff_max_ms", 10_000) / 1e3,
                watchdog=(self.watchdog
                          if cfg.get("watchdog_enabled", True) else None),
                rebuild_deadline_s=cfg.get(
                    "watchdog_rebuild_deadline_s", 120.0),
            )
        return self._retained_engine

    def retained_collector(self):
        """Retained-replay batch collector, or None when the device
        retained path is off (config) — the subscribe path then serves
        the exact host walk directly."""
        cfg = self.config
        if (cfg.default_reg_view != "tpu"
                or not cfg.get("tpu_retained_enabled", True)):
            return None
        if self._retained_collector is None:
            from ..retained.collector import RetainedBatchCollector

            self._retained_collector = RetainedBatchCollector(
                self.retained_engine(), self.retain,
                window_us=cfg.get("tpu_retained_window_us", 500),
                max_batch=cfg.get("tpu_retained_max_batch", 1024),
                host_threshold=cfg.get("tpu_retained_host_threshold", 4),
                latency_budget_ms=cfg.get(
                    "overload_dispatch_budget_ms", 50.0),
                watchdog=self.watchdog,
                dispatch_deadline_ms=self._dispatch_deadline_ms(),
                item_expiry_ms=self._collector_expiry_ms(),
            )
            if self.overload is not None:
                # L2 response: replay storms defer behind live publishes
                self._retained_collector.defer_gate = \
                    self.overload.defer_replay
        return self._retained_collector

    def _resolve_base_dirs(self) -> None:
        """Honor the setup.data_dir / setup.log_dir release knobs
        (vmq_server.schema setup.* tree): relative storage paths resolve
        under data_dir, a bare log filename under log_dir."""
        import os as _os

        data_dir = self.config.get("data_dir", "")
        if data_dir:
            for key in ("message_store_dir", "metadata_dir",
                        "cluster_spool_dir"):
                path = self.config.get(key, "")
                if path and not _os.path.isabs(path):
                    self.config.set(
                        key,
                        _os.path.normpath(_os.path.join(data_dir, path)))
        log_dir = self.config.get("log_dir", "")
        log_file = self.config.get("log_file", "")
        if log_dir and log_file and not _os.path.isabs(log_file):
            self.config.set("log_file", _os.path.join(log_dir, log_file))

    async def _publish_worker_stats(self, interval: float = 0.25) -> None:
        """Heartbeat this worker's health row into the shared stats
        block (pid, live sessions, admitted publishes). The overload
        level/pressure pair is written by the governor's own tick and
        the loop-lag samples by sysmon — every field has exactly one
        writer, so the block needs no locking."""
        from ..observability import events as _events
        from ..observability import histogram as _hist

        ws = self.worker_stats
        idx = self.worker_index
        while True:
            try:
                ws.write_health(
                    idx, pid=os.getpid(), sessions=len(self.sessions),
                    admitted=self.metrics.value("mqtt_publish_received"))
                # publish this worker's stage histograms into its slot:
                # the scrape-point aggregation reads every live slot so
                # ANY worker's /metrics (and the parent's read)
                # shows the node-level merged families
                ws.write_hist(idx, _hist.pack_all())
                ws.write_events(idx, _events.journal().pack())
            except Exception:
                log.exception("worker stats heartbeat failed")
            await asyncio.sleep(interval)

    async def start_systree(self) -> None:
        """$SYS tree publisher (vmq_systree.erl): periodic internal publish
        of all metrics to $SYS/<node>/... topics. Mountpoint, QoS and
        retain flag follow the systree_* knobs (vmq_server.schema
        systree_mountpoint/qos/retain)."""
        interval = self.config.systree_interval
        if interval <= 0:
            return  # 0 = disabled (reference schema systree_interval)
        mountpoint = self.config.get("systree_mountpoint", "")
        qos = min(max(int(self.config.get("systree_qos", 0)), 0), 2)
        retain = bool(self.config.get("systree_retain", False))
        while True:
            await asyncio.sleep(interval)
            for name, value in self.metrics.all_metrics().items():
                topic = ("$SYS", self.node_name, *name.split("_"))
                msg = Msg(topic=topic, payload=str(value).encode(),
                          qos=qos, retain=retain, mountpoint=mountpoint)
                try:
                    self.registry.publish(msg)
                except RuntimeError:
                    pass

    # ---------------------------------------------------- session tracing

    def trace_frame(self, direction: str, mountpoint: str,
                    client_id: Optional[str], frame: Any,
                    session_start: bool = False) -> None:
        """Frame tap from the session layer; no-op unless a tracer is
        active and the client matches (vmq_tracer role)."""
        t = self.tracer
        if t is None or not t.matches(mountpoint, client_id):
            return
        if session_start:
            t.session_event(f'New session for client "{client_id}"')
        t.trace(direction, client_id, frame)

    def start_trace(self, client_id: str, mountpoint: str = "",
                    **opts) -> Any:
        """vmq-admin trace client client-id=X; single tracer at a time
        (vmq_tracer_cli: "another trace is already running")."""
        if self.tracer is not None:
            raise RuntimeError("another trace is already running")
        from ..admin.tracer import Tracer

        opts.setdefault("metrics", self.metrics)
        self.tracer = Tracer(client_id, mountpoint, **opts)
        n = sum(1 for sid in self.sessions
                if sid == (mountpoint, client_id))
        self.tracer.session_event(
            f'Starting trace for {n} existing sessions for client "{client_id}"')
        return self.tracer

    def stop_trace(self) -> None:
        self.tracer = None

    def _setup_logging(self) -> None:
        """Attach the configured log sinks (console is the host app's
        concern; file + syslog mirror the reference's lager sinks)."""
        import logging as _logging

        if not self.config.log_file and not self.config.log_syslog:
            return  # no sink knobs set: leave the host app's config alone
        root = _logging.getLogger("vernemq_tpu")
        level = getattr(_logging, str(self.config.log_level).upper(),
                        _logging.INFO)
        root.setLevel(level)
        fmt = _logging.Formatter(
            "%(asctime)s [%(levelname)s] %(name)s: %(message)s")
        if self.config.log_file:
            fh = _logging.FileHandler(self.config.log_file)
            fh.setFormatter(fmt)
            root.addHandler(fh)
            self._log_handlers.append(fh)
        if self.config.log_syslog:
            import logging.handlers as _lh

            try:
                sh = _lh.SysLogHandler(address=self.config.log_syslog_address)
                sh.setFormatter(fmt)
                root.addHandler(sh)
                self._log_handlers.append(sh)
            except OSError as e:
                log.warning("syslog sink unavailable: %s", e)

    async def start(self) -> None:
        self._log_handlers: List[Any] = []
        self._setup_logging()
        # the outbox's writer thread: plain-socket sends off the loop
        self.outbox.start()
        # observability master switch: off reduces every histogram/
        # profiler seam to one module-global boolean test (PERF.md
        # §6, PR 25, has what the seams cost when on). The flag is
        # process-global like the registries it gates.
        from ..observability import histogram as _hist
        from ..observability import profiler as _profiler

        _hist.set_enabled(
            bool(self.config.get("observability_enabled", True)))
        _profiler().set_capacity(
            int(self.config.get("profiler_capacity", 2048)))
        from ..observability import events as _events

        _events.journal().set_capacity(
            int(self.config.get("events_capacity", 2048)))
        # warm-load from persisted metadata: routing state, offline queues,
        # retain cache (boot order of vmq_server_sup + vmq_reg_trie /
        # vmq_retain_srv warm-loads)
        self.registry.bootstrap()
        if self.filter_engine is not None:
            # time-window closes + aggregate emissions marshal onto the
            # loop from the dispatch threads
            self.filter_engine.arm(asyncio.get_event_loop())
        for key, value in self.metadata.fold("retain"):
            self.retain.apply_remote(key[0], tuple(key[1:]),
                                     self._retain_term(value))
        # mesh slice map: claim this node's slices (deterministic
        # round-robin over the membership; a single node claims all) and
        # re-claim whenever membership changes — the map gossips through
        # the metadata plane like the netsplit CAPs, and newly-owned
        # slices replay their rows exactly once (_on_mesh_adopt)
        if self.mesh_map is not None:
            def _mesh_reclaim(*_a) -> None:
                try:
                    members = (self.cluster.members()
                               if self.cluster is not None else None)
                    self.mesh_map.claim_local(members)
                except Exception:
                    log.exception("mesh slice claim failed")

            _mesh_reclaim()
            self.metadata.subscribe("members", _mesh_reclaim)
        # boot-time fault plan (robustness harness): deterministic
        # injected faults per the fault_injection config — empty list =
        # nothing installed, zero overhead
        plan_spec = self.config.get("fault_injection", [])
        if plan_spec:
            self._boot_fault_plan = faults.install(
                faults.FaultPlan.from_config(
                    plan_spec,
                    seed=self.config.get("fault_injection_seed", 0)))
            log.warning("fault-injection plan ACTIVE at boot: %d rules, "
                        "seed %s", len(plan_spec),
                        self.config.get("fault_injection_seed", 0))
        # crash-restart supervision (vmq_server_sup one_for_one analog)
        from .supervisor import Supervisor

        self.supervisor = Supervisor(
            self,
            max_restarts=self.config.get("supervisor_max_restarts", 20),
            restart_window=self.config.get("supervisor_restart_window",
                                           60.0))
        self.supervisor.watch_listeners()
        if self.config.systree_enabled:
            self.supervisor.spawn("systree", self.start_systree)
        if self.config.http_enabled:
            from ..admin.http import HttpServer

            self.http = HttpServer(self, self.config.http_host,
                                   self.config.http_port,
                                   tuple(self.config.http_modules))
            await self.http.start()
        if self.config.graphite_enabled:
            from ..admin.graphite import GraphiteReporter

            self.graphite = GraphiteReporter(self)
            self.graphite.start()
        if self.config.get("bridges"):
            self.plugins.enable("vmq_bridge")
        # conf-file plugins (plugins.<name> = on) and listeners
        # (listener.<kind>.<name> = ip:port) — the boot-time half of the
        # vernemq.conf layer (broker/conf.py)
        for p in self.config.get("plugins", []):
            self.plugins.enable(p["name"], **p.get("opts", {}))
        conf_listeners = self.config.get("listeners", [])
        if conf_listeners:
            if self.listeners is None:
                from .listeners import ListenerManager

                ListenerManager(self)
            for ln in conf_listeners:
                await self.listeners.start_listener(
                    ln["kind"], ln.get("addr", "127.0.0.1"),
                    ln.get("port", 0), ln.get("opts"))
        # stall watchdog: monitor thread scanning the monitored-op
        # registry for overdue waits (robustness/watchdog.py). Started
        # before the governor/sysmon so a wedge during boot warm-up is
        # already observable.
        if self.config.get("watchdog_enabled", True):
            self.watchdog.tick_s = self.config.get(
                "watchdog_tick_ms", 100) / 1e3
            self.watchdog.start()
        # budgeted store maintenance: segment compaction + checkpoints
        # for every engine (msg store buckets + cluster spool journal)
        # run OFF the loop on the sacrificial executor, at most
        # store_compact_budget_bytes copied per engine per tick; the
        # store breaker pauses it (append-only degraded mode) on
        # injected or real failures without touching delivery
        if float(self.config.get("store_compact_interval_ms", 1000)) > 0:
            self._bg_tasks.append(asyncio.get_event_loop().create_task(
                self._store_maintenance_loop()))
        # multi-process front end: attach the shared worker stats slot
        # and, when the parent configured a match service, mount the
        # ring-backed reg view so folds route to the service process
        # (broker/match_service.py). Both are worker-only — the classic
        # boot leaves the config keys empty and changes nothing.
        stats_name = str(self.config.get("worker_stats_block", "") or "")
        if stats_name:
            from ..parallel.shm_ring import WorkerStatsBlock

            try:
                self.worker_stats = WorkerStatsBlock.attach(stats_name)
                # the parent's workers_total must agree with the slot
                # count baked into the segment header: a mismatch means
                # this worker attached a STALE block from a previous
                # group generation (or a torn rolling restart) — peer
                # pressure fusion and `workers show` would read slots
                # that belong to nobody
                expected = int(self.config.get("workers_total", 1) or 0)
                if expected and expected != self.worker_stats.n_workers:
                    log.warning(
                        "worker stats block %r has %d slots but "
                        "workers_total=%d — parent and worker config "
                        "generations disagree (stale segment?)",
                        stats_name, self.worker_stats.n_workers,
                        expected)
                # scrape-point histogram aggregation: merge the OTHER
                # live workers' slot blocks into this worker's scrape
                # (our own observations come from the live in-process
                # registry, which is fresher than our own slot)
                self.metrics.histogram_extra = self._peer_histograms
            except Exception:
                log.exception("worker stats block %r unavailable; "
                              "running without fused worker pressure",
                              stats_name)
        req_ring = str(self.config.get("match_service_req_ring", "") or "")
        if req_ring and stats_name:
            from .match_service import MatchServiceClient, ShmMatchView

            try:
                client = MatchServiceClient(
                    req_ring,
                    str(self.config.get("match_service_resp_ring", "")),
                    stats_name, self.worker_index, self.node_name,
                    timeout_ms=float(self.config.get(
                        "match_service_timeout_ms", 2000)))
                self.match_client = client
                # pre-mounting "tpu" short-circuits the accelerator
                # probe: the worker never touches a device — the
                # service owns the mirror; the worker's trie stays the
                # degraded-mode oracle
                self.registry.reg_views["tpu"] = ShmMatchView(
                    self.registry, client)
                client.start(self.registry)
            except Exception:
                log.exception("match-service rings unavailable; this "
                              "worker matches on its local trie")
        # materialize the reg views listed in the reg_views knob
        # (vmq_server.schema reg_views: views started at BOOT, not on
        # first default_reg_view routing) — an operator listing tpu with
        # default_reg_view=trie wants the device table building now so
        # a later `config set default_reg_view tpu` flips onto a warm
        # view; the worker-mode ShmMatchView mount above stays
        # authoritative (already-present names are skipped)
        from .schema import REG_VIEW_ALIASES

        valid_views = sorted(set(REG_VIEW_ALIASES.values()))
        for view_name in self.config.get("reg_views", ["trie"]):
            if view_name in self.registry.reg_views:
                continue
            if view_name not in valid_views:
                log.error("reg_views names unknown view %r (valid: %s)",
                          view_name, ", ".join(valid_views))
                continue
            if view_name == self.config.default_reg_view:
                continue  # built below, where failure is a boot error
            try:
                self.registry.reg_view(view_name)
            except Exception:
                # pre-building a NON-default view is an optimization,
                # never a boot gate: it logs and stays lazy
                log.exception("reg_views: building view %r failed at "
                              "boot; it stays lazy", view_name)
        if self.config.default_reg_view == "tpu":
            # the view routing depends on: a backend that cannot
            # initialise (or a tpu_mesh it cannot satisfy) stops the
            # boot here instead of serving from the host trie unnoticed
            view = self.registry.reg_view("tpu")
            if len(self.registry.trie("")) and hasattr(view, "begin_load"):
                # subscriptions came back with the subscriber DB: build
                # their device table now, off the loop thread, instead of
                # behind the first publish burst
                view.begin_load("")
        # adaptive overload governor BEFORE sysmon so the lag sampler can
        # feed it from its very first sample (robustness/overload.py)
        from ..robustness.overload import OverloadGovernor

        cfg = self.config
        self.overload = OverloadGovernor(
            self,
            mode=cfg.get("overload_mode", "governor"),
            tick_s=cfg.get("overload_tick_ms", 250) / 1e3,
            hold_s=cfg.get("overload_hold_s", 5.0),
            exit_ratio=cfg.get("overload_exit_ratio", 0.5),
            l1_enter=cfg.get("overload_l1_enter", 0.25),
            l2_enter=cfg.get("overload_l2_enter", 0.5),
            l3_enter=cfg.get("overload_l3_enter", 0.8),
            l1_throttle_ms=cfg.get("overload_l1_throttle_ms", 100),
            l2_client_rate=cfg.get("overload_l2_client_rate", 50),
            l2_burst=cfg.get("overload_l2_burst", 100),
            l3_disconnect_top=cfg.get("overload_l3_disconnect_top", 5))
        self.overload.start()
        if self.worker_stats is not None:
            # fuse per-worker governors into one cluster-style level:
            # each tick writes THIS worker's local pressure into its
            # slot and reads the peers' as the "workers" signal
            self.overload.attach_worker_stats(self.worker_stats,
                                              self.worker_index)
            self.supervisor.spawn("worker-stats",
                                  self._publish_worker_stats)
        if self.config.get("sysmon_enabled", True):
            from .sysmon import Sysmon

            self.sysmon = Sysmon(
                self,
                lag_threshold=self.config.get("sysmon_lag_threshold", 0.25),
                memory_high_watermark=self.config.get(
                    "sysmon_memory_high_watermark", 0),
                lag_exit_ratio=self.config.get("sysmon_lag_exit_ratio",
                                               0.5))
            self.sysmon.start()
        from .sysmon import CrlRefresher

        self.crl_refresher = CrlRefresher(
            self, interval=self.config.get("crl_refresh_interval", 60.0))
        self.crl_refresher.start()
        # canary SLO probe: a loopback subscriber + a periodic synthetic
        # publish through the FULL path feeding e2e_canary_ms — the
        # continuous black-box end-to-end signal. Supervised like the
        # systree reporter; zero footprint unless enabled.
        if (bool(self.config.get("canary_enabled", False))
                and bool(self.config.get("observability_enabled", True))):
            from ..observability.canary import CanaryProbe

            self.canary = CanaryProbe(
                self,
                interval_ms=float(self.config.get("canary_interval_ms",
                                                  1000)),
                slo_ms=float(self.config.get("canary_slo_ms", 250.0)))
            self.supervisor.spawn("canary", self.canary.run)
        # hot-upgrade baseline LAST, after every boot-time lazy import,
        # so `vmq-admin updo diff` is relative to what this boot loaded
        # (vmq_updo.erl:60-71 diffs loaded vsn vs on-disk beam); modules
        # imported even later are adopted on first diff() sight
        from . import updo

        updo.baseline()

    async def stop(self) -> None:
        for t in self._bg_tasks:
            t.cancel()
        for t in self._delayed_wills.values():
            t.cancel()
        self._delayed_wills.clear()
        # sessions first so lifecycle hooks (on_client_offline/gone) still
        # reach enabled plugins; then plugins (a bridge keeps an outbound
        # client reconnecting); listeners last — Server.wait_closed blocks
        # until every connection handler (incl. bridge links) has returned
        if getattr(self, "supervisor", None) is not None:
            self.supervisor.stop()
        import logging as _logging

        for h in getattr(self, "_log_handlers", []):
            _logging.getLogger("vernemq_tpu").removeHandler(h)
            h.close()
        if self.sysmon is not None:
            self.sysmon.stop()
        if self.overload is not None:
            self.overload.stop()
        if self.crl_refresher is not None:
            self.crl_refresher.stop()
        for s in list(self.sessions.values()):
            await s.close("broker_shutdown", send_will=False)
        await self.plugins.stop_all()
        if self.cluster is not None:
            # the inter-node channel goes down after sessions/plugins
            # (migration + lifecycle hooks may still need it) and before
            # listeners; idempotent when the cluster was started as a
            # `vmq` listener (stop_all covers that handle too)
            await self.cluster.stop()
        if self.listeners is not None:
            await self.listeners.stop_all()
        for server in self._servers:
            server.close()
        # wind down the tpu view's background warm threads (they hold no
        # broker state, but must not keep compiling into a dead matcher)
        tpu_view = self.registry.reg_views.get("tpu")
        if tpu_view is not None and hasattr(tpu_view, "close"):
            tpu_view.close()
        if self._retained_collector is not None:
            # settle pending replay futures (host walk) and disarm the
            # flush timer BEFORE closing the engine it dispatches into
            self._retained_collector.close()
        if self._retained_engine is not None:
            self._retained_engine.close()
        if self.filter_engine is not None:
            self.filter_engine.close()
        # the fault registry is process-global: a plan THIS broker
        # installed at boot must not keep injecting into other broker
        # instances in the process (multi-node tests, embedding) — but
        # leave a plan installed live via the admin surface alone
        if (getattr(self, "_boot_fault_plan", None) is not None
                and faults.active() is self._boot_fault_plan):
            faults.clear()
        if self.worker_stats is not None:
            # the match client's own attachment went down with the tpu
            # view close above; this is the broker's direct handle
            self.worker_stats.close()
            self.worker_stats = None
        # after the collectors/views that dispatch through it are down;
        # wedged sacrificial threads are daemons and die with the process
        self.watchdog.stop()
        if self._resume_collector is not None:
            # settle pending resume futures (per-session reads) BEFORE
            # closing the store they read from
            self._resume_collector.close()
        self.msg_store.close()
        self.metadata.close()
        # last: every listener is down and every session closed, so what
        # they wrote is handed off; the writer's thread is joined
        self.outbox.close()
