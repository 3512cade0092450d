"""Layered broker configuration.

Mirrors the reference's config system shape (``vmq_config.erl``: file <
app-default < stored-global < stored-per-node, cached lookups;
``priv/vmq_server.schema`` for the knob names) without cuttlefish — plain
defaults dict + override layers. Knob names keep the reference's schema
names so an operator coming from the reference finds the same switches.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

DEFAULTS: Dict[str, Any] = {
    # connection / session (vmq_server.schema)
    # off by default like the reference: with no auth plugin answering the
    # auth_on_register chain, connects are denied (vmq_auth.erl:3-8
    # registers deny-all fallback hooks when allow_anonymous=off)
    "allow_anonymous": False,
    "max_client_id_size": 100,
    "persistent_client_expiration": 0,  # seconds; 0 = never expire
    "max_inflight_messages": 20,
    "max_online_messages": 1000,
    "max_offline_messages": 1000,
    "queue_deliver_mode": "fanout",  # fanout | balance (vmq_queue.erl:826-835)
    "queue_type": "fifo",  # fifo | lifo offline drop policy (vmq_queue.erl:845-865)
    "upgrade_outgoing_qos": False,
    "allow_multiple_sessions": False,
    "retry_interval": 20,
    "max_message_rate": 0,  # msgs/sec per session; 0 = unlimited
    "max_message_size": 0,  # bytes; 0 = unlimited
    "m5_max_packet_size": 0,  # broker->v5-client frame cap; 0 = client's say
    "max_last_will_delay": 0,  # v5 will-delay cap, seconds
    "receive_max_broker": 10,
    "receive_max_client": 65535,
    "suppress_lwt_on_session_takeover": False,
    "coordinate_registrations": True,
    # netsplit CAP flags (vmq_server.schema:13-35, vmq_reg.erl:65-70)
    "allow_register_during_netsplit": False,
    "allow_publish_during_netsplit": False,
    "allow_subscribe_during_netsplit": False,
    "allow_unsubscribe_during_netsplit": False,
    # shared subscriptions (vmq_shared_subscriptions.erl:90-106)
    "shared_subscription_policy": "prefer_local",  # prefer_local|local_only|random
    # cluster (vmq_cluster_node.erl buffering; vmq_queue drain batching)
    "outgoing_clustering_buffer_size": 10_000_000,  # bytes
    "max_msgs_per_drain_step": 100,
    "max_drain_time": 500,  # ms cap per migration drain step
    "remote_enqueue_timeout": 5000,  # ms ack timeout for remote enqueues
    # store-and-forward spool for QoS>=1 cluster frames (cluster/spool.py):
    # journaled before the writer, seq-tagged on the wire (msq), deleted
    # on cumulative acks, replayed on channel re-establishment — the
    # cross-node delivery guarantee through partitions and peer restarts
    "cluster_spool_enabled": True,
    # journal directory; empty = in-memory journal (replay across
    # partitions and buffer overflow, no crash durability); set a path
    # (resolved under data_dir) for crash-restart replay from disk
    "cluster_spool_dir": "",
    "cluster_spool_max_bytes": 128 * 1024 * 1024,
    # cumulative-ack pacing on the receiver (ms between acks per origin)
    "cluster_spool_ack_interval": 50,
    # ack watchdog: unacked frames older than this replay over the live
    # channel (recovers in-channel loss where no reconnect fires replay)
    "cluster_spool_retransmit_ms": 1000,
    # frames the watchdog replays per tick, with a persistent per-peer
    # cursor resuming where the last tick stopped — a long partition at
    # high publish rates no longer re-ships the whole journal every
    # tick (the quadratic wire cost flagged in ROADMAP). 0 = unbudgeted
    # (full replay per tick, the old behaviour).
    "cluster_spool_replay_burst": 512,
    # compat no-op (see schema.COMPAT_NOOPS): queues are dict-sharded
    "queue_sup_sup_children": 50,
    # reg views started at boot; entries from schema.REG_VIEW_ALIASES
    "reg_views": ["trie"],
    # bounded migration-drain retry (max_drain_time apart) before the
    # backlog is restored locally and the migration is marked failed
    "migrate_drain_retries": 60,
    # live handoff (cluster/handoff.py): per-phase deadlines of the
    # freeze→drain→fence→adopt state machine. The freeze deadline
    # bounds the pause a moving unit's clients can observe (freeze,
    # fence and adopt each run under it); the drain deadline bounds
    # the backlog flush — past either the handoff rolls back and the
    # OLD owner keeps serving (degraded, never stuck).
    "handoff_freeze_deadline_ms": 500,
    "handoff_drain_deadline_s": 10.0,
    # live v5 handoff: moved sessions get DISCONNECT 0x9D (Server
    # moved, with the Server Reference property) after fence+adopt
    # instead of a takeover kick — the client reconnects straight to
    # the new owner. v3/4 sessions always keep the takeover path.
    "handoff_v5_redirect": True,
    # sessions per batched drain handoff: each batch bound for one
    # target shares ONE fence write (store_many) instead of a
    # per-session record rewrite
    "handoff_batch_max_sessions": 64,
    # membership health plane (cluster/health.py): phi-accrual failure
    # detection over the existing cluster traffic. Every delivered
    # inbound batch is a heartbeat (the 1s idle ping guarantees a
    # floor); phi scores the current silence in units of the observed
    # cadence — suspect at ~3.5 missed intervals, down at ~18. The
    # exit_ratio/hold pair is the governor's flap-suppression
    # hysteresis: re-entering alive needs phi below
    # phi_suspect*exit_ratio for hold_s straight.
    "health_enabled": True,
    "health_tick_ms": 500,
    "health_window": 64,
    "health_phi_suspect": 1.5,
    "health_phi_down": 8.0,
    "health_exit_ratio": 0.5,
    "health_hold_s": 3.0,
    # automatic rebalance planner: fires on join/leave/down/alive,
    # debounced. The debounce doubles as the correlated-failure
    # confirmation window: when this node is being isolated, its links
    # die together but the DOWN verdicts skew by up to the 1s idle-ping
    # phase, so the window must exceed that cadence for both verdicts
    # to land in one batch and the quorum gate to see them together.
    # Per-peer cooldown is the anti-ping-pong rail (at most
    # one cycle per peer per window); the quorum gate refuses automatic
    # action while this node cannot see a membership majority (a
    # netsplit minority sits still — CAP machinery owns partitions);
    # max_concurrent caps in-flight handoffs node-wide (automation must
    # not freeze half the node at once).
    "rebalance_enabled": True,
    "rebalance_require_quorum": True,
    "rebalance_debounce_s": 1.5,
    "rebalance_cooldown_s": 10.0,
    "rebalance_max_concurrent": 4,
    # client-facing address gossiped to peers (hlo/ping "caddr"): what
    # a v5 server-redirect DISCONNECT hands out as the Server Reference
    # for sessions moved HERE. Empty = peers fall back to the node name.
    "cluster_advertised_address": "",
    # QoS2 exactly-once dedup bound: max awaiting-release pids held
    # per session before oldest-first eviction (qos2_dedup_evictions);
    # 0 = unbounded (the pre-cap behaviour)
    "qos2_dedup_max": 4096,
    # v5
    "topic_alias_max_client": 0,
    "topic_alias_max_broker": 0,
    "max_session_expiry_interval": 0,  # 0 → no cap (v5 session_expiry_interval)
    # matcher
    "default_reg_view": "trie",  # trie | tpu — the reg-view seam (vmq_mqtt_fsm.erl:105)
    "tpu_batch_window_us": 200,
    # per-part width (k) of the device's flat result: a publish that
    # matches more rows in a part is answered by a second, wide pass (the
    # whole bit mask of its regions) — 256 balances extraction cost vs
    # how often that second round trip is paid
    "tpu_max_fanout": 256,
    # flat result-buffer slots per pub, batch-averaged (C = Bpad * this);
    # publishes past the buffer's end take the wide pass too
    "tpu_flat_avg": 128,
    # pre-size the device table for a known subscriber scale: growth
    # rebuilds (repartition + full re-upload) happen at doublings, so an
    # operator expecting 1M subscriptions boots with the bucketed layout
    # already in place instead of rebuilding through the ladder
    "tpu_initial_capacity": 1024,
    # scripting: SQL function wrapping the password in the bundled
    # mysql auth-script query — password | md5 | sha1 | sha256
    # (vmq_diversity_mysql.erl:119-129 hash_method)
    "mysql_password_hash_method": "password",
    # Lua interpreter states per script (the balancing pool of
    # vmq_diversity_script_sup_sup.erl): concurrent auth hooks each
    # check a state out instead of serialising on one interpreter
    "diversity_num_states": 4,
    # fused Pallas tile matcher for the probe phases (ops/pallas_match.py);
    # off by default until an on-chip A/B shows a win (a perf_opt PR
    # judged by the benchmark, ROADMAP S8); a lowering failure is a
    # device failure (breaker), never a silent switch back to the XLA
    # kernel
    "tpu_use_pallas": False,
    # flushes this small are matched on the host trie instead of paying a
    # device round trip (hybrid dispatch, SURVEY.md §7.2); 0 disables
    "tpu_host_batch_threshold": 8,
    # multi-device serving mesh "BxS" (batch x sub axes, e.g. "1x8") or
    # "S" (sub-only) — when set, the tpu reg view shards the subscription
    # table over the 'sub' axis and the publish batch over 'batch'
    # (SURVEY §5.7: the per-node trie replica sharded across chips,
    # vmq_reg_trie.erl:503-520). Empty = single-device matcher.
    "tpu_mesh": "",
    # mesh implementation: the mesh-native matcher (persistent
    # NamedSharding/pjit arrays placed via partition rules, slice-routed
    # delta scatter, multi-process capable — parallel/mesh_match.py) is
    # the default when tpu_mesh is set; false keeps the legacy per-call
    # shard_map seat
    "tpu_mesh_native": True,
    # device flush waits at most this long for the matcher lock before
    # the whole flush serves from the host trie (0 = unbounded wait)
    "tpu_lock_busy_shed_ms": 500,
    # wire plane (protocol/fastpath.py + native/codec.cc): the QoS0
    # object-free fast path over the batched frame table. Off = every
    # frame materialises and takes the classic session handler (the
    # pre-wire-plane behaviour); the batch parser itself stays on
    # either way (it is byte-identical). The NATIVE codec has its own
    # escape hatch: the VMQ_NATIVE_CODEC=0 environment variable.
    "wire_fastpath_enabled": True,
    # under load, up to this many full batch windows coalesce into ONE
    # device dispatch (match_many super-batches: K round trips -> 1,
    # the continuous-batching posture); 1 disables
    "tpu_super_batch_k": 8,
    # device-path circuit breaker (robustness/breaker.py): N consecutive
    # dispatch failures open it — ALL matching serves from the exact
    # host trie until a half-open probe (exponential backoff + jitter
    # between attempts, bounded by the max) succeeds and the matcher
    # re-warms. Disabled = raw device errors propagate to publishers.
    "tpu_breaker_enabled": True,
    "tpu_breaker_failure_threshold": 3,
    "tpu_breaker_backoff_initial_ms": 200,
    "tpu_breaker_backoff_max_ms": 10_000,
    # pre-compile the delta-scatter shape ladder (Dpad 2..this) at
    # matcher startup so the first post-subscribe flush pays a scatter,
    # not a compile (the sub_to_matchable_ms_max tail); 0 disables
    "tpu_delta_warm_max": 128,
    # device-resident retained-message index (vernemq_tpu/retained/):
    # SUBSCRIBE retained replay reverse-matches filter batches against
    # the retained-topic table on the device instead of the serial host
    # walk. Active only when default_reg_view=tpu; any degraded signal (breaker open, rebuild,
    # per-filter escape) serves the exact host walk.
    "tpu_retained_enabled": True,
    # replay coalescing window (µs) and max filters per dispatch
    "tpu_retained_window_us": 500,
    "tpu_retained_max_batch": 1024,
    # flushes this small are served by the host walk on the event loop
    # (a lone subscribe must not pay a device round trip); 0 disables
    "tpu_retained_host_threshold": 4,
    # per-filter device match cap: a filter matching more retained
    # topics than this resolves against the host store instead
    "tpu_retained_max_fanout": 256,
    # pre-size the retained device table (growth rebuilds at doublings)
    "tpu_retained_initial_capacity": 2048,
    # payload filtering & windowed aggregation (vernemq_tpu/filters/,
    # MQTT+): subscriptions may carry a ?$-suffix predicate/aggregation
    # over fields named in the per-mountpoint schema registry
    # (`vmq-admin schema set`). Disabled = the '?' stays part of the
    # topic and no engine is built — byte-identical to the pre-filter
    # broker. Enabled with no schemas/predicates registered costs one
    # dict probe per publish.
    "payload_filters_enabled": True,
    # boot-installed schemas: [{mountpoint, topic, fields}] dicts, e.g.
    # {"mountpoint": "", "topic": "sensors/+/temp",
    #  "fields": "value:number,unit:enum(c|f)"}
    "payload_schemas": [],
    # (matched-subscriber x predicate) pairs below this are evaluated
    # by the exact host evaluator instead of paying a device round trip
    # (the predicate analog of tpu_host_batch_threshold)
    "predicate_host_threshold": 16,
    # device pair cap per predicate dispatch; larger batches host-serve
    "predicate_max_pairs": 65536,
    # aggregation accumulator table: initial slots (grows in doublings)
    # and the hard cap — past it aggregation subs degrade to raw
    # per-message delivery, visibly (aggregate_window_overflows)
    "aggregate_initial_windows": 256,
    "aggregate_max_windows": 4096,
    # time-window close scan interval (ms)
    "aggregate_tick_ms": 250,
    # multi-process session front end (broker/workers.py +
    # broker/match_service.py): N worker processes share the MQTT port
    # via SO_REUSEPORT, each running parse/auth/session/queue locally;
    # matching optionally centralizes in ONE device-match service
    # process reached over shared-memory rings. workers=1 (the default,
    # and what every test boots) runs byte-identical to the classic
    # single-process broker — none of the keys below change any code
    # path until the WorkerGroup parent sets them.
    # vmqlint: allow(knob-registry): consumed by the worker CLI via the
    # RAW parsed conf (workers.py probes parse_conf output, deliberately
    # not a Config — DEFAULTS merging would make the cpu_count/2
    # fallback unreachable), a read the config-shaped taint cannot see
    "workers": 1,
    # shared-memory stats table name (parallel/shm_ring.py
    # WorkerStatsBlock): per-worker health/pressure slots the governors
    # fuse and `vmq-admin workers show` reads. Empty = not a worker.
    "worker_stats_block": "",
    "worker_index": 0,
    "workers_total": 1,
    # request/response ring names for the match-service channel; empty =
    # no service (each process matches in-process, the classic path)
    "match_service_req_ring": "",
    "match_service_resp_ring": "",
    # worker-side fold reply deadline: past it the fold degrades to the
    # worker's local trie through the client breaker
    "match_service_timeout_ms": 2000,
    # deterministic fault injection (robustness/faults.py): a list of
    # rule dicts ({point, kind, probability, after, count, latency_ms})
    # installed at boot; also live-toggleable via `vmq-admin fault ...`.
    # Empty = no plan, zero overhead.
    "fault_injection": [],
    "fault_injection_seed": 0,
    # supervisor restart budget: more than max_restarts CONSECUTIVE
    # crashy restarts of one child escalates (listener teardown — the
    # node fails health checks instead of crash-looping forever); a
    # stint healthier than the current backoff, or longer than
    # restart_window seconds, resets the count. 0 = unlimited.
    "supervisor_max_restarts": 20,
    "supervisor_restart_window": 60.0,
    # systree / metrics
    "systree_enabled": True,
    "systree_interval": 20,
    "systree_mountpoint": "",
    "systree_qos": 0,
    "systree_retain": False,
    "systree_reg_view": "",  # compat no-op (schema.COMPAT_NOOPS)
    "graphite_enabled": False,
    "graphite_host": "localhost",
    "graphite_port": 2003,
    "graphite_interval": 20,
    "graphite_prefix": "",
    "graphite_api_key": "",  # hosted-graphite key, prepended to the path
    "graphite_connect_timeout": 5.0,   # seconds
    "graphite_reconnect_timeout": 10.0,  # seconds between retries
    "graphite_include_labels": False,  # compat no-op (unlabeled metrics)
    # http endpoints (vmq_http_config.erl http_modules)
    "http_enabled": False,
    "http_host": "127.0.0.1",
    "http_port": 8888,
    "http_modules": ["metrics", "health", "status", "mgmt"],
    "http_mgmt_api_auth": True,
    # storage
    "message_store": "memory",  # memory | file | native (C++ engine)
    "message_store_dir": "./data/msgstore",
    # opt-in fsync per message-store write: the stores flush to the OS
    # on every write either way; fsync makes each write power-loss
    # durable at a large throughput cost (the reference's sync knob)
    "msg_store_fsync": False,
    # with fsync on, coalesce to ONE fsync per write burst at the
    # flush-tick boundary (msg_store_fsync_coalesced counts the saved
    # syncs); off = the legacy per-record fsync
    "msg_store_group_commit": True,
    # engines hashed by msg-ref; reference runs 12 (vmq_lvldb_store_sup.erl)
    "msg_store_instances": 12,
    # unified segment engine (storage/segment.py): seal size of the
    # append segment, checkpoint cadence (bytes appended between index
    # checkpoints — recovery replays only what landed after one), and
    # the budgeted off-loop compaction driver (bytes copied per engine
    # per tick; 0 interval disables the driver)
    "store_segment_max_bytes": 8 * 1024 * 1024,
    "store_checkpoint_every_bytes": 32 * 1024 * 1024,
    "store_compact_interval_ms": 1000,
    "store_compact_budget_bytes": 4 * 1024 * 1024,
    # expired parked offline messages classified per maintenance tick
    # (refs examined, not bytes; the sweep rides the compaction tick)
    "store_expire_sweep_budget": 256,
    # batched reconnect-storm resumption (storage/resume.py): coalesce
    # concurrent offline replays into one off-loop read per window
    "resume_batched": True,
    "resume_window_us": 500,
    "resume_max_batch": 512,
    "resume_host_threshold": 4,
    # queued-resume deadline before the exact per-session fallback
    # serves on the loop (a 100k-session storm legitimately queues for
    # seconds — this is a wedge bound, not a latency target)
    "resume_expiry_ms": 30_000,
    "metadata_dir": "./data/meta",
    "metadata_persistence": False,  # durable subscriber-db/retain via kvstore
    # metadata backend: "lww" (plumtree-flavored) | "swc" (server-wide
    # clocks, vmq_swc) — the metadata_impl knob (vmq_metadata.erl:24-28)
    "metadata_plugin": "lww",
    # MQTT bridges (vmq_bridge): list of {host, port, topics:[{pattern,
    # direction, qos, local_prefix, remote_prefix}], ...} dicts — the
    # vmq_bridge.tcp.* config tree flattened
    "bridges": [],
    # scripting plugin (vmq_diversity): operator script files exposing the
    # hook surface; Python here where the reference embeds Lua
    "diversity_scripts": [],
    # sysmon / overload protection (vmq_sysmon; riak_sysmon knobs)
    "sysmon_enabled": True,
    "sysmon_lag_threshold": 0.25,  # seconds of event-loop lag = long_schedule
    "sysmon_memory_high_watermark": 0,  # bytes RSS; 0 = off (large_heap)
    # overload exits only after lag stays below threshold * this ratio
    # for a full cooldown (hysteresis — no shed/unshed flap at the edge)
    "sysmon_lag_exit_ratio": 0.5,
    # adaptive overload governor (robustness/overload.py): fuses loop-lag
    # EWMA + RSS watermark, collector pending-depth/dispatch-latency,
    # breaker state and cluster buffer/spool depth into a pressure level
    # 0-3 with per-level hysteresis. Staged cheapest-first responses:
    # L1 proportional per-session read throttle, L2 per-client token
    # buckets + QoS0 fanout shedding + retained-replay deferral, L3
    # connect refusal (CONNACK 0x97 / server unavailable) + top-talker
    # disconnects (Server busy). "binary" keeps the legacy posture (the
    # sysmon flag + fixed 0.1s sleep) for A/B runs.
    "overload_mode": "governor",  # governor | binary
    "overload_tick_ms": 250,
    "overload_hold_s": 5.0,       # per-level hysteresis hold window
    "overload_exit_ratio": 0.5,   # exit below enter_threshold * this
    "overload_l1_enter": 0.25,    # pressure gates per level
    "overload_l2_enter": 0.5,
    "overload_l3_enter": 0.8,
    "overload_l1_throttle_ms": 100,  # base read-throttle, scaled by
                                     # level and the session's talker
                                     # share (heaviest wait longest)
    "overload_l2_client_rate": 50,   # token-bucket refill, msgs/s/client
    "overload_l2_burst": 100,
    "overload_l3_disconnect_top": 5,  # heaviest talkers shed at L3 entry
    # dispatch-latency EWMA budget for the collector pressure signal
    "overload_dispatch_budget_ms": 50.0,
    # stall watchdog (robustness/watchdog.py): monitored-operation
    # registry + deadline abandonment for SILENT failures — a device
    # dispatch that never returns, a wedged rebuild thread, a half-open
    # cluster peer whose acks stop. Off = stalls wedge exactly as far
    # as their own seams (lock timeouts, injection caps) allow.
    "watchdog_enabled": True,
    "watchdog_tick_ms": 100,      # overdue-op scan interval
    # device dispatch deadline: a collector flush whose device call has
    # not returned by then is ABANDONED — the waiters are served by the
    # exact host trie, the stall feeds the breaker, the wedged executor
    # thread is sacrificed and its late result discarded. 0 disables
    # (the pre-watchdog unbounded wait).
    "watchdog_dispatch_deadline_ms": 5000,
    # background device-table (re)build deadline: past it the build is
    # abandoned like a failed one (breaker fed, host path serves, late
    # install discarded). Generous — full builds at millions of rows
    # legitimately take seconds; this catches WEDGES, not slowness.
    "watchdog_rebuild_deadline_s": 120.0,
    # queued-item expiry, in multiples of overload_dispatch_budget_ms:
    # a publish/replay still queued in a collector after this many
    # dispatch budgets is served by the host oracle even if every
    # pipeline slot is wedged — the bounded-tail guarantee. Where the
    # publish collector's dispatches measurably take longer than the
    # budget, it counts this many MEASURED dispatches instead (capped
    # at watchdog_dispatch_deadline_ms). 0 disables.
    "watchdog_collector_expiry_budgets": 4,
    # cluster connection-level stall detection: unacked spooled bytes
    # with no cumulative-ack progress for this long cycle the channel
    # (drop + reconnect + spool replay — loss-free by PR 3); catches
    # half-open peers whose writes succeed but whose acks never arrive.
    # 0 disables.
    "cluster_stall_timeout_s": 10.0,
    # observability (vernemq_tpu/observability/): stage latency
    # histograms + publish-path flight recorder + device dispatch
    # profiler. Off reduces every instrumented seam to one boolean test
    # (PERF.md §6, PR 25: what the spans cost when on).
    "observability_enabled": True,
    # flight recorder: every Nth admitted publish carries a stage-
    # stamped trace through the whole path (0 disables sampling)
    "flight_recorder_sample_n": 32,
    "flight_recorder_capacity": 4096,
    # device dispatch profiler ring (records kept for `vmq-admin
    # profile device` / `timeline dump`)
    "profiler_capacity": 2048,
    # control-plane event journal ring (observability/events.py):
    # breaker/governor/watchdog/supervisor/mesh/spool/wire transitions
    # kept for `vmq-admin events show|dump` and trace interleaving
    "events_capacity": 2048,
    # canary SLO probe (observability/canary.py): a loopback subscriber
    # + a periodic synthetic publish through the FULL path feeding the
    # e2e_canary_ms histogram and the canary_slo_breaches burn counter.
    # Off by default: the probe adds one routing-table row and a
    # publish per interval — opt in per deployment.
    "canary_enabled": False,
    "canary_interval_ms": 1000,
    "canary_slo_ms": 250.0,
    "crl_refresh_interval": 60.0,  # seconds (vmq_crl_srv schema knob)
    "swc_replication_groups": 8,  # reference runs 10 (vmq_swc_plugin.erl:36-44)
    "swc_sync_interval": 2.0,  # seconds between AE rounds (sync_interval)
    # storage engine behind the vmq_swc_db seam (cluster/swc_db.py):
    # kvstore (one native engine) | bucketed (N engines by key hash) —
    # the reference's leveldb/rocksdb/leveled choice (vmq_swc_db.erl)
    "swc_db_backend": "kvstore",
    # plumtree EBT safety valves (plumtree.* schema tree): cap on
    # announced-but-unreceived ids awaiting GRAFT, and the backlog size
    # past which new IHAVE announcements are dropped (digest AE repairs)
    "plumtree_outstanding_limit": 10_000,
    "plumtree_drop_ihave_threshold": 0,  # 0 = never drop
    # shared-subscription delivery on remote-ack timeout: queue retry
    # gives requeue semantics either way (schema.COMPAT_NOOPS)
    "shared_subscription_timeout_action": "ignore",
    # raw tcp listen options string (reference erlang proplist); nodelay
    # is parsed and applied, the rest is accepted for compatibility
    "tcp_listen_options":
        "[{nodelay, true}, {linger, {true, 0}}, {send_timeout, 30000}, "
        "{send_timeout_close, true}]",
    # release-layout base directories (setup.* schema tree): when set,
    # relative message_store_dir/metadata_dir/log_file resolve under them
    "data_dir": "",
    "log_dir": "",
    # logging sinks (the lager console/file/syslog triple of the
    # reference's release config; syslog uses the OS socket via the
    # stdlib handler — the reference's C port driver seat)
    "log_level": "info",
    "log_file": "",          # path; empty = no file sink
    "log_syslog": False,
    "log_syslog_address": "/dev/log",
    # structured keys filled by the conf-file loader (broker/conf.py):
    # listeners started at boot (vmq_ranch_config listener tree) and
    # plugins enabled at boot (plugins.<name> = on)
    "listeners": [],  # [{kind, name, addr, port, opts}]
    "plugins": [],    # [{name, opts}]
}


class Config:
    """Override layers: constructor kwargs > set() calls > DEFAULTS."""

    def __init__(self, **overrides: Any):
        import copy

        # deep copy: DEFAULTS holds mutable values (http_modules list) that
        # must not be shared across Config instances
        self._values: Dict[str, Any] = copy.deepcopy(DEFAULTS)
        for k, v in overrides.items():
            if k not in DEFAULTS:
                raise KeyError(f"unknown config key: {k}")
            self._values[k] = v
        self._listeners: List[Callable[[str, Any], None]] = []

    def get(self, key: str, default: Any = None) -> Any:
        if key in self._values:
            return self._values[key]
        if default is not None:
            return default
        raise KeyError(key)

    def __getattr__(self, key: str) -> Any:
        try:
            return self._values[key]
        except KeyError:
            raise AttributeError(key) from None

    def set(self, key: str, value: Any) -> None:
        """Runtime config change with change-event fan-out
        (vmq_config.erl:220-246 change_config)."""
        if key not in DEFAULTS:
            raise KeyError(f"unknown config key: {key}")
        self._values[key] = value
        for fn in self._listeners:
            fn(key, value)

    @classmethod
    def from_file(cls, path: str) -> "Config":
        """Boot-from-conf-file entry point (the vernemq.conf layer)."""
        from .conf import load_conf_file

        return load_conf_file(path)

    def on_change(self, fn: Callable[[str, Any], None]) -> None:
        self._listeners.append(fn)

    def snapshot(self) -> Dict[str, Any]:
        return dict(self._values)
