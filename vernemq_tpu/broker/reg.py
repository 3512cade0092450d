"""Registry: subscribe/unsubscribe/register ops and the publish fanout.

Mirrors ``apps/vmq_server/src/vmq_reg.erl``:

- the **reg-view seam** (``vmq_reg_view.erl:20-27``): a RegView exposes
  ``fold(topic) -> match rows``; ``TrieRegView`` (host trie) and the TPU
  engine's view are interchangeable via config ``default_reg_view``;
- ``publish``: retain set/delete first, then fold the view; per matched row
  enqueue locally, collect shared-subscription group members for policy
  selection, forward remote-node pointers to the cluster channel
  (``vmq_reg.erl:265-353``);
- RAP flag: live-routed deliveries clear the retain flag unless the v5
  retain-as-published option is set (``vmq_reg.erl:355-360``);
- ``no_local``: a subscriber never receives its own publishes on a no-local
  subscription (``vmq_reg.erl:330-341``);
- subscribe triggers retained replay per filter (``vmq_reg.erl:380-418``)
  honoring v5 retain-handling;
- shared-subscription member selection by policy with online members
  preferred (``vmq_shared_subscriptions.erl:26-63,90-106``).

Single-node in round 1: remote-node entries and the is_ready CAP gate are
wired (cluster layer fills them in), with local behavior already faithful.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import random
import time
from itertools import repeat
from typing import TYPE_CHECKING, Any, Dict, Iterable, List, Optional, Sequence, Tuple

from ..models.trie import SubscriptionTrie
from ..observability import histogram as obs
from ..protocol import fastpath
from ..protocol.topic import is_shared, unshare
from ..protocol.types import PROTO_5, SubOpts
from .message import Msg, SubscriberId, wire_v4_iov_qos0
from .queue import OFFLINE, ONLINE, QueueOpts, SubscriberQueue
from .session import wire_take_qos_fanout
from .shared import ShareGroup, row_key
from .subscriber_db import (SubscriberDB, SubscriberRecord, opts_from_dict,
                            opts_to_dict)

if TYPE_CHECKING:
    from .broker import Broker

log = logging.getLogger("vernemq_tpu.reg")


def _varint_len(n: int) -> int:
    """Bytes of an MQTT variable-length integer encoding ``n``."""
    if n < 128:
        return 1
    if n < 16_384:
        return 2
    if n < 2_097_152:
        return 3
    return 4


class RetainedMsg:
    """Stored retained message (#retain_msg{}, vmq_reg.erl:281-287)."""

    __slots__ = ("payload", "properties", "expiry_ts", "qos")

    def __init__(self, payload: bytes, properties: Dict[str, Any], qos: int,
                 expiry_ts: Optional[float] = None):
        self.payload = payload
        self.properties = properties
        self.qos = qos
        self.expiry_ts = expiry_ts


class TrieRegView:
    """Default reg view: fold over the host subscription trie
    (vmq_reg_trie:fold/4)."""

    name = "trie"

    def __init__(self, registry: "Registry"):
        self._registry = registry

    def fold(self, mountpoint: str, topic: Sequence[str]):
        """Yield match rows: (filter, key, subopts). Keys are SubscriberId
        for plain subs, ("$g", group, None) for a shared subscription's
        one row (no subopts: the members are the registry's), a node name
        for a remote node's pointer row."""
        return self._registry.trie(mountpoint).match(topic)


def _route_begin(trace):
    """The routing of one publish whose rows the collector just released
    begins: the span it is timed under (close with ``obs.span_end``), and
    the flight recorder's ``release`` stamp of a sampled one (what came
    before was the release queue)."""
    if trace is not None:
        trace.stamp("release")
    return obs.span_begin("stage_route_ms")


class Registry:
    def __init__(self, broker: "Broker"):
        self.broker = broker
        self.node_name = broker.node_name
        self._tries: Dict[str, SubscriptionTrie] = {}  # per-mountpoint
        # subscriber DB over the replicated metadata store
        # (vmq_subscriber_db.erl); the trie is maintained purely from its
        # change events — local writes fire them synchronously
        # (read-your-writes), remote writes arrive via metadata replication
        # (vmq_reg_trie.erl:198-210 event consumption)
        self.db = SubscriberDB(broker.metadata, broker.node_name)
        self.db.subscribe_db_events(self._on_subs_event)
        self.queues: Dict[SubscriberId, SubscriberQueue] = {}
        self.reg_views: Dict[str, Any] = {"trie": TrieRegView(self)}
        self.fanout_fast_pubs = 0
        # remote plain subscriptions collapse to one node-pointer trie row
        # per (mountpoint, filter, node), refcounted
        # (vmq_reg_trie.erl:503-520 remote-subs handling)
        self._remote_refs: Dict[Tuple[str, Tuple[str, ...], str], int] = {}
        # remote-node fanout hooks, filled by the cluster layer:
        self.remote_publish = None  # fn(node, msg) (vmq_cluster:publish/2)
        self.remote_enqueue_nowait = None  # fn(node, sid, [msg]) shared subs
        # shared subscriptions: ONE trie/table row each, keyed
        # ("$g", group, None); the members by (mountpoint, group, filter
        # words), and each local member's groups for its queue's moves
        self._groups: Dict[Tuple[str, str, Tuple[str, ...]], ShareGroup] = {}
        self._member_of: Dict[SubscriberId, List[ShareGroup]] = {}

    def bootstrap(self) -> None:
        """Warm-load routing state from a persisted subscriber DB —
        STREAMING: the raw stored terms go straight to trie rows (the
        fresh-record case of the change-event diff, with no
        SubscriberRecord allocation per record and the common plain
        opts shapes interned to a handful of shared objects), and
        offline queues for persistent sessions homed here re-create
        with the lazy-recovery pattern — the stored backlog loads on
        first attach (via the ResumeCollector) or at drain. Boot cost
        is one trie add per filter plus one queue object per parked
        session, never a whole-DB object graph (the async trie
        warm-load of ``vmq_reg_trie.erl:144-149``;
        ``vmq_reg_mgr.erl:64-72``)."""
        interned: Dict[Tuple, SubOpts] = {}
        for sid, term in self.db.fold_raw():
            if term is None:
                continue
            mountpoint = sid[0]
            node = term["node"]
            for f, od in (term.get("subs") or {}).items():
                fw = tuple(f)
                if "sid" in od or "flt" in od:
                    # subscription-id / payload-filter rows keep their
                    # own opts object (the filter engine refcounts and
                    # windows per row — these must not be shared)
                    opts = opts_from_dict(od)
                else:
                    k = (od.get("qos", 0), od.get("nl", False),
                         od.get("rap", False), od.get("rh", 0))
                    opts = interned.get(k)
                    if opts is None:
                        opts = interned[k] = opts_from_dict(od)
                self._trie_add(mountpoint, fw, sid, node, opts)
            if (node == self.node_name and not term.get("clean", True)
                    and sid not in self.queues):
                queue = self._start_queue(
                    sid, _qopts_from_dict(dict(term.get("qopts") or {}),
                                          self.broker.config))
                self.broker.recover_offline(sid, queue, lazy=True)
                queue._arm_expiry()  # session/persistent expiry clock

    @property
    def subscriptions(self) -> Dict[SubscriberId, Dict[Tuple[str, ...], SubOpts]]:
        """Local-view of the subscriber DB (introspection/back-compat)."""
        return {sid: rec.subs for sid, rec in self.db.fold()}

    def trie(self, mountpoint: str = "") -> SubscriptionTrie:
        t = self._tries.get(mountpoint)
        if t is None:
            t = self._tries[mountpoint] = SubscriptionTrie()
        return t

    def reg_view(self, name: Optional[str] = None):
        name = name or self.broker.config.default_reg_view
        view = self.reg_views.get(name)
        if view is None and name == "tpu":
            # built in this process: a backend that cannot initialise or
            # a tpu_mesh the devices cannot satisfy raises here, and
            # Broker.start() builds the default view, so such a broker
            # refuses to boot. A device that fails WHILE serving is the
            # breaker's business (robustness/breaker.py), not this one's.
            view = self.reg_views["tpu"] = self._make_tpu_view()
        if view is None:
            raise KeyError(f"unknown reg view {name!r}")
        return view

    def _make_tpu_view(self):
        from ..models.tpu_matcher import TpuRegView

        cfg = self.broker.config
        return TpuRegView(
            self, max_fanout=cfg.tpu_max_fanout,
            flat_avg=cfg.tpu_flat_avg,
            use_pallas=cfg.tpu_use_pallas,
            breaker_enabled=cfg.get("tpu_breaker_enabled", True),
            breaker_failure_threshold=cfg.get(
                "tpu_breaker_failure_threshold", 3),
            breaker_backoff_initial=cfg.get(
                "tpu_breaker_backoff_initial_ms", 200) / 1e3,
            breaker_backoff_max=cfg.get(
                "tpu_breaker_backoff_max_ms", 10_000) / 1e3,
            delta_warm_max=cfg.get("tpu_delta_warm_max", 128),
            initial_capacity=cfg.tpu_initial_capacity,
            mesh=self._mesh_from_config(),
            mesh_native=bool(cfg.get("tpu_mesh_native", True)),
            watchdog=(self.broker.watchdog
                      if cfg.get("watchdog_enabled", True) else None),
            rebuild_deadline_s=cfg.get("watchdog_rebuild_deadline_s",
                                       120.0),
        )

    def _mesh_from_config(self):
        """Build the serving mesh from the ``tpu_mesh`` knob ("BxS" or
        "S"); None (single-device matcher) when unset. A spec that does
        not parse, or asks for more devices than exist, raises — the
        broker must not boot onto fewer chips than it was told to use."""
        import jax

        from ..cluster.mesh_map import parse_mesh_spec

        # initialises the backend; a missing accelerator raises here
        devs = jax.devices()
        spec = str(self.broker.config.get("tpu_mesh", "") or "").strip()
        if not spec:
            return None
        parsed = parse_mesh_spec(spec)
        if parsed is None:
            raise ValueError(f"invalid tpu_mesh {spec!r}")
        batch, sub = parsed
        need = batch * sub
        if len(devs) < need:
            raise RuntimeError(
                f"tpu_mesh={spec} wants {need} devices but only "
                f"{len(devs)} present")
        from ..parallel.mesh import make_mesh

        return make_mesh(devs[:need], batch=batch)

    def batched_view_active(self) -> bool:
        """True when sessions should publish through the BatchCollector —
        i.e. the configured view is the TPU engine (or a stand-in with
        the batch interface, e.g. the worker-side ShmMatchView)."""
        if self.broker.config.default_reg_view != "tpu":
            return False
        return hasattr(self.reg_view("tpu"), "fold_batch")

    # -- session registration ---------------------------------------------

    def register_subscriber(
        self, sid: SubscriberId, clean_start: bool, queue_opts: QueueOpts
    ) -> Tuple[SubscriberQueue, bool]:
        """Create/reuse the subscriber queue; returns (queue,
        session_present) (vmq_reg:register_subscriber, vmq_reg.erl:107-140).
        Session takeover of live sessions is handled by the session layer
        before calling this. A persistent subscriber whose record points at
        another node is remapped here (maybe_remap_subscriber,
        vmq_reg.erl:676-699) — the node change event triggers queue
        migration on the old owner."""
        cfg = self.broker.config
        if not self.broker.cluster_ready() and not cfg.allow_register_during_netsplit:
            raise RuntimeError("not_ready")
        existing = self.queues.get(sid)
        rec = self.db.read(sid)
        if clean_start:
            if existing is not None or rec is not None:
                self.cleanup_subscriber(sid)
            queue = self._start_queue(sid, queue_opts)
            return queue, False
        session_present = existing is not None or rec is not None
        if rec is not None and rec.node != self.node_name:
            # remap: rewrite the record to this node; every node's trie
            # re-points, the old owner starts draining its queue to us
            rec.node = self.node_name
            rec.clean_session = queue_opts.clean_session
            rec.queue_opts = _qopts_to_dict(queue_opts)
            self.db.store(sid, rec)
        elif rec is None:
            # persist an empty record immediately: every node must learn who
            # owns this ClientId's queue even before the first SUBSCRIBE
            # (maybe_remap_subscriber stores {Node, CleanSession, []},
            # vmq_reg.erl:676-699) — this is what a concurrent register on
            # another node races against
            from .subscriber_db import SubscriberRecord

            self.db.store(sid, SubscriberRecord(
                self.node_name, queue_opts.clean_session,
                queue_opts=_qopts_to_dict(queue_opts)))
        if existing is not None:
            existing.opts = queue_opts
            return existing, session_present
        queue = self._start_queue(sid, queue_opts)
        if session_present:
            # the reconnect path: a session is attaching right now, so
            # the replay may ride the batched ResumeCollector (one
            # off-loop read per storm window) — boot/remap recovery
            # stays synchronous
            self.broker.recover_offline(sid, queue, may_defer=True)
        return queue, session_present

    async def register_subscriber_synced(
        self, sid: SubscriberId, clean_start: bool, queue_opts: QueueOpts
    ) -> Tuple[SubscriberQueue, bool]:
        """Cluster-serialized registration: the whole register (incl. the
        record remap that triggers the old owner's drain) runs holding the
        cluster-wide per-SubscriberId lock (vmq_reg.erl:115-126 running
        register_subscriber_ via vmq_reg_sync:sync). Without it, two nodes
        registering the same ClientId concurrently race on the subscriber
        record. Raises RuntimeError('not_ready') like the direct path."""
        cluster = self.broker.cluster
        if cluster is None or not self.broker.config.coordinate_registrations:
            return self.register_subscriber(sid, clean_start, queue_opts)
        return await cluster.reg_sync.sync(
            sid,
            lambda: self.register_subscriber(sid, clean_start, queue_opts))

    async def cleanup_subscriber_synced(self, sid: SubscriberId) -> None:
        """Serialized cleanup (the vmq_reg_sync 'cleanup' action): session
        expiry racing a concurrent re-register on another node must not
        delete the record the other node just claimed."""
        cluster = self.broker.cluster
        if cluster is None or not self.broker.config.coordinate_registrations:
            self.cleanup_subscriber(sid)
            return

        def _do() -> None:
            rec = self.db.read(sid)
            if rec is not None and rec.node != self.node_name:
                return  # another node owns it now; nothing to clean here
            self.cleanup_subscriber(sid)

        await cluster.reg_sync.sync(sid, _do)

    def _start_queue(self, sid: SubscriberId, opts: QueueOpts) -> SubscriberQueue:
        queue = SubscriberQueue(self.broker, sid, opts)
        self.queues[sid] = queue
        self.broker.metrics.incr("queue_setup")
        return queue

    def get_queue(self, sid: SubscriberId) -> Optional[SubscriberQueue]:
        return self.queues.get(sid)

    def queue_terminated(self, sid: SubscriberId) -> None:
        """Callback from SubscriberQueue.terminate: drop registry state for
        clean sessions."""
        q = self.queues.pop(sid, None)
        if q is not None and q.opts.clean_session:
            rec = self.db.read(sid)
            if rec is not None:
                self.db.delete(sid)

    def cleanup_subscriber(self, sid: SubscriberId) -> None:
        """Full cleanup: subscriptions + queue + offline storage
        (vmq_reg cleanup via vmq_reg_sync, and client_expired path)."""
        if self.db.read(sid) is not None:
            self.db.delete(sid)
        q = self.queues.pop(sid, None)
        if q is not None:
            q.opts.clean_session = True  # prevent re-offline
            q.terminate("cleanup")
        self.broker.delete_offline(sid)

    # -- subscriber-db change events → trie (vmq_reg_trie event consumer) --

    def _on_subs_event(self, sid: SubscriberId, old, new,
                       origin: str = "") -> None:
        """Apply a subscriber-record change to this node's routing state:
        the diff of old vs new subscriptions (vmq_subscriber:get_changes,
        vmq_subscriber.erl:54-58) becomes trie/TPU-table deltas. Local
        subscribers become direct rows; remote plain subscriptions collapse
        into per-node pointer rows; a shared subscription is ONE row
        whatever its members, who are kept in its ShareGroup with the
        owning node in the opts (the reference trie's {Node, Group,
        SubscriberId, SubInfo} rows, folded by group)."""
        mountpoint = sid[0]
        old_subs = old.subs if old is not None else {}
        new_subs = new.subs if new is not None else {}
        old_node = old.node if old is not None else None
        new_node = new.node if new is not None else None
        for fw, opts in old_subs.items():
            if fw not in new_subs or new_node != old_node:
                self._trie_remove(mountpoint, fw, sid, old_node, opts)
        for fw, opts in new_subs.items():
            prev = old_subs.get(fw)
            if prev is None or old_node != new_node:
                self._trie_add(mountpoint, fw, sid, new_node, opts)
            elif opts_to_dict(prev) != opts_to_dict(opts):
                # opts-only change: local/group rows carry opts and must be
                # replaced; remote pointer rows don't (and must not have
                # their refcount bumped)
                group, _ = unshare(list(fw))
                if group is not None or new_node == self.node_name:
                    # in-place row replace: balance the filter-engine
                    # refcount (and free the old opts' windows) before
                    # the add bumps it — a re-subscribe changing the
                    # predicate must not leak a wants() ref or inherit
                    # a dead window's accumulator
                    self._filters_delta("remove", mountpoint, prev,
                                        fw, sid)
                    self._trie_add(mountpoint, fw, sid, new_node, opts)
        # a remote node took over a persistent subscriber we hold a queue
        # for → queue migration trigger (vmq_reg_mgr.erl:155-243, task:
        # drain handled by the migration protocol)
        if (new is not None and new_node != self.node_name
                and sid in self.queues and old_node == self.node_name):
            self.broker.on_subscriber_moved(sid, new_node)
        # a persistent subscriber was remapped TO this node by someone else
        # (queue migration / fix-dead-queues): create the offline queue
        # eagerly so publishes and drain frames land in it
        # (vmq_reg_mgr:handle_new_sub_event → setup_queue). A local-origin
        # remap is the register path, which creates its own queue.
        if (new is not None and new_node == self.node_name
                and origin != self.node_name
                and old_node != self.node_name):
            self.ensure_offline_queue(sid, new)

    def ensure_offline_queue(self, sid: SubscriberId, rec) -> None:
        """Create + recover the offline queue for a persistent subscriber
        homed here, if missing (vmq_reg_mgr setup_queue — used by the
        remote-remap event path and fix-dead-queues)."""
        if (rec is None or rec.clean_session or rec.node != self.node_name
                or sid in self.queues or sid in self.broker.sessions):
            return
        queue = self._start_queue(
            sid, _qopts_from_dict(rec.queue_opts, self.broker.config))
        self.broker.recover_offline(sid, queue, lazy=True)
        queue._arm_expiry()

    def _trie_add(self, mountpoint: str, fw: Tuple[str, ...],
                  sid: SubscriberId, node: str, opts: SubOpts) -> None:
        trie = self.trie(mountpoint)
        opts.node = node  # locality for shared-sub policy + introspection
        self._filters_delta("add", mountpoint, opts)
        group, rest = unshare(list(fw))
        if group is not None:
            self._share_join(mountpoint, group, tuple(rest), sid, node, opts)
        elif node == self.node_name:
            trie.add(list(fw), sid, opts)
            self._emit_delta("add", mountpoint, list(fw), sid, opts)
        else:
            ref = (mountpoint, fw, node)
            n = self._remote_refs.get(ref, 0)
            self._remote_refs[ref] = n + 1
            if n == 0:
                trie.add(list(fw), node, None)
                self._emit_delta("add", mountpoint, list(fw), node, None)

    def _trie_remove(self, mountpoint: str, fw: Tuple[str, ...],
                     sid: SubscriberId, node: str,
                     opts: Optional[SubOpts] = None) -> None:
        trie = self.trie(mountpoint)
        self._filters_delta("remove", mountpoint, opts, fw, sid)
        group, rest = unshare(list(fw))
        if group is not None:
            self._share_leave(mountpoint, group, tuple(rest), sid)
        elif node == self.node_name:
            trie.remove(list(fw), sid)
            self._emit_delta("remove", mountpoint, list(fw), sid, None)
        else:
            ref = (mountpoint, fw, node)
            n = self._remote_refs.get(ref, 0) - 1
            if n <= 0:
                self._remote_refs.pop(ref, None)
                trie.remove(list(fw), node)
                self._emit_delta("remove", mountpoint, list(fw), node, None)
            else:
                self._remote_refs[ref] = n

    # -- shared subscriptions: one row, the members in a ShareGroup --------

    def _share_join(self, mountpoint: str, group: str,
                    rest: Tuple[str, ...], sid: SubscriberId, node: str,
                    opts: SubOpts) -> None:
        """A member subscribed (or changed its options or node): the
        first member adds the group's one row, later ones only join."""
        gk = (mountpoint, group, rest)
        g = self._groups.get(gk)
        if g is None:
            g = self._groups[gk] = ShareGroup(group)
            key = row_key(group)
            self.trie(mountpoint).add(list(rest), key, None)
            self._emit_delta("add", mountpoint, list(rest), key, None)
        local = node == self.node_name
        q = self.queues.get(sid) if local else None
        g.join(sid, opts, local, q is not None and q.state == ONLINE,
               bool(getattr(opts, "filter_expr", None))
               and self.broker.filter_engine is not None)
        if local:
            of = self._member_of.setdefault(sid, [])
            if g not in of:
                of.append(g)

    def _share_leave(self, mountpoint: str, group: str,
                     rest: Tuple[str, ...], sid: SubscriberId) -> None:
        """A member unsubscribed or expired: the last one takes the
        group's row away."""
        gk = (mountpoint, group, rest)
        g = self._groups.get(gk)
        if g is None or not g.leave(sid):
            return
        of = self._member_of.get(sid)
        if of is not None and g in of:
            of.remove(g)
            if not of:
                del self._member_of[sid]
        if not g.members:
            del self._groups[gk]
            key = row_key(group)
            self.trie(mountpoint).remove(list(rest), key)
            self._emit_delta("remove", mountpoint, list(rest), key, None)

    def share_member_moved(self, sid: SubscriberId) -> None:
        """A local queue's state changed (``SubscriberQueue._set_state``):
        each group the session is a member of files it under online or
        offline by the queue the registry now holds for it."""
        groups = self._member_of.get(sid)
        if groups:
            q = self.queues.get(sid)
            online = q is not None and q.state == ONLINE
            for g in groups:
                g.moved(sid, online)

    def share_member_rows(self, mountpoint: str, rows):
        """The predicate phase's view of a fold result: beside each
        shared subscription's row, one row ``("$g", group, sid)`` for
        each of its members that carries a payload predicate (whose
        predicate then decides, row by row, as for a plain subscription;
        an aggregating member's value folds into ITS window). Runs on
        the engine's thread; rows already expanded are not repeated, and
        a member of two filters of one ShareName gets a row under each."""
        out = None
        for f, key, _opts in rows:
            if not (isinstance(key, tuple) and len(key) == 3
                    and key[2] is None and key[0] == "$g"):
                continue
            fw = tuple(f)
            g = self._groups.get((mountpoint, key[1], fw))
            if g is None or not g.filtered:
                continue
            if out is None:
                out = list(rows)
                seen = {(tuple(rf), k) for rf, k, _o in rows}
            for sid, opts in list(g.filtered.items()):
                mk = ("$g", key[1], sid)
                if (fw, mk) not in seen:
                    seen.add((fw, mk))
                    out.append((f, mk, opts))
        return rows if out is None else out

    def share_group(self, mountpoint: str, group: str,
                    filter_words: Sequence[str]) -> Optional[ShareGroup]:
        """The members of ``$share/<group>/<filter_words>`` (introspection
        and tests)."""
        return self._groups.get((mountpoint, group, tuple(filter_words)))

    def node_left(self, node: str) -> None:
        """A member left: its subscriber records are rewritten by migration
        (task of the leave path); nothing to do eagerly here — CAP flags
        gate routing while the cluster is inconsistent."""

    # -- subscribe / unsubscribe ------------------------------------------

    def subscribe(
        self, sid: SubscriberId, topics: List[Tuple[List[str], SubOpts]]
    ) -> List[int]:
        """Add subscriptions; returns granted qos per topic
        (vmq_reg:subscribe → subscribe_op, vmq_reg.erl:62-99,636-653)."""
        cfg = self.broker.config
        if not self.broker.cluster_ready() and not cfg.allow_subscribe_during_netsplit:
            raise RuntimeError("not_ready")
        rec = self.db.read(sid)
        if rec is None:
            q = self.queues.get(sid)
            clean = q.opts.clean_session if q is not None else True
            rec = SubscriberRecord(self.node_name, clean)
        rec.node = self.node_name
        q = self.queues.get(sid)
        if q is not None:
            rec.queue_opts = _qopts_to_dict(q.opts)
        existed_before = {tuple(w) for w, _ in topics if tuple(w) in rec.subs}
        granted = []
        for words, opts in topics:
            rec.subs[tuple(words)] = opts
            granted.append(opts.qos)
        self.db.store(sid, rec)  # events update the trie synchronously
        for words, opts in topics:
            group, _ = unshare(list(words))
            # retained replay (vmq_reg.erl:380-418); none for shared subs
            # (MQTT5: retained messages are not sent to shared subscriptions)
            if group is None and opts.retain_handling != 2:
                if not (opts.retain_handling == 1 and tuple(words) in existed_before):
                    self._deliver_retained(sid, words, opts)
        return granted

    def _emit_delta(self, op: str, mountpoint: str, filter_words, key, opts) -> None:
        """Subscription change event → TPU table delta stream (the analog of
        vmq_reg_trie consuming subscriber-db change events; BASELINE
        config 5 trie-delta streaming)."""
        view = self.reg_views.get("tpu")
        if view is not None and hasattr(view, "on_delta"):
            # (the accelerator-down fallback aliases "tpu" to the trie
            # view, which is fed through the trie events directly)
            view.on_delta(op, mountpoint, filter_words, key, opts)

    def _filters_delta(self, op: str, mountpoint: str, opts,
                       fw=None, sid=None) -> None:
        """Subscription change → payload-filter engine refcounts (the
        wants() gate of vernemq_tpu/filters/engine.py): predicate-
        carrying subscriptions register per mountpoint so unfiltered
        traffic skips the predicate phase at one dict probe. Removes
        carry the routing-row key so the engine frees the
        subscription's aggregation windows."""
        eng = getattr(self.broker, "filter_engine", None)
        if eng is None:
            return
        key = None
        if fw is not None and sid is not None:
            group, _ = unshare(list(fw))
            key = ("$g", group, sid) if group is not None else sid
        eng.on_sub_delta(op, mountpoint, opts, key)

    def unsubscribe(self, sid: SubscriberId, topics: List[List[str]]) -> List[bool]:
        cfg = self.broker.config
        if not self.broker.cluster_ready() and not cfg.allow_unsubscribe_during_netsplit:
            raise RuntimeError("not_ready")
        rec = self.db.read(sid)
        results = []
        if rec is None:
            return [False] * len(topics)
        for words in topics:
            results.append(rec.subs.pop(tuple(words), None) is not None)
        if rec.subs:
            self.db.store(sid, rec)
        elif self.queues.get(sid) is None or rec.clean_session:
            self.db.delete(sid)
        else:
            self.db.store(sid, rec)  # persistent session keeps its record
        return results

    def _deliver_retained(self, sid: SubscriberId, filter_words: List[str], opts: SubOpts) -> None:
        """Retained replay for one new subscription (vmq_reg.erl:380-418).
        With the device retained index active the filter rides the
        replay batch collector (concurrent SUBSCRIBEs coalesce into one
        reverse-match dispatch) and enqueues when the batch resolves;
        otherwise — collector off, accelerator down, or the device path
        degraded — the exact host walk serves synchronously."""
        if self.queues.get(sid) is None:
            return
        col = self.broker.retained_collector()
        if col is not None:
            fut = col.submit(sid[0], tuple(filter_words))

            def _done(f: "asyncio.Future") -> None:
                exc = f.exception()
                if exc is not None:
                    # unexpected collector error: the replay must still
                    # happen — exact host walk, loudly
                    log.exception("retained replay batch failed; serving "
                                  "the host walk", exc_info=exc)
                    matches = self.broker.retain.match_filter(
                        sid[0], list(filter_words))
                else:
                    matches = f.result()
                self._enqueue_retained(sid, opts, matches)

            fut.add_done_callback(_done)
            return
        self._enqueue_retained(
            sid, opts,
            self.broker.retain.match_filter(sid[0], list(filter_words)))

    def _enqueue_retained(self, sid: SubscriberId, opts: SubOpts,
                          matches) -> None:
        queue = self.queues.get(sid)
        if queue is None:
            return  # session ended between subscribe and batch resolve
        now = time.time()
        # payload-filter replay seam: a predicated subscription replays
        # only passing retained messages (exact host evaluator — the
        # payload is in hand); aggregation subs get no raw replay
        eng = (self.broker.filter_engine
               if getattr(opts, "filter_expr", None) else None)
        for topic, rmsg in matches:
            if rmsg.expiry_ts is not None and rmsg.expiry_ts < now:
                continue
            if eng is not None and eng.passes_single(
                    sid[0], topic, rmsg.payload, opts) is False:
                continue
            props = dict(rmsg.properties)
            expires_at = None
            if rmsg.expiry_ts is not None:
                # MQTT5 3.3.2.3.3: the replayed message carries the
                # REMAINING expiry, not the interval it was stored with
                # (re-stamped from expires_at by the send path); the
                # stored wall-clock deadline converts to the session's
                # monotonic domain here
                expires_at = time.monotonic() + (rmsg.expiry_ts - now)
                props.pop("message_expiry_interval", None)
            msg = Msg(
                topic=topic,
                payload=rmsg.payload,
                qos=min(opts.qos, rmsg.qos),
                retain=True,
                mountpoint=sid[0],
                properties=props,
                expires_at=expires_at,
            )
            queue.enqueue(msg)

    # -- publish fanout (HOT PATH) ----------------------------------------

    def publish(
        self,
        msg: Msg,
        from_sid: Optional[SubscriberId] = None,
        reg_view: Optional[str] = None,
        trace=None,
    ) -> int:
        """Retain handling + fold + enqueue; returns number of local matches
        (used for the v5 no-matching-subscribers reason code).
        vmq_reg:publish/4 (vmq_reg.erl:265-319)."""
        msg = self._pre_publish(msg)
        name = reg_view or self.broker.config.default_reg_view
        if name == "tpu" and reg_view is None:
            # synchronous callers (systree, wills, plugins) must never run
            # the device matcher on the event loop — the host trie is
            # maintained in parallel as the source of truth and gives
            # identical results; sessions reach the tpu view via
            # publish_async/BatchCollector
            name = "trie"
        rows = self.reg_view(name).fold(msg.mountpoint, msg.topic)
        rows = self._filter_rows_host(msg, rows)
        return self.route_rows(msg, rows, from_sid, trace=trace)

    def _filter_rows_host(self, msg: Msg, rows):
        """Payload-predicate phase for the synchronous fold paths (the
        exact host evaluator; the device phase rides the collector).
        One dict probe when no predicates exist on the mountpoint."""
        eng = getattr(self.broker, "filter_engine", None)
        if eng is None or not eng.wants(msg.mountpoint):
            return rows
        feat = eng.encode(msg.mountpoint, msg.topic, msg.payload)
        return eng.filter_single(msg.mountpoint, msg.topic, feat,
                                 list(rows))

    def _filters_feat(self, msg: Msg):
        """Feature row riding the collector submit (the K-batch staging
        of the device predicate phase); None when the phase won't run."""
        eng = getattr(self.broker, "filter_engine", None)
        if eng is None or not eng.wants(msg.mountpoint):
            return None
        return eng.encode(msg.mountpoint, msg.topic, msg.payload)

    async def publish_async(
        self, msg: Msg, from_sid: Optional[SubscriberId] = None,
        trace=None,
    ) -> int:
        """Batched publish path: retain handling is synchronous (local
        read-your-writes ordering like the reference's synchronous trie
        events), then the match rides the broker's BatchCollector — many
        concurrent publishes share one device call. ``trace`` (flight
        recorder) rides the collector item into the fold envelope."""
        msg = self._pre_publish(msg)
        rows = await self.broker.batch_collector().submit(
            msg.mountpoint, msg.topic, trace, feat=self._filters_feat(msg))
        tok = _route_begin(trace)
        try:
            return self.route_rows(msg, rows, from_sid, trace=trace)
        finally:
            obs.span_end("stage_route_ms", tok)

    def publish_nowait(self, msg: Msg,
                       from_sid: Optional[SubscriberId] = None,
                       trace=None) -> int:
        """QoS0 fast path for the batched view: submit to the collector and
        route when the batch resolves, without blocking the session reader
        on the batch window (a single publisher would otherwise get exactly
        one message per window). Retain handling stays synchronous so local
        read-your-writes ordering holds. Per-publisher delivery order is
        preserved by collector submission order. A sampled publish's
        ``trace`` finishes here, after route_rows — the record's route
        stage covers the fanout work too."""
        msg = self._pre_publish(msg)

        def _routed(rows, exc) -> None:
            if exc is not None:
                self.broker.metrics.incr("mqtt_publish_error")
                return
            tok = _route_begin(trace)
            try:
                self.route_rows(msg, rows, from_sid, trace=trace)
            finally:
                obs.span_end("stage_route_ms", tok)
            self._route_finish(trace)

        self.broker.batch_collector().submit(
            msg.mountpoint, msg.topic, trace, feat=self._filters_feat(msg),
            cont=_routed)
        return 0

    def publish_wire_qos0(self, mountpoint: str,
                          words: Tuple[str, ...], topic_str: str,
                          payload: Optional[bytes],
                          from_sid: Optional[SubscriberId],
                          wire_frame: Optional[bytes] = None,
                          payload_skip: int = 0,
                          trace=None) -> int:
        """The wire-plane QoS0 publish: route straight from frame-table
        spans — no Msg, no Publish frame — for fanouts whose every
        recipient is a plain local online lone-session v4 subscriber
        with no delivery transform. Anything else (shared groups,
        remote nodes, v5 receivers, offline queues, predicates,
        QoS-upgrade) materialises ONE Msg and takes the classic
        ``route_rows`` unchanged. With the batched view active the
        match rides the collector's staging exactly like
        ``publish_nowait`` (same submission-order guarantee, same
        device/host fold seam); the trie view folds synchronously.
        The session layer pre-gates retain/dup/auth/filters, so no
        retain handling happens here. ``payload`` may be None when
        ``wire_frame`` is given — it then lives at
        ``wire_frame[payload_skip:]`` and is sliced out lazily only by
        the branches that need it."""
        if self.batched_view_active():
            def _routed(rows, exc) -> None:
                if exc is not None:
                    self.broker.metrics.incr("mqtt_publish_error")
                    return
                tok = _route_begin(trace)
                try:
                    self._wire_route(mountpoint, words, topic_str, payload,
                                     rows, from_sid, wire_frame,
                                     payload_skip, trace=trace)
                finally:
                    obs.span_end("stage_route_ms", tok)
                self._route_finish(trace)

            self.broker.batch_collector().submit(
                mountpoint, words, trace, cont=_routed)
            return 0
        n = self._wire_route(mountpoint, words, topic_str, payload,
                             self.trie(mountpoint).match(list(words)),
                             from_sid, wire_frame, payload_skip,
                             trace=trace)
        self._route_finish(trace)
        return n

    def publish_wire(self, mountpoint: str, words: Tuple[str, ...],
                     topic_str: str, payload: bytes,
                     from_sid: Optional[SubscriberId], qos: int,
                     trace=None, *, done) -> Optional[int]:
        """The wire-plane QoS1/2 publish: like
        :meth:`publish_wire_qos0` but the fanout stamps each QoS≥1
        recipient's packet id into its in-flight window and
        batch-encodes all recipients' headers in ONE native call
        (``fastpath.publish_headers_batch``). The session needs the
        match count for the PUBACK/PUBREC reason code: the trie view
        folds here and returns it; with the batched view active the
        match rides the collector, None is returned, and
        ``done(matches, exc)`` is called from the collector's release —
        in submission order, inline, AFTER the route returned (every
        recipient enqueued or written), ``exc`` being what the fold or
        the route raised. No future, no task: the caller's reader goes
        on to its next record meanwhile."""
        if self.batched_view_active():
            def _routed(rows, exc) -> None:
                n = 0
                if exc is None:
                    tok = _route_begin(trace)
                    try:
                        n = self._wire_route(mountpoint, words, topic_str,
                                             payload, rows, from_sid,
                                             qos=qos, trace=trace)
                    except Exception as e:
                        exc = e
                    finally:
                        obs.span_end("stage_route_ms", tok)
                if exc is None:
                    self._route_finish(trace)
                done(n, exc)

            self.broker.batch_collector().submit(
                mountpoint, words, trace, cont=_routed)
            return None
        n = self._wire_route(mountpoint, words, topic_str, payload,
                             self.trie(mountpoint).match(list(words)),
                             from_sid, qos=qos, trace=trace)
        self._route_finish(trace)
        return n

    def _route_finish(self, trace) -> None:
        """A sampled publish's routing returned: the flight recorder's
        ``route`` stamp and its record."""
        if trace is not None:
            trace.stamp("route")
            self.broker.recorder.finish(trace)

    def _wire_route(self, mountpoint: str, words: Tuple[str, ...],
                    topic_str: str, payload: Optional[bytes], rows,
                    from_sid: Optional[SubscriberId],
                    wire_frame: Optional[bytes] = None,
                    payload_skip: int = 0, qos: int = 0,
                    trace=None) -> int:
        """One publish's fan-out on the wire plane, in one pass over its
        matched rows: each row is classified once, into a flat list of
        recipients and their kinds; if EVERY row is the plain fast shape
        they are registered and written by ``_wire_fanout`` (verbatim
        inbound span for v4 QoS0 publishers, one shared native-encoded
        header, or one batched per-recipient header arena for
        pid/alias-bearing groups — always with the shared payload riding
        the iovec uncopied) — the object-free half of the wire plane. One
        complex row routes the whole fanout through the classic Msg path
        for exact semantics; nothing was registered by then.

        Fast rows include v5 recipients (alias-aware headers from the
        per-connection LRU via ``wire_alias_for``) and QoS≥1 deliveries
        (in-flight bookkeeping via ``wire_take_qos_fanout``); a
        qos-downgrade row (subscription qos below the publish qos but
        above 0) builds its own shared Msg per effective qos. Counted:
        ``fastpath.fanout_pass_pubs`` / ``fanout_classic_pubs``."""
        rows = list(rows)
        upgrade = self.broker.config.upgrade_outgoing_qos
        queues = self.queues
        # the recipients in row order, and each one's kind: its effective
        # QoS times 2, plus 1 for a v5 session
        sessions: List[Any] = []
        kinds: List[int] = []
        fast = True
        frame_bound = 0
        shared: Optional[List[str]] = None  # ShareName of each draw
        for _f, key, opts in rows:
            if not (isinstance(key, tuple) and len(key) == 2):
                # a shared subscription's row, one a (ShareName, filter):
                # its member is drawn now and classified below as a
                # plain row is; two filters of one name are two rows and
                # two draws. A remote node's pointer, a member's own
                # predicate row, or a group whose draw is no local online
                # member take the classic path
                picked = None
                if (isinstance(key, tuple) and len(key) == 3
                        and key[2] is None and key[0] == "$g"):
                    picked = self._share_wire_pick(mountpoint, key[1], _f,
                                                   from_sid)
                if picked is None:
                    fast = False
                    break
                if shared is None:
                    shared = [key[1]]
                else:
                    shared.append(key[1])
                key, opts = picked
            if opts.no_local and key == from_sid:
                continue
            eff = opts.qos
            if (getattr(opts, "filter_expr", None)
                    or getattr(opts, "subscription_id", None)
                    or (upgrade and eff > 0)):
                fast = False
                break
            q = queues.get(key)
            if q is None:
                continue
            try:
                (sess,) = q.sessions
                # a consumer that is no Session (a bridge endpoint) lacks
                # these and classifies complex, as in the classic fan0
                # collection
                closed = sess.closed
                is5 = sess.proto_ver == PROTO_5
            except (ValueError, AttributeError):
                fast = False  # no session, or several; not a Session
                break
            if closed or q.state is not ONLINE:
                fast = False  # a closed session, or an offline backlog
                break
            if eff > qos:
                eff = qos
            if is5:
                ok5 = getattr(sess, "wire_v5_fast_ok", None)
                if ok5 is None:
                    fast = False
                    break
                if frame_bound == 0:
                    # conservative worst-case v5 frame size, computed
                    # once per fanout: full topic (no alias), pid,
                    # prop-len byte, and a 3-byte topic-alias property
                    # — every batch-encoded variant is <= this, so a
                    # cap check against it can never pass an oversize
                    # frame (MQTT-3.1.2-24: exceeding the client's
                    # maximum_packet_size is a protocol error)
                    plen = (len(payload) if payload is not None
                            else len(wire_frame) - payload_skip)
                    body = 2 + len(topic_str.encode("utf-8")) \
                        + 2 + 1 + 3 + plen
                    frame_bound = 1 + _varint_len(body) + body
                if not ok5(frame_bound):
                    fast = False  # frame may exceed the session's cap
                    break
                kinds.append(eff + eff + 1)
            else:
                kinds.append(eff + eff)
            sessions.append(sess)
        if fast:
            fastpath.fanout_pass_pubs += 1
            if sessions:
                self._wire_fanout(mountpoint, words, topic_str, payload,
                                  wire_frame, payload_skip, sessions, kinds)
            if shared:
                n = len(shared)
                fastpath.share_picks += n
                fastpath.share_wire_picks += n
                if n > 1:
                    fastpath.share_split_picks += n - len(set(shared))
            return len(sessions)
        # complex fanout: ONE Msg, the exact classic path (host
        # predicate phase included — a racing filter subscription must
        # still filter). The payload materialises HERE, lazily, when
        # the fast fanout didn't need it as separate bytes.
        fastpath.fanout_classic_pubs += 1
        if payload is None:
            payload = wire_frame[payload_skip:]
        msg = Msg(topic=tuple(words), payload=payload, qos=qos,
                  mountpoint=mountpoint)
        return self.route_rows(msg, self._filter_rows_host(msg, rows),
                               from_sid, trace=trace)

    def _wire_fanout(self, mountpoint: str, words: Tuple[str, ...],
                     topic_str: str, payload: Optional[bytes],
                     wire_frame: Optional[bytes], payload_skip: int,
                     sessions: List[Any], kinds: List[int]) -> None:
        """The object-free fast fanout write of ``_wire_route``'s
        recipients, one group a kind — all of them, as a rule, in one:

        - v4 effective-QoS0 recipients share ONE frame — the verbatim
          inbound span when the publisher gave us one, else one
          encoded header + payload iovec;
        - every pid- or alias-bearing group (QoS≥1 and/or v5) encodes
          ALL its per-recipient headers in ONE
          ``fastpath.publish_headers_batch`` call, and the outbox queues
          memoryview slices of the arena, the shared payload riding
          each iovec uncopied (``Outbox.queue_frames``);
        - QoS≥1 recipients register the (lazily built, shared) Msg in
          their in-flight window first (``wire_take_qos_fanout``); a full
          window parks the Msg in pending, or past its cap in the queue's
          backlog, exactly like the classic deliver path — no wire write
          now, the ack-driven pump owns it. A message in a session's
          pending was handed to the session and counts as out of its
          queue, as there."""
        t0 = time.monotonic()
        b = self.broker
        cfg = b.config
        ob = b.outbox
        k0 = kinds[0]
        if kinds.count(k0) == len(kinds):
            groups = [(k0, sessions)]
        else:
            # a downgrade, or v4 beside v5: v4 QoS0 first, then each kind
            # in the order it first appears
            order = sorted(dict.fromkeys(kinds), key=bool)
            groups = [(k, [s for s, sk in zip(sessions, kinds) if sk == k])
                      for k in order]
        nbytes = 0
        sent = 0
        pended = 0  # parked in a session's pending: handed to it
        parked = 0
        msg_by_eff: Dict[int, Msg] = {}
        for kind, members in groups:
            if kind == 0:  # v4 QoS0: one frame for all
                if wire_frame is not None:
                    fb = len(wire_frame)
                    ob.queue_frames(members, repeat(wire_frame))
                else:
                    hdr = fastpath.publish_header(
                        topic_str, 0, False, False, None, len(payload))
                    fb = len(hdr) + len(payload)
                    ob.queue_frames(members, repeat(hdr), payload)
                nbytes += fb * len(members)
                sent += len(members)
                continue
            if payload is None:
                payload = wire_frame[payload_skip:]
            eff, is5 = kind >> 1, kind & 1
            if eff:
                msg = msg_by_eff.get(eff)
                if msg is None:
                    msg = msg_by_eff[eff] = Msg(
                        topic=tuple(words), payload=payload, qos=eff,
                        mountpoint=mountpoint)
                members, pids, n, nq = wire_take_qos_fanout(
                    members, msg, cfg.max_inflight_messages,
                    cfg.max_online_messages)
                pended += n
                parked += n + nq
                if not members:
                    continue
            else:
                pids = [None] * len(members)
            aliases = ([s.wire_alias_for(words) for s in members]
                       if is5 else None)
            plen = len(payload)
            arena, offs = fastpath.publish_headers_batch(
                topic_str, eff, False, False, pids, plen, bool(is5),
                aliases)
            fastpath.fanout_batches += 1
            ob.queue_frames(members,
                            map(memoryview(arena).__getitem__,
                                map(slice, offs, offs[1:])), payload)
            nbytes += offs[-1] - offs[0] + plen * len(members)
            sent += len(members)
        if sent or parked:
            b.metrics.observe("stage_wire_encode_ms",
                              (time.monotonic() - t0) * 1e3)
            self.fanout_fast_pubs += 1
            # plain integers, folded into Metrics by the outbox's flush
            # ahead of this turn's writes (broker/egress.py)
            ob.queue_in += sent + parked
            ob.queue_out += sent + pended
            ob.bytes_sent += nbytes
            ob.publish_sent += sent
            ob.matches_local += len(sessions)
            ob.touch()

    def _pre_publish(self, msg: Msg) -> Msg:
        cfg = self.broker.config
        if not self.broker.cluster_ready() and not cfg.allow_publish_during_netsplit:
            raise RuntimeError("not_ready")
        if msg.retain:
            if not msg.payload:
                self.broker.retain.delete(msg.mountpoint, msg.topic)
                msg = msg_with_retain(msg, False)
            else:
                self.broker.retain.insert(
                    msg.mountpoint,
                    msg.topic,
                    RetainedMsg(
                        msg.payload,
                        dict(msg.properties),
                        msg.qos,
                        expiry_ts=_retain_expiry(msg),
                    ),
                )
                self.broker.metrics.incr("retain_messages_stored")
        return msg

    def route_rows(
        self,
        msg: Msg,
        rows: Iterable[Tuple[Tuple[str, ...], Any, SubOpts]],
        from_sid: Optional[SubscriberId],
        origin_local: bool = True,
        trace=None,
    ) -> int:
        """The fold body (vmq_reg:publish/3 fold fun, vmq_reg.erl:326-353):
        local rows enqueue, shared rows collect by shared subscription,
        node rows forward; each shared subscription then gets one member
        by policy selection. A shared subscription is (ShareName, filter)
        as MQTT 5.0 §4.8.2 keys it: "a message that matches both Shared
        Subscriptions will be processed separately by both of them", so
        ``$share/g/a/+`` and ``$share/g/a/#`` each get a delivery of
        ``a/x``. Upstream 1.6's fold collects by ShareName alone and
        would make one.
        ``origin_local=False`` (publish arriving over the cluster channel)
        serves local plain rows only — node and group rows were already
        covered by the origin node (vmq_cluster_com.erl:198-203).
        ``trace`` (a sampled publish's flight-recorder context) rides
        node-row forwards onto the cluster envelope so the receiving
        node resumes it (one cross-node Perfetto trace)."""
        matches = 0
        # by (ShareName, filter words): the shared subscription's
        # ShareGroup, and its members whose predicate passed
        groups: Dict[Tuple[str, Tuple[str, ...]], List[Any]] = {}
        forwarded_nodes = set()  # one msg frame per remote node per publish
        # batched QoS0 fanout (the host hot path): recipients whose
        # delivery needs NO per-subscription transform and whose session
        # is a lone online v4 connection all receive the SAME wire
        # frame — collect them and write it once per socket, with
        # per-publish (not per-delivery) metric accounting. Everything
        # else takes the queue path unchanged.
        fan0: Optional[List[Any]] = \
            [] if (msg.qos == 0 and msg.expires_at is None
                   and self.broker.tracer is None) else None
        for _filter, key, opts in rows:
            if isinstance(key, tuple) and len(key) == 3 and key[0] == "$g":
                if not origin_local:
                    continue
                _, group, sid = key
                sk = (group, tuple(_filter))
                if sid is None:  # the shared subscription's one row
                    g = self._groups.get((msg.mountpoint,) + sk)
                    if g is not None:
                        groups.setdefault(sk, []).append(g)
                elif not (opts.no_local and sid == from_sid):
                    # a member whose payload predicate passed
                    groups.setdefault(sk, []).append((sid, opts))
                continue
            if isinstance(key, str):  # remote node pointer
                if origin_local and key not in forwarded_nodes:
                    # overlapping filters yield multiple pointer rows to the
                    # same node; the receiving node re-folds its own view, so
                    # exactly one frame goes out (vmq_reg.erl:346-353).
                    # The forward QoS-splits at the cluster layer: QoS 0
                    # stays fire-and-forget (sheddable), QoS >= 1 rides
                    # the durable spool (cluster/spool.py) when the peer
                    # supports it — False back means dropped, visibly.
                    forwarded_nodes.add(key)
                    if self.remote_publish is not None:
                        # keyword only when a trace rides along: test
                        # stubs and older embeddings keep their 2-arg
                        # remote_publish signature
                        ok = (self.remote_publish(key, msg, trace=trace)
                              if trace is not None
                              else self.remote_publish(key, msg))
                        if ok:
                            self.broker.metrics.incr("router_matches_remote")
                        else:
                            self.broker.metrics.incr("cluster_publish_drop")
                    else:
                        # cluster channel stopped/detached: the forward is
                        # dropped VISIBLY (same counter as a down writer)
                        self.broker.metrics.incr("cluster_publish_no_channel")
                continue
            sid = key
            if opts.no_local and sid == from_sid:
                continue
            if self._enqueue_to(sid, msg, opts, fan0):
                matches += 1
        if fan0:
            self._fanout_qos0(msg, fan0)
        if groups:
            names = set()
            for (group, _f), found in groups.items():
                if self._publish_shared(msg, found, from_sid):
                    matches += 1
                    if group in names:
                        fastpath.share_split_picks += 1
                    names.add(group)
        if matches:
            self.broker.metrics.incr("router_matches_local", matches)
        return matches

    def publish_from_remote(self, msg: Msg, trace=None) -> int:
        """Entry for ``msg`` frames from the cluster channel: fold the local
        view, local subscribers only (vmq_cluster_com.erl:153-157).

        This is a flight-recorder ADMISSION point: a cluster-ingress
        publish without a propagated context competes in the same
        1-in-N sample count as local publishes (the recorder used to be
        blind to remote traffic — the one admission decision lived only
        in ``session._handle_publish``). A ``trace`` resumed from the
        origin node's envelope context takes precedence: its sample
        decision was already made at the origin, and the finished
        record carries both nodes' stamps."""
        if trace is None:
            trace = self.broker.recorder.admit(
                "(cluster)", "/".join(msg.topic), msg.qos)
            if trace is not None:
                trace.stamp("remote_recv")
        rows = self.reg_view("trie").fold(msg.mountpoint, msg.topic)
        rows = self._filter_rows_host(msg, rows)
        n = self.route_rows(msg, rows, None, origin_local=False)
        if trace is not None:
            trace.stamp("route")
            self.broker.recorder.finish(trace)
        return n

    def enqueue_remote(self, sid: SubscriberId, msgs: List[Msg],
                       migrate: bool = False) -> bool:
        """Entry for ``enq`` frames (remote shared-sub delivery and queue
        migration drain): enqueue into the local queue
        (vmq_cluster_com.erl:160-196). With ``migrate`` the sender is
        the record owner running a coordinated handoff: the drain lands
        BEFORE the fence repoints the record, so accept the queue even
        though the record still names the old owner."""
        queue = self.queues.get(sid)
        if queue is None:
            rec = self.db.read(sid)
            if rec is None:
                return False
            if rec.node != self.node_name and not (
                    migrate and not rec.clean_session):
                return False
            queue = self._start_queue(sid, QueueOpts(
                clean_session=rec.clean_session))
        for m in msgs:
            queue.enqueue(m)
        return True

    def _prep_out(self, msg: Msg, opts: SubOpts) -> Msg:
        """Per-subscription delivery transform: RAP flag, outgoing QoS
        (upgrade_outgoing_qos), subscription identifier — applied the same
        whether the member is local or remote."""
        out = msg if opts.rap else msg_with_retain(msg, False)
        qos = opts.qos if self.broker.config.upgrade_outgoing_qos else min(opts.qos, msg.qos)
        out = out.with_qos(qos)
        return _maybe_add_sub_id(out, opts)

    def _enqueue_to(self, sid: SubscriberId, msg: Msg, opts: SubOpts,
                    fan0: Optional[List[Any]] = None) -> bool:
        queue = self.queues.get(sid)
        if queue is None:
            return False
        out = self._prep_out(msg, opts)
        if fan0 is not None and out is msg and queue.state is ONLINE \
                and len(queue.sessions) == 1:
            # out IS msg → no rap/qos/sub-id transform applied, so this
            # recipient gets the identical wire frame; a lone online v4
            # session takes the shared-frame write in _fanout_qos0
            sess = next(iter(queue.sessions))
            if (not getattr(sess, "closed", True)
                    and getattr(sess, "proto_ver", PROTO_5) != PROTO_5):
                fan0.append(sess)
                return True
        queue.enqueue(out)
        return True

    def _fanout_qos0(self, msg: Msg, sessions: List[Any]) -> None:
        """Shared-frame QoS0 fanout: one serialisation, one buffered
        socket write per recipient, metric increments once per PUBLISH
        instead of 4x per delivery (the dominant cost of the Python
        delivery path at fanout — profiled at 36%). Semantics match the
        queue path exactly for the collected class of recipients
        (online, lone session, v4, no transform, no tracing)."""
        t0 = time.monotonic()
        iov = wire_v4_iov_qos0(msg)
        nbytes = sum(len(c) for c in iov)
        handlers = self.broker.hooks.handlers("on_deliver")
        delivered = 0
        for sess in sessions:
            if sess.closed:  # closed between collect and write
                q = self.queues.get(sess.sid)
                if q is not None:
                    q.enqueue(msg)
                continue
            for fn in handlers:  # prefetched once per publish
                try:
                    res = fn(sess.username, sess.sid, msg.topic,
                             msg.payload)
                    if asyncio.iscoroutine(res):
                        # async hooks schedule, same as hooks_fire_all
                        asyncio.ensure_future(res)
                except Exception:
                    log.exception("on_deliver hook failed")
            sess.transport.write_iov(iov)
            delivered += 1
        if delivered:
            self.broker.metrics.observe(
                "stage_wire_encode_ms", (time.monotonic() - t0) * 1e3)
            self.fanout_fast_pubs += 1
            m = self.broker.metrics
            m.incr("queue_message_in", delivered)
            m.incr("queue_message_out", delivered)
            m.incr("bytes_sent", delivered * nbytes)
            m.incr("mqtt_publish_sent", delivered)

    def _publish_shared(self, msg: Msg, found: List[Any],
                        from_sid: Optional[SubscriberId]) -> bool:
        """Deliver to ONE member of a shared subscription, drawn by
        policy with online members first
        (vmq_shared_subscriptions.erl:26-63,90-106): uniformly within the
        first class that yields a delivery — ``prefer_local``: online
        local, then remote, then offline local; ``random``: online local
        and remote together, then offline local; ``local_only``: online
        local, then offline local. ``found`` holds the shared
        subscription's ShareGroup and the members whose payload predicate
        passed. A member's own QoS, rap and subscription id apply
        (``_prep_out``); a remote member's delivery rides the cluster
        ``enq`` channel (vmq_shared_subscriptions.erl:86-88)."""
        classes = self._share_classes(found)
        last = len(classes) - 1
        for c, pools in enumerate(classes):
            for sid, opts, pool in self._share_order(pools):
                if opts.no_local and sid == from_sid:
                    continue
                if pool[3]:  # drawn as online here: is it still?
                    q = self.queues.get(sid)
                    if q is None or q.state != ONLINE:
                        fastpath.share_stale_picks += 1
                        if pool[2] is not None:
                            pool[2].moved(sid, False)
                        continue
                if self._share_send(msg, sid, opts):
                    fastpath.share_picks += 1
                    if c == last:
                        fastpath.share_offline_picks += 1
                    return True
        return False

    def _share_wire_pick(self, mountpoint: str, group: str, filter_words,
                         from_sid: Optional[SubscriberId]):
        """(sid, opts) of the member the wire plane writes a group's
        delivery to: drawn from the policy's first class, and a local
        member online here. None (a remote member drawn, no member online,
        a member with a predicate) leaves the publish to the classic path,
        whose draw is made afresh from the same classes."""
        g = self._groups.get((mountpoint, group, tuple(filter_words)))
        if g is None or g.filtered:
            return None
        for sid, opts, pool in self._share_order(self._share_classes([g])[0]):
            if opts.no_local and sid == from_sid:
                continue
            if not pool[3]:
                return None
            q = self.queues.get(sid)
            if q is None or q.state != ONLINE:
                fastpath.share_stale_picks += 1
                g.moved(sid, False)
                continue
            return sid, opts
        return None

    def _share_classes(self, found: List[Any]) -> List[List[tuple]]:
        """The policy's candidate classes, in order, each a list of pools
        ``(sids, opts by sid, the ShareGroup whose online list a stale
        draw repairs, whether the member must be online here)``: a
        group's indexed sets as they stand, and the members whose
        predicate passed, classed now."""
        online: List[tuple] = []
        remote: List[tuple] = []
        offline: List[tuple] = []
        extra: Dict[SubscriberId, SubOpts] = {}
        x_on: List[SubscriberId] = []
        x_remote: List[SubscriberId] = []
        x_off: List[SubscriberId] = []
        for f in found:
            if type(f) is ShareGroup:
                online.append((f.online.sids, f.members, f, True))
                remote.append((f.remote.sids, f.members, None, False))
                offline.append((f.offline.sids, f.members, None, False))
                continue
            sid, opts = f
            extra[sid] = opts
            if getattr(opts, "node", self.node_name) != self.node_name:
                x_remote.append(sid)
            elif (q := self.queues.get(sid)) is not None \
                    and q.state == ONLINE:
                x_on.append(sid)
            else:
                x_off.append(sid)
        if extra:
            online.append((x_on, extra, None, True))
            remote.append((x_remote, extra, None, False))
            offline.append((x_off, extra, None, False))
        policy = self.broker.config.shared_subscription_policy
        if policy == "local_only":
            return [online, offline]
        if policy == "random":
            return [online + remote, offline]
        return [online, remote, offline]  # prefer_local

    #: draws by position a class gets before the rest of its members are
    #: offered in a shuffled order (draws refused: a member already
    #: offered, the publisher's own no-local membership, a stale entry, a
    #: delivery that failed)
    _SHARE_DRAWS = 4

    def _share_order(self, pools: List[tuple]):
        """The members of one class in a uniformly random order, made
        lazily: a draw by position is O(1) whatever the class's size, so
        a consumer that takes the first member pays nothing for the
        rest. Yields ``(sid, opts, pool)``; the consumer may move a stale
        member out of a pool between draws."""
        seen = set()
        for _ in range(self._SHARE_DRAWS):
            n = 0
            for p in pools:
                n += len(p[0])
            if n == 0:
                return
            i = random.randrange(n)
            for p in pools:
                if i < len(p[0]):
                    break
                i -= len(p[0])
            sid = p[0][i]
            if sid not in seen:
                seen.add(sid)
                yield sid, p[1][sid], p
        rest = [(sid, p) for p in pools for sid in list(p[0])
                if sid not in seen]
        random.shuffle(rest)
        for sid, p in rest:
            if sid not in seen and sid in p[1]:
                seen.add(sid)
                yield sid, p[1][sid], p

    def _share_send(self, msg: Msg, sid: SubscriberId,
                    opts: SubOpts) -> bool:
        node = getattr(opts, "node", self.node_name)
        if node == self.node_name:
            return self._enqueue_to(sid, msg, opts)
        if self.remote_enqueue_nowait is not None and \
                self.remote_enqueue_nowait(node, sid,
                                           [self._prep_out(msg, opts)]):
            self.broker.metrics.incr("router_matches_remote")
            return True
        return False

    # -- introspection -----------------------------------------------------

    def stats(self) -> Dict[str, float]:
        # a shared subscription is one trie row; each member counts
        total = sum(len(t) for t in self._tries.values()) + sum(
            len(g.members) - 1 for g in self._groups.values())
        mem = sum(t.stats()["memory"] for t in self._tries.values())
        out = {
            "router_subscriptions": total,
            "router_memory": mem,
            "queue_processes": len(self.queues),
            # publishes whose whole local fanout took the shared-frame
            # QoS0 fast path (vs the per-recipient queue path)
            "router_fanout_fast_pubs": self.fanout_fast_pubs,
        }
        # device-matcher gauges when the TPU reg view is live (the
        # router_subscriptions/router_memory pair extended with the HBM
        # table's health — fallbacks rising means fanouts exceed
        # tpu_max_fanout and the exact host path is absorbing them)
        tpu = self.reg_views.get("tpu")
        if tpu is not None:
            for mp, m in getattr(tpu, "_matchers", {}).items():
                ts = m.table.stats()
                out["tpu_table_rows"] = out.get("tpu_table_rows", 0) + \
                    ts["subscriptions"]
                out["tpu_table_bytes"] = out.get("tpu_table_bytes", 0) + \
                    ts["table_bytes"]
                out["tpu_match_batches"] = out.get("tpu_match_batches", 0) \
                    + m.match_batches
                out["tpu_match_publishes"] = \
                    out.get("tpu_match_publishes", 0) + m.match_publishes
                out["tpu_host_fallbacks"] = \
                    out.get("tpu_host_fallbacks", 0) + m.host_fallbacks
                out["tpu_warmup_batches"] = \
                    out.get("tpu_warmup_batches", 0) + m.warmup_batches
                out["tpu_async_rebuilds"] = \
                    out.get("tpu_async_rebuilds", 0) + m.rebuilds_async
                out["tpu_device_failures"] = \
                    out.get("tpu_device_failures", 0) + m.device_failures
                out["tpu_degraded_sheds"] = \
                    out.get("tpu_degraded_sheds", 0) + m.degraded_sheds
                out["tpu_delta_shapes_warmed"] = \
                    out.get("tpu_delta_shapes_warmed", 0) \
                    + m.delta_shapes_warmed
                # stall-watchdog fallout (abandoned dispatches fed to
                # the breaker, wedged rebuilds reaped)
                out["tpu_dispatch_stalls"] = \
                    out.get("tpu_dispatch_stalls", 0) + m.dispatch_stalls
                out["tpu_rebuild_abandons"] = \
                    out.get("tpu_rebuild_abandons", 0) + m.rebuild_abandons
                br = getattr(m, "breaker", None)
                if br is not None:
                    # state: worst across mountpoints (0 closed, 1
                    # half-open, 2 open) — any open matcher means the
                    # node is in degraded matching mode
                    out["tpu_breaker_state"] = max(
                        out.get("tpu_breaker_state", 0), br.state)
                    out["tpu_breaker_opens"] = \
                        out.get("tpu_breaker_opens", 0) + br.opens
                    out["tpu_breaker_closes"] = \
                        out.get("tpu_breaker_closes", 0) + br.closes
                    out["tpu_breaker_time_degraded_seconds"] = round(
                        out.get("tpu_breaker_time_degraded_seconds", 0.0)
                        + br.time_degraded(), 3)
            # the wide result: publishes past the flat form's caps that
            # the device answered whole (failures: host-matched); the
            # matcher module's process totals
            from ..models import tpu_matcher as _tm

            out["tpu_wide_publishes"] = _tm.wide_publishes
            out["tpu_wide_dispatches"] = _tm.wide_dispatches
            out["tpu_wide_topics"] = _tm.wide_topics
            out["tpu_wide_rows"] = _tm.wide_rows
            out["tpu_wide_failures"] = _tm.wide_failures
            # the flat form's phases: programs executed and the phases
            # compiled into them (1-3 each; 1 = probe A alone)
            out["tpu_phase_dispatches"] = _tm.phase_dispatches
            out["tpu_phase_runs"] = _tm.phase_runs
            out["tpu_phase_dense_dispatches"] = _tm.phase_dense_dispatches
        col = getattr(self.broker, "_collector", None)
        if col is not None:
            # small flushes served host-side by hybrid dispatch
            out["tpu_hybrid_host_pubs"] = col.host_hybrid_pubs
            out["tpu_overload_shed_pubs"] = col.overload_host_pubs
            out["tpu_saturated_merges"] = col.saturated_merges
            # pubs the trie served while the device table rebuilt
            out["tpu_rebuild_shed_pubs"] = col.rebuild_host_pubs
            # pubs the trie served past the matcher-lock busy bound
            out["tpu_busy_shed_pubs"] = col.busy_host_pubs
            # pubs the trie served while the device breaker was open
            out["tpu_degraded_host_pubs"] = col.degraded_host_pubs
            # pubs the trie served after a dispatch-deadline abandon /
            # past their queued-item expiry (stall watchdog bounds)
            out["tpu_stalled_host_pubs"] = col.stalled_host_pubs
            out["tpu_expired_host_pubs"] = col.expired_host_pubs
            out["tpu_release_rows"] = col.release_rows
        # deterministic fault-injection harness (robustness/faults.py)
        from ..robustness import faults as _faults

        out.update(_faults.stats())
        # wire plane (protocol/fastpath.py): native-vs-pure batch split,
        # codec breaker state, object-free admissions
        out.update(fastpath.stats())
        return out

    def fold_subscriptions(self, mountpoint: str = ""):
        """Iterate every (filter, key, opts) — warm-load feed for the TPU
        table (mirrors vmq_reg:fold_subscriptions, vmq_reg_trie warm load)."""
        return self.trie(mountpoint).entries()


def _qopts_to_dict(opts: "QueueOpts") -> Dict[str, Any]:
    """Durable queue parameters carried in the subscriber record so boot
    re-creation keeps them (session expiry above all — MQTT5 semantics)."""
    return {
        "session_expiry": opts.session_expiry,
        "max_offline_messages": opts.max_offline_messages,
        "max_online_messages": opts.max_online_messages,
        "queue_type": opts.queue_type,
        "deliver_mode": opts.deliver_mode,
    }


def _qopts_from_dict(d: Dict[str, Any], config) -> "QueueOpts":
    from .queue import QueueOpts

    return QueueOpts(
        clean_session=False,
        session_expiry=d.get("session_expiry", 0),
        max_offline_messages=d.get("max_offline_messages",
                                   config.max_offline_messages),
        max_online_messages=d.get("max_online_messages",
                                  config.max_online_messages),
        queue_type=d.get("queue_type", config.queue_type),
        deliver_mode=d.get("deliver_mode", config.queue_deliver_mode),
    )


def msg_with_retain(msg: Msg, retain: bool) -> Msg:
    if msg.retain == retain:
        return msg
    return dataclasses.replace(msg, retain=retain)


def _maybe_add_sub_id(msg: Msg, opts: SubOpts) -> Msg:
    sub_id = getattr(opts, "subscription_id", None)
    if not sub_id:
        return msg
    props = dict(msg.properties)
    props.setdefault("subscription_identifier", []).append(sub_id)
    return dataclasses.replace(msg, properties=props)


def _retain_expiry(msg: Msg) -> Optional[float]:
    interval = msg.properties.get("message_expiry_interval")
    if interval:
        return time.time() + interval
    return None
