"""Shared subscriptions (``$share/<group>/<filter>``) as ONE routing row.

A shared subscription is one value in the host trie, one row in the
device table and one row through the match service, whatever its number
of members: keyed ``("$g", group, None)`` and carrying no options. Its
members live in the registry's :class:`ShareGroup`, keyed by (mountpoint,
group, filter words), sid -> options, split into the classes upstream's
selection draws from (``vmq_shared_subscriptions.erl:26-106``): local
members whose queue is online, remote members, local members offline.
Each class is an indexed set, so a draw costs the same at 500 members as
at 3; the queue's own state changes keep the online class
(``Registry.share_member_moved``).

A member whose subscription carries a payload predicate is in none of the
classes: the predicate phase (``filters/engine.py``) sees a row of its
own beside the group's, keyed ``("$g", group, sid)`` as before, and only
a member whose predicate passed reaches the draw (``extra`` below)."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

def row_key(group: str) -> Tuple[str, str, None]:
    """The key of a shared subscription's one row in a trie or table."""
    return ("$g", group, None)


class Members:
    """A set of sids with O(1) add, discard, and draw by position (a
    list and each sid's position in it; a discard moves the last sid
    into the hole)."""

    __slots__ = ("sids", "pos")

    def __init__(self) -> None:
        self.sids: List[Any] = []
        self.pos: Dict[Any, int] = {}

    def __len__(self) -> int:
        return len(self.sids)

    def add(self, sid) -> None:
        if sid not in self.pos:
            self.pos[sid] = len(self.sids)
            self.sids.append(sid)

    def discard(self, sid) -> None:
        i = self.pos.pop(sid, None)
        if i is None:
            return
        last = self.sids.pop()
        if i < len(self.sids):
            self.sids[i] = last
            self.pos[last] = i


class ShareGroup:
    """The members of one shared subscription on this node's registry."""

    __slots__ = ("name", "members", "filtered", "online", "offline",
                 "remote")

    def __init__(self, name: str) -> None:
        self.name = name
        self.members: Dict[Any, Any] = {}    # sid -> SubOpts (opts.node)
        self.filtered: Dict[Any, Any] = {}   # of those, with a predicate
        self.online = Members()              # local, queue online
        self.offline = Members()             # local, no queue online
        self.remote = Members()              # on another node

    def join(self, sid, opts, local: bool, online: bool,
             filtered: bool) -> None:
        """A member subscribed, or changed its options or its node."""
        self.leave(sid)
        self.members[sid] = opts
        if filtered:
            self.filtered[sid] = opts
        elif not local:
            self.remote.add(sid)
        elif online:
            self.online.add(sid)
        else:
            self.offline.add(sid)

    def leave(self, sid) -> bool:
        if self.members.pop(sid, None) is None:
            return False
        self.filtered.pop(sid, None)
        self.online.discard(sid)
        self.offline.discard(sid)
        self.remote.discard(sid)
        return True

    def moved(self, sid, online: bool) -> None:
        """A local member's queue came online, or is online no more."""
        src, dst = ((self.offline, self.online) if online
                    else (self.online, self.offline))
        if sid in src.pos:
            src.discard(sid)
            dst.add(sid)
