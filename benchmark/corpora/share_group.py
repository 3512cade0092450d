"""Fan-in to one share group: ``topics`` topics ``<prefix>/<k>``,
``live_publishers`` publishers that hold a connection, publisher ``p``
publishing to topic ``p``, and ``subscribers`` live sessions that each
SUBSCRIBE ``$share/<group>/<prefix>/#`` over TCP at QoS ``qos``: one
shared subscription whose members they all are, so a publish owes ONE
delivery, to whichever member the broker picks (``reference``'s rule).
Nothing stands in the persisted subscriber DB. ``bystanders`` (default 0)
further live sessions hold one plain subscription each, to a topic nobody
publishes to: they are owed nothing, so whatever reaches one is a stray
(the control's ``stray`` break needs a session that is no member). The
structure is the same for every seed; ``--seed`` permutes the topic
numbers (which word each publisher sends to, and the order the words are
interned) and the client ids (which member falls into which subscriber
process, and the order the rows reach the table)."""

from __future__ import annotations

import numpy as np

from ..corpus import Corpus, LiveSession


def build(spec: dict, seed: int) -> Corpus:
    n_topics, n_subs = int(spec["topics"]), int(spec["subscribers"])
    n_pubs, n_by = int(spec["live_publishers"]), int(spec.get("bystanders", 0))
    prefix, qos, group = spec["topic_prefix"], int(spec["qos"]), spec["group"]
    if not n_pubs <= int(spec["publishers"]) <= n_topics:
        raise ValueError("a publisher needs a topic of its own")
    rng = np.random.Generator(np.random.PCG64([int(seed), 0x5A4E]))
    words = [str(k) for k in rng.permutation(n_topics)]
    member = [(f"$share/{group}/{prefix}/#", qos)]
    live = [LiveSession(f"sub{c}", True, list(member))
            for c in rng.permutation(n_subs)]
    live += [LiveSession(f"by{c}", True, [(f"{prefix}-aside/{c}", qos)])
             for c in range(n_by)]

    def records():
        return iter(())

    def topics(publisher: int, start: int, count: int) -> np.ndarray:
        return np.tile(np.asarray([0, publisher], np.int32), (count, 1))

    return Corpus([[prefix], words], 0, live, records, n_subs + n_by, topics,
                  publishers=n_pubs)
