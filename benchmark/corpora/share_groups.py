"""Fan-in to several shared subscriptions: ``topics`` topics
``<prefix>/<k>``, ``live_publishers`` publishers that hold a connection,
publisher ``p`` publishing to topic ``p``, and the members of each entry of
``shared`` — ``{"share": <ShareName>, "filter": <filter>, "members": n,
"clean_session": bool, "client_prefix": <id prefix>}`` — live sessions
that are members of ``$share/<share>/<filter>`` at QoS ``qos``. A shared
subscription is (ShareName, filter) (MQTT 5.0 section 4.8.2, the
``reference``'s rule): two entries may share a ShareName under different
filters and are then two shared subscriptions, each owed its own delivery
of a publish both match. A clean-session member SUBSCRIBEs over TCP after
CONNACK; a persistent one (``clean_session`` false) finds its membership in
the persisted subscriber DB at boot (``records``) and CONNECTs with clean
session false. Every session is a member of ONE shared subscription, so
the members stay disjoint (the reference cannot attribute a frame of a
session that is a member of two that match one topic).

The structure is the same for every seed; ``--seed`` permutes the topic
numbers (which word each publisher sends to, and the order the words are
interned) and each entry's client ids (which member falls into which
subscriber process, and the order the members reach the registry)."""

from __future__ import annotations

import numpy as np

from ..corpus import Corpus, LiveSession


def build(spec: dict, seed: int) -> Corpus:
    n_topics, n_pubs = int(spec["topics"]), int(spec["live_publishers"])
    prefix, qos = spec["topic_prefix"], int(spec["qos"])
    if not n_pubs <= int(spec["publishers"]) <= n_topics:
        raise ValueError("a publisher needs a topic of its own")
    rng = np.random.Generator(np.random.PCG64([int(seed), 0x6E1B]))
    words = [str(k) for k in rng.permutation(n_topics)]
    live = []
    stored = []
    for entry in spec["shared"]:
        f = f"$share/{entry['share']}/{entry['filter']}"
        clean = bool(entry["clean_session"])
        for c in rng.permutation(int(entry["members"])):
            cid = f"{entry['client_prefix']}{c}"
            if clean:
                live.append(LiveSession(cid, True, [(f, qos)]))
            else:
                member = [(tuple(f.split("/")), qos)]
                live.append(LiveSession(cid, False, stored=member))
                stored.append((cid, member))
    if len({s.client_id for s in live}) != len(live):
        raise ValueError("a session is a member of one shared subscription")

    def records():
        return iter(stored)

    def topics(publisher: int, start: int, count: int) -> np.ndarray:
        return np.tile(np.asarray([0, publisher], np.int32), (count, 1))

    return Corpus([[prefix], words], len(stored), live, records, len(live),
                  topics, publishers=n_pubs)
