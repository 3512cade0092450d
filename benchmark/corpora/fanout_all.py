"""Fan-out: ``topics`` topics ``<prefix>/<k>``, ``subscribers`` live
sessions that each SUBSCRIBE every one of them over TCP at QoS ``qos``
(one SUBSCRIBE packet, ``topics`` filters), and ``publishers`` publishers,
publisher ``p`` publishing to topic ``p``. Nothing stands in the persisted
subscriber DB: every subscription is a live session's, so a publish owes
``subscribers`` deliveries. The structure is the same for every seed;
``--seed`` permutes the topic numbers (which word each publisher sends to,
and the order the words are interned) and the client ids (which session
falls into which subscriber process, and the order the rows reach the
table)."""

from __future__ import annotations

import numpy as np

from ..corpus import Corpus, LiveSession


def build(spec: dict, seed: int) -> Corpus:
    n_topics, n_subs = int(spec["topics"]), int(spec["subscribers"])
    prefix, qos = spec["topic_prefix"], int(spec["qos"])
    if int(spec["publishers"]) > n_topics:
        raise ValueError("a publisher needs a topic of its own")
    rng = np.random.Generator(np.random.PCG64([int(seed), 0xFA40]))
    words = [str(k) for k in rng.permutation(n_topics)]
    filters = [(f"{prefix}/{w}", qos) for w in words]
    live = [LiveSession(f"sub{c}", True, list(filters))
            for c in rng.permutation(n_subs)]

    def records():
        return iter(())

    def topics(publisher: int, start: int, count: int) -> np.ndarray:
        return np.tile(np.asarray([0, publisher], np.int32), (count, 1))

    return Corpus([[prefix], words], 0, live, records, n_topics * n_subs,
                  topics)
