"""Point to point: ``topics`` topics ``<prefix>/<k>``, one subscriber to
each at QoS ``qos``, one publisher to each. ``live_pairs`` of the pairs
are live — the subscriber a TCP connection that SUBSCRIBEs its own topic,
the publisher a connection of the mix — and the other subscribers stand
in the persisted subscriber DB (a subscription, no connection). The
structure is the same for every seed; ``--seed`` permutes the topic
numbers, so which pairs are live, and in which order the rows reach the
table, differs. Publisher ``p`` always publishes to the topic of live
subscriber ``p``."""

from __future__ import annotations

import numpy as np

from ..corpus import Corpus, LiveSession


def build(spec: dict, seed: int) -> Corpus:
    n, live_n = int(spec["topics"]), int(spec["live_pairs"])
    prefix, qos = spec["topic_prefix"], int(spec["qos"])
    rng = np.random.Generator(np.random.PCG64([int(seed), 0xC0B9]))
    words = [str(k) for k in rng.permutation(n)]
    live = [LiveSession(f"sub{w}", True, [(f"{prefix}/{w}", qos)])
            for w in words[:live_n]]

    def records():
        for w in words[live_n:]:
            yield f"sub{w}", [((prefix, w), qos)]

    def topics(publisher: int, start: int, count: int) -> np.ndarray:
        return np.tile(np.asarray([0, publisher], np.int32), (count, 1))

    return Corpus([[prefix], words], n - live_n, live, records, n, topics)
