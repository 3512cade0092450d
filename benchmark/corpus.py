"""What a corpus builder returns.

A corpus is the deployment's subscriber database: who holds which filter
at which QoS, which of those sessions are live (a TCP connection of the
load generator), the words of each topic level, and which topic each of a
publisher's publishes goes to. Builders live one per file in
``benchmark/corpora/`` and are found by the name in the configuration's
``corpus_builder``; the parent process and every load-generator process
call the same builder with the same arguments and get the same corpus, so
nothing but (configuration, seed) crosses a pipe.

The STRUCTURE of a corpus (how many subscriptions of which kind fall
under which word) is the same in every run; ``--seed`` permutes words and
client ids over it and picks the live sessions. So every seed gives the
broker the same set of bucket sizes in another order — and with them the
same device-table geometry, which the program's compile signatures are
made of (``TpuMatcher._geometry``: rows, bucket maxima): a seed that
changed the structure would compile every program anew in every run.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

Words = Tuple[str, ...]


@dataclass
class LiveSession:
    """One TCP connection of a subscriber process."""
    client_id: str
    clean_session: bool
    #: filters this client SUBSCRIBEs over TCP after CONNACK
    tcp_filters: List[Tuple[str, int]] = field(default_factory=list)
    #: filters the persisted subscriber DB already holds for it
    stored: List[Tuple[Words, int]] = field(default_factory=list)

    def subscriptions(self) -> Dict[Words, int]:
        """(client, filter) is a key: a later QoS replaces an earlier. A
        membership of a shared subscription keeps its words as subscribed,
        ``("$share", group, ...)``: the reference tells the two apart."""
        out: Dict[Words, int] = {}
        for words, qos in self.stored:
            out[words] = qos
        for f, qos in self.tcp_filters:
            out[tuple(f.split("/"))] = qos
        return out


@dataclass
class Corpus:
    pools: List[List[str]]            # words of each topic level
    n_stored: int                     # subscriptions in the persisted DB
    live: List[LiveSession]
    #: yields (client_id, [(filter words, qos), ...]): the persisted DB,
    #: one subscriber record at a time
    records: Callable[[], Iterator[Tuple[str, list]]]
    #: resident subscriptions once the TCP SUBSCRIBEs are in
    n_resident: int
    #: ``topics(publisher, start, n)``: level indices ``[n, levels]`` into
    #: ``pools`` of that publisher's publishes ``start .. start + n``; a
    #: sender and a checker that ask for different ranges see the same
    topics: Callable[[int, int, int], np.ndarray]
    #: publisher connections of a mix that opens ``one_per_live_session``;
    #: None: as many as ``live`` (point to point, a publisher a subscriber)
    publishers: Optional[int] = None


def build(config: dict, seed: int) -> Corpus:
    mod = importlib.import_module(
        "benchmark.corpora." + config["corpus_builder"])
    return mod.build(config, seed)
