"""The part of the verdict the parent makes of the shards' reports: the
rule is exact. A delivery still absent when the wait for it ends is lost,
whatever the broker counted and wherever in a connection's stream it
falls; a publish never acknowledged is one too."""

import numpy as np

from benchmark import harness


def reports(lost=(), acked=100, served=95, host=5, system="vernemq_tpu"):
    n = 100
    pub = {"n_sent": {0: n, 1: n}, "n_acked": {0: acked, 1: n},
           "stamps": {p: np.arange(n, dtype=np.int64) + 10 for p in (0, 1)},
           "lost_connections": [], "late_ms": np.zeros(3, np.float32),
           "cpu_s": 0.1, "wall_s": 1.0}
    keys = np.asarray([(p << 36) | s for p, s in lost], np.int64)
    sub = {"failed_pubseq": keys, "owed": 2 * n, "owed_in_window": 2 * n,
           "received": 2 * n - len(lost),
           "received_in_window": 2 * n - len(lost),
           "redelivered_with_dup": 0,
           "lost_qos1": len(lost), "lost_qos0": 0, "duplicates": 0,
           "strays": 0, "misordered": 0, "n_closed": 0,
           "lat_ms": np.ones(5, np.float32), "steps": [],
           "verdict_ns": 10**9, "examples": {},
           "share_owed": np.zeros(0, np.int64),
           "share_received": np.zeros(0, np.int64),
           "share_by_member": np.zeros((0, 3), np.int64), "shares": []}
    fin = harness._finish_request([pub], {"qos": 1}, (0, 10**6))
    delta = {"match_publishes": served, "host_hybrid_pubs": host}
    return harness._reduce([pub], [sub], fin, delta, {}, 1.0, system)


def test_a_clean_run_is_correct():
    run = reports()
    assert run["correct"] is True and run["failed"] == 0
    assert run["compared"]["device_served_pct"]["value"] == 95.0


def test_the_unserved_tail_of_a_connection_is_lost_not_late():
    run = reports(lost=[(0, 98), (0, 99), (1, 99)])
    assert run["correct"] is False and run["failed"] == 3
    assert run["compared"]["lost_qos1"] == {"value": 3, "limit": 0}
    # what never came is in the latency too, as waited for to the verdict
    assert run["deliver_max_ms"] > 900


def test_a_publish_never_acknowledged_fails_the_run():
    run = reports(acked=97)
    assert run["correct"] is False and run["failed"] == 3
    assert run["compared"]["unacked"]["value"] == 3


def test_a_run_the_host_trie_served_is_not_correct():
    assert reports(served=10, host=90)["correct"] is False
    assert reports(served=0, host=0)["correct"] is False
    assert reports(served=0, host=0, system="reference")["correct"] is True
