"""The parent's handle on the load-generator processes.

Spawned (never forked) before the parent imports JAX, so no child ever
holds the chip; every call blocks on pipes and is made from an executor
thread, because the broker runs on the parent's event loop.
"""

from __future__ import annotations

import multiprocessing as mp
import time
from typing import Any, Dict, List

from . import loadgen

REPLY_BOUND_S = 900.0


class Generator:
    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        self.config, self.mix = config, mix
        ctx = mp.get_context("spawn")
        self.shards: List[tuple] = []  # (role, process, pipe)
        n_sub = int(config["subscriber_processes"])
        n_pub = int(mix["publisher_processes"])
        for role, n in (("sub", n_sub), ("pub", n_pub)):
            for k in range(n):
                here, there = ctx.Pipe()
                spec = {"config": config, "mix": mix, "seed": seed,
                        "shard": k, "shards": n, "host": "127.0.0.1"}
                p = ctx.Process(target=loadgen.main, name=f"bench-{role}{k}",
                                args=(there, role, spec), daemon=True)
                self.shards.append((role, p, here, spec))
        self._started = False

    def spawn(self) -> None:
        """Start the processes; they build their corpus and wait."""
        for _role, p, _c, _s in self.shards:
            p.start()
        self._started = True

    def _recv(self, conn, bound: float = REPLY_BOUND_S) -> Any:
        if not conn.poll(bound):
            raise TimeoutError("a load-generator process did not answer")
        ok, value = conn.recv()
        if not ok:
            raise RuntimeError("load generator failed:\n" + str(value))
        return value

    def _all(self, role: str, cmd: str, arg: Any) -> List[Any]:
        conns = [c for r, _p, c, _s in self.shards if r == role]
        for c in conns:
            c.send((cmd, arg))
        return [self._recv(c) for c in conns]

    def ready(self) -> None:
        for _r, _p, c, _s in self.shards:
            self._recv(c)

    def connect(self, port: int) -> Dict[str, int]:
        """Subscribers first (their SUBSCRIBEs belong to the first device
        build), then publishers."""
        out: Dict[str, int] = {}
        for role in ("sub", "pub"):
            for reply in self._all(role, "connect", port):
                for k, v in reply.items():
                    out[f"{role}.{k}"] = out.get(f"{role}.{k}", 0) + v
        return out

    def run(self, start: dict) -> List[dict]:
        return self._all("pub", "start", start)

    def finish(self, fin: dict) -> List[dict]:
        """Wait for each delivery owed, then compare. A delivery owed to a
        shared subscription is read by whichever process holds the member
        the broker chose, so no process knows when it has read its own:
        the wait is for the sum, here, while the processes go on reading.
        It ends when as many frames were read as are owed, after
        ``drain_max_s``, or when no socket read any for ``drain_quiet_s``."""
        owed = self._all("sub", "owed", fin)
        want = sum(o["owed"] for o in owed) + owed[0]["owed_shared"]
        t0 = time.monotonic()
        while True:
            got = self._all("sub", "progress", None)
            if (sum(g["received"] for g in got) >= want
                    or time.monotonic() - t0 > fin["drain_max_s"]
                    or min(g["quiet_s"] for g in got) > fin["drain_quiet_s"]):
                break
            time.sleep(0.05)
        return self._all("sub", "finish", None)

    def close(self) -> None:
        for _r, p, c, _s in self.shards:
            if p.is_alive():
                try:
                    c.send(("exit", None))
                except OSError:
                    pass
        for _r, p, c, _s in self.shards:
            if self._started:
                p.join(20.0)
                if p.is_alive():
                    p.terminate()
                    p.join(10.0)
            c.close()
