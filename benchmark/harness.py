"""One run of one cell: set-up, the measured window, the verdict.

``drive`` is the whole of a run but for the choice of system: boot, connect
the generator's sessions, warm, offer the mix's traffic for ``warm_s`` +
``seconds``, read the program's counters at both ends of the window, wait
for what is owed, and reduce everything to the contract's last line. The
end-to-end metrics are taken here, from the clients' side, on the host's
clock: the subscriber processes' read times against the stamps in the
payloads.
"""

from __future__ import annotations

import asyncio
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import reference
from .manifest import ROOT, Manifest, metric_reader
from .systems import HOST_SERVED

#: seconds of the mix's traffic before the window opens: the collector's
#: EWMAs, the sessions' topic caches and the cyclic collector's freeze
#: reach their steady state; counted as set-up
WARM_S = 3.0
TRACE_S = 4.0          # seconds of the window a --trace 1 run traces
#: how long a publisher waits for its PUBACKs and a subscriber for what it
#: is owed once the generator stopped (or, the subscriber, until nothing
#: at all arrived for DRAIN_QUIET_S): late is late; what is absent after
#: that is lost
ACK_WAIT_S = 60.0
DRAIN_MAX_S = 60.0
DRAIN_QUIET_S = 10.0
RUN_DIR = os.path.join(ROOT, ".bench_run")


def note(**kw: Any) -> None:
    """A fact of the run, on standard error (the last line of standard
    output is the result and nothing else is printed there)."""
    print(json.dumps(kw, default=_plain), file=sys.stderr, flush=True)


def _plain(x: Any) -> Any:
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.floating,)):
        return float(x)
    return str(x)


async def _off_loop(fn, *a):
    return await asyncio.get_running_loop().run_in_executor(None, fn, *a)


async def drive(system, gen, manifest: Manifest, cell: Dict[str, Any],
                corpus, seconds: float, trace: bool, t_process: float,
                warm_s: float = WARM_S, ack_wait_s: float = ACK_WAIT_S
                ) -> Dict[str, Any]:
    cfg, mix = cell["config"], cell["mix"]
    port = await system.boot(corpus)
    await system.calm()  # at level 3 the listener refuses CONNECTs
    connected = await _off_loop(gen.connect, port)
    note(phase="connected", **connected)
    if connected.get("sub.stored_session_absent"):
        raise RuntimeError("stored sessions were not found at CONNECT")
    await system.warm()
    if trace:
        system.tap_folds()

    t0_ns = time.monotonic_ns() + int(0.3e9)
    w0 = t0_ns + int(warm_s * 1e9)
    w1 = w0 + int(seconds * 1e9)
    start = {"t0_ns": t0_ns, "warm_s": warm_s, "seconds": seconds,
             "ack_wait_s": ack_wait_s}
    pubs_task = asyncio.ensure_future(_off_loop(gen.run, start))

    async def until(ns: int) -> None:
        await asyncio.sleep(max(0.0, (ns - time.monotonic_ns()) / 1e9))

    await until(w0)
    setup_s = time.monotonic() - t_process
    before = system.counters()
    system.probes()
    traced = None
    if trace:
        # the last seconds of the window: tracing slows the host (at 0.8
        # of a knee a backlog forms and outlasts the trace), so what it
        # disturbs falls after the spans and counters of the window
        span = min(TRACE_S, seconds / 2)
        await until(w1 - int((span + 0.5) * 1e9))
        traced = await _trace_window(system, span)
    await until(w1)
    after = system.counters()
    probes = system.probes()
    pub_reports = await pubs_task
    fin = _finish_request(pub_reports, mix, (w0, w1))
    sub_reports = await _off_loop(gen.finish, fin)
    device = system.device()
    await system.stop()
    traced = reduce_trace(traced)

    delta = {k: after[k] - before[k] for k in after}
    run = _reduce(pub_reports, sub_reports, fin, delta, probes, seconds,
                  system.name)
    run["setup_s"] = setup_s
    run["counters"] = delta
    note(phase="window",
         **{k: v for k, v in run.items()
            if isinstance(v, (int, float, str))},
         counters={k: v for k, v in delta.items() if v})
    ctx = dict(run, config=cfg, mix=mix, trace=traced,
               device=device, resident=corpus.n_resident,
               levels=len(corpus.pools))
    group = "per_layer" if trace else "end_to_end"
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in manifest.metrics(group, cell["name"]):
        if group == "end_to_end":
            value = run.get(m["name"])
        else:
            read, args = metric_reader(m["name"])
            value = read(ctx, **args)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": run["correct"], "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if traced is not None and traced.get("devices"):
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        result["breakdown"] = traced["breakdown"]
    result["facts"] = {
        "deliveries": run["deliveries"], "owed": run["owed_in_window"],
        "redelivered_with_dup": run["redelivered_with_dup"],
        "deliver_p50_ms": run.get("deliver_p50_ms"),
        "deliver_p95_ms": run.get("deliver_p95_ms"),
        "deliver_p99_ms": run.get("deliver_p99_ms"),
        "deliver_max_ms": run.get("deliver_max_ms"),
        "publishes_per_s": run["attempted"] / seconds,
        "compiles_in_window": delta.get("compile_requests", 0),
        "governor_level_max": probes.get("level_max", 0),
        "generator_cpu_share": run["generator_cpu_share"],
    }
    if "member_shares" in run:  # not judged: the broker's choice
        result["facts"]["member_shares"] = run["member_shares"]
    result["compared"] = run["compared"]
    return result


async def _trace_window(system, seconds: float) -> Optional[Dict[str, Any]]:
    """Trace ``seconds`` of the steady window with jax.profiler and reduce
    the trace at once; the trace's files are removed again."""
    from .trace import reduce as tr

    jax = system.jax
    out = os.path.join(RUN_DIR, "trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out, exist_ok=True)
    # host spans and device events only: with the Python tracer on, a
    # broker's event loop writes a trace of hundreds of megabytes
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    await _off_loop(lambda: jax.profiler.start_trace(
        out, profiler_options=opts))
    t0 = time.monotonic()
    await asyncio.sleep(seconds)
    window_s = time.monotonic() - t0
    await _off_loop(jax.profiler.stop_trace)
    path = tr.find_xplane(out)
    note(phase="traced", window_s=window_s, trace_bytes=os.path.getsize(path))
    return {"path": path, "window_s": window_s}


def reduce_trace(traced: Optional[Dict[str, Any]]
                 ) -> Optional[Dict[str, Any]]:
    """After the broker stopped: read the trace file (seconds of Python)
    off the measured window's clock."""
    if traced is None:
        return None
    from .trace import reduce as tr

    red = tr.reduce(traced["path"], traced["window_s"])
    shutil.rmtree(os.path.join(RUN_DIR, "trace"), ignore_errors=True)
    return red


def _finish_request(pub_reports: List[dict], mix: dict, window) -> dict:
    n_sent: Dict[int, int] = {}
    stamps: Dict[int, np.ndarray] = {}
    for r in pub_reports:
        n_sent.update(r["n_sent"])
        stamps.update(r["stamps"])
    return {"n_sent": n_sent, "stamps": stamps, "pub_qos": int(mix["qos"]),
            "window_ns": window, "drain_max_s": DRAIN_MAX_S,
            "drain_quiet_s": DRAIN_QUIET_S}


def _reduce(pub_reports, sub_reports, fin, delta, probes, seconds: float,
            system_name: str) -> Dict[str, Any]:
    w0, w1 = fin["window_ns"]
    stamps = fin["stamps"]
    attempted = int(sum(np.count_nonzero((s >= w0) & (s < w1))
                        for s in stamps.values()))
    sent = sum(fin["n_sent"].values())
    acked = sum(sum(r["n_acked"].values()) for r in pub_reports)
    unacked = (sent - acked) if fin["pub_qos"] else 0
    # publishes of the window that failed: unacknowledged, or short of or
    # over a delivery on any session
    failed_keys = [r["failed_pubseq"] for r in sub_reports]
    if fin["pub_qos"]:
        for r in pub_reports:
            for p, n in r["n_sent"].items():
                a = r["n_acked"][p]
                if a < n:  # acknowledged in order: the tail is unacked
                    s = stamps[p][a:n]
                    seq = np.arange(a, n)[(s >= w0) & (s < w1)]
                    failed_keys.append((np.int64(p) << 36) | seq)
    tot = {k: int(sum(r[k] for r in sub_reports)) for k in (
        "owed", "owed_in_window", "received", "received_in_window",
        "redelivered_with_dup", "lost_qos1", "lost_qos0", "duplicates",
        "strays", "misordered", "n_closed")}
    # what shared subscriptions are owed is owed to no process: the same
    # in every report, compared with what all processes' sockets read
    share_owed = sub_reports[0]["share_owed"]
    shared = None
    if len(share_owed):
        shared = reference.compare(share_owed, np.concatenate(
            [r["share_received"] for r in sub_reports]))
        for k in ("owed", "lost_qos1", "lost_qos0", "duplicates"):
            tot[k] += shared[k]
        tot["owed_in_window"] += len(_in_window(share_owed, stamps, w0, w1))
        failed_keys += [np.unique(_in_window(shared[k], stamps, w0, w1))
                        for k in ("short_keys", "over_keys")]
    failed = int(len(np.unique(np.concatenate(failed_keys)))) \
        if failed_keys else 0
    served = delta.get("match_publishes", 0)
    host = delta.get("host_hybrid_pubs", 0) + sum(
        delta.get(k, 0) for k in HOST_SERVED)
    numbers = {k: tot[k] for k in ("lost_qos1", "lost_qos0", "duplicates",
                                   "strays", "misordered")}
    numbers["unacked"] = int(unacked)
    numbers["device_served_pct"] = (100.0 * served / (served + host)
                                    if served + host else 0.0)
    correct, compared = reference.decide(
        numbers, floors=system_name != "reference")
    lat = np.concatenate([r["lat_ms"] for r in sub_reports])
    missing_w = tot["owed_in_window"] - tot["received_in_window"]
    if missing_w > 0:
        # never seen: waited for until the verdict, over any limit
        waited = (max(r["verdict_ns"] for r in sub_reports) - w0) / 1e6
        lat = np.concatenate([lat, np.full(missing_w, waited, np.float32)])
    late = np.concatenate([r["late_ms"] for r in pub_reports])
    out: Dict[str, Any] = {
        "correct": correct, "compared": compared,
        "attempted": attempted, "failed": failed,
        "publishes": attempted,
        "deliveries": tot["received_in_window"],
        "owed_in_window": tot["owed_in_window"],
        "redelivered_with_dup": tot["redelivered_with_dup"],
        "sessions_closed": tot["n_closed"],
        "publisher_connections_lost": sum(
            len(r["lost_connections"]) for r in pub_reports),
        "generator_cpu_share": sum(r["cpu_s"] for r in pub_reports)
        / max(1e-9, sum(r["wall_s"] for r in pub_reports)),
        "generator_late_ms": late,
        "probes": probes, "seconds": seconds,
        "examples": [r["examples"] for r in sub_reports
                     if any(r["examples"].values())][:2],
    }
    if shared is not None:
        out["member_shares"] = reference.member_shares(
            sub_reports[0]["shares"], np.concatenate(
                [r["share_by_member"] for r in sub_reports]))
        if len(shared["short_keys"]) or len(shared["over_keys"]):
            out["examples"].append({k: [int(x) for x in shared[k][:4]]
                                    for k in ("short_keys", "over_keys")})
    if len(lat):
        for q in (50, 95, 99):
            out[f"deliver_p{q}_ms"] = float(np.percentile(lat, q))
        out["deliver_max_ms"] = float(lat.max())
    return out


def _in_window(keys: np.ndarray, stamps: Dict[int, np.ndarray], w0: int,
               w1: int) -> np.ndarray:
    """(publisher, sequence), packed publisher << 36 | sequence, of the
    ``keys`` whose publish was stamped inside the window."""
    pub, seq = reference.pub_seq(keys)
    pubs = np.asarray(sorted(stamps), np.int64)
    base = np.cumsum([0] + [len(stamps[p]) for p in pubs[:-1]])
    t = np.concatenate([stamps[p] for p in pubs] or [np.zeros(0, np.int64)])
    t = t[base[np.searchsorted(pubs, pub)] + seq]
    inside = (t >= w0) & (t < w1)
    return (pub[inside] << 36) | seq[inside]


def print_result(result: Dict[str, Any]) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    standard error; the result as the last line of standard output."""
    sys.stdout.flush()
    result["compared"] = result.pop("compared")  # the last key of the line
    for name, row in result["compared"].items():
        print(f"compared {name}: {json.dumps(row)}", file=sys.stderr)
    print(f"correct: {json.dumps(result['correct'])}", file=sys.stderr,
          flush=True)
    print(json.dumps(result, default=_plain), flush=True)
