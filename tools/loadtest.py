"""Broker-level load test: real MQTT clients over TCP on localhost.

Measures end-to-end publish->deliver throughput through the full broker
path (parser -> session FSM -> reg view -> queue -> writer), the layer
above bench.py's kernel-level numbers. Two modes:

- single process (default): broker in-process, clients inline.
- ``--workers N``: spawns an N-process :class:`WorkerGroup` sharing one
  SO_REUSEPORT MQTT port (broker/workers.py), and shards the client
  load across ``--client-procs`` OS processes so the harness itself
  isn't the GIL bottleneck it is measuring around.

``--latency`` samples end-to-end publish->deliver latency (monotonic
clock is system-wide on Linux, so cross-process samples are
comparable) and reports p50/p99.

Usage:

  python tools/loadtest.py [--subs 50] [--pubs 8] [--secs 5]
      [--view trie|tpu] [--qos 0] [--window 32]
      [--workers 4] [--client-procs 4] [--latency]
"""
import argparse
import asyncio
import multiprocessing as mp
import socket
import struct
import sys
import time

sys.path.insert(0, "/root/repo")

_LAT_MAGIC = b"LT1"
_SAMPLE_EVERY = 16


def _now_ns() -> int:
    return time.monotonic_ns()


async def _run_clients(host: str, port: int, sub_ids, pub_ids, secs: float,
                       qos: int, window: int, payload_len: int,
                       latency: bool, tag: str, rate: float = 0.0,
                       lat_skip_secs: float = 0.0):
    """Drive one shard of subscribers+publishers; returns
    (sent, failed, received, elapsed, lat_samples_ns)."""
    from vernemq_tpu.client import MQTTClient

    received = 0
    lat_ns = []
    done = asyncio.Event()
    # samples before this cutoff are warmup (first-compile windows on a
    # cold backend) and excluded from the latency report
    lat_from = time.perf_counter() + lat_skip_secs

    async def subscriber(i: int) -> None:
        nonlocal received
        c = MQTTClient(host, port, f"lt-sub{tag}{i}")
        await c.connect()
        await c.subscribe(f"lt/{i % 16}/+", qos=qos)
        while not done.is_set():
            try:
                f = await c.recv(0.5)
            except Exception:
                continue
            if f is not None:
                received += 1
                if latency and f.payload[:3] == _LAT_MAGIC \
                        and time.perf_counter() >= lat_from:
                    t0 = struct.unpack(">Q", f.payload[3:11])[0]
                    lat_ns.append(_now_ns() - t0)
        await c.disconnect()

    sent = 0
    failed = 0

    async def publisher(i: int) -> None:
        nonlocal sent, failed
        c = MQTTClient(host, port, f"lt-pub{tag}{i}")
        await c.connect()
        base_payload = b"x" * payload_len
        j = 0
        inflight: set = set()

        def reap(f):
            inflight.discard(f)
            if not f.cancelled() and f.exception() is not None:
                nonlocal failed
                failed += 1  # acked count excludes this one

        interval = (1.0 / rate) if rate > 0 else 0.0
        next_at = time.perf_counter()
        while not done.is_set():
            if interval:
                # paced publishing: measures broker-ADDED latency, not
                # self-inflicted queueing from an uncapped firehose
                now = time.perf_counter()
                if now < next_at:
                    await asyncio.sleep(next_at - now)
                next_at += interval
            payload = base_payload
            if latency and j % _SAMPLE_EVERY == 0:
                stamp = _LAT_MAGIC + struct.pack(">Q", _now_ns())
                payload = stamp + base_payload[len(stamp):] \
                    if payload_len > len(stamp) else stamp
            if qos and window > 1:
                # pipelined QoS1: keep up to `window` unacked publishes
                # in flight (awaiting each PUBACK serialises the
                # publisher on broker RTT and measures the client, not
                # the broker — the reference's inflight-window behavior)
                fut = asyncio.ensure_future(
                    c.publish(f"lt/{j % 16}/m{tag}{i}", payload, qos=qos))
                inflight.add(fut)
                fut.add_done_callback(reap)
                if len(inflight) >= window:
                    await asyncio.wait(
                        inflight, return_when=asyncio.FIRST_COMPLETED)
            else:
                await c.publish(f"lt/{j % 16}/m{tag}{i}", payload, qos=qos)
            sent += 1
            j += 1
            if j % 64 == 0:
                await asyncio.sleep(0)  # let the loop breathe
        if inflight:
            await asyncio.gather(*inflight, return_exceptions=True)
        await c.disconnect()

    subs = [asyncio.create_task(subscriber(i)) for i in sub_ids]
    await asyncio.sleep(0.5)
    t0 = time.perf_counter()
    pubs = [asyncio.create_task(publisher(i)) for i in pub_ids]
    await asyncio.sleep(secs)
    done.set()
    elapsed = time.perf_counter() - t0
    await asyncio.gather(*pubs, *subs, return_exceptions=True)
    return sent, failed, received, elapsed, lat_ns


def _client_proc(host, port, sub_ids, pub_ids, secs, qos, window,
                 payload_len, latency, tag, out_q, rate=0.0,
                 lat_skip_secs=0.0):
    """Spawn-safe client-shard entry point."""
    res = asyncio.run(_run_clients(host, port, sub_ids, pub_ids, secs,
                                   qos, window, payload_len, latency, tag,
                                   rate, lat_skip_secs))
    out_q.put(res)


def _pctile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[idx]


def _report(view, qos, sent, failed, received, elapsed, lat_ns, subs,
            pubs, workers):
    acked = sent - failed
    line = (f"view={view} qos={qos} workers={workers} "
            f"pubs/s={acked/elapsed:.0f} "
            f"deliveries/s={received/elapsed:.0f} "
            f"(subscribers={subs}, publishers={pubs}"
            + (f", failed={failed}" if failed else "") + ")")
    if lat_ns:
        lat = sorted(lat_ns)
        line += (f" latency_ms p50={_pctile(lat, 0.50)/1e6:.2f}"
                 f" p99={_pctile(lat, 0.99)/1e6:.2f}"
                 f" (n={len(lat)})")
    print(line, flush=True)


async def _main_inproc(args) -> None:
    if args.view == "tpu":
        import jax  # noqa: F401  (matcher path needs a backend)

        if args.jax_platform:
            jax.config.update("jax_platforms", args.jax_platform)

    from vernemq_tpu.broker.config import Config
    from vernemq_tpu.broker.server import start_broker

    b, server = await start_broker(
        Config(systree_enabled=False, allow_anonymous=True,
               default_reg_view=args.view, sysmon_enabled=False),
        port=0)
    sent, failed, received, elapsed, lat = await _run_clients(
        server.host, server.port, range(args.subs), range(args.pubs),
        args.secs, args.qos, args.window, args.payload, args.latency, "",
        args.rate, args.lat_skip_secs)
    if args.view == "tpu" and getattr(b, "_collector", None) is not None:
        col = b._collector
        mb = sum(m.match_batches
                 for m in getattr(col.view, "_matchers", {}).values())
        mp_ = sum(m.match_publishes
                  for m in getattr(col.view, "_matchers", {}).values())
        print(f"collector: host_hybrid_pubs={col.host_hybrid_pubs} "
              f"device_batches={mb} device_pubs={mp_} "
              f"merges={col.saturated_merges} "
              f"shed={col.overload_host_pubs} "
              f"busy_shed={col.busy_host_pubs} "
              f"rebuild_shed={col.rebuild_host_pubs}", flush=True)
    await b.stop()
    await server.stop()
    _report(args.view, args.qos, sent, failed, received, elapsed, lat,
            args.subs, args.pubs, 0)


def _main_workers(args) -> None:
    import os

    from vernemq_tpu.broker.workers import WorkerGroup

    if args.jax_platform:
        # worker and match-service processes inherit this env var
        os.environ["JAX_PLATFORMS"] = args.jax_platform

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    group = WorkerGroup(args.workers, "127.0.0.1", port,
                        cluster_base=args.cluster_base,
                        allow_anonymous=True, systree_enabled=False,
                        sysmon_enabled=False,
                        default_reg_view=args.view)
    group.start()
    try:
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                socket.create_connection(("127.0.0.1", port), 0.5).close()
                break
            except OSError:
                time.sleep(0.3)
        else:
            raise RuntimeError("workers never became reachable")
        # give the worker mesh a moment to form before subscribing
        time.sleep(1.5)
        nproc = args.client_procs or args.workers
        ctx = mp.get_context("spawn")
        out_q = ctx.Queue()
        procs = []
        for p in range(nproc):
            sub_ids = [i for i in range(args.subs) if i % nproc == p]
            pub_ids = [i for i in range(args.pubs) if i % nproc == p]
            procs.append(ctx.Process(
                target=_client_proc,
                args=("127.0.0.1", port, sub_ids, pub_ids, args.secs,
                      args.qos, args.window, args.payload, args.latency,
                      f"p{p}-", out_q, args.rate,
                      args.lat_skip_secs)))
        for p in procs:
            p.start()
        totals = [0, 0, 0, 0.0]
        lat_all = []
        import queue as _queue

        shards_ok = 0
        try:
            for _ in procs:
                sent, failed, received, elapsed, lat = out_q.get(
                    timeout=args.secs + 120)
                totals[0] += sent
                totals[1] += failed
                totals[2] += received
                totals[3] = max(totals[3], elapsed)
                lat_all.extend(lat)
                shards_ok += 1
        except _queue.Empty:
            print(f"WARNING: only {shards_ok}/{len(procs)} client shards "
                  "reported (crashed shard?); partial numbers below",
                  file=sys.stderr, flush=True)
        finally:
            for p in procs:
                p.join(5)
                if p.is_alive():
                    p.terminate()
                    p.join(5)
        if totals[3] > 0:
            _report(args.view, args.qos, totals[0], totals[1], totals[2],
                    totals[3], lat_all, args.subs, args.pubs, args.workers)
    finally:
        group.stop()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--subs", type=int, default=50)
    ap.add_argument("--pubs", type=int, default=8)
    ap.add_argument("--secs", type=float, default=5.0)
    ap.add_argument("--qos", type=int, default=0)
    ap.add_argument("--view", default="trie")
    ap.add_argument("--payload", type=int, default=64)
    ap.add_argument("--window", type=int, default=1,
                    help="pipelined unacked publishes per publisher "
                         "(QoS>0; 1 = await each ack)")
    ap.add_argument("--workers", type=int, default=0,
                    help="run the broker as N SO_REUSEPORT worker "
                         "processes (0 = in-process single broker)")
    ap.add_argument("--client-procs", type=int, default=0,
                    help="client shard processes (default: = workers)")
    ap.add_argument("--cluster-base", type=int, default=25600)
    ap.add_argument("--latency", action="store_true",
                    help="sample end-to-end delivery latency")
    ap.add_argument("--jax-platform", default=None,
                    help="force the JAX backend for --view tpu (e.g. "
                         "cpu)")
    ap.add_argument("--lat-skip-secs", type=float, default=0.0,
                    help="exclude latency samples from the first N "
                         "seconds (cold-backend compile warmup)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="paced publishes/sec per publisher (0 = "
                         "uncapped firehose)")
    args = ap.parse_args()
    if args.workers:
        _main_workers(args)
    else:
        asyncio.run(_main_inproc(args))


if __name__ == "__main__":
    main()
