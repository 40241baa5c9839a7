"""Service-path throughput: serialized messages through a REAL detector
service process over ipc sockets — socket recv, micro-batch engine loop,
TPU scoring, alert fan-out — not just the in-process detector contract that
bench.py times.

Spawns `detectmateservice_tpu.cli` with the mlp scorer, pumps N ParserSchema
messages through the engine socket, and measures from first send until the
service's device-lines counter covers all N (scraped from /metrics). Alerts
arriving on the output socket are drained concurrently and counted.

Multi-ingress mode (``--shards K``, the regime docs/benchmarks.md sizes for
>2M lines/s chip-local): the service listens on K ingress shard sockets
(``engine_ingress_addrs``) merged into one engine loop, and K SEPARATE
sender processes blast one shard each — so sender-side Python cost, the
GIL, and the per-socket kernel path all scale out, and the measured number
is the aggregate the single dispatch loop actually drains.

Usage:
    python scripts/bench_service.py [N]              # single ingress
    python scripts/bench_service.py N --shards 4     # K-shard aggregate
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench as B  # noqa: E402

HTTP_PORT = 18941


def scrape_processed(port: int):
    """Messages scored on the device path so far (counted when a batch's
    scores are host-readable); None while the metrics endpoint is
    unreachable (the readiness gate needs that distinction).
    Uses the per-device counter, NOT data_processed_lines_total: the latter
    counts 0x0A bytes in the raw payload (reference line-counting semantics)
    and protobuf framing contains plenty of those, so it overcounts ~4x."""
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=2) as resp:
            body = resp.read().decode()
    except Exception:
        return None
    for line in body.splitlines():
        if line.startswith("detector_device_lines_total"):
            return float(line.rsplit(" ", 1)[1])
    return 0.0  # endpoint up, counter not created yet


def processed_at_least(port: int, target: float) -> bool:
    value = scrape_processed(port)
    return value is not None and value >= target


def sender_main(addr: str, n: int, seed: int, ready: str, go: str) -> None:
    """One sender process: pre-pack frames, signal ready, blast on go.
    Packing happens BEFORE the go signal so the measured window contains
    only socket+service work, and each sender pays it on its own core."""
    import logging

    from detectmateservice_tpu.engine.framing import pack_batch
    from detectmateservice_tpu.engine.socket import ZmqPairSocketFactory

    msgs = B.make_messages(n, anomaly_rate=0.01, seed=seed)
    frame_n = 512
    frames = [pack_batch(msgs[i:i + frame_n]) for i in range(0, n, frame_n)]
    sock = ZmqPairSocketFactory().create_output(
        addr, logging.getLogger("sender"), buffer_size=8192)
    Path(ready).touch()
    while not os.path.exists(go):
        time.sleep(0.01)
    for frame in frames:
        sock.send(frame)
    # zmq sends are async: stay alive so queued frames drain; the parent
    # kills senders once the service-side counter covers the target
    time.sleep(600)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("n", nargs="?", type=int, default=262144)
    ap.add_argument("--shards", type=int, default=1,
                    help="ingress shard count (and sender process count)")
    ap.add_argument("--sender", nargs=5, metavar=("ADDR", "N", "SEED",
                                                  "READY", "GO"))
    args = ap.parse_args()
    if args.sender:
        sender_main(args.sender[0], int(args.sender[1]), int(args.sender[2]),
                    args.sender[3], args.sender[4])
        return

    n, shards = args.n, max(1, args.shards)
    work = tempfile.mkdtemp(prefix="dmbench-svc-")
    n_train = B.BENCH_SCORER_CONFIG["data_use_training"]
    shard_addrs = [f"ipc://{work}/shard{i}.ipc" for i in range(shards)]
    settings = {
        "component_name": "benchdet",
        "component_type": "detectors.jax_scorer.JaxScorerDetector",
        "engine_addr": f"ipc://{work}/det.ipc",
        "out_addr": [f"ipc://{work}/alerts.ipc"],
        "http_port": HTTP_PORT,
        "config_file": f"{work}/config.yaml",
        "log_dir": work,
        # the engine burst cap is in MESSAGES (frames mode estimates via
        # frame headers); match the scorer's max_batch so steady-state
        # device batches ride the largest warmed compile bucket
        "engine_batch_size": 16384,
        # sender-side SNDHWM is the pipe's flow-control window; the 100
        # default lockstepped the sender to the engine's wakeup cadence;
        # 8192 lets the engine drain full bursts
        "engine_buffer_size": 8192,
        # pack alerts going out; the senders pack their ingress frames —
        # one zmq send per 512 messages instead of per message
        "engine_frame_batch": 512,
    }
    if shards > 1:
        settings["engine_ingress_addrs"] = shard_addrs
    else:
        shard_addrs = [settings["engine_addr"]]
    # the canonical headline-bench scorer config (ONE home: bench.py). The
    # host twin is off: the progress counter
    # (scrape_processed) counts rows the DEVICE path scored, and a probe
    # message or a burst's remainder that rode the twin would never reach it
    config = {"detectors": {"JaxScorerDetector": dict(
        B.BENCH_SCORER_CONFIG, host_score_max_batch=0)}}
    import yaml

    with open(f"{work}/settings.yaml", "w") as f:
        yaml.safe_dump(settings, f)
    with open(f"{work}/config.yaml", "w") as f:
        yaml.safe_dump(config, f)

    proc = subprocess.Popen(
        [sys.executable, "-m", "detectmateservice_tpu.cli",
         "--settings", f"{work}/settings.yaml"],
        stdout=open(f"{work}/service.out", "w"), stderr=subprocess.STDOUT)
    senders: list = []
    try:
        deadline = time.time() + 300
        while time.time() < deadline:
            if scrape_processed(HTTP_PORT) is not None and _status_up():
                break
            time.sleep(2)
        else:
            raise RuntimeError("service never came up; see " + work)

        import logging

        from detectmateservice_tpu.engine.framing import pack_batch, unpack_batch
        from detectmateservice_tpu.engine.socket import (
            TransportTimeout, ZmqPairSocketFactory)

        log = logging.getLogger("bench")
        factory = ZmqPairSocketFactory()
        alerts_sock = factory.create(f"ipc://{work}/alerts.ipc", log)
        alerts_sock.recv_timeout = 500
        ingress = factory.create_output(shard_addrs[0], log,
                                        buffer_size=8192)

        alerts = []
        stop = threading.Event()

        def drain():
            while not stop.is_set():
                try:
                    frame = alerts_sock.recv()
                except TransportTimeout:
                    continue
                msgs = unpack_batch(frame)
                alerts.extend(msgs if msgs is not None else [frame])

        drainer = threading.Thread(target=drain, daemon=True)
        drainer.start()

        train_msgs = B.make_messages(n_train, anomaly_rate=0.0)
        for m in train_msgs:
            ingress.send(m)
        # training messages are buffered, not device-scored; probe messages
        # only reach the device counter once the boundary fit is done, so
        # waiting on them waits out the fit (and warms the compile buckets)
        n_probe = 256
        for m in B.make_messages(n_probe, anomaly_rate=0.0, seed=7):
            ingress.send(m)
        deadline = time.time() + 600
        while not processed_at_least(HTTP_PORT, n_probe) and time.time() < deadline:
            time.sleep(1)

        per_sender = n // shards
        go_file = f"{work}/go"
        if shards == 1:
            msgs = B.make_messages(n, anomaly_rate=0.01, seed=1)
            frame_n = 512
            frames = [pack_batch(msgs[i:i + frame_n])
                      for i in range(0, n, frame_n)]
            t0 = time.perf_counter()
            for frame in frames:
                ingress.send(frame)
            t_sent = time.perf_counter()
        else:
            ready_files = [f"{work}/ready{i}" for i in range(shards)]
            for i in range(shards):
                senders.append(subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__), "--sender",
                     shard_addrs[i], str(per_sender), str(i + 1),
                     ready_files[i], go_file],
                    stdout=open(f"{work}/sender{i}.out", "w"),
                    stderr=subprocess.STDOUT))
            deadline = time.time() + 300
            while (not all(os.path.exists(r) for r in ready_files)
                   and time.time() < deadline):
                time.sleep(0.1)
            n = per_sender * shards  # exact target with integer division
            t0 = time.perf_counter()
            Path(go_file).touch()
            t_sent = None
        target = n_probe + n
        deadline = time.time() + 600
        while not processed_at_least(HTTP_PORT, target) and time.time() < deadline:
            time.sleep(0.05)
        elapsed = time.perf_counter() - t0
        time.sleep(1.0)  # let the last alerts land
        stop.set()
        drainer.join()
        processed = (scrape_processed(HTTP_PORT) or 0.0) - n_probe
        result = {
            "metric": ("service_path_lines_per_sec" if shards == 1 else
                       f"service_path_aggregate_lines_per_sec_{shards}shards"),
            "value": round(n / elapsed, 1),
            "unit": "lines/s",
            "shards": shards,
            "processed": processed,
            "alerts": len(alerts),
            "n": n,
            "elapsed_s": round(elapsed, 3),
        }
        if t_sent is not None:
            result["send_only_lines_per_s"] = round(n / (t_sent - t0), 1)
        print(json.dumps(result))
    finally:
        for s in senders:
            try:
                s.kill()
            except OSError:
                pass
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{HTTP_PORT}/admin/shutdown",
                data=b"", timeout=3)
        except Exception:
            proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            # a service wedged in a heavy device batch must not turn a
            # completed measurement into a failed bench run
            proc.kill()


def _status_up() -> bool:
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{HTTP_PORT}/admin/status", timeout=2) as r:
            return bool(r.read())
    except Exception:
        return False


if __name__ == "__main__":
    main()
