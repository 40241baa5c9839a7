"""Macro-pipeline throughput: LogSchema → parser service → NewValueDetector
service → alert sink, every hop a REAL service process over ipc sockets.

This is the reference's headline deployment shape (fluentin → parser →
detector → fluentout; reference docker-compose.yml) driven at speed: the
sender packs LogSchema batch frames, the parser stage micro-batches
(MatcherParser.process_batch) and packs ParserSchema frames downstream, the
detector stage micro-batches (NewValueDetector.process_batch) and emits
alerts for the injected anomalies only.

Completion is detected exactly via byte counters (data_read_bytes /
data_written_bytes scraped from each stage's /metrics): bytes are exact on
the wire, unlike the newline-based line counters.

Usage: python scripts/bench_pipeline.py [N]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PARSER_PORT, DETECTOR_PORT = 18951, 18952


def scrape(port: int, metric: str):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=2) as resp:
            body = resp.read().decode()
    except Exception:
        return None
    for line in body.splitlines():
        if line.startswith(metric):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def wait_up(port: int, deadline_s: float = 240.0) -> None:
    end = time.time() + deadline_s
    while time.time() < end:
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/admin/status", timeout=2) as r:
                if r.read():
                    return
        except Exception:
            pass
        time.sleep(1)
    raise RuntimeError(f"service on :{port} never came up")


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 262144
    work = tempfile.mkdtemp(prefix="dmbench-pipe-")
    import yaml

    templates = os.path.join(work, "templates.txt")
    with open(templates, "w") as f:
        f.write("type=<*> msg=audit(<*>): arch=<*> syscall=<*> success=<*> "
                "exit=<*> pid=<*> comm=<*>\n")
    stage_common = {"log_dir": work, "engine_buffer_size": 8192,
                    "engine_batch_size": 1024, "engine_frame_batch": 256,
                    # flow control: the slower stage throttles its upstream
                    # instead of dropping frames in 100 ms retry windows
                    "out_backpressure": "block"}
    configs = {
        "parser": ({
            "component_name": "pipeparser",
            "component_type": "parsers.template_matcher.MatcherParser",
            "engine_addr": f"ipc://{work}/parser.ipc",
            "out_addr": [f"ipc://{work}/detector.ipc"],
            "http_port": PARSER_PORT,
            "config_file": f"{work}/parser_config.yaml",
            **stage_common,
        }, {"parsers": {"MatcherParser": {
            "method_type": "matcher_parser", "auto_config": False,
            "log_format": None,
            "params": {"lowercase": True, "path_templates": templates},
        }}}),
        "detector": ({
            "component_name": "pipenvd",
            "component_type": "detectors.new_value_detector.NewValueDetector",
            "engine_addr": f"ipc://{work}/detector.ipc",
            "out_addr": [f"ipc://{work}/alerts.ipc"],
            "http_port": DETECTOR_PORT,
            "config_file": f"{work}/detector_config.yaml",
            **stage_common,
        }, {"detectors": {"NewValueDetector": {
            "method_type": "new_value_detector", "auto_config": False,
            "data_use_training": 2048,
            "global": {"g": {"variables": [{"pos": 7}]}},  # comm field
        }}}),
    }
    procs = []
    try:
        for name, (settings, config) in configs.items():
            with open(f"{work}/{name}_settings.yaml", "w") as f:
                yaml.safe_dump(settings, f)
            with open(settings["config_file"], "w") as f:
                yaml.safe_dump(config, f)
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "detectmateservice_tpu.cli",
                 "--settings", f"{work}/{name}_settings.yaml"],
                stdout=open(f"{work}/{name}.out", "w"),
                stderr=subprocess.STDOUT))
        wait_up(PARSER_PORT)
        wait_up(DETECTOR_PORT)

        import logging

        from detectmateservice_tpu.engine.framing import pack_batch, unpack_batch
        from detectmateservice_tpu.engine.socket import (
            TransportTimeout, ZmqPairSocketFactory)
        from detectmateservice_tpu.schemas import LogSchema

        log = logging.getLogger("bench")
        factory = ZmqPairSocketFactory()
        sink = factory.create(f"ipc://{work}/alerts.ipc", log)
        sink.recv_timeout = 500
        ingress = factory.create_output(f"ipc://{work}/parser.ipc", log,
                                        buffer_size=8192)
        alerts = []
        stop = threading.Event()

        def drain():
            while not stop.is_set():
                try:
                    frame = sink.recv()
                except TransportTimeout:
                    continue
                msgs = unpack_batch(frame)
                alerts.extend(msgs if msgs is not None else [frame])

        threading.Thread(target=drain, daemon=True).start()

        def audit_line(i: int, comm: str) -> bytes:
            return LogSchema(logID=str(i), log=(
                f"type=SYSCALL msg=audit(17000{i % 100}.{i % 997}:{i}): "
                f"arch=c000003e syscall=59 success=yes exit=0 "
                f"pid={300 + i % 80} comm={comm}")).serialize()

        n_train = 2048
        msgs = [audit_line(i, ["cron", "sshd", "systemd", "bash"][i % 4])
                for i in range(n_train + n)]
        n_anom = max(1, n // 1000)
        for j in range(n_anom):  # sprinkle unknown comm values post-training
            k = n_train + (j * 997) % n
            msgs[k] = audit_line(k, f"evil{j}")
        frame_n = 512
        train_frames = [pack_batch(msgs[i:i + frame_n])
                        for i in range(0, n_train, frame_n)]
        bench_frames = [pack_batch(msgs[i:i + frame_n])
                        for i in range(n_train, len(msgs), frame_n)]
        sent_bytes = 0
        for frame in train_frames:
            ingress.send(frame)
            sent_bytes += len(frame)
        # settle training through both stages before the timed region
        deadline = time.time() + 120
        while time.time() < deadline:
            if (scrape(PARSER_PORT, "data_read_bytes_total") or 0) >= sent_bytes:
                pw = scrape(PARSER_PORT, "data_written_bytes_total") or 0
                dr = scrape(DETECTOR_PORT, "data_read_bytes_total") or 0
                if pw > 0 and dr >= pw:
                    break
            time.sleep(0.5)

        t0 = time.perf_counter()
        for frame in bench_frames:
            ingress.send(frame)
            sent_bytes += len(frame)
        deadline = time.time() + 600
        prev = None
        while time.time() < deadline:
            pr = scrape(PARSER_PORT, "data_read_bytes_total") or 0
            pw = scrape(PARSER_PORT, "data_written_bytes_total") or 0
            dr = scrape(DETECTOR_PORT, "data_read_bytes_total") or 0
            dp = scrape(DETECTOR_PORT, "data_processed_bytes_total") or 0
            state = (pr, pw, dr, dp)
            # done = parser consumed all input, detector consumed all parser
            # output, AND nothing moved since the last sample (the detector
            # may still be chewing after the byte counters line up)
            if pr >= sent_bytes and dr >= pw > 0 and state == prev:
                break
            prev = state
            time.sleep(0.25)
        elapsed = time.perf_counter() - t0 - 0.25  # stability sample lag
        time.sleep(2.0)  # let the tail alerts land at the sink
        stop.set()
        print(json.dumps({
            "metric": "pipeline_2stage_lines_per_sec",
            "value": round(n / elapsed, 1),
            "unit": "lines/s",
            "n": n,
            "elapsed_s": round(elapsed, 3),
            "alerts": len(alerts),
            "expected_alerts": n_anom,
        }))
    finally:
        for port in (PARSER_PORT, DETECTOR_PORT):
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/admin/shutdown", data=b"",
                    timeout=3)
            except Exception:
                pass
        for proc in procs:
            try:
                proc.wait(timeout=15)
            except Exception:
                proc.terminate()


if __name__ == "__main__":
    main()
