"""Chip smoke: the parser → scorer → output pipeline, once, on the attached TPU.

Boots the three service processes a deployment runs — ``MatcherParser`` →
``JaxScorerDetector`` → ``OutputWriter``, each a
``python -m detectmateservice_tpu.cli --settings …`` process over ``ipc://`` —
with the detector at the flagship configuration (``logbert``, vocab 32768,
dim 256, depth 4, heads 4, seq_len 32, exact head, bf16, max_batch 16384,
deadline-aware coalescing, ``backend: tpu``), feeds it training lines, scoring
bursts with injected anomalies and a handful of lone frames, and checks from
the outside (sink, ``/metrics``, ``GET /admin/xla``) that the chip did the
work. Then it restarts the detector against the same compile cache and checks
that the second boot hits it.

Only the detector child touches jax: this parent and the parser and output
stages stay jax-free (a process that has touched jax holds the chip).

    python chip_smoke.py                  # needs a TPU; fails without one
    python chip_smoke.py --rehearse-cpu   # same flow, tiny model, on the CPU
    python chip_smoke.py --mesh data=4    # one scorer process over 4 chips

The rehearsal is how the flow is debugged before chip time is spent; it is
never reached by falling through. On success stdout carries two JSON lines:
first the report (``{"report": {...}}`` — the scorer configuration, counts and
set-up seconds, no rates, ending ``"claim": null``), then, as the last line,
the verdict with exactly these keys:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` — the
device as the detector's jax reported it. Any failed check, child crash or
timeout exits non-zero and prints neither line.
"""
from __future__ import annotations

import argparse
import itertools
import json
import math
import os
import random
import re
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
import traceback
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

AUDIT_LOG_FORMAT = "type=<Type> msg=audit(<Time>): <Content>"
AUDIT_TEMPLATE = ("arch=<*> syscall=<*> success=<*> exit=<*> pid=<*> "
                  "uid=<*> comm=<*> exe=<*>")
FRAME_ROWS = 256

# BASELINE.json config #3 / __graft_entry__.py: the configuration the repo
# names as its flagship, at full width and depth
FLAGSHIP = {
    "scorer": {"model": "logbert", "vocab_size": 32768, "dim": 256,
               "depth": 4, "heads": 4, "seq_len": 32, "dtype": "auto",
               "max_batch": 16384},
    "backend": "tpu",
    "n_train": 2048,
    # one burst fills the largest bucket (a "full" release at max_batch);
    # the mid-sized ones leave by deadline on a bucket the coalescer warms
    # on first use — all far above host_score_max_batch
    "bursts": [16384, 3000, 3000],
    "warmup_timeout_s": 700.0,
}
REHEARSAL = {
    "scorer": {"model": "logbert", "vocab_size": 2048, "dim": 32, "depth": 1,
               "heads": 2, "seq_len": 32, "dtype": "float32",
               "max_batch": 256},
    "backend": "cpu",
    "n_train": 256,
    "bursts": [256, 200, 200],
    "warmup_timeout_s": 300.0,
}
N_LONE = 6
ANOMALY_RATE = 0.01


class SmokeFailure(Exception):
    """A check failed, a child died or a wait timed out."""


# ---------------------------------------------------------------------------
# plumbing
# ---------------------------------------------------------------------------
def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def http_json(port: int, path: str, post: bool = False, timeout: float = 5.0):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=b"" if post else None,
                                 method="POST" if post else "GET")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def scrape(port: int):
    """One ``/metrics`` scrape → ``series(name, **matchers)`` returning
    ``[(labels, value), ...]`` (parsed by the soak harness's SampleStore)."""
    from detectmateservice_tpu.loadgen.alerteval import SampleStore

    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=5.0) as resp:
        store = SampleStore()
        store.ingest_exposition(resp.read().decode(), 0.0)
    return lambda name, **matchers: store.instant(name, matchers, 0.0)


class Stage:
    """One service process: settings + config on disk, output to a log."""

    def __init__(self, name: str, work: str, settings: dict, config: dict):
        import yaml

        self.name = name
        self.port = settings["http_port"]
        self.settings_path = os.path.join(work, f"{name}_settings.yaml")
        self.log_path = os.path.join(work, f"{name}.out")
        self.proc = None
        self.boots = 0
        self.extra_env: dict = {}
        with open(settings["config_file"], "w", encoding="utf-8") as fh:
            yaml.safe_dump(config, fh)
        with open(self.settings_path, "w", encoding="utf-8") as fh:
            yaml.safe_dump(settings, fh)
        self._work = work

    def start(self) -> float:
        env = dict(os.environ, **self.extra_env)
        env["PYTHONPATH"] = REPO + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        self.boots += 1
        with open(self.log_path, "ab") as log:
            log.write(f"--- {self.name} boot {self.boots} ---\n".encode())
            log.flush()
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "detectmateservice_tpu.cli",
                 "--settings", self.settings_path],
                stdout=log, stderr=subprocess.STDOUT, env=env, cwd=self._work)
        return time.monotonic()

    def check_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise SmokeFailure(
                f"{self.name} exited with code {rc} — log tail:\n"
                + self.log_tail())

    def wait_running(self, timeout_s: float) -> None:
        """Poll ``/admin/status`` (a cold scorer warm-up is minutes)."""
        end = time.monotonic() + timeout_s
        while time.monotonic() < end:
            self.check_alive()
            try:
                if http_json(self.port, "/admin/status")["status"]["running"]:
                    return
            except (OSError, urllib.error.URLError, KeyError, ValueError):
                pass
            time.sleep(0.5)
        raise SmokeFailure(f"{self.name} not running after {timeout_s:.0f}s "
                           f"— log tail:\n" + self.log_tail())

    def shutdown(self, timeout_s: float = 60.0) -> int:
        """Ask the service to stop, reap it, return its exit code."""
        if self.proc is None:
            return 0
        if self.proc.poll() is None:
            try:
                http_json(self.port, "/admin/shutdown", post=True)
            except (OSError, urllib.error.URLError, ValueError):
                self.proc.terminate()
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)
                return -9
        return self.proc.returncode

    def log_tail(self, n_bytes: int = 3000) -> str:
        try:
            with open(self.log_path, "rb") as fh:
                fh.seek(0, os.SEEK_END)
                fh.seek(max(0, fh.tell() - n_bytes))
                return fh.read().decode("utf-8", "replace")
        except OSError:
            return "(no log)"


def wait_for(predicate, timeout_s: float, what: str, stages=()) -> None:
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        for stage in stages:
            stage.check_alive()
        if predicate():
            return
        time.sleep(0.25)
    raise SmokeFailure(f"timed out after {timeout_s:.0f}s waiting for {what}")


def build_stages(work: str, profile: dict) -> dict:
    common = {"log_dir": os.path.join(work, "logs"), "log_to_file": False,
              "engine_buffer_size": 8192,
              # flow control: a stage paused in a compile throttles its
              # upstream instead of dropping frames
              "out_backpressure": "block"}
    templates = os.path.join(work, "templates.txt")
    with open(templates, "w", encoding="utf-8") as fh:
        fh.write(AUDIT_TEMPLATE + "\n")
    mesh = profile.get("mesh")
    scorer = dict(
        profile["scorer"], method_type="jax_scorer", auto_config=False,
        **({"mesh_shape": mesh} if mesh else {}),
        data_use_training=profile["n_train"],
        # long enough that a max_batch burst coalesces into ONE full-width
        # release however the parser paces its frames; latency is S0's
        batch_deadline_ms=400.0)
    return {
        "output": Stage("output", work, dict(
            common, component_type="outputs.file_sink.OutputWriter",
            component_id="smoke-output",
            engine_addr=f"ipc://{work}/output.ipc",
            out_addr=[f"ipc://{work}/final.ipc"], http_port=free_port(),
            config_file=os.path.join(work, "output_config.yaml"),
        ), {"outputs": {"OutputWriter": {
            "method_type": "output_writer", "auto_config": False,
            "aggregate_count": 1, "write_files": False,
            "emit_records": True}}}),
        "detector": Stage("detector", work, dict(
            common, component_type="detectors.jax_scorer.JaxScorerDetector",
            component_id="smoke-detector", backend=profile["backend"],
            engine_addr=f"ipc://{work}/detector.ipc",
            out_addr=[f"ipc://{work}/output.ipc"], http_port=free_port(),
            config_file=os.path.join(work, "detector_config.yaml"),
            engine_batch_size=16384, engine_batch_timeout_ms=5.0,
        ), {"detectors": {"JaxScorerDetector": scorer}}),
        "parser": Stage("parser", work, dict(
            common, component_type="parsers.template_matcher.MatcherParser",
            component_id="smoke-parser",
            engine_addr=f"ipc://{work}/parser.ipc",
            out_addr=[f"ipc://{work}/detector.ipc"], http_port=free_port(),
            config_file=os.path.join(work, "parser_config.yaml"),
            engine_batch_size=1024, engine_frame_batch=FRAME_ROWS,
        ), {"parsers": {"MatcherParser": {
            "method_type": "matcher_parser", "auto_config": False,
            "log_format": AUDIT_LOG_FORMAT,
            "params": {"path_templates": templates}}}}),
    }


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
def run(profile: dict, work: str) -> dict:
    from detectmateservice_tpu.engine.framing import pack_batch
    from detectmateservice_tpu.engine.socket import (TransportTimeout,
                                                     ZmqPairSocketFactory)
    from detectmateservice_tpu.loadgen.corpus import make_line
    from detectmateservice_tpu.schemas import LogSchema, OutputSchema

    stages = build_stages(work, profile)
    det = stages["detector"]
    mesh = profile.get("mesh")
    mesh_size = math.prod((mesh or {}).values())
    if mesh and profile["backend"] == "cpu":
        # the rehearsal's stand-in for a multi-chip host (make_mesh wants
        # the mesh to cover every device jax reports)
        det.extra_env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={mesh_size}")
    live = list(stages.values())
    factory = ZmqPairSocketFactory()
    sink = factory.create(f"ipc://{work}/final.ipc")
    sink.recv_timeout = 200
    alert_ids: list = []
    alert_scores: list = []      # (score, threshold) as the detector wrote them
    stop_sink = threading.Event()

    def drain_sink() -> None:
        while not stop_sink.is_set():
            try:
                record = OutputSchema.from_bytes(sink.recv())
            except TransportTimeout:
                continue
            alert_ids.extend(record.logIDs)
            for text in dict(record.alertsObtain).values():
                found = re.search(r"score ([-\d.einf]+) > ([-\d.einf]+)", text)
                if found:
                    alert_scores.append((float(found[1]), float(found[2])))

    sink_thread = threading.Thread(target=drain_sink, name="smoke-sink")
    sink_thread.start()
    failures: list = []

    def check(name: str, ok: bool, detail: str = "") -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {name}"
              + (f" — {detail}" if detail else ""), file=sys.stderr)
        if not ok:
            failures.append(f"{name}: {detail}")

    try:
        # -- boot 1 (cold) ------------------------------------------------
        stages["output"].start()
        t0 = det.start()
        stages["parser"].start()
        stages["output"].wait_running(60.0)
        stages["parser"].wait_running(60.0)
        det.wait_running(profile["warmup_timeout_s"])
        boot1_s = time.monotonic() - t0
        xla = http_json(det.port, "/admin/xla?limit=1")
        device = xla.get("device") or {}
        print(f"chip_smoke: detector up in {boot1_s:.1f}s on "
              f"{device.get('platform')} ({device.get('device_kind')} x"
              f"{device.get('device_count')}); cache "
              f"{device.get('compile_cache_dir')}", file=sys.stderr)
        check("platform", device.get("platform") == profile["backend"],
              f"resolved {device.get('platform')!r}, "
              f"want {profile['backend']!r}")
        check("warmup_complete", bool(xla.get("warmup_complete")))
        if failures:
            raise SmokeFailure("detector did not come up on the device")

        # -- traffic ------------------------------------------------------
        ingress = factory.create_output(f"ipc://{work}/parser.ipc",
                                        buffer_size=8192)
        rng = random.Random(7)
        row_ids = itertools.count()
        anomalies: set = set()

        def rows(n: int, anomaly_rate: float) -> list:
            out = []
            for i in itertools.islice(row_ids, n):
                anomaly = rng.random() < anomaly_rate
                if anomaly:
                    anomalies.add(str(i))
                out.append(LogSchema(logID=str(i), logSource="smoke",
                                     log=make_line(i, rng, anomaly)
                                     ).serialize())
            return out

        def send_burst(payloads: list) -> None:
            for start in range(0, len(payloads), FRAME_ROWS):
                ingress.send(pack_batch(payloads[start:start + FRAME_ROWS]))

        def device_info() -> dict:
            return http_json(det.port, "/admin/xla?limit=1")["device"]

        def scored_rows() -> int:
            # one ledger span per batch whose scores reached the host, on
            # either path (detector_device_lines_total counts the device
            # path's rows alone; the lone frames ride the host twin)
            return sum(span["real"] for span in
                       http_json(det.port, "/admin/xla")["batches"])

        n_train = profile["n_train"]
        send_burst(rows(n_train, 0.0))
        wait_for(lambda: device_info().get("scorer", {}).get("fitted"),
                 600.0, "the boundary fit", live)
        expect = 0
        for burst in profile["bursts"]:
            send_burst(rows(burst, ANOMALY_RATE))
            expect += burst
            wait_for(lambda: scored_rows() >= expect, 600.0,
                     f"{expect} scored rows", live)
        # lone frames: one unpacked message at a time, the last an anomaly
        for k in range(N_LONE):
            payload = rows(1, 1.0 if k == N_LONE - 1 else 0.0)[0]
            ingress.send(payload)
            expect += 1
            wait_for(lambda: scored_rows() >= expect, 120.0,
                     f"lone frame {k + 1}", live)
        wait_for(lambda: bool(set(alert_ids) & anomalies), 60.0,
                 "an alert for an injected anomaly at the sink", live)
        time.sleep(1.0)  # let the tail of the alerts land

        # -- what the services say happened ------------------------------
        series = scrape(det.port)
        xla = http_json(det.port, "/admin/xla")
        device = xla["device"]
        totals = xla["totals"]

        def total(name: str, **matchers: str) -> int:
            return int(sum(value for _, value in series(name, **matchers)))

        by_path = {path: total("detector_bucket_selected_total", path=path)
                   for path in ("device", "host")}
        device_buckets = sorted(
            int(labels["bucket"]) for labels, value in
            series("detector_bucket_selected_total", path="device")
            if value > 0)
        native_rows = total("featurize_native_rows_total")
        fallback_rows = total("featurize_fallback_rows_total")
        hit = set(alert_ids) & anomalies
        check("alerts_for_injected_anomalies", bool(hit),
              f"{len(hit)}/{len(anomalies)} injected anomalies alerted; "
              f"{len(set(alert_ids) - anomalies)} other alerts")
        check("device_path_batches", by_path["device"] > 0, str(by_path))
        check("full_width_bucket_dispatched",
              profile["scorer"]["max_batch"] in device_buckets,
              f"device buckets {device_buckets}")
        check("native_featurize_rows", native_rows > 0,
              f"native={native_rows} fallback={fallback_rows}")
        check("native_featurize_loaded",
              bool(device["native_featurize"]["loaded"]),
              str(device["native_featurize"]))
        check("zero_unexpected_compiles", totals["unexpected"] == 0,
              f"unexpected={totals['unexpected']} of "
              f"{totals['compiles']} compiles")
        for key, want in profile["scorer"].items():
            if key != "dtype":
                check(f"scorer_{key}", device["scorer"].get(key) == want,
                      f"service reports {device['scorer'].get(key)!r}")
        # per-device memory as the runtime reports it (none on the CPU)
        hbm: dict = {}
        for labels, value in series("device_hbm_bytes"):
            hbm.setdefault(labels["device"], {})[labels["kind"]] = int(value)
        if mesh:
            check("mesh_shape", device["mesh"] == mesh, str(device["mesh"]))
            check("scorer_spans_the_mesh",
                  len(device["scorer_devices"]) == mesh_size,
                  str(device["scorer_devices"]))
            if profile["backend"] != "cpu":   # the CPU reports no memory
                # params (replicated over a data axis) occupy every chip of
                # the mesh, not the first; which shard sits where is
                # scripts/chip_mesh.py's to show
                in_use = [hbm.get(dev, {}).get("in_use", 0)
                          for dev in device["scorer_devices"]]
                check("every_mesh_device_holds_params",
                      min(in_use) > 0 and min(in_use) >= 0.5 * max(in_use),
                      json.dumps(hbm))
        boot1 = {"boot_to_running_s": round(boot1_s, 1),
                 "warmup_phases_s": xla["warmup_phases"],
                 "compile_cache": xla["compile_cache"]}

        # -- boot 2: same detector, same cache ---------------------------
        rc = det.shutdown()
        check("detector_clean_exit", rc == 0, f"exit code {rc}")
        t0 = det.start()
        det.wait_running(profile["warmup_timeout_s"])
        boot2_s = time.monotonic() - t0
        xla2 = http_json(det.port, "/admin/xla?limit=1")
        cache2 = xla2["compile_cache"]
        boot2 = {"boot_to_running_s": round(boot2_s, 1),
                 "warmup_phases_s": xla2["warmup_phases"],
                 "compile_cache": cache2}
        if cache2["armed"]:
            cache_check = "passed"   # a failed check fails the whole run
            check("second_boot_cache_hits", cache2["hits"] > 0,
                  f"{cache2} in {xla2['device']['compile_cache_dir']}")
        else:
            # the persistent cache is off by default on the CPU backend
            cache_check = "skipped"
            check("second_boot_cache_hits_skipped_on_cpu",
                  profile["backend"] == "cpu", f"cache not armed: {cache2}")
    finally:
        stop_sink.set()
        sink_thread.join(timeout=5)
        exit_codes = {stage.name: stage.shutdown() for stage in live}
        sink.close()
    for name, rc in exit_codes.items():
        check(f"{name}_clean_exit", rc == 0, f"exit code {rc}")
    check("parent_never_imported_jax", "jax" not in sys.modules)
    if failures:
        raise SmokeFailure("; ".join(failures))
    return {
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["device_kind"]),
                   "count": int(device["device_count"])},
        "scorer": device["scorer"],
        "rows": {"trained": n_train, "scored": expect,
                 "injected_anomalies": len(anomalies),
                 "alerted_anomalies": len(hit),
                 "other_alerts": len(set(alert_ids) - anomalies)},
        "alert_scores": ({"threshold": alert_scores[0][1],
                          "min": min(a for a, _ in alert_scores),
                          "max": max(a for a, _ in alert_scores)}
                         if alert_scores else None),
        "batches": {"device_path": by_path["device"],
                    "host_path": by_path["host"],
                    "device_buckets": device_buckets,
                    # which kernels each traced bucket took (auto's answers)
                    "head_route": xla["buckets"].get("head_route"),
                    "attn_route": xla["buckets"].get("attn_route")},
        "featurize_rows": {"native": native_rows, "fallback": fallback_rows},
        "host_twin": device["host_twin"]["state"],
        "mesh": device["mesh"],
        "scorer_devices": device["scorer_devices"],
        "hbm_bytes": hbm,
        "compiles": {"total": totals["compiles"],
                     "unexpected_after_warmup": totals["unexpected"]},
        "compile_cache_dir": device["compile_cache_dir"],
        "second_boot_cache_check": cache_check,
        "setup_seconds": {"boot1": boot1, "boot2": boot2},
        "claim": None,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the same flow at tiny size on the CPU backend "
                         "(debugging and tier-1 tests; not a chip result)")
    ap.add_argument("--mesh", metavar="AXIS=N", default=None,
                    help="run the scorer over a device mesh (e.g. data=4 on "
                         "the four-chip host); the mesh must cover every "
                         "device jax reports")
    args = ap.parse_args()
    profile = dict(REHEARSAL if args.rehearse_cpu else FLAGSHIP)
    if args.mesh:
        axis, _, size = args.mesh.partition("=")
        profile["mesh"] = {axis: int(size)}
    work = tempfile.mkdtemp(prefix="dmsmoke-")
    try:
        result = run(profile, work)
    except Exception as exc:  # noqa: BLE001 — top level: report, keep the logs, fail
        if isinstance(exc, SmokeFailure):
            print(f"chip_smoke: FAILED — {exc}", file=sys.stderr)
        else:
            traceback.print_exc()
        out_dir = os.path.join(REPO, "chiprun_out", "chip_smoke_logs")
        for name in ("parser", "detector", "output"):
            path = os.path.join(work, f"{name}.out")
            if os.path.exists(path):
                os.makedirs(out_dir, exist_ok=True)
                shutil.copy(path, os.path.join(out_dir, f"{name}.out"))
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"report": result}))
    # the last line is the verdict alone: nothing but "ok" and the device
    print(json.dumps({"ok": True, "device": result["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
