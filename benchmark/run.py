"""The benchmark's one command: one cell, one run, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It measures the served path from the client's side. Its own generator plays
the reader and dials the parser's ingress; its own sink listens where the
output stage dials; between them run the three
``python -m detectmateservice_tpu.cli --settings …`` processes of the
configuration, over ``ipc://``. Only the detector child touches jax: this
parent never imports it.

Set-up (counted in ``setup_s``): boot, the pool from the seed, training lines
and the boundary fit, one burst per compile bucket, then the traffic's ramp.
Then the window of ``--seconds``; then drain, checkpoint, shutdown, and —
outside both — the float32 reference in a CPU child and the verdict.

The last line of stdout is the result object and nothing else; the report a
person reads goes on the lines before it. Without a TPU the detector cannot
boot, and the command exits non-zero and prints no result; so does a traced
run whose capture ended in ``error`` or holds no operation of the device.
"""
from __future__ import annotations

import time

T_START = time.monotonic()      # set-up is counted from the command's start

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import random                   # noqa: E402
import re                       # noqa: E402
import shutil                   # noqa: E402
import subprocess               # noqa: E402
import sys                      # noqa: E402
import tempfile                 # noqa: E402
import threading                # noqa: E402
import traceback                # noqa: E402
import urllib.error             # noqa: E402
from bisect import bisect_right  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import (corpus, layers, manifest, memory, prom,  # noqa: E402
                           quantiles, schedule, verdict)
from benchmark.lib.stages import (ORDER, HarnessFailure, build_stages,  # noqa: E402
                                  http_json, scorer_of, wait_for)

TRAIN_FIRST_INDEX = 0           # training lines: make_line(0 ..)
WARM_FIRST_INDEX = 1_000_000    # warm-up lines' times and serials lie apart
BURST_FRAME_LINES = 8192        # the widest frame the settings allow
PROFILE_SECONDS = 4.0
PROFILE_AFTER_S = 1.0
SAMPLE_EVERY_S = 0.5
QUIET_S = 1.0
DRAIN_TIMEOUT_S = 240.0
_ALERT_TEXT = re.compile(r"score ([-\d.einfa]+) > ([-\d.einfa]+)")


def say(text: str) -> None:
    print(text, flush=True)


class Sink(threading.Thread):
    """Listens where the output stage dials; stamps each record on arrival
    and keeps the bytes for after the window."""

    def __init__(self, zmq, ctx, addr: str):
        super().__init__(name="bench-sink", daemon=True)
        self._zmq = zmq
        self.sock = ctx.socket(zmq.DEALER)
        self.sock.setsockopt(zmq.LINGER, 0)
        self.sock.setsockopt(zmq.RCVHWM, 0)
        self.sock.bind(addr)
        self.records = []               # (arrival, bytes)
        self.stop = threading.Event()

    def run(self) -> None:
        poller = self._zmq.Poller()
        poller.register(self.sock, self._zmq.POLLIN)
        while not self.stop.is_set():
            if not poller.poll(100):
                continue
            while True:
                try:
                    data = self.sock.recv(self._zmq.NOBLOCK)
                except self._zmq.Again:
                    break
                self.records.append((time.monotonic(), data))


class Generator(threading.Thread):
    """Open loop: frame *i* goes out at ``t0 + offsets[i]`` or, when that has
    passed, at once. A full ingress blocks the send (counted apart as
    ``blocked_s``); the schedule never moves. Stops at ``t_stop``."""

    def __init__(self, zmq, sock, frames, offsets, t0: float, t_stop: float):
        super().__init__(name="bench-generator", daemon=True)
        self._zmq, self._sock = zmq, sock
        self._frames, self._offsets = frames, offsets
        self.t0, self.t_stop = t0, t_stop
        self.sent_at = []               # send completion, per frame sent
        self.late_s = []                # send start - due, per frame sent
        self.blocked_s = 0.0
        self.halt = threading.Event()
        self.error = None

    def run(self) -> None:
        zmq, sock, frames = self._zmq, self._sock, self._frames
        n_pool = len(frames)
        try:
            for i, offset in enumerate(self._offsets):
                due = self.t0 + offset
                now = time.monotonic()
                while now < due:
                    time.sleep(min(due - now, 0.05))
                    now = time.monotonic()
                if now >= self.t_stop or self.halt.is_set():
                    return
                while True:
                    try:
                        sock.send(frames[i % n_pool], zmq.NOBLOCK)
                        break
                    except zmq.Again:
                        t_block = time.monotonic()
                        sock.poll(50, zmq.POLLOUT)
                        self.blocked_s += time.monotonic() - t_block
                        if (self.halt.is_set()
                                or time.monotonic() >= self.t_stop):
                            return
                self.late_s.append(now - due)
                self.sent_at.append(time.monotonic())
        except Exception as exc:  # noqa: BLE001 — reported by the main thread
            self.error = exc


def send_all(zmq, sock, frames, stages) -> None:
    for frame in frames:
        while not sock.poll(1000, zmq.POLLOUT):
            for stage in stages:
                stage.check_alive()
        sock.send(frame)


class ScoredRows:
    """Rows whose scores reached the host, on the device path or the host
    twin's, summed over the detector's batch spans (``GET /admin/xla``;
    ``detector_device_lines_total`` leaves the twin's rows out). Each poll
    reads the newest 256 spans, so this is for set-up only."""

    def __init__(self, port: int):
        self.port, self.seq, self.rows = port, 0, 0

    def poll(self) -> int:
        for span in http_json(self.port, "/admin/xla?limit=256")["batches"]:
            if span["seq"] > self.seq:
                self.seq = span["seq"]
                self.rows += span["real"]
        return self.rows


def warm_up(zmq, ingress, stages, det, config: dict, seed: int,
            frame_lines: int, train_lines, serialize) -> int:
    """Training lines and the fit, then one burst per compile bucket until
    the detector reports each bucket warm. Returns the lines sent."""
    live = list(stages.values())
    train = corpus.tagged_messages("T", train_lines, serialize)
    send_all(zmq, ingress, corpus.pack_frames(train, frame_lines), live)
    sent = len(train)

    def device_block() -> dict:
        return http_json(det.port, "/admin/xla?limit=0")

    wait_for(lambda: device_block()["device"]["scorer"]["fitted"], 600.0,
             "the boundary fit", live, poll_s=0.25)
    buckets = config["warmup_buckets"]
    warm = corpus.tagged_messages("W", corpus.normal_lines(
        seed, "warm", max(buckets), WARM_FIRST_INDEX), serialize)
    scored = ScoredRows(det.port)
    expect = 0
    for _ in range(4):
        state = device_block().get("buckets") or {}
        cold = [b for b in buckets if b not in (state.get("warm") or [])]
        if not cold:
            break
        for bucket in cold:
            # one wire frame per burst (two for the widest bucket): the
            # parser then emits the burst's frames in one go, and a detector
            # that dispatches what one receive delivered (no coalescer) sees
            # the bucket's own size rather than pieces of it
            send_all(zmq, ingress,
                     corpus.pack_frames(warm[:bucket], BURST_FRAME_LINES),
                     live)
            sent += bucket
            expect += bucket
            wait_for(lambda: scored.poll() >= expect, 600.0,
                     f"warm-up burst of {bucket} rows", live)
    else:
        say(f"warm-up: buckets still cold after 4 rounds: {cold}")

    def twin_settled() -> bool:
        # the host twin compiles its buckets on a background thread after
        # the fit; the window must not run beside those compiles
        twin = device_block()["device"]["host_twin"]
        if twin["state"] in ("pending", "ready"):
            return (twin["state"] == "ready"
                    and twin["max_batch"] in twin["warm_buckets"])
        return True     # off, unsupported or failed: nothing will compile

    wait_for(twin_settled, 600.0, "the host twin's warm set", live,
             poll_s=0.25)
    return sent


def rows_handed(series) -> float:
    """Messages handed to a stage's ``process*`` call: the sum of its
    batch-size histogram. (``data_processed_lines_total`` counts newline
    bytes, and a serialized message holds several.)"""
    return prom.total(series, "detector_batch_size_sum")


def quiesce(det, parser, sink: Sink, sent_lines: int, stages) -> None:
    """After the last send: every line handed to the detector's process(),
    then no row held by the coalescer, no new batch and no new record at the
    sink for ``QUIET_S``."""
    live = list(stages.values())

    def handed() -> bool:
        return (rows_handed(prom.scrape(parser.port)) >= sent_lines
                and rows_handed(prom.scrape(det.port)) >= sent_lines)

    wait_for(handed, DRAIN_TIMEOUT_S, "every line to reach the detector",
             live, poll_s=0.25)
    state = {"mark": None, "since": time.monotonic()}

    def quiet() -> bool:
        series = prom.scrape(det.port)
        held = prom.total(series, "detector_coalesce_depth")
        mark = (prom.total(series, "detector_bucket_selected_total"),
                len(sink.records), held)
        if mark != state["mark"] or held:
            # rows the coalescer still holds leave at their deadline, which
            # may lie further off than QUIET_S
            state["mark"], state["since"] = mark, time.monotonic()
        return time.monotonic() - state["since"] >= QUIET_S

    wait_for(quiet, DRAIN_TIMEOUT_S, "the pipeline to drain", live,
             poll_s=0.25)


def parse_alerts(records, pool_ids: set) -> list:
    """``[(arrival, logID, score, threshold)]`` for each alert of a pool
    line, in arrival order; other records are counted, not kept."""
    from detectmateservice_tpu.schemas import OutputSchema

    alerts, others = [], 0
    for arrival, data in records:
        record = OutputSchema.from_bytes(data)
        texts = list(dict(record.alertsObtain).values())
        found = _ALERT_TEXT.search(texts[0]) if texts else None
        for log_id in record.logIDs:
            if log_id in pool_ids and found:
                alerts.append((arrival, log_id, float(found[1]),
                               float(found[2])))
            else:
                others += 1
    return alerts, others


def reference_scores(cell: dict, work: str, pool, alerted_ids: list,
                     seed: int) -> dict:
    """Scores of every anomalous pool line, a seeded sample of normal lines
    and a seeded sample of other alerted lines, from the CPU child."""
    check = cell["config"]["check"]
    rng = random.Random(f"check:{seed}")
    marks = set(pool.anomalous)
    normal = [i for i in range(len(pool.lines)) if i not in marks]
    chosen = set(pool.anomalous) | set(rng.sample(normal,
                                                  check["normal_sample"]))
    extra = sorted({int(i) for i in alerted_ids} - chosen)
    if len(extra) > check["extra_alerted_sample"]:
        extra = rng.sample(extra, check["extra_alerted_sample"])
    chosen |= set(extra)
    request = {
        "repo": REPO, "config_file": cell["config_file"],
        "checkpoint_dir": os.path.join(work, "checkpoint"),
        "out": os.path.join(work, "reference.json"),
        "lines": [{"id": pool.pool_id(i), "log": pool.lines[i]}
                  for i in sorted(chosen)],
    }
    path = os.path.join(work, "reference_request.json")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(request, fh)
    t0 = time.monotonic()
    child = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "lib",
                                      "refcheck.py"), path],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    if child.returncode != 0:
        raise HarnessFailure("reference child failed:\n"
                             + child.stderr[-3000:])
    out = manifest.read_json(request["out"])
    out["seconds"] = time.monotonic() - t0
    return out


def reduce_trace(work: str) -> dict:
    out = os.path.join(work, "trace.json")
    child = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "lib", "xplane.py"),
         os.path.join(work, "profile"), out],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    if child.returncode != 0:
        raise HarnessFailure("trace reduction failed:\n"
                             + child.stderr[-3000:])
    return manifest.read_json(out)


def refuse_failed_capture(status: dict) -> None:
    """``GET /admin/profile`` after the capture has ended: a capture whose
    record says ``error`` (``stop_trace`` left no file, or raised) ends the
    run with that error and no line. A program that keeps no ``state``
    (before PR 38) is taken at its word."""
    last = status.get("last") or {}
    if last.get("state") == "error":
        raise HarnessFailure("the profiler capture failed: "
                             f"{last.get('error')}")


def hbm_in_use(series) -> float:
    """Bytes in use on the fullest chip, as the detector's jax reports."""
    per_device = {}
    for (name, labels), value in series.items():
        have = dict(labels)
        if name == "device_hbm_bytes" and have.get("kind") == "in_use":
            per_device[have["device"]] = value
    return max(per_device.values(), default=0.0)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, platform: str = "tpu",
             t_start: float = T_START) -> dict:
    """One run of one cell. ``root`` holds ``BENCHMARK.json`` and the data
    files; the code and the program are this checkout's. ``platform`` is what
    the detector's jax must report — the command always asks for ``tpu``."""
    measured = measure(root, workload, seed, seconds, trace, platform,
                       t_start)
    try:
        return conclude(measured)
    finally:
        shutil.rmtree(measured["work"], ignore_errors=True)


def measure(root: str, workload: str, seed: int, seconds: float,
            trace: bool, platform: str, t_start: float,
            checkpoint: bool = True) -> dict:
    """Boot, set-up, the window, the drain and the checkpoint; the stages
    have stopped when it returns. What it returns is ``conclude``'s to read;
    the run's work directory (``work``) is the caller's to remove. Without
    ``checkpoint`` (``sweep.py --flood``, which reads the drain and nothing
    else) no parameter is written and ``conclude`` cannot follow."""
    import zmq

    from detectmateservice_tpu.schemas import LogSchema

    cell = manifest.load_cell(root, workload)
    config, traffic = cell["config"], cell["traffic"]
    rate = float(cell["cell"]["rate_lines_per_s"])
    frame_lines = int(traffic["frame_lines"])
    scorer = scorer_of(config)
    say(f"cell {workload}: config {config['name']}, traffic "
        f"{traffic['name']} at {rate:.0f} lines/s, seed {seed}, window "
        f"{seconds:g}s, trace {int(trace)}")
    say(f"configuration: reduced {config['reduced'] or 'nothing'}"
        + "".join(f"; {key} {cut['published']} -> {cut['here']}"
                  for key, cut in config.get("cut", {}).items()))

    def serialize(log_id: str, line: str) -> bytes:
        return LogSchema(logID=log_id, logSource="bench", log=line).serialize()

    work = tempfile.mkdtemp(prefix="dmb-")
    ctx = zmq.Context()
    sink = Sink(zmq, ctx, f"ipc://{work}/sink.ipc")
    stages = build_stages(work, REPO, config, seed, f"ipc://{work}/sink.ipc")
    live = list(stages.values())
    det, parser = stages["detector"], stages["parser"]
    gen = None
    try:
        sink.start()
        for name in ORDER:
            stages[name].start()
        source = config["traffic_source"]
        train_lines = corpus.normal_lines(seed, "train",
                                          source["train_lines"],
                                          TRAIN_FIRST_INDEX)
        pool = corpus.build_pool(seed, source["pool_lines"], frame_lines,
                                 traffic["anomaly_share"], train_lines,
                                 TRAIN_FIRST_INDEX, serialize)
        for name in ORDER:
            stages[name].wait_running(1100.0 if name == "detector" else 120.0)
        t_booted = time.monotonic()
        device = http_json(det.port, "/admin/xla?limit=0")["device"]
        if (device.get("platform") != platform
                or int(device.get("device_count") or 0)
                < int(cell["entry"]["chips"])):
            raise HarnessFailure(
                f"the detector runs on {device.get('platform')!r} x"
                f"{device.get('device_count')}, the cell asks for "
                f"{platform!r} x{cell['entry']['chips']}")
        if device["device_kind"] not in cell["peaks"] and platform == "tpu":
            raise HarnessFailure(f"no peaks for device kind "
                                 f"{device['device_kind']!r} in peaks.json")
        ingress = ctx.socket(zmq.DEALER)
        ingress.setsockopt(zmq.LINGER, 0)
        ingress.setsockopt(zmq.SNDHWM, 8192)
        ingress.connect(f"ipc://{work}/parser.ipc")
        setup_lines = warm_up(zmq, ingress, stages, det, config, seed,
                              frame_lines, train_lines, serialize)
        t_warm = time.monotonic()

        # -- ramp, then the window ---------------------------------------
        ramp_s = float(traffic["ramp_s"])
        offsets = schedule.offsets(traffic["arrival"], rate, frame_lines,
                                   ramp_s + seconds, seed)
        xla_w0 = http_json(det.port, "/admin/xla?limit=0")["totals"]
        det_ramp0 = prom.scrape(det.port)
        hbm = [hbm_in_use(det_ramp0)]
        t0 = time.monotonic() + 0.05
        w0, w1 = t0 + ramp_s, t0 + ramp_s + seconds
        gen = Generator(zmq, ingress, pool.frames, offsets, t0, w1)
        gen.start()
        time.sleep(max(0.0, w0 - time.monotonic()))
        setup_s = w0 - t_start
        prom_w0 = ({name: prom.scrape(stages[name].port) for name in ORDER}
                   if trace else {})
        t_prom0 = time.monotonic()
        gauge_samples = {"parser": [], "detector": []}
        capture, capture_buckets = None, None
        next_sample = w0 + SAMPLE_EVERY_S
        while True:
            now = time.monotonic()
            if now >= w1:
                break
            for stage in live:
                stage.check_alive()
            if trace and capture is None and now >= w0 + PROFILE_AFTER_S:
                before = prom.scrape(det.port)
                capture = {"info": http_json(
                    det.port, f"/admin/profile?seconds={PROFILE_SECONDS}",
                    post=True), "before": before,
                    "ask_again": now + PROFILE_SECONDS}
            if (trace and capture is not None and capture_buckets is None
                    and now >= capture["ask_again"]):
                # the capture ends later than asked, by as long as the
                # profiler took to start: ask until it has
                if http_json(det.port, "/admin/profile")["running"]:
                    capture["ask_again"] = now + 0.25
                else:
                    capture_buckets = memory.dispatched_buckets(
                        capture["before"], prom.scrape(det.port))
            if trace and now >= next_sample:
                for name in gauge_samples:
                    sample = prom.scrape(stages[name].port)
                    gauge_samples[name].append(sample)
                    if name == "detector":
                        hbm.append(hbm_in_use(sample))
                next_sample += SAMPLE_EVERY_S
            time.sleep(min(0.05, max(0.0, w1 - time.monotonic())))
        prom_w1 = ({name: prom.scrape(stages[name].port) for name in ORDER}
                   if trace else {})
        t_prom1 = time.monotonic()
        xla_w1 = http_json(det.port, "/admin/xla?limit=0")["totals"]
        det_w1 = prom.scrape(det.port)
        hbm.append(hbm_in_use(det_w1))
        gen.join(timeout=30.0)
        if gen.is_alive() or gen.error is not None:
            raise HarnessFailure(f"the generator did not end: {gen.error!r}")

        # -- drain, read the counters, checkpoint, stop ------------------
        stream_lines = len(gen.sent_at) * frame_lines
        quiesce(det, parser, sink, setup_lines + stream_lines, stages)
        t_drained = time.monotonic()
        final = {name: prom.scrape(stages[name].port) for name in ORDER}
        hbm.append(hbm_in_use(final["detector"]))
        hbm_drained = hbm[-1]
        xla_end = http_json(det.port, "/admin/xla?limit=64")
        if trace:
            wait_for(lambda: not http_json(det.port,
                                           "/admin/profile")["running"],
                     120.0, "the profiler capture to finish", live)
            if capture is not None and capture_buckets is None:
                capture_buckets = memory.dispatched_buckets(
                    capture["before"], final["detector"])
            refuse_failed_capture(http_json(det.port, "/admin/profile"))
        if checkpoint:
            http_json(det.port, "/admin/checkpoint", post=True,
                      timeout=300.0)
    except BaseException:
        if gen is not None:
            gen.halt.set()
        for name in reversed(ORDER):
            stages[name].shutdown(timeout_s=20.0)
        sink.stop.set()
        if sink.is_alive():
            sink.join(timeout=2.0)
        for stage in live:
            print(f"--- {stage.name} log tail ---\n{stage.log_tail()}",
                  file=sys.stderr)
        ctx.destroy(linger=0)
        shutil.rmtree(work, ignore_errors=True)
        raise
    exit_codes = {name: stages[name].shutdown() for name in reversed(ORDER)}
    sink.stop.set()
    sink.join(timeout=5.0)
    ingress.close()
    sink.sock.close()
    ctx.term()
    programs, allocator_at_exit = memory.read_programs(det.programs_path)
    return dict(
        cell=cell, work=work, pool=pool, records=sink.records, gen=gen,
        offsets=offsets, seed=seed, device=device, scorer=scorer,
        trace=trace, platform=platform,
        t=dict(t0=t0, w0=w0, w1=w1, seconds=seconds, setup_s=setup_s,
               boot_s=t_booted - t_start, warm_s=t_warm - t_booted,
               drain_s=t_drained - w1, frame_lines=frame_lines,
               setup_lines=setup_lines, stream_lines=stream_lines),
        obs=dict(xla_w0=xla_w0, xla_w1=xla_w1, xla_end=xla_end, final=final,
                 prom_w0=prom_w0, prom_w1=prom_w1,
                 window_prom_s=t_prom1 - t_prom0,
                 gauge_samples=gauge_samples, hbm=hbm,
                 hbm_drained=hbm_drained, programs=programs,
                 allocator_at_exit=allocator_at_exit,
                 window_buckets=memory.dispatched_buckets(det_ramp0, det_w1),
                 capture_buckets=capture_buckets, exit_codes=exit_codes))


def conclude(measured: dict) -> dict:
    """Everything after the processes have stopped: the reference, the
    verdict, the metrics and the report."""
    cell, work, pool = (measured[k] for k in ("cell", "work", "pool"))
    records, gen, offsets = (measured[k] for k in ("records", "gen",
                                                    "offsets"))
    seed, t, obs = (measured[k] for k in ("seed", "t", "obs"))
    device, scorer = measured["device"], measured["scorer"]
    trace, platform = measured["trace"], measured["platform"]
    config, traffic = cell["config"], cell["traffic"]
    # a traced run on the chip whose capture holds no operation of the
    # device has nothing to report: no line, rather than one without
    # ``busy_s`` and ``window_s`` (the CPU has no device plane to hold one)
    trace_doc = reduce_trace(work) if trace else None
    if trace and platform == "tpu" and not trace_doc.get("devices"):
        raise HarnessFailure(
            "the capture's device plane holds no event (planes and lines: "
            f"{trace_doc.get('inventory')})")
    frame_lines, n_pool_frames = t["frame_lines"], len(pool.frames)
    pool_ids = {pool.pool_id(i) for i in range(len(pool.lines))}
    alerts, other_records = parse_alerts(records, pool_ids)

    # each alert's place in the sent stream: the k-th alert of a pool line
    # belongs to the k-th time its frame went out
    seen, placed = {}, []
    for arrival, log_id, score, thr in alerts:
        k = seen.get(log_id, 0)
        seen[log_id] = k + 1
        index = int(log_id)
        frame_no = k * n_pool_frames + index // frame_lines
        placed.append((arrival, frame_no,
                       frame_no * frame_lines + index % frame_lines + 1))
    frames_sent = len(gen.sent_at)

    def sent_times(index: int) -> int:
        first = index // frame_lines
        return (0 if frames_sent <= first
                else (frames_sent - 1 - first) // n_pool_frames + 1)

    ref = reference_scores(cell, work, pool, sorted(seen), seed)
    tol = float(config["check"]["tolerance_nats"])
    alerts_by_id = {}
    for _, log_id, score, _ in alerts:
        alerts_by_id.setdefault(log_id, []).append(score)
    judged = verdict.judge(
        alerts_by_id, {i: sent_times(int(i)) for i in ref["scores"]},
        ref["scores"], float(ref["threshold"]), config["check"],
        [thr for _, _, _, thr in alerts])

    # -- counters: nothing dropped, every line handed to every stage ------
    sent_lines = t["setup_lines"] + t["stream_lines"]
    dropped = sum(prom.total(obs["final"][name], "data_dropped_lines_total")
                  for name in ORDER)
    handed = {name: rows_handed(obs["final"][name])
              for name in ("parser", "detector")}
    unexpected = obs["xla_w1"]["unexpected"] - obs["xla_w0"]["unexpected"]
    compiles = obs["xla_w1"]["compiles"] - obs["xla_w0"]["compiles"]
    numbers = judged["numbers"] + [
        ["dropped_lines", dropped, 0],
        ["parser_lines_short", abs(handed["parser"] - sent_lines), 0],
        ["detector_lines_short", abs(handed["detector"] - sent_lines), 0],
        ["unexpected_compiles_after_warmup", unexpected, 0],
        ["compiles_after_warmup", compiles, 0],
    ]

    # -- the window ------------------------------------------------------
    w0, w1, seconds = t["w0"], t["w1"], t["seconds"]
    arrivals = [a for a, _, _ in placed]
    reach, top = [], 0
    for _, _, position in placed:
        top = max(top, position)
        reach.append(top)

    def completed(at: float) -> int:
        k = bisect_right(arrivals, at)
        return reach[k - 1] if k else 0

    done_in_window = completed(w1) - completed(w0)
    sent_in_window = frame_lines * (bisect_right(gen.sent_at, w1)
                                    - bisect_right(gen.sent_at, w0))
    due_in_window = frame_lines * sum(
        1 for off in offsets if w0 <= t["t0"] + off < w1)
    latencies = [1000.0 * (arrival - (t["t0"] + offsets[frame_no]))
                 for arrival, frame_no, _ in placed
                 if frame_no < len(offsets)
                 and w0 <= t["t0"] + offsets[frame_no] < w1]
    late_ms = [1000.0 * late for late, at in zip(gen.late_s, gen.sent_at)
               if w0 <= at < w1]
    if traffic.get("saturating"):
        # a generator that fell short of its schedule without being held
        # back by the ingress was starved: not a slow server, no result
        short = sent_in_window < 0.98 * due_in_window
        starved = short and gen.blocked_s < 0.02 * seconds
        numbers.append(["generator_starved", int(starved), 0])
    correct = (all(value <= limit for _, value, limit in numbers)
               and device.get("platform") == platform)

    values = {"setup_s": t["setup_s"]}
    if done_in_window > 0:
        values["lines_per_s"] = done_in_window / seconds
    if latencies:
        p50, p95 = (quantiles.quantile(latencies, q) for q in (0.5, 0.95))
        values["alert_p50_ms"] = p50

    held = memory.peak(obs["hbm_drained"], obs["programs"],
                       obs["allocator_at_exit"], str(device["platform"]),
                       obs["window_buckets"])
    device_out = {
        "platform": str(device["platform"]),
        "kind": str(device["device_kind"]),
        "count": int(device["device_count"]),
        "memory_peak_bytes": held["peak_bytes"],
    }
    metrics = {}
    if trace:
        ctx = {
            "prom": {name: (obs["prom_w0"][name], obs["prom_w1"][name])
                     for name in ORDER},
            "window_s": obs["window_prom_s"],
            "gauge_samples": obs["gauge_samples"],
            "generator": {"late_ms": late_ms, "latency_ms": latencies},
            "trace": trace_doc, "scorer": dict(scorer),
            "capture_buckets": obs["capture_buckets"],
            "peak": cell["peaks"].get(device["device_kind"]),
            "capture_dir": os.path.join(work, "profile"),
        }
        ctx["scorer"].setdefault("vocab_size", 32768)
        for key, value in config.get("assumed", {}).items():
            if isinstance(value, (int, float)):     # sizes the scorer's
                ctx["scorer"].setdefault(key, value)  # block leaves unsaid
        for spec in cell["per_layer"]:
            value = layers.evaluate(spec, ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
        if trace_doc and trace_doc.get("devices"):
            device_out["busy_s"] = trace_doc["busy_s"]
            device_out["window_s"] = trace_doc["window_s"]
    else:
        for metric in cell["end_to_end"]:
            if metric["name"] in values:
                metrics[metric["name"]] = {"value": values[metric["name"]],
                                           "unit": metric["unit"]}

    # -- the report a person reads ---------------------------------------
    say(f"device: {device_out['platform']} {device_out['kind']} x"
        f"{device_out['count']}; memory peak {held['peak_bytes']} bytes = "
        f"{held['resident_bytes']} held by the drained process + "
        f"{held['scratch_bytes']} scratch of the {held['scratch_bucket']}-row "
        f"executable (XLA's buffer assignment; buckets dispatched from the "
        f"ramp to the window's end: {obs['window_buckets']}; "
        f"{len(obs['programs'])} executables recorded); allocator in use, "
        f"max of {len(obs['hbm'])} samples: "
        f"{int(max(obs['hbm'], default=0))}; allocator peak at exit: "
        f"{held['allocator_peak_bytes']}")
    say(f"set-up {t['setup_s']:.2f}s = boot {t['boot_s']:.2f} + fit and "
        f"warm-up {t['warm_s']:.2f} + ramp; drain {t['drain_s']:.2f}s; "
        f"reference {ref['seconds']:.2f}s over {judged['lines_scored']} "
        f"lines; stage exit codes {obs['exit_codes']}")
    say(f"lines: set-up {t['setup_lines']}, stream {t['stream_lines']} "
        f"(window: due {due_in_window}, sent {sent_in_window}, completed "
        f"{done_in_window}); generator blocked {gen.blocked_s:.3f}s")
    say(f"alerts: {len(alerts)} of pool lines, {other_records} other "
        f"records; expected {judged['expected_alerts']}; "
        f"{judged['lines_in_band']} scored lines inside the band; fitted "
        f"threshold {ref['threshold']:.4f}, tolerance {tol:g} nats")
    # a line the pool's cycle sent twice was scored in two batches: what
    # its two alerts say should not differ (four decimals in the text)
    again = [max(s) - min(s) for s in alerts_by_id.values() if len(s) > 1]
    say(f"served scores: {len(again)} lines alerted more than once, the "
        f"widest difference between one line's alerts "
        f"{max(again, default=0.0):.4f} nats")
    scored = [i for i in alerts_by_id if i in ref["scores"]]
    if scored:
        worst = max(scored, key=lambda i: max(
            abs(score - ref["scores"][i]) for score in alerts_by_id[i]))
        say(f"widest gap to the reference at line {worst}: served "
            f"{alerts_by_id[worst]}, reference {ref['scores'][worst]:.4f}")
    if latencies:
        say(f"send-to-alert, frames due in the window: n={len(latencies)} "
            f"({quantiles.samples_beyond(len(latencies), 0.95)} beyond p95) "
            f"p50 {p50:.2f} ms, p95 {p95:.2f} ms, max "
            f"{max(latencies):.2f} ms")
    if late_ms:
        say(f"generator lateness: p50 {quantiles.quantile(late_ms, 0.5):.3f} "
            f"ms, p95 {quantiles.quantile(late_ms, 0.95):.3f} ms")
    warm = (obs["xla_end"].get("buckets") or {}).get("warm")
    say(f"compiles: total {obs['xla_end']['totals']['compiles']}, cache "
        f"{obs['xla_end']['compile_cache']}, warm buckets {warm}")
    if compiles:
        for event in obs["xla_end"].get("compiles", [])[-int(compiles) - 4:]:
            say(f"compile event: {json.dumps(event)}")
    for name, value, limit in numbers:
        say(f"compared: {name} = {value:g} (limit {limit:g}) "
            f"{'ok' if value <= limit else 'FAIL'}")
    for name, value in sorted(values.items()):
        say(f"value: {name} = {value!r}")
    result = {
        "correct": bool(correct),
        "attempted": int(sent_in_window),
        "failed": int(judged["failed"] + dropped),
        "metrics": metrics,
        "device": device_out,
    }
    if trace_doc and trace_doc.get("devices"):
        result["breakdown"] = {"device_ops": trace_doc["device_ops"],
                               "idle_gaps": trace_doc["idle_gaps"]}
        for (cause, gap_s), shares in zip(trace_doc["idle_gaps"],
                                          trace_doc["idle_gap_cover"]):
            if gap_s >= 1e-3:
                by_share = sorted(shares.items(), key=lambda kv: -kv[1])[:4]
                say(f"idle gap {gap_s:.4f}s, {cause}: " + ", ".join(
                    f"{name} {100 * share:.1f}%" for name, share in by_share))
        for kernel, runs in sorted(trace_doc["kernels"].items()):
            say(f"kernel {kernel}: " + "; ".join(
                f"{run['count']} calls, {run['seconds']:.6f}s in {module}"
                for module, run in runs.items()))
        by_self = sorted(trace_doc.get("scopes", {}).items(),
                         key=lambda kv: -kv[1]["self_s"])[:12]
        for scope, entry in by_self:
            say(f"scope {scope}: {entry['self_s']:.6f}s self, "
                f"{entry['events']} operations")
    # every number compared beside its limit: the run's last lines on
    # standard error, and the result's last key
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in numbers}
    for name, value, limit in numbers:
        print(f"compared: {name} = {value:g} (limit {limit:g}) "
              f"{'ok' if value <= limit else 'FAIL'}", file=sys.stderr,
              flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    try:
        result = run_cell(REPO, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except (HarnessFailure, KeyError, ValueError, OSError,
            urllib.error.URLError) as exc:
        if not isinstance(exc, HarnessFailure):
            traceback.print_exc()
        print(f"benchmark: no result — {exc}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
