"""dmwal tests: segment framing, crash injection on the commit path,
retention bounds, byte-deterministic replay, and the engine's durable
ingress integration (append → crash_abort → recovery replay).

The crash-injection tests kill a real subprocess with SIGKILL between
append / fsync / manifest-commit and assert the recovery invariants the
subsystem promises: no torn record is ever served, recovered sequences are
strictly increasing, every recovered frame was actually appended, and a
record replays at most once per crash (the acks persisted to the manifest
never replay; the unpersisted tail may — at-least-once, never at-most-once).
"""
import json
import os
import signal
import subprocess
import sys
import time
import zlib
from pathlib import Path

import pytest

from detectmateservice_tpu.engine.framing import (
    Hop,
    TraceContext,
    pack_batch,
    wrap_trace,
)
from detectmateservice_tpu.wal import (
    IngressSpool,
    ReplayDriver,
    iter_records,
    list_segments,
    read_spool,
    scan_segment,
)
from detectmateservice_tpu.wal.segment import pack_record

from conftest import wait_until


# -- segment framing ---------------------------------------------------------


class TestSegmentFraming:
    def test_roundtrip(self, tmp_path):
        path = tmp_path / "seg-00000000000000000001.wal"
        frames = [b"alpha", b"\x00" * 100, b"\xd7DM\x01junk", b""]
        with open(path, "wb") as fh:
            for i, frame in enumerate(frames):
                fh.write(pack_record(i + 1, 1000 + i, frame))
        recs = list(iter_records(path))
        assert [(r.seq, r.append_ns, r.frame) for r in recs] == [
            (i + 1, 1000 + i, f) for i, f in enumerate(frames)]
        scan = scan_segment(path)
        assert not scan.torn
        assert (scan.first_seq, scan.last_seq, scan.records) == (1, 4, 4)

    def test_torn_tail_header(self, tmp_path):
        path = tmp_path / "seg-00000000000000000001.wal"
        with open(path, "wb") as fh:
            fh.write(pack_record(1, 7, b"whole"))
            fh.write(b"\x05\x00")           # half a header
        scan = scan_segment(path)
        assert scan.torn and scan.records == 1

    def test_torn_tail_body(self, tmp_path):
        path = tmp_path / "seg-00000000000000000001.wal"
        rec = pack_record(2, 7, b"payload-bytes")
        with open(path, "wb") as fh:
            fh.write(pack_record(1, 7, b"whole"))
            fh.write(rec[:-4])              # body cut short
        scan = scan_segment(path)
        assert scan.torn and scan.records == 1

    def test_crc_damage_stops_reader(self, tmp_path):
        path = tmp_path / "seg-00000000000000000001.wal"
        rec2 = bytearray(pack_record(2, 7, b"damaged"))
        rec2[-1] ^= 0xFF                    # flip a payload bit
        with open(path, "wb") as fh:
            fh.write(pack_record(1, 7, b"whole"))
            fh.write(bytes(rec2))
            fh.write(pack_record(3, 7, b"after"))
        # the reader must stop at the damage, not resync past it: a bad
        # record invalidates everything after it in this segment
        assert [r.seq for r in iter_records(path)] == [1]

    def test_garbage_length_is_tail_damage(self, tmp_path):
        path = tmp_path / "seg-00000000000000000001.wal"
        with open(path, "wb") as fh:
            fh.write(pack_record(1, 7, b"whole"))
            fh.write((2 ** 31).to_bytes(4, "little"))  # absurd body_len
            fh.write(zlib.crc32(b"x").to_bytes(4, "little"))
        assert [r.seq for r in iter_records(path)] == [1]


# -- spool lifecycle ---------------------------------------------------------


class TestSpool:
    def test_append_ack_depth_age(self, tmp_path):
        clock = [1000.0]
        spool = IngressSpool(tmp_path, fsync_interval_ms=0,
                             clock=lambda: clock[0])
        for i in range(10):
            assert spool.append(b"f%d" % i) == i + 1
        assert spool.depth_frames() == 10
        clock[0] += 5.0
        assert spool.oldest_unacked_age_seconds() == pytest.approx(5.0)
        spool.ack(4)
        assert spool.depth_frames() == 6
        spool.ack(2)                        # acks never regress
        assert spool.acked_seq == 4
        spool.ack(10)
        assert spool.depth_frames() == 0
        assert spool.oldest_unacked_age_seconds() == 0.0
        spool.close()

    def test_reopen_recovers_unacked_and_seq(self, tmp_path):
        spool = IngressSpool(tmp_path, fsync_interval_ms=0)
        for i in range(20):
            spool.append(b"frame-%02d" % i)
        spool.ack(12)
        spool.close()                       # commits acked_seq=12

        spool2 = IngressSpool(tmp_path, fsync_interval_ms=0)
        assert spool2.acked_seq == 12
        assert spool2.last_appended_seq == 20
        recovered = spool2.recover_unacked()
        assert [seq for seq, _ in recovered] == list(range(13, 21))
        assert [f for _, f in recovered] == [b"frame-%02d" % i
                                             for i in range(12, 20)]
        # appends continue the sequence, never reuse it
        assert spool2.append(b"next") == 21
        spool2.close()

    def test_torn_tail_truncated_on_reopen(self, tmp_path):
        spool = IngressSpool(tmp_path, fsync_interval_ms=0)
        for i in range(5):
            spool.append(b"ok-%d" % i)
        spool.close()
        seg = list_segments(tmp_path)[-1]
        with open(seg, "ab") as fh:
            fh.write(pack_record(6, 7, b"torn")[:-3])
        spool2 = IngressSpool(tmp_path, fsync_interval_ms=0)
        # the torn record is gone — physically — and seq 6 is reusable
        assert not scan_segment(seg).torn
        assert spool2.last_appended_seq == 5
        assert spool2.append(b"fresh-6") == 6
        spool2.close()
        assert [r.frame for r in read_spool(tmp_path, start_seq=5)] \
            == [b"fresh-6"]

    def test_segment_roll_and_order(self, tmp_path):
        spool = IngressSpool(tmp_path, segment_bytes=4096,
                             fsync_interval_ms=0)
        frames = [os.urandom(256) for _ in range(64)]
        for frame in frames:
            spool.append(frame)
        spool.close()
        assert len(list_segments(tmp_path)) > 1
        assert [r.frame for r in read_spool(tmp_path)] == frames

    def test_retention_never_prunes_unacked(self, tmp_path):
        clock = [1000.0]
        spool = IngressSpool(tmp_path, segment_bytes=4096,
                             fsync_interval_ms=0, retain_bytes=4096,
                             retain_age_s=10.0, clock=lambda: clock[0])
        for i in range(64):
            spool.append(os.urandom(256))
        clock[0] += 100.0                    # everything over the age bound
        spool.tick(force=True)
        # nothing acked -> nothing pruned, both bounds exceeded or not
        assert [r.seq for r in read_spool(tmp_path)] == list(range(1, 65))

        spool.ack(40)
        spool.tick(force=True)
        kept = [r.seq for r in read_spool(tmp_path)]
        # sealed fully-acked head segments pruned; the unacked suffix and
        # the segment containing the watermark survive
        assert kept[0] > 1 and kept[-1] == 64
        assert all(seq in kept for seq in range(41, 65))
        spool.close()

    def test_retention_by_bytes_keeps_under_bound(self, tmp_path):
        spool = IngressSpool(tmp_path, segment_bytes=4096,
                             fsync_interval_ms=0, retain_bytes=8192,
                             retain_age_s=1e9)
        for i in range(64):
            seq = spool.append(os.urandom(256))
            spool.ack(seq)                   # fully acked as we go
            spool.tick(force=True)
        assert spool.spool_bytes() <= 8192 + 4096  # bound + active slack
        assert len(list_segments(tmp_path)) <= 3
        spool.close()

    def test_clean_close_replays_nothing(self, tmp_path):
        spool = IngressSpool(tmp_path, fsync_interval_ms=0)
        for i in range(5):
            spool.ack(spool.append(b"x%d" % i))
        spool.close()
        spool2 = IngressSpool(tmp_path)
        assert spool2.recover_unacked() == []
        spool2.close()


# -- crash injection (real SIGKILL on the commit path) -----------------------

_CRASH_CHILD = r"""
import json, os, sys, time
sys.path.insert(0, {repo!r})
from detectmateservice_tpu.wal import IngressSpool

spool = IngressSpool({wal!r}, segment_bytes=4096,
                     fsync_interval_ms={fsync_ms})
log = open({log!r}, "w", buffering=1)
seq = 0
while True:
    seq = spool.append(b"frame-%06d" % seq)
    # the ack watermark trails; manifest commits ride tick()
    if seq % 5 == 0:
        spool.ack(seq - 3)
    spool.tick()
    log.write("%d\n" % seq)
    if seq == 3:
        print("ready", flush=True)   # parent may kill any time after this
"""


@pytest.mark.parametrize("fsync_ms", [0, 5])
def test_sigkill_recovery_invariants(tmp_path, fsync_ms):
    """Kill a spool writer with SIGKILL mid-commit-path (append/fsync/
    manifest interleaved at full speed) and verify recovery: no torn
    record served, sequences strictly increasing, every recovered frame
    was appended by the child, the persisted-ack prefix never replays,
    and every frame the child appended *and fsynced* beyond the persisted
    watermark replays exactly once (once per crash)."""
    wal = tmp_path / "wal"
    log = tmp_path / "appended.log"
    child = subprocess.Popen(
        [sys.executable, "-c", _CRASH_CHILD.format(
            repo=str(Path(__file__).resolve().parent.parent),
            wal=str(wal), log=str(log), fsync_ms=fsync_ms)],
        stdout=subprocess.PIPE)
    assert child.stdout.readline().strip() == b"ready"
    time.sleep(0.2)                          # let it race all three steps
    os.kill(child.pid, signal.SIGKILL)
    child.wait(timeout=10)

    appended = [int(line) for line in log.read_text().split()]
    assert appended, "child never appended"
    manifest = json.loads((wal / "MANIFEST.json").read_text())
    persisted_ack = manifest["acked_seq"]

    spool = IngressSpool(wal, fsync_interval_ms=0)
    recovered = spool.recover_unacked()
    seqs = [seq for seq, _ in recovered]
    # 1. no torn record: every recovered frame is exactly what was written
    assert all(frame == b"frame-%06d" % (seq - 1)
               for seq, frame in recovered)
    # 2. strictly increasing, no duplicates within one recovery
    assert seqs == sorted(set(seqs))
    # 3. nothing recovered that was never appended (the child logs AFTER
    #    each append returns, so the kill can leave at most one durable
    #    append unlogged — allow that single-record race tail)
    assert not set(seqs) - set(appended) - {max(appended) + 1}
    # 4. the persisted-ack prefix never replays (at-most-once for acks
    #    that reached the manifest)
    assert all(seq > persisted_ack for seq in seqs)
    # 5. continuity: the replayed suffix has no holes from its start to
    #    the last durable record (a hole would be silent loss)
    if seqs:
        assert seqs == list(range(seqs[0], seqs[-1] + 1))
    # the writer continues where durability ended
    nxt = spool.append(b"post-crash")
    assert nxt == (seqs[-1] if seqs else persisted_ack) + 1
    spool.close()


def test_sigkill_between_roll_and_manifest(tmp_path):
    """A crash right after a segment file is created but before any
    manifest names it: the directory scan must still find it."""
    wal = tmp_path / "wal"
    spool = IngressSpool(wal, segment_bytes=4096, fsync_interval_ms=0)
    for i in range(40):
        spool.append(os.urandom(200))
    spool.close()
    # simulate the crash window: delete the manifest entirely — harsher
    # than any mid-roll state, since ALL metadata is gone
    (wal / "MANIFEST.json").unlink()
    spool2 = IngressSpool(wal, fsync_interval_ms=0)
    assert spool2.last_appended_seq == 40
    assert len(spool2.recover_unacked()) == 40   # ack watermark lost -> 0
    spool2.close()


# -- deterministic replay ----------------------------------------------------


class _Reverser:
    def process(self, data):
        return None if data == b"drop-me" else data[::-1]


class _BatchStamp:
    """Batch-capable, with held rows drained at flush — the deferring-
    processor shape the driver must drain before digesting."""

    def __init__(self):
        self.held = []

    def process_batch(self, batch):
        self.held.extend(d.upper() for d in batch)
        out, self.held = self.held[:-1], self.held[-1:]
        return out

    def flush(self):
        out, self.held = self.held, []
        return out


class TestReplayDriver:
    def _record(self, tmp_path, frames):
        spool = IngressSpool(tmp_path, fsync_interval_ms=0)
        for frame in frames:
            spool.append(frame)
        spool.close()

    def test_two_replays_byte_identical(self, tmp_path):
        ctx = TraceContext(0xDEADBEEF, 123456789,
                           [Hop("loadgen", 1, 2)])
        frames = [
            b"plain-single",
            pack_batch([b"one", b"two", b"drop-me", b"three"]),
            wrap_trace(pack_batch([b"traced-a", b"traced-b"]), ctx),
            wrap_trace(b"traced-single", TraceContext(7, 99)),
        ]
        self._record(tmp_path, frames)
        outs1 = []
        r1 = ReplayDriver(tmp_path, _Reverser(),
                          deliver=outs1.append).run()
        outs2 = []
        r2 = ReplayDriver(tmp_path, _Reverser(),
                          deliver=outs2.append).run()
        assert r1["output_digest"] == r2["output_digest"]
        assert outs1 == outs2                 # byte-identical wire frames
        assert r1["frames"] == 4 and r1["messages"] == 8
        assert r1["outputs"] == 7             # drop-me filtered
        # original trace context preserved verbatim on delivered frames
        assert any(o.startswith(b"\xd7DM\x02") for o in outs1)

    def test_digest_sensitive_to_spool_change(self, tmp_path):
        self._record(tmp_path, [b"aa", b"bb"])
        base = ReplayDriver(tmp_path, _Reverser()).run()["output_digest"]
        spool = IngressSpool(tmp_path)
        spool.append(b"cc")
        spool.close()
        assert ReplayDriver(tmp_path, _Reverser()).run()["output_digest"] \
            != base

    def test_start_seq_and_limit(self, tmp_path):
        self._record(tmp_path, [b"f%d" % i for i in range(10)])
        result = ReplayDriver(tmp_path, _Reverser()).run(start_seq=3,
                                                         limit=4)
        assert (result["first_seq"], result["last_seq"]) == (4, 7)
        assert result["frames"] == 4

    def test_deferring_processor_drained(self, tmp_path):
        self._record(tmp_path, [pack_batch([b"a", b"b"]),
                                pack_batch([b"c", b"d"])])
        r1 = ReplayDriver(tmp_path, _BatchStamp()).run()
        r2 = ReplayDriver(tmp_path, _BatchStamp()).run()
        assert r1["outputs"] == 4             # flush drained the held row
        assert r1["output_digest"] == r2["output_digest"]

    def test_passthrough_without_processor(self, tmp_path):
        self._record(tmp_path, [b"x", b"y"])
        result = ReplayDriver(tmp_path, None).run()
        assert result["outputs"] == 2


# -- engine integration ------------------------------------------------------


class _EchoProcessor:
    def process(self, data):
        return data


def _durable_settings(tmp_path, tag, **kw):
    from detectmateservice_tpu.settings import ServiceSettings

    return ServiceSettings(
        component_type="core", component_id=f"wal-{tag}",
        engine_addr=f"inproc://wal-{tag}-in",
        out_addr=[f"inproc://wal-{tag}-out"],
        durable_ingress=True, wal_dir=str(tmp_path / "wal"),
        wal_fsync_interval_ms=0, engine_recv_timeout=20,
        log_to_file=False, log_to_console=False, **kw)


class TestEngineDurableIngress:
    def _boot(self, tmp_path, tag, **kw):
        from detectmateservice_tpu.engine import Engine
        from detectmateservice_tpu.engine.socket import (
            InprocQueueSocketFactory,
        )

        factory = InprocQueueSocketFactory(maxsize=4096)
        settings = _durable_settings(tmp_path, tag, **kw)
        engine = Engine(settings, _EchoProcessor(), socket_factory=factory)
        sink = factory.create(f"inproc://wal-{tag}-out")
        sink.recv_timeout = 50
        sender = factory.create_output(f"inproc://wal-{tag}-in")
        return engine, sender, sink

    @staticmethod
    def _drain(sink):
        out = []
        try:
            while True:
                out.append(sink.recv())
        except Exception:
            return out

    def test_settings_require_wal_dir(self):
        from pydantic import ValidationError

        from detectmateservice_tpu.settings import ServiceSettings

        with pytest.raises(ValidationError, match="wal_dir"):
            ServiceSettings(component_type="core", durable_ingress=True)

    def test_durable_off_has_no_spool(self, tmp_path):
        from detectmateservice_tpu.engine import Engine
        from detectmateservice_tpu.engine.socket import (
            InprocQueueSocketFactory,
        )
        from detectmateservice_tpu.settings import ServiceSettings

        engine = Engine(
            ServiceSettings(component_type="core",
                            engine_addr="inproc://wal-off-in",
                            log_to_file=False, log_to_console=False),
            _EchoProcessor(),
            socket_factory=InprocQueueSocketFactory(maxsize=16))
        assert engine._spool is None
        engine.stop()

    def test_append_ack_and_clean_restart(self, tmp_path):
        engine, sender, sink = self._boot(tmp_path, "clean")
        engine.start()
        for i in range(8):
            sender.send(b"m%d" % i)
        wait_until(lambda: len(self._drain(sink)) >= 0 and
                   engine._spool.last_appended_seq >= 8, timeout=5)
        # acks advance at the next iteration once results are out
        wait_until(lambda: engine._spool.depth_frames() == 0, timeout=5)
        engine.stop()
        # clean stop committed the watermark: a restart replays nothing
        engine2, _, sink2 = self._boot(tmp_path, "clean2")
        engine2.start()
        time.sleep(0.3)
        assert self._drain(sink2) == []
        assert engine2._spool.acked_seq == engine2._spool.last_appended_seq
        engine2.stop()

    def test_crash_recovery_zero_unique_loss(self, tmp_path):
        engine, sender, sink = self._boot(tmp_path, "crash")
        engine.start()
        for i in range(10):
            sender.send(b"pre-%02d" % i)
        wait_until(lambda: engine._spool.depth_frames() == 0, timeout=5)
        delivered = self._drain(sink)
        # bank frames and kill the engine before it can send their results
        for i in range(10, 30):
            sender.send(b"post-%02d" % i)
        engine.crash_abort()
        assert not engine.running
        depth_at_crash = engine._spool.depth_frames()

        engine.start()                        # the "restarted process"
        wait_until(lambda: engine._spool.depth_frames() == 0, timeout=10)
        delivered += self._drain(sink)
        uniq = set(delivered)
        expect = {b"pre-%02d" % i for i in range(10)} \
            | {b"post-%02d" % i for i in range(10, 30)}
        assert expect <= uniq, f"lost: {sorted(expect - uniq)}"
        # at-least-once: duplicates allowed, bounded by one replay
        assert len(delivered) <= len(expect) + max(1, int(depth_at_crash))
        assert engine._m_wal_recovered._value.get() >= 0
        engine.stop()

    def test_crash_mid_process_replays_inflight(self, tmp_path):
        """The frame the processor held when the crash hit is exactly what
        recovery must re-drive (the router-memory window the WAL closes)."""
        from detectmateservice_tpu.engine import Engine
        from detectmateservice_tpu.engine.socket import (
            InprocQueueSocketFactory,
        )
        import threading

        factory = InprocQueueSocketFactory(maxsize=256)
        settings = _durable_settings(tmp_path, "wedge")
        gate = threading.Event()
        entered = threading.Event()

        class Wedging:
            def __init__(self):
                self.calls = 0

            def process(self, data):
                self.calls += 1
                if self.calls == 1:
                    entered.set()
                    gate.wait(timeout=10)
                    raise RuntimeError("crashed mid-process")
                return data

        proc = Wedging()
        engine = Engine(settings, proc, socket_factory=factory)
        sink = factory.create("inproc://wal-wedge-out")
        sink.recv_timeout = 50
        sender = factory.create_output("inproc://wal-wedge-in")
        engine.start()
        sender.send(b"the-inflight-frame")
        assert entered.wait(timeout=5)
        # frame is appended (durable) but wedged inside process()
        assert engine._spool.depth_frames() >= 1
        killer = threading.Thread(target=engine.crash_abort)
        killer.start()
        gate.set()
        killer.join(timeout=5)
        assert self._drain(sink) == []        # nothing ever left

        engine.start()
        wait_until(lambda: engine._spool.depth_frames() == 0, timeout=5)
        assert self._drain(sink) == [b"the-inflight-frame"]
        engine.stop()

    def test_shadow_replay_offline_canary(self, tmp_path):
        """The offline dmroll canary: score a recorded detector-ingress
        spool through live AND candidate params. Identical params must
        report zero divergence (and a byte-stable gate verdict); a scaled
        candidate must diverge, with the worst rows keyed by spool seq."""
        import jax
        from test_rollout import make_detector, msg

        from detectmateservice_tpu.rollout import CheckpointStore
        from detectmateservice_tpu.wal.replay import shadow_replay

        det = make_detector()
        frames = [pack_batch([msg(1000 + 8 * f + i) for i in range(8)])
                  for f in range(4)]
        spool = IngressSpool(tmp_path / "wal", fsync_interval_ms=0)
        for frame in frames:
            spool.append(frame)
        spool.close()

        # identical candidate through the versioned store: zero divergence
        store = CheckpointStore(tmp_path / "store")
        version = store.allocate_version()
        det.save_params_checkpoint(str(store.version_dir(version)),
                                   det._exec.params, det._exec.opt_state)
        store.record(version, {"model": "mlp"})
        report = shadow_replay(tmp_path / "wal", det,
                               store_dir=str(tmp_path / "store"))
        assert report["candidate_version"] == version
        assert report["rows_scored"] == 32
        assert report["mean_abs_delta"] == 0.0
        assert report["verdict"] == "promote"

        # a scaled candidate diverges; worst offenders carry spool seqs
        broken = jax.tree_util.tree_map(lambda a: a * 10.0, det._exec.params)
        report2 = shadow_replay(tmp_path / "wal", det, params=broken,
                                max_mean_delta=1e-6, track_top=4)
        assert report2["mean_abs_delta"] > 0.0
        assert report2["verdict"] == "hold"
        tops = report2["top_divergent"]
        assert len(tops) == 4
        assert all(1 <= t["row_id"] <= 4 for t in tops)
        det.teardown()

    def test_recorded_frames_preserve_trace_bytes(self, tmp_path):
        """The spool records the exact wire bytes — v2 trace header and
        all — so replay re-drives the original trace ids and ingest
        stamps, not reconstructed ones."""
        ctx = TraceContext(0xABCD, 777)
        engine, sender, _sink = self._boot(tmp_path, "trace")
        engine.start()
        wire = wrap_trace(b"payload", ctx)
        sender.send(wire)
        wait_until(lambda: engine._spool.last_appended_seq == 1, timeout=5)
        engine.stop()
        assert [r.frame for r in read_spool(tmp_path / "wal")] == [wire]
