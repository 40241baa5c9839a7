"""In-tree component-library tests: parser, detectors, readers, doubles."""
import json

import pytest

from detectmateservice_tpu.library.common.core import CoreConfig, LibraryError
from detectmateservice_tpu.library.common.detector import CoreDetector, CoreDetectorConfig
from detectmateservice_tpu.library.detectors import (
    NewValueComboDetector,
    NewValueDetector,
    RandomDetector,
)
from detectmateservice_tpu.library.helper import From
from detectmateservice_tpu.library.parsers import MatcherParser
from detectmateservice_tpu.library.readers import LogFileReader
from detectmateservice_tpu.library.testing import DummyDetector, DummyParser
from detectmateservice_tpu.schemas import DetectorSchema, LogSchema, ParserSchema

NGINX_FORMAT = '<IP> - - [<Time>] "<Method> <URL> <Protocol>" <Status> <Bytes> "<Referer>" "<UserAgent>"'


def nginx_line(url="/hello", ip="::1"):
    return f'{ip} - - [18/Mar/2026:11:43:30 +0000] "GET {url} HTTP/1.1" 404 615 "-" "curl/8.5.0"'


def parser_config(templates_path=None, **params):
    base = {"remove_spaces": False, "remove_punctuation": False, "lowercase": False}
    base.update(params)
    base["path_templates"] = str(templates_path) if templates_path else None
    return {"parsers": {"MatcherParser": {
        "method_type": "matcher_parser", "auto_config": False,
        "log_format": NGINX_FORMAT, "time_format": None, "params": base,
    }}}


class TestMatcherParser:
    def test_header_variable_extraction(self):
        parser = MatcherParser(config=parser_config())
        out = parser.process(LogSchema(logID="1", log=nginx_line("/x")).serialize())
        ps = ParserSchema.from_bytes(out)
        hv = dict(ps.logFormatVariables)
        assert hv["URL"] == "/x"
        assert hv["Method"] == "GET"
        assert hv["Status"] == "404"
        assert ps.logID == "1"

    def test_log_field_quirk_preserved(self):
        # the reference's MatcherParser writes its own name into `log`
        # (pinned by test_pipe_filereader_matcher_nvd.py:158-160)
        parser = MatcherParser(config=parser_config())
        ps = ParserSchema.from_bytes(
            parser.process(LogSchema(log=nginx_line()).serialize())
        )
        assert ps.log == "MatcherParser"

    def test_template_matching(self, tmp_path):
        templates = tmp_path / "templates.txt"
        templates.write_text("user <*> logged in from <*>\nquery failed: <*>\n")
        config = {"parsers": {"MatcherParser": {
            "method_type": "matcher_parser", "auto_config": False,
            "log_format": None, "time_format": None,
            "params": {"lowercase": True, "remove_spaces": False,
                       "remove_punctuation": False, "path_templates": str(templates)},
        }}}
        parser = MatcherParser(config=config)
        event_id, template, variables = parser.match_templates("User john logged in from 1.2.3.4")
        assert event_id == 1
        assert variables == ["john", "1.2.3.4"]
        event_id2, _, vars2 = parser.match_templates("Query failed: timeout")
        assert event_id2 == 2
        assert vars2 == ["timeout"]
        assert parser.match_templates("no such line")[0] == -1

    def test_empty_line_filtered(self):
        parser = MatcherParser(config=parser_config())
        assert parser.process(LogSchema(log="").serialize()) is None

    def test_method_type_mismatch_rejected(self):
        bad = {"parsers": {"MatcherParser": {"method_type": "wrong_parser",
                                             "auto_config": True}}}
        with pytest.raises(Exception):
            MatcherParser(config=bad)

    def test_process_batch_matches_process(self, tmp_path):
        """The pb2-direct batched hot path must be field-equivalent to the
        single-message wrapper path (only parsedLogID and timestamps may
        legitimately differ)."""
        templates = tmp_path / "templates.txt"
        templates.write_text("user <*> logged in from <*>\n")
        config = {"parsers": {"MatcherParser": {
            "method_type": "matcher_parser", "auto_config": False,
            "log_format": "<IP> - <Content>", "time_format": None,
            "params": {"lowercase": True, "path_templates": str(templates)},
        }}}
        parser = MatcherParser(config=config)
        raws = [
            LogSchema(logID=str(i),
                      log=f"10.0.0.{i} - User u{i} logged in from 1.2.3.{i}"
                      ).serialize()
            for i in range(5)
        ] + [LogSchema(log="").serialize(),           # filtered
             LogSchema(logID="x", log="unmatchable").serialize()]
        batched = parser.process_batch(raws)
        singles = [parser.process(r) for r in raws]
        assert len(batched) == len(singles)
        for got, want in zip(batched, singles):
            assert (got is None) == (want is None)
            if got is None:
                continue
            a = ParserSchema.from_bytes(got)
            b = ParserSchema.from_bytes(want)
            for field in ("parserType", "parserID", "EventID", "template",
                          "variables", "logID", "log"):
                assert str(a.get(field)) == str(b.get(field)), field
            # a protobuf map has no order: upb iterates it by hash, seeded
            # per process, and where two keys collide by the order they
            # arrived on the wire — compare it as the mapping it is
            assert (dict(a.get("logFormatVariables"))
                    == dict(b.get("logFormatVariables")))
            assert len(a["parsedLogID"]) == 32  # 16-byte hex unique id

    def test_wildcard_free_template_requires_whole_line(self, tmp_path):
        """A constant template must match the WHOLE line, not a prefix —
        'connection closed' must not claim 'connection closed by 1.2.3.4'
        (that belongs to the wildcard template after it). Pins native and
        pure-Python agreement."""
        templates = tmp_path / "templates.txt"
        templates.write_text("connection closed\nconnection closed by <*>\n")
        config = {"parsers": {"MatcherParser": {
            "method_type": "matcher_parser", "auto_config": False,
            "log_format": None, "time_format": None,
            "params": {"lowercase": True, "path_templates": str(templates)},
        }}}
        parser = MatcherParser(config=config)
        assert parser.match_templates("connection closed") == (
            1, "connection closed", [])
        eid, _, variables = parser.match_templates("connection closed by 1.2.3.4")
        assert (eid, variables) == (2, ["1.2.3.4"])
        # pure-Python fallback agrees
        parser._native = None
        assert parser.match_templates("connection closed")[0] == 1
        eid2, _, vars2 = parser.match_templates("connection closed by 1.2.3.4")
        assert (eid2, vars2) == (2, ["1.2.3.4"])

    def test_nvd_process_batch_matches_process(self):
        """NewValueDetector's pb2-direct batched path must produce exactly
        the alerts (and Nones) the single-message wrapper path does —
        including training-phase filtering, event+global scopes, header and
        positional variables."""
        def mk():
            return NewValueDetector(config={"detectors": {"NewValueDetector": {
                "method_type": "new_value_detector", "auto_config": False,
                "data_use_training": 6,
                "events": {1: {"inst": {"variables": [{"pos": 0}]}}},
                "global": {"g": {"variables": [{"pos": 1}],
                                 "header_variables": [{"pos": "Host"}]}},
            }}})

        def pmsg(u, ip, host, log_id):
            return ParserSchema(
                EventID=1, template="user <*> from <*>", variables=[u, ip],
                logID=log_id,
                logFormatVariables={"Time": "1700000000", "Host": host},
            ).serialize()

        stream = [pmsg(f"u{i % 3}", f"ip{i % 2}", f"h{i % 2}", str(i))
                  for i in range(8)]
        stream.append(pmsg("mallory", "ip-evil", "h0", "evil"))
        stream.append(pmsg("u0", "ip0", "h0", "benign"))
        singles = [mk().process(m) for m in []]  # silence lints
        a, b = mk(), mk()
        singles = [a.process(m) for m in stream]
        batched = b.process_batch(stream)
        assert [o is None for o in singles] == [o is None for o in batched]
        for x, y in zip(singles, batched):
            if x is None:
                continue
            da, db = DetectorSchema.from_bytes(x), DetectorSchema.from_bytes(y)
            for field in ("detectorID", "detectorType", "logIDs", "score",
                          "description", "alertsObtain"):
                assert str(da.get(field)) == str(db.get(field)), field
            assert list(da["extractedTimestamps"]) == list(db["extractedTimestamps"])

    def test_mktime_overflow_contained(self, monkeypatch):
        """time.mktime can raise OverflowError/OSError on out-of-range years
        on some platforms (advisor round-2 low finding): the line must keep
        its raw Time and parse, and one bad line must not abort the batch.
        This platform's glibc mktime accepts year 1, so the failure is
        injected."""
        import time as _time

        import detectmateservice_tpu.library.parsers.template_matcher as tm

        config = {"parsers": {"MatcherParser": {
            "method_type": "matcher_parser", "auto_config": False,
            "log_format": "<Time> <Content>", "time_format": "%Y",
            "params": {"lowercase": False, "remove_spaces": False,
                       "remove_punctuation": False, "path_templates": None},
        }}}
        parser = MatcherParser(config=config)

        real_mktime = _time.mktime

        def exploding_mktime(t):
            if t.tm_year == 1234:
                raise OverflowError("mktime argument out of range")
            return real_mktime(t)

        monkeypatch.setattr(tm.time, "mktime", exploding_mktime)
        out = parser.process(LogSchema(logID="1", log="1234 boom").serialize())
        assert out is not None
        assert dict(ParserSchema.from_bytes(out).logFormatVariables)["Time"] == "1234"
        outs = parser.process_batch([
            LogSchema(logID="1", log="1234 boom").serialize(),
            LogSchema(logID="2", log="2026 fine").serialize(),
        ])
        assert outs[0] is not None and outs[1] is not None
        assert dict(ParserSchema.from_bytes(outs[1]).logFormatVariables)["Time"] != "2026"

    def test_process_batch_counts_decode_errors(self):
        """Corrupt frames in a batch are dropped VISIBLY: error counter +
        log, matching the single-message path's LibraryError handling."""
        from detectmateservice_tpu.engine import metrics as m

        parser = MatcherParser(config=parser_config())
        counter = m.PROCESSING_ERRORS().labels(
            component_type=parser.config.method_type, component_id=parser.name)
        before = counter._value.get()
        outs = parser.process_batch([
            b"\xff\xff not protobuf",
            LogSchema(logID="1", log=nginx_line("/ok")).serialize(),
        ])
        assert outs[0] is None and outs[1] is not None
        assert counter._value.get() == before + 1


def nvd_config(training=2, alert_once=False):
    return {"detectors": {"NewValueDetector": {
        "method_type": "new_value_detector", "data_use_training": training,
        "auto_config": False, "alert_once": alert_once,
        "global": {"global_instance": {"header_variables": [{"pos": "URL"}]}},
    }}}


def parsed(url, log_id="1"):
    return ParserSchema(
        EventID=1, logID=log_id, logFormatVariables={"URL": url, "Time": "1700000000"},
    ).serialize()


class TestNewValueDetector:
    def test_train_then_detect(self):
        det = NewValueDetector(config=nvd_config(training=2))
        assert det.process(parsed("/a")) is None   # training
        assert det.process(parsed("/b")) is None   # training
        assert det.process(parsed("/a")) is None   # known value
        out = det.process(parsed("/evil"))
        alert = DetectorSchema.from_bytes(out)
        assert dict(alert.alertsObtain) == {"Global - URL": "Unknown value: '/evil'"}
        assert alert.score == pytest.approx(1.0)
        assert alert.detectorID == "NewValueDetector"
        assert alert.detectorType == "new_value_detector"
        assert list(alert.logIDs) == ["1"]
        assert list(alert.extractedTimestamps) == [1700000000]

    def test_alert_every_occurrence_by_default(self):
        det = NewValueDetector(config=nvd_config(training=1))
        det.process(parsed("/a"))
        assert det.process(parsed("/evil")) is not None
        assert det.process(parsed("/evil")) is not None

    def test_alert_once(self):
        det = NewValueDetector(config=nvd_config(training=1, alert_once=True))
        det.process(parsed("/a"))
        assert det.process(parsed("/evil")) is not None
        assert det.process(parsed("/evil")) is None

    def test_event_scoped_variables(self):
        config = {"detectors": {"NewValueDetector": {
            "method_type": "new_value_detector", "data_use_training": 1,
            "auto_config": False,
            "events": {1: {"inst": {"variables": [{"pos": 0, "name": "user"}]}}},
        }}}
        det = NewValueDetector(config=config)
        msg = lambda user: ParserSchema(EventID=1, variables=[user]).serialize()
        assert det.process(msg("alice")) is None  # training
        assert det.process(msg("alice")) is None
        alert = DetectorSchema.from_bytes(det.process(msg("mallory")))
        assert dict(alert.alertsObtain) == {"Event 1 - user": "Unknown value: 'mallory'"}

    def test_state_roundtrip(self):
        det = NewValueDetector(config=nvd_config(training=1))
        det.process(parsed("/a"))
        state = det.state_dict()
        det2 = NewValueDetector(config=nvd_config(training=1))
        det2.load_state_dict(state)
        assert det2.process(parsed("/a")) is None       # knows /a, no training
        assert det2.process(parsed("/new")) is not None

    def test_empty_config_never_alerts(self):
        det = NewValueDetector()
        assert det.process(parsed("/anything")) is None

    def test_overflow_time_degrades_to_now(self):
        """Attacker-controllable Time='1e400' (float inf → OverflowError on
        int()) must degrade to now, not escape as an exception (advisor
        round-2 medium finding)."""
        det = NewValueDetector(config=nvd_config(training=1))
        det.process(parsed("/a"))
        for poison in ("1e400", "inf", "-inf", "nan"):
            raw = ParserSchema(
                EventID=1, logID="p",
                logFormatVariables={"URL": "/evil-" + poison, "Time": poison},
            ).serialize()
            out = det.process(raw)
            assert out is not None, poison
            alert = DetectorSchema.from_bytes(out)
            assert alert.extractedTimestamps[0] > 1_500_000_000  # ≈ now

    def test_poisoned_message_does_not_sink_batch(self):
        """One poisoned message in a micro-batch costs one message, never the
        chunk: the healthy alert in the same batch still comes out."""
        det = NewValueDetector(config=nvd_config(training=1))
        det.process(parsed("/a"))
        poison = ParserSchema(
            EventID=1, logFormatVariables={"URL": "/evil1", "Time": "1e400"},
        ).serialize()
        healthy = ParserSchema(
            EventID=1, logID="h",
            logFormatVariables={"URL": "/evil2", "Time": "1700000000"},
        ).serialize()
        outs = det.process_batch([poison, healthy])
        assert len(outs) == 2
        assert outs[0] is not None  # overflow degraded to now, alert kept
        assert outs[1] is not None
        alert = DetectorSchema.from_bytes(outs[1])
        assert list(alert.logIDs) == ["h"]

    def test_extract_timestamp_overflow_returns_none(self):
        from detectmateservice_tpu.library.common.detector import CoreDetector

        assert CoreDetector.extract_timestamp(
            ParserSchema(logFormatVariables={"Time": "1e400"})) is None
        assert CoreDetector.extract_timestamp(
            ParserSchema(logFormatVariables={"Time": "inf"})) is None


class TestNewValueComboDetector:
    def test_combo_detection(self):
        config = {"detectors": {"NewValueComboDetector": {
            "method_type": "new_value_combo_detector", "data_use_training": 1,
            "auto_config": False,
            "global": {"combo": {"header_variables": [{"pos": "URL"}, {"pos": "Method"}]}},
        }}}
        det = NewValueComboDetector(config=config)
        msg = lambda url, method: ParserSchema(
            EventID=1, logFormatVariables={"URL": url, "Method": method}
        ).serialize()
        assert det.process(msg("/a", "GET")) is None     # training
        assert det.process(msg("/a", "GET")) is None     # known combo
        assert det.process(msg("/a", "POST")) is not None  # new combination


class TestRandomDetector:
    def test_threshold_zero_always_detects(self):
        config = {"detectors": {"RandomDetector": {
            "method_type": "random_detector", "auto_config": False,
            "events": {1: {"test": {"variables": [
                {"pos": 0, "name": "var1", "params": {"threshold": -0.1}}]}}},
        }}}
        det = RandomDetector(config=config)
        out = det.process(ParserSchema(EventID=1, variables=["x"]).serialize())
        assert out is not None

    def test_threshold_one_never_detects(self):
        config = {"detectors": {"RandomDetector": {
            "method_type": "random_detector", "auto_config": False,
            "events": {1: {"test": {"variables": [
                {"pos": 0, "name": "var1", "params": {"threshold": 1.1}}]}}},
        }}}
        det = RandomDetector(config=config)
        assert det.process(ParserSchema(EventID=1, variables=["x"]).serialize()) is None


class TestDoubles:
    def test_dummy_parser_fixed_output(self):
        parser = DummyParser()
        out = ParserSchema.from_bytes(parser.process(LogSchema(logID="9", log="x").serialize()))
        assert out.template == "User <*> logged in from <*>"
        assert list(out.variables) == ["john", "192.168.1.100"]
        assert out.logID == "9"

    def test_dummy_detector_false_true_false(self):
        det = DummyDetector()
        results = [det.process(parsed(f"/{i}")) for i in range(6)]
        pattern = [r is not None for r in results]
        assert pattern == [False, True, False, False, True, False]


class TestReaderAndFrom:
    def test_log_file_reader_process(self):
        reader = LogFileReader()
        out = LogSchema.from_bytes(reader.process(b"a log line\n"))
        assert out.log == "a log line"
        assert out.logID

    def test_log_file_reader_read(self, tmp_path):
        f = tmp_path / "x.log"
        f.write_text("one\n\ntwo\n")
        reader = LogFileReader(config={"readers": {"LogFileReader": {
            "method_type": "log_file", "auto_config": False, "path": str(f)}}})
        logs = list(reader.read())
        assert [l.log for l in logs] == ["one", "two"]

    def test_from_log_yields_schemas_and_nones(self, tmp_path):
        f = tmp_path / "x.log"
        f.write_text("alpha\n\nbeta\n")
        parser = MatcherParser(config=parser_config())
        items = list(From.log(parser, f, do_process=True))
        assert items[1] is None
        kept = [i for i in items if i is not None]
        assert [i.log for i in kept] == ["alpha", "beta"]
        assert all(hasattr(i, "logID") for i in kept)


class TestFixedBufferMode:
    """BufferMode.FIXED: windowed detection (one alert per filled window,
    logIDs cover the window; a partial window drains at stop)."""

    def _nvd_fixed(self, window=3, training=2):
        from detectmateservice_tpu.library.utils import BufferMode

        cfg = nvd_config(training=training)
        cfg["detectors"]["NewValueDetector"]["buffer_size"] = window
        return NewValueDetector(config=cfg, buffer_mode=BufferMode.FIXED)

    def test_window_fills_then_one_alert_with_all_log_ids(self):
        det = self._nvd_fixed(window=3)
        assert det.process(parsed("/a", "1")) is None  # training
        assert det.process(parsed("/b", "2")) is None  # training
        assert det.process(parsed("/a", "3")) is None  # window 1/3
        assert det.process(parsed("/evil", "4")) is None  # window 2/3
        out = det.process(parsed("/b", "5"))  # window full -> detect
        alert = DetectorSchema.from_bytes(out)
        assert list(alert.logIDs) == ["3", "4", "5"]
        assert "'/evil'" in json.dumps(dict(alert.alertsObtain))

    def test_clean_window_produces_no_output(self):
        det = self._nvd_fixed(window=2)
        det.process(parsed("/a", "1"))
        det.process(parsed("/b", "2"))
        assert det.process(parsed("/a", "3")) is None
        assert det.process(parsed("/b", "4")) is None  # full, but all known

    def test_flush_final_drains_partial_window(self):
        det = self._nvd_fixed(window=8)
        det.process(parsed("/a", "1"))
        det.process(parsed("/b", "2"))
        assert det.process(parsed("/evil", "9")) is None  # buffered (1/8)
        out = [o for o in det.flush_final() if o is not None]
        assert len(out) == 1
        assert list(DetectorSchema.from_bytes(out[0]).logIDs) == ["9"]

    def test_runtime_buffer_size_reconfigure_rebuilds_window(self):
        det = self._nvd_fixed(window=8)
        det.process(parsed("/a", "1"))
        det.process(parsed("/b", "2"))
        assert det.process(parsed("/evil", "3")) is None  # buffered 1/8
        cfg = nvd_config(training=2)
        cfg["detectors"]["NewValueDetector"]["buffer_mode"] = "fixed"
        cfg["detectors"]["NewValueDetector"]["buffer_size"] = 2
        det.reconfigure(cfg)
        # the buffered anomaly completed a window during the resize: its
        # alert surfaces via the engine idle hook, nothing is lost
        pending = [o for o in det.flush() if o is not None]
        carried = ([list(DetectorSchema.from_bytes(o).logIDs) for o in pending]
                   if pending else [])
        if not any("3" in ids for ids in carried):
            out = det.process(parsed("/a", "4"))
            assert out is not None
            assert "3" in list(DetectorSchema.from_bytes(out).logIDs)

    def test_buffer_shrink_loses_no_buffered_message(self):
        det = self._nvd_fixed(window=8)
        det.process(parsed("/a", "1"))
        det.process(parsed("/b", "2"))
        for i in range(5):  # 5 buffered incl. one anomaly
            assert det.process(parsed("/evil" if i == 2 else "/a",
                                      str(10 + i))) is None
        cfg = nvd_config(training=2)
        cfg["detectors"]["NewValueDetector"]["buffer_size"] = 2
        det.reconfigure(cfg)
        outs = [o for o in det.flush() + det.flush_final() if o is not None]
        ids = [i for o in outs for i in DetectorSchema.from_bytes(o).logIDs]
        assert "12" in ids  # the buffered anomaly was detected, not dropped

    def test_buffer_mode_selected_from_yaml_config(self):
        # the service loader only passes config — FIXED must be reachable
        # from the YAML document alone
        from detectmateservice_tpu.library.utils import BufferMode

        cfg = nvd_config(training=0)
        cfg["detectors"]["NewValueDetector"]["buffer_mode"] = "fixed"
        cfg["detectors"]["NewValueDetector"]["buffer_size"] = 3
        det = NewValueDetector(config=cfg)  # loader-style: config only
        assert det.buffer_mode == BufferMode.FIXED
        assert det._buffer is not None

    def test_unknown_buffer_mode_rejected(self):
        cfg = nvd_config(training=0)
        cfg["detectors"]["NewValueDetector"]["buffer_mode"] = "bogus"
        with pytest.raises(LibraryError, match="buffer_mode"):
            NewValueDetector(config=cfg)

    def test_buffer_mode_change_vetoed_at_runtime(self):
        det = self._nvd_fixed(window=4)
        cfg = nvd_config(training=2)
        cfg["detectors"]["NewValueDetector"]["buffer_mode"] = "no_buf"
        with pytest.raises(LibraryError, match="buffer_mode cannot change"):
            det.reconfigure(cfg)


class TestReconfigureRollback:
    def test_parser_keeps_old_state_when_new_config_is_broken(self, tmp_path):
        good = tmp_path / "good.txt"
        good.write_text("user <*> did <*>\n")
        cfg = {"parsers": {"MatcherParser": {
            "method_type": "matcher_parser", "auto_config": False,
            "params": {"path_templates": str(good)}}}}
        parser = MatcherParser(config=cfg)
        assert parser.parse_line("user alice did ls", "1") is not None

        bad = dict(cfg["parsers"]["MatcherParser"])
        bad["params"] = {"path_templates": str(tmp_path / "missing.txt")}
        with pytest.raises(LibraryError, match="templates file"):
            parser.reconfigure({"parsers": {"MatcherParser": bad}})
        # the failed reconfigure left the live parser fully functional
        assert parser.parse_line("user bob did cat", "2") is not None


class TestCoreDetectorContract:
    def test_subclass_must_implement_detect(self):
        class Incomplete(CoreDetector):
            pass

        det = Incomplete(config=None)
        with pytest.raises(NotImplementedError):
            det.process(parsed("/x"))

    def test_bad_bytes_raise_library_error(self):
        det = NewValueDetector()
        with pytest.raises(LibraryError):
            det.process(b"\xff\xfe garbage")

    def test_alert_ids_increment_from_start_id(self):
        config = {"detectors": {"DummyDetector": {
            "method_type": "dummy_detector", "auto_config": False,
            "start_id": 10, "pattern": [True],
        }}}
        det = DummyDetector(config=config)
        a1 = DetectorSchema.from_bytes(det.process(parsed("/a")))
        a2 = DetectorSchema.from_bytes(det.process(parsed("/b")))
        assert (a1.alertID, a2.alertID) == ("10", "11")
