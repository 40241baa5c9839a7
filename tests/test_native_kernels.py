"""Native kernel parity tests: the C featurizer/tokenizer/template-matcher
must agree exactly with the pure-Python implementations."""
import random

import numpy as np
import pytest

matchkern = pytest.importorskip("detectmateservice_tpu.utils.matchkern")

from detectmateservice_tpu.models.tokenizer import HashTokenizer
from detectmateservice_tpu.schemas import ParserSchema


class TestFeatureVersion:
    """The checked-in binaries must report the feature version the bindings
    expect — a stale .so fails loudly at import instead of silently running
    without the newer kernels (the bindings enforce it; these tests pin the
    contract end to end, including the C-source default build.sh falls back
    to when it cannot extract the stamp)."""

    def test_kernel_library_reports_expected_version(self):
        assert matchkern.lib_feature_version() == matchkern.DM_FEATURE_VERSION

    def test_c_source_default_matches_bindings(self):
        src = matchkern._SRC_PATH.read_text()
        assert (f"#define DM_FEATURE_VERSION {matchkern.DM_FEATURE_VERSION}"
                in src), "bump dmkern.c's default in lockstep with matchkern.py"

    def test_transport_library_reports_expected_version(self):
        nt = pytest.importorskip(
            "detectmateservice_tpu.engine.native_transport")
        assert nt._lib_feature_version(nt._lib) == nt.DMT_FEATURE_VERSION
        src = nt._SRC_PATH.read_text()
        assert (f"#define DMT_FEATURE_VERSION {nt.DMT_FEATURE_VERSION}"
                in src), "bump dmtransport.cpp's default in lockstep"

    def test_version_mismatch_raises_import_error(self, monkeypatch):
        # doctor the expectation: the on-disk library now looks stale, and
        # with the rebuild neutered the loader must refuse it loudly
        monkeypatch.setattr(matchkern, "DM_FEATURE_VERSION",
                            matchkern.DM_FEATURE_VERSION + 1)
        monkeypatch.setattr(matchkern, "_rebuild", lambda: None)
        with pytest.raises(ImportError, match="stale native kernel"):
            matchkern._load()

    def test_staleness_is_the_feature_version_not_file_times(
            self, monkeypatch, tmp_path):
        """One staleness rule: a missing library is built from native/; one
        reporting the expected feature version loads as it is however old
        its file looks (file times are arbitrary on a fresh checkout); one
        reporting another version is rebuilt with the expected number
        stamped in."""
        import os

        rebuilds = []
        real_rebuild = matchkern._rebuild
        monkeypatch.setattr(matchkern, "_LIB_PATH", tmp_path / "libdmkern.so")
        monkeypatch.setattr(matchkern, "_rebuild",
                            lambda: rebuilds.append(1) or real_rebuild())
        first = matchkern._load()                      # missing → built
        assert rebuilds == [1]
        os.utime(matchkern._LIB_PATH, (1, 1))          # "older than its source"
        second = matchkern._load()
        assert rebuilds == [1], "a version-current library was rebuilt"
        for lib in (first, second):
            assert (matchkern._lib_feature_version(lib)
                    == matchkern.DM_FEATURE_VERSION)
            matchkern._close(lib)
        monkeypatch.setattr(matchkern, "DM_FEATURE_VERSION",
                            matchkern.DM_FEATURE_VERSION + 1)
        third = matchkern._load()                      # stale → rebuilt
        assert rebuilds == [1, 1]
        assert (matchkern._lib_feature_version(third)
                == matchkern.DM_FEATURE_VERSION)
        matchkern._close(third)

    def test_pre_versioning_library_reports_zero(self):
        class _NoSymbol:
            def __getattr__(self, name):
                raise AttributeError(name)

        assert matchkern._lib_feature_version(_NoSymbol()) == 0


class TestFeaturizeParity:
    def test_matches_python_path(self):
        tok = HashTokenizer(vocab_size=32768, seq_len=32)
        msgs, py_rows = [], []
        for i in range(64):
            template = f"event <*> type {i % 5} from <*>"
            variables = [f"val{i}", f"host-{i % 9}"]
            hv = {"Time": str(1700000000 + i), "level": "WARN", "b": "x", "a": f"y{i}"}
            msgs.append(ParserSchema(EventID=i, template=template,
                                     variables=variables,
                                     logFormatVariables=hv).serialize())
            parts = [template] + variables + [f"{k}={v}" for k, v in sorted(hv.items())]
            py_rows.append(tok.encode(" ".join(parts)))
        c_rows, ok = matchkern.featurize_batch(msgs, 32, 32768)
        assert ok.all()
        assert (c_rows == np.stack(py_rows)).all()

    def test_garbage_flagged_not_ok(self):
        _, ok = matchkern.featurize_batch([b"\xff\xff\xff\xff"], 16, 1024)
        assert not ok[0]

    def test_empty_message_ok(self):
        rows, ok = matchkern.featurize_batch([ParserSchema().serialize()], 16, 1024)
        assert ok[0]
        assert rows[0][0] == 2  # CLS only


class TestEncodeParity:
    @pytest.mark.parametrize("text", [
        "simple line", "", "MIXED Case 123", "punct!@#$%^&*()sep",
        "unicode café line", "a" * 500,
    ])
    def test_matches_python(self, text):
        c = matchkern.encode_batch([text], 16, 4096)
        p = HashTokenizer(4096, 16).encode_batch([text])
        assert (c == p).all()


class TestTemplateMatcherParity:
    def test_against_python_regexes(self):
        from detectmateservice_tpu.library.parsers.template_matcher import compile_template

        templates = [
            "user <*> logged in from <*>",
            "query failed: <*>",
            "<*> startup complete",
            "exact literal line",
            "a<*>b<*>c",
        ]
        tm = matchkern.TemplateMatcher(templates)
        regexes = [compile_template(t) for t in templates]
        lines = [
            "user bob logged in from 1.2.3.4",
            "query failed: timeout after 3s",
            "service x startup complete",
            "exact literal line",
            "aXbYc", "abc", "aXbc", "abXc",
            "no template matches this",
            "user  logged in from ",
        ]
        for line in lines:
            py_idx = -1
            for i, rx in enumerate(regexes):
                if rx.match(line):
                    py_idx = i
                    break
            c_idx, c_vars = tm.match(line)
            assert c_idx == py_idx, f"{line!r}: C={c_idx} PY={py_idx}"
            if py_idx >= 0:
                py_vars = [g for g in regexes[py_idx].match(line).groups() if g is not None]
                assert c_vars == py_vars


class TestMapOverflowParity:
    def test_native_rows_match_python_below_limit(self):
        # ≤64 entries: the native kernel handles the row itself — compare its
        # output against the pure-Python featurization to pin real parity
        from detectmateservice_tpu.library.detectors import JaxScorerDetector
        from detectmateservice_tpu.schemas import ParserSchema
        from detectmateservice_tpu.utils import matchkern
        import numpy as np

        lfv = {f"key{i:03d}": f"value{i}" for i in range(60)}
        raw = ParserSchema(EventID=1, template="t <*>", variables=["x"],
                           logFormatVariables=lfv).serialize()
        tokens_native, ok = matchkern.featurize_batch([raw], 512, 32768)
        assert ok.all()

        det = JaxScorerDetector(config={"detectors": {"JaxScorerDetector": {
            "method_type": "jax_scorer", "auto_config": False,
            "seq_len": 512, "data_use_training": 0}}})
        tokens_py = np.zeros_like(tokens_native)
        ok_py = np.zeros(1, dtype=bool)
        det._featurize_python_rows([raw], tokens_py, ok_py, [0])
        assert ok_py.all()
        np.testing.assert_array_equal(tokens_native, tokens_py)

    def test_many_header_variables_match_python_path(self):
        # >64 logFormatVariables entries: the native kernel refuses the row
        # (bounded sort buffer) and the detector retries it in Python —
        # the resulting token row must equal the all-Python featurization
        # (regression: entries past 64 were silently dropped)
        from detectmateservice_tpu.library.detectors import JaxScorerDetector
        from detectmateservice_tpu.schemas import ParserSchema

        lfv = {f"key{i:03d}": f"value{i}" for i in range(100)}
        raw = ParserSchema(EventID=1, template="t <*>", variables=["x"],
                           logFormatVariables=lfv).serialize()

        det = JaxScorerDetector(config={"detectors": {"JaxScorerDetector": {
            "method_type": "jax_scorer", "auto_config": False,
            "seq_len": 512, "data_use_training": 0}}})
        tokens_native, ok = det._featurize_raw_batch([raw])
        assert ok.all()

        import numpy as np
        tokens_py = np.zeros_like(tokens_native)
        ok_py = np.zeros(1, dtype=bool)
        det._featurize_python_rows([raw], tokens_py, ok_py, [0])
        assert ok_py.all()
        np.testing.assert_array_equal(tokens_native, tokens_py)


class TestFeaturizeFuzzParity:
    """Differential fuzz: over randomized ParserSchema messages (unicode,
    truncation at seq_len, ragged/empty variables, header-map ordering) the
    detector's featurize path must produce token matrices byte-identical to
    HashTokenizer.encode_parsed — rows the C kernel cannot do exactly are
    flagged, retried in Python (so the FINAL matrix is always the Python
    one), and counted in featurize_fallback_rows_total."""

    SEQ_LEN = 24
    VOCAB = 4096

    # pools chosen to hit the tokenizer's edges: ASCII case folding,
    # multi-byte separators, the two ASCII-lowering codepoints the kernel
    # must flag (İ, K), long runs that truncate, and empty strings
    _POOLS = (
        "abcdefXYZ0189",
        "=_-./:!?#@%&*()[]{}",
        " \t\r\n\x1c\x1d",
        "céäßøñ",
        "日本語ログイン検出",
        "Ωπ𝔘🚀",
        "\u0130\u212a",    # U+0130 / U+212A: ASCII-lowering
        "A" * 40,
    )

    def _rand_text(self, rng, max_len=48):
        # the ASCII-lowering pool guarantees a Python-fallback row, so keep
        # it rare — the suite must prove BOTH paths, mostly the native one
        pool = (self._POOLS[-2] if rng.random() < 0.02
                else rng.choice(self._POOLS[:-2] + self._POOLS[-1:]))
        return "".join(rng.choice(pool) for _ in range(rng.randrange(max_len)))

    def _messages(self, rng, n):
        msgs, expected = [], []
        tok = HashTokenizer(vocab_size=self.VOCAB, seq_len=self.SEQ_LEN)
        for i in range(n):
            template = self._rand_text(rng)
            variables = [self._rand_text(rng)
                         for _ in range(rng.randrange(8))]
            if rng.random() < 0.3:
                variables.append("")              # empty variable
            hv = {}
            for _ in range(rng.randrange(6)):
                hv[self._rand_text(rng, 12)] = self._rand_text(rng, 20)
            if rng.random() < 0.1:
                hv[""] = self._rand_text(rng, 8)  # empty map key
            msgs.append(ParserSchema(
                EventID=i, template=template, variables=variables,
                logID=str(i), logFormatVariables=hv).serialize())
            expected.append(tok.encode_parsed(template, variables, hv))
        return msgs, np.stack(expected)

    def test_fuzz_detector_path_matches_python(self):
        from detectmateservice_tpu.engine import metrics as m
        from detectmateservice_tpu.library.detectors import JaxScorerDetector

        rng = random.Random(0xD317)
        msgs, expected = self._messages(rng, 1200)
        det = JaxScorerDetector(
            name="FuzzParityDet",
            config={"detectors": {"JaxScorerDetector": {
                "method_type": "jax_scorer", "auto_config": False,
                "seq_len": self.SEQ_LEN, "vocab_size": self.VOCAB,
                "data_use_training": 0}}})
        tokens, ok = det._featurize_raw_batch(msgs)
        assert ok.all(), "valid serialized messages must all featurize"
        np.testing.assert_array_equal(tokens, expected)
        # the two counters partition the batch, and the fuzz pools force a
        # non-zero fallback share (İ/K rows must not ride the native path)
        labels = dict(component_type="jax_scorer", component_id="FuzzParityDet")
        native = m.FEATURIZE_NATIVE_ROWS().labels(**labels)._value.get()
        fallback = m.FEATURIZE_FALLBACK_ROWS().labels(**labels)._value.get()
        assert native + fallback == len(msgs)
        assert fallback > 0, "fuzz pools should have produced flagged rows"
        assert native > fallback, "most rows must ride the native path"

    def test_fuzz_raw_kernel_flags_never_lie(self):
        """Every row the raw kernel reports ok=1 must already be byte-exact
        (no Python retry involved)."""
        rng = random.Random(0xBEEF)
        msgs, expected = self._messages(rng, 400)
        tokens, ok = matchkern.featurize_batch(msgs, self.SEQ_LEN, self.VOCAB)
        idx = np.flatnonzero(ok)
        assert len(idx) > 0
        np.testing.assert_array_equal(tokens[idx], expected[idx])

    def test_ascii_lowering_codepoints_flagged(self):
        for text in ("\u0130stanbul", "3\u212a resistor",
                     "deep \u0130 \u212a mix"):
            raw = ParserSchema(template=text, variables=[],
                               logFormatVariables={}).serialize()
            _, ok = matchkern.featurize_batch([raw], 16, 1024)
            assert not ok[0], text

    def test_invalid_utf8_template_flagged(self):
        # valid wire shape, invalid UTF-8 in template (field 5): upb would
        # reject the message, so the kernel must not emit a token stream
        raw = b"\x2a\x03\xff\xfe\x41"  # field 5, len 3, bad bytes
        _, ok = matchkern.featurize_batch([raw], 16, 1024)
        assert not ok[0]

    def test_duplicate_wire_map_keys_last_wins(self):
        # two wire entries with the same key: proto3 keeps the LAST value;
        # the kernel must not tokenize both
        entry1 = b"\x0a\x01k\x12\x01a"     # k -> a
        entry2 = b"\x0a\x01k\x12\x01b"     # k -> b
        raw = (b"\x52" + bytes([len(entry1)]) + entry1
               + b"\x52" + bytes([len(entry2)]) + entry2)
        c_rows, ok = matchkern.featurize_batch([raw], 16, 1024)
        assert ok[0]
        tok = HashTokenizer(vocab_size=1024, seq_len=16)
        np.testing.assert_array_equal(
            c_rows[0], tok.encode_parsed("", [], {"k": "b"}))


class TestNativeFeaturizeKnob:
    def _det(self, name, **over):
        from detectmateservice_tpu.library.detectors import JaxScorerDetector

        cfg = {"method_type": "jax_scorer", "auto_config": False,
               "seq_len": 32, "data_use_training": 0, **over}
        return JaxScorerDetector(
            name=name, config={"detectors": {"JaxScorerDetector": cfg}})

    def _counts(self, name):
        from detectmateservice_tpu.engine import metrics as m

        labels = dict(component_type="jax_scorer", component_id=name)
        return (m.FEATURIZE_NATIVE_ROWS().labels(**labels)._value.get(),
                m.FEATURIZE_FALLBACK_ROWS().labels(**labels)._value.get())

    def test_off_forces_python_path_and_counts_fallback(self):
        det = self._det("KnobOffDet", native_featurize=False)
        assert det._matchkern() is None
        msgs = [ParserSchema(EventID=i, template="t <*>", variables=[str(i)],
                             logFormatVariables={"k": "v"}).serialize()
                for i in range(16)]
        tokens, ok = det._featurize_raw_batch(msgs)
        assert ok.all()
        native, fallback = self._counts("KnobOffDet")
        assert native == 0 and fallback == len(msgs)
        # identical rows to the default-on native path
        det_on = self._det("KnobOnDet")
        tokens_on, ok_on = det_on._featurize_raw_batch(msgs)
        assert ok_on.all()
        np.testing.assert_array_equal(tokens, tokens_on)
        native_on, fallback_on = self._counts("KnobOnDet")
        assert native_on == len(msgs) and fallback_on == 0

    def test_explicit_thread_width_applies(self):
        before = matchkern.featurize_threads()
        try:
            self._det("KnobThreadsDet", featurize_threads=2)
            assert matchkern.featurize_threads() == 2
        finally:
            matchkern.set_featurize_threads(before)


class TestParseBatchKernelParity:
    """dm_parse_batch (round 5): the fused MatcherParser row — decode +
    header extraction + normalization + template match + ParserSchema
    encode — must be FIELD-IDENTICAL to the Python batch path for every
    row it emits, and must flag (not guess at) everything else."""

    AUDIT_FORMAT = "type=<Type> msg=audit(<Time>): <Content>"

    def _parser(self, tmp_path, templates=None, **params):
        import yaml

        from detectmateservice_tpu.library.parsers.template_matcher import (
            MatcherParser,
        )

        cfg = {"method_type": "matcher_parser", "auto_config": False,
               "log_format": params.pop("log_format", self.AUDIT_FORMAT),
               "params": {"remove_spaces": False, **params}}
        if templates is not None:
            tf = tmp_path / "templates.txt"
            tf.write_text("\n".join(templates) + "\n")
            cfg["params"]["path_templates"] = str(tf)
        parser = MatcherParser(config={"parsers": {"MatcherParser": cfg}})
        assert parser._parse_native is not None, "fused kernel must be active"
        return parser

    @staticmethod
    def _fields(raw):
        from detectmateservice_tpu.schemas import schemas_pb2 as pb

        if raw is None:
            return None
        m = pb.ParserSchema()
        m.ParseFromString(raw)
        # parsedLogID is random and the timestamps can straddle a second —
        # assert their SHAPE, compare everything else exactly
        assert len(m.parsedLogID) == 32 and int(m.parsedLogID, 16) >= 0
        assert m.receivedTimestamp > 1_700_000_000
        assert m.parsedTimestamp == m.receivedTimestamp
        return {
            "version": getattr(m, "__version__"),
            "parserType": m.parserType, "parserID": m.parserID,
            "EventID": m.EventID, "template": m.template,
            "variables": list(m.variables), "logID": m.logID, "log": m.log,
            "map": dict(m.logFormatVariables),
        }

    def _assert_parity(self, parser, payloads):
        errors = []
        parser.count_processing_errors = (      # capture, don't metric
            lambda n, what: errors.append(n))
        native = parser.process_batch(list(payloads))
        n_err_native = sum(errors)
        errors.clear()
        python = parser._process_batch_python(list(payloads))
        n_err_python = sum(errors)
        assert len(native) == len(python)
        for i, (a, b) in enumerate(zip(native, python)):
            assert self._fields(a) == self._fields(b), f"row {i} diverged"
        assert n_err_native == n_err_python
        return native

    def audit_payloads(self, n=64):
        from detectmateservice_tpu.schemas import LogSchema

        return [LogSchema(logID=str(i),
                          log=f'type=SYSCALL msg=audit(17000{i % 7}.{i}): '
                              f'arch=c000003e syscall={i % 30} pid={300 + i} '
                              f'uid={i % 3} comm="cron"').serialize()
                for i in range(n)]

    def test_standard_audit_flow_with_templates(self, tmp_path):
        parser = self._parser(tmp_path, templates=[
            "arch=<*> syscall=<*> pid=<*> uid=<*> comm=<*>",
            "connection closed",
        ])
        out = self._assert_parity(parser, self.audit_payloads())
        assert all(o is not None for o in out)
        assert self._fields(out[0])["EventID"] == 1

    def test_no_templates_event_id_minus_one(self, tmp_path):
        """EventID -1 exercises the negative-int32 varint encoding (upb
        sign-extends to 64 bits; a 32-bit encoder would corrupt it)."""
        parser = self._parser(tmp_path)
        out = self._assert_parity(parser, self.audit_payloads(8))
        assert self._fields(out[0])["EventID"] == -1

    def test_no_log_format(self, tmp_path):
        parser = self._parser(tmp_path, log_format=None,
                              templates=["type=<*> msg=audit(<*>): <*>"])
        self._assert_parity(parser, self.audit_payloads(8))

    def test_header_mismatch_keeps_whole_line_as_content(self, tmp_path):
        from detectmateservice_tpu.schemas import LogSchema

        parser = self._parser(tmp_path, templates=["no match here"])
        payloads = [LogSchema(logID="1", log="completely different shape").serialize()]
        out = self._assert_parity(parser, payloads)
        assert self._fields(out[0])["map"] == {}

    def test_blank_lines_filtered(self, tmp_path):
        from detectmateservice_tpu.schemas import LogSchema

        parser = self._parser(tmp_path)
        payloads = [LogSchema(logID="1", log="   \t \n").serialize(),
                    LogSchema(logID="2", log="").serialize()]
        out = self._assert_parity(parser, payloads)
        assert out == [None, None]

    def test_undecodable_strict_counts_errors(self, tmp_path):
        parser = self._parser(tmp_path)
        self._assert_parity(parser, [b"\xff\xfe garbage \xff",
                                     *self.audit_payloads(2)])

    def test_accept_raw_bare_line_and_json(self, tmp_path):
        parser = self._parser(tmp_path, accept_raw_lines=True,
                              templates=["type=<*> msg=audit(<*>): <*>"])
        line = b'type=LOGIN msg=audit(1700.5): pid=9 uid=1\n'
        json_rec = (b'{"message": "type=LOGIN msg=audit(1700.9): pid=7 uid=0",'
                    b' "logSource": "/var/log/a", "hostname": "h1"}\n')
        out = self._assert_parity(parser, [line, json_rec,
                                           *self.audit_payloads(2)])
        assert all(o is not None for o in out)
        assert self._fields(out[0])["map"]["Time"] == "1700.5"
        assert self._fields(out[1])["map"]["Time"] == "1700.9"

    def test_unicode_content_in_captures(self, tmp_path):
        from detectmateservice_tpu.schemas import LogSchema

        parser = self._parser(tmp_path,
                              templates=["user=<*> action=<*>"])
        payloads = [LogSchema(logID="u", log=(
            "type=AUTH msg=audit(1.1): user=Jürgen-日本 action=ログイン"
        )).serialize()]
        out = self._assert_parity(parser, payloads)
        f = self._fields(out[0])
        assert f["variables"] == ["Jürgen-日本", "ログイン"]

    def test_normalization_flags_ascii(self, tmp_path):
        parser = self._parser(tmp_path, lowercase=True,
                              remove_punctuation=True, remove_spaces=True,
                              templates=["archc000003esyscall<*>pid<*>uid<*>commcron"])
        self._assert_parity(parser, self.audit_payloads(16))

    def test_lowercase_nonascii_falls_back_identically(self, tmp_path):
        from detectmateservice_tpu.schemas import LogSchema

        parser = self._parser(tmp_path, lowercase=True,
                              templates=["straße <*>"])
        payloads = [LogSchema(logID="1",
                              log="type=X msg=audit(1.0): STRASSE Straße 7").serialize()]
        self._assert_parity(parser, payloads)

    def test_format_ending_with_capture_is_greedy(self, tmp_path):
        from detectmateservice_tpu.schemas import LogSchema

        parser = self._parser(tmp_path, log_format="<Level>: <Rest>")
        payloads = [LogSchema(logID="1", log="WARN: a: b: c").serialize()]
        out = self._assert_parity(parser, payloads)
        assert self._fields(out[0])["map"] == {"Level": "WARN", "Rest": "a: b: c"}

    def test_format_with_leading_capture_and_suffix_literal(self, tmp_path):
        from detectmateservice_tpu.schemas import LogSchema

        parser = self._parser(tmp_path, log_format="<Head> end")
        payloads = [LogSchema(logID="1", log="x end y end").serialize(),
                    LogSchema(logID="2", log="no suffix").serialize()]
        out = self._assert_parity(parser, payloads)
        # non-greedy + anchored suffix: capture runs to the LAST ' end'
        assert self._fields(out[0])["map"] == {"Head": "x end y"}

    def test_adjacent_captures(self, tmp_path):
        from detectmateservice_tpu.schemas import LogSchema

        parser = self._parser(tmp_path, log_format="<A><B> tail")
        payloads = [LogSchema(logID="1", log="payload tail").serialize()]
        out = self._assert_parity(parser, payloads)
        # non-greedy first capture is empty; second takes the span
        assert self._fields(out[0])["map"] == {"A": "", "B": "payload"}

    def test_duplicate_capture_names_last_wins(self, tmp_path):
        from detectmateservice_tpu.schemas import LogSchema

        parser = self._parser(tmp_path, log_format="<X>-<X>")
        payloads = [LogSchema(logID="1", log="first-second").serialize()]
        out = self._assert_parity(parser, payloads)
        assert self._fields(out[0])["map"] == {"X": "second"}

    def test_single_process_matches_native_batch_fields(self, tmp_path):
        parser = self._parser(tmp_path, templates=[
            "arch=<*> syscall=<*> pid=<*> uid=<*> comm=<*>"])
        payload = self.audit_payloads(1)[0]
        single = parser.process(payload)
        batch = parser.process_batch([payload])[0]
        assert self._fields(single) == self._fields(batch)

    def test_trailing_newline_in_envelope_log_matches_python(self, tmp_path):
        """Python's `$` matches before a trailing newline and `.` never
        crosses one — newline-bearing logs must take the Python path (and
        so produce identical captures), not diverge natively."""
        from detectmateservice_tpu.schemas import LogSchema

        parser = self._parser(tmp_path, templates=["pid=<*> uid=<*>"])
        payloads = [
            LogSchema(logID="1", log="type=X msg=audit(1.0): pid=7 uid=0\n").serialize(),
            LogSchema(logID="2", log="type=X msg=audit(1.0): pid=8\nuid=1").serialize(),
        ]
        self._assert_parity(parser, payloads)

    @pytest.mark.parametrize("tag", [0x0A, 0x22, 0x2A],
                             ids=["__version__", "logSource", "hostname"])
    def test_invalid_utf8_in_any_declared_field_matches_python(self, tmp_path,
                                                               tag):
        """Invalid UTF-8 in ANY wt==2 LogSchema field 1-5 — not just
        log/logID — is a parse failure to upb, so the kernel must treat the
        payload exactly as Python does (strict: decode error; accept_raw:
        raw-line shapes), never emit a row from a message Python rejects."""
        good = self.audit_payloads(2)
        bad = good[0] + bytes([tag]) + b"\x02\xff\xfe"
        parser = self._parser(tmp_path, templates=["arch=<*> syscall=<*>"])
        self._assert_parity(parser, [bad, *good])
        raw_parser = self._parser(tmp_path, accept_raw_lines=True,
                                  templates=["arch=<*> syscall=<*>"])
        self._assert_parity(raw_parser, [bad, *good])

    def test_json_heavy_batch_takes_batched_python_path(self, tmp_path,
                                                        monkeypatch):
        """A batch the kernel flags (almost) entirely — every payload of a
        ``@type json`` edge starts with ``{`` — must fall back to the
        BATCHED Python path, not serialize through per-row parse_line."""
        parser = self._parser(tmp_path, accept_raw_lines=True,
                              templates=["type=<*> msg=audit(<*>): <*>"])
        payloads = [
            (b'{"message": "type=LOGIN msg=audit(1700.%d): pid=%d uid=0",'
             b' "hostname": "h"}\n' % (i, i)) for i in range(32)]
        ref = parser._process_batch_python(list(payloads))
        monkeypatch.setattr(
            parser, "parse_line",
            lambda *a, **kw: (_ for _ in ()).throw(
                AssertionError("per-row fallback used for an all-JSON batch")))
        out = parser.process_batch(list(payloads))
        assert ([self._fields(a) for a in out]
                == [self._fields(b) for b in ref])

    def test_flagged_rows_ride_one_batched_fallback(self, tmp_path,
                                                    monkeypatch):
        """A handful of flagged rows in a clean batch ride ONE batched
        fallback sub-call (native decode spans + native emit), never the
        per-row ``parse_line`` path that builds two throwaway pb2 objects
        per row — the PR-7 host-path fix, regression-pinned here."""
        parser = self._parser(tmp_path, accept_raw_lines=True,
                              templates=["type=<*> msg=audit(<*>): <*>"])
        payloads = self.audit_payloads(30)
        payloads.insert(7, b'{"message": "type=J msg=audit(9.9): x=1"}\n')
        payloads.insert(19, b'{"message": "type=J msg=audit(8.8): y=2"}\n')
        calls = []
        orig = parser._process_batch_python
        monkeypatch.setattr(
            parser, "_process_batch_python",
            lambda batch: calls.append(len(batch)) or orig(batch))
        monkeypatch.setattr(
            parser, "parse_line",
            lambda *a, **kw: (_ for _ in ()).throw(
                AssertionError("flagged rows must not use per-row parse_line")))
        out = parser.process_batch(list(payloads))
        assert calls == [2]          # the two JSON rows, one batched sub-call
        assert all(o is not None for o in out)
        assert self._fields(out[7])["map"]["Time"] == "9.9"
        assert self._fields(out[19])["map"]["Time"] == "8.8"

    def test_capacity_retry_policy_distinguishes_oom(self, tmp_path):
        """-1 (output buffer too small) grows and retries; -2 (C-side malloc
        failure) raises MemoryError immediately — growing our buffer cannot
        fix the C side being out of memory."""
        parser = self._parser(tmp_path)
        pk = parser._parse_native
        caps = []

        def short(out, cap):
            caps.append(cap)
            return -1

        with pytest.raises(MemoryError, match="overflowing"):
            pk._run_with_capacity(64, 1, short)
        assert len(caps) == 4 and caps[1] == caps[0] * 4  # grew between tries

        caps.clear()

        def oom(out, cap):
            caps.append(cap)
            return -2

        with pytest.raises(MemoryError, match="OOM"):
            pk._run_with_capacity(64, 1, oom)
        assert len(caps) == 1                             # no grow-and-retry

        with pytest.raises(RuntimeError, match="unknown error code"):
            pk._run_with_capacity(64, 1, lambda out, cap: -7)

    def test_wrong_wire_type_fields_are_not_envelopes(self, tmp_path):
        """A payload whose only recognizable field numbers carry the WRONG
        wire type parses with all HasField false — in accept_raw mode it is
        a bare line, never an empty envelope (which would filter it)."""
        parser = self._parser(tmp_path, accept_raw_lines=True,
                              log_format=None)
        # field 5 (hostname, declared string) encoded as varint: Python
        # treats it as unknown -> bare-line path; it is also printable text
        payload = b"\x28\x31"  # tag(5,varint) + value 0x31 — also text "(1"
        out = self._assert_parity(parser, [payload])
        assert out[0] is not None  # processed as a line, not dropped

    def test_duplicate_names_serialize_one_wire_entry(self, tmp_path):
        """Byte-level: duplicate capture names must not put extra map
        entries on the wire (the featurizer tokenizes raw wire entries, so
        extra entries would skew downstream features by parser path)."""
        from detectmateservice_tpu.schemas import LogSchema

        parser = self._parser(tmp_path, log_format="<X>-<X>")
        out = parser.process_batch(
            [LogSchema(logID="1", log="first-second").serialize()])
        raw = out[0]
        n_map_entries = 0
        i = 0
        while i < len(raw):  # count top-level field-10 tags
            tag = raw[i]
            if tag == (10 << 3) | 2:
                n_map_entries += 1
            i += 1
            if tag & 7 == 2:  # LEN field: skip its payload
                ln = 0
                shift = 0
                while raw[i] & 0x80:
                    ln |= (raw[i] & 0x7F) << shift
                    shift += 7
                    i += 1
                ln |= raw[i] << shift
                i += 1 + ln
            elif tag & 7 == 0:
                while raw[i] & 0x80:
                    i += 1
                i += 1
        assert n_map_entries == 1

    def test_process_frames_matches_process_batch(self, tmp_path):
        """The frames path (packed batch frames + bare single-message
        frames) must produce the same fields, in order, as expanding the
        frames and running process_batch."""
        from detectmateservice_tpu.engine.framing import pack_batch

        parser = self._parser(tmp_path, templates=[
            "arch=<*> syscall=<*> pid=<*> uid=<*> comm=<*>"])
        payloads = self.audit_payloads(24)
        frames = [pack_batch(payloads[:10]), payloads[10],
                  pack_batch(payloads[11:24])]
        outs, n_msgs, n_lines = parser.process_frames(frames)
        assert n_msgs == 24
        # n_lines follows the ENGINE's newline-count rule over raw payload
        # bytes (protobuf blobs legitimately contain 0x0A tag bytes)
        expected_lines = sum(
            max(1, p.count(b"\n") + (0 if p.endswith(b"\n") else 1))
            for p in payloads)
        assert n_lines == expected_lines
        ref = parser.process_batch(payloads)
        assert [self._fields(a) for a in outs] == [self._fields(b) for b in ref]

    def test_process_frames_counts_corrupt_frames(self, tmp_path):
        parser = self._parser(tmp_path)
        errors = []
        parser.count_processing_errors = lambda n, what: errors.append((n, what))
        bad = b"\xd7DM\x01\xff\xff\xff\xff"          # batch magic, bogus body
        outs, n_msgs, _ = parser.process_frames([bad, self.audit_payloads(1)[0]])
        assert n_msgs == 1 and len(outs) == 1
        assert any("corrupt" in what for _, what in errors)

    def test_process_frames_python_fallback_matches(self, tmp_path):
        """Kill the kernel on one instance: the Python fallback must keep
        the same contract (fields + counts), just slower."""
        from detectmateservice_tpu.engine.framing import pack_batch

        parser = self._parser(tmp_path, templates=["arch=<*> syscall=<*>"])
        payloads = self.audit_payloads(8)
        frames = [pack_batch(payloads[:5]), payloads[5], pack_batch(payloads[6:])]
        native = parser.process_frames(frames)
        parser._parse_native = None
        fallback = parser.process_frames(frames)
        assert native[1:] == fallback[1:]  # counts identical
        assert ([self._fields(a) for a in native[0]]
                == [self._fields(b) for b in fallback[0]])

    def test_process_frames_flagged_rows_fall_back_per_row(self, tmp_path):
        """A frame mixing kernel-clean rows with Python-only rows (JSON
        record in accept_raw mode) emits both correctly in order."""
        from detectmateservice_tpu.engine.framing import pack_batch

        parser = self._parser(tmp_path, accept_raw_lines=True)
        json_rec = (b'{"message": "type=A msg=audit(2.2): x=1", '
                    b'"hostname": "h"}\n')
        payloads = [self.audit_payloads(1)[0], json_rec,
                    b'type=B msg=audit(3.3): y=2\n']
        outs, n_msgs, _ = parser.process_frames([pack_batch(payloads)])
        assert n_msgs == 3
        assert self._fields(outs[1])["map"]["Time"] == "2.2"
        assert self._fields(outs[2])["map"]["Time"] == "3.3"


class TestNvdScanKernelParity:
    """dm_nvd_scan: the steady-state set-membership filter must be EXACT on
    its 0-verdicts (proven no-alert) and conservative everywhere else —
    outputs, alerts, and state evolution must be indistinguishable from the
    pure-Python path."""

    def _build(self, **cfg):
        from detectmateservice_tpu.library.detectors.new_value_detector import (
            NewValueDetector,
        )

        base = {"method_type": "new_value_detector", "auto_config": False,
                "data_use_training": 8,
                "global": {"gi": {"header_variables": [{"pos": "Type"}],
                                  "variables": [{"pos": 0}]}},
                "events": {"1": {"e1": {"variables": [{"pos": 1}]}}}}
        base.update(cfg)
        return NewValueDetector(config={"detectors": {"NewValueDetector": base}})

    def _pair(self, **cfg):
        native, python = self._build(**cfg), self._build(**cfg)
        python._ensure_scan_kernel = lambda: None
        return native, python

    @staticmethod
    def _msg(event=1, variables=("a", "b"), type_="SYSCALL", log_id="1"):
        from detectmateservice_tpu.schemas import ParserSchema

        kw = {} if event is None else {"EventID": event}
        return ParserSchema(variables=list(variables), logID=log_id,
                            logFormatVariables={"Type": type_}, **kw).serialize()

    def _assert_parity(self, native, python, payloads):
        from detectmateservice_tpu.schemas import DetectorSchema

        a = native.process_batch(list(payloads))
        b = python.process_batch(list(payloads))
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert (x is None) == (y is None)
            if x is not None:
                da, db = DetectorSchema.from_bytes(x), DetectorSchema.from_bytes(y)
                assert dict(da.alertsObtain) == dict(db.alertsObtain)
                assert da.score == db.score
                assert list(da.logIDs) == list(db.logIDs)
        assert native._seen == python._seen  # state evolution identical
        return a

    def _train(self, *dets):
        train = [self._msg(variables=(f"v{i % 3}", f"w{i % 2}"),
                           type_=["SYSCALL", "LOGIN"][i % 2], log_id=str(i))
                 for i in range(8)]
        for d in dets:
            d.process_batch(train)

    def test_steady_state_no_alerts_and_kernel_engaged(self):
        native, python = self._pair()
        self._train(native, python)
        payloads = [self._msg(variables=("v1", "w0"), type_="LOGIN",
                              log_id=str(i)) for i in range(64)]
        out = self._assert_parity(native, python, payloads)
        assert all(o is None for o in out)
        assert native._scan_kernel is not None, "kernel must engage"

    def test_new_values_alert_identically(self):
        native, python = self._pair()
        self._train(native, python)
        payloads = [self._msg(variables=("v0", "w1"), log_id="ok"),
                    self._msg(variables=("EVIL", "w1"), log_id="bad1"),
                    self._msg(variables=("v1", "99"), type_="ROOTKIT",
                              log_id="bad2")]
        out = self._assert_parity(native, python, payloads)
        assert out[0] is None and out[1] is not None and out[2] is not None

    def test_alert_once_staleness_is_safe(self):
        """alert_once inserts values Python-side AFTER the table build: the
        stale table must keep routing those rows to Python (which then
        suppresses repeats), never suppress or double-alert natively."""
        native, python = self._pair(alert_once=True)
        self._train(native, python)
        evil = [self._msg(variables=("EVIL", "w0"), log_id=str(i))
                for i in range(6)]
        out = self._assert_parity(native, python, evil)
        assert out[0] is not None                      # first sighting alerts
        assert all(o is None for o in out[1:])         # alert_once suppresses

    def test_unknown_event_id_and_missing_event_id(self):
        native, python = self._pair()
        self._train(native, python)
        payloads = [self._msg(event=7, variables=("v0", "w0"), log_id="e7"),
                    self._msg(event=None, variables=("v0", "w0"), log_id="eN")]
        self._assert_parity(native, python, payloads)

    def test_decode_errors_counted_identically(self):
        native, python = self._pair()
        self._train(native, python)
        counts = {"native": 0, "python": 0}
        native.count_processing_errors = (
            lambda n, what, _c=counts: _c.__setitem__("native", _c["native"] + n))
        python.count_processing_errors = (
            lambda n, what, _c=counts: _c.__setitem__("python", _c["python"] + n))
        payloads = [b"\xff\xfenot a proto", self._msg(variables=("v0", "w0"))]
        self._assert_parity(native, python, payloads)
        assert counts["native"] == counts["python"] == 1

    def test_unicode_values(self):
        native, python = self._pair()
        train = [self._msg(variables=("Jürgen", "日本"), type_="ログ",
                           log_id=str(i)) for i in range(8)]
        native.process_batch(train)
        python.process_batch(train)
        ok = [self._msg(variables=("Jürgen", "日本"), type_="ログ", log_id="ok")]
        bad = [self._msg(variables=("Jürgén", "日本"), type_="ログ", log_id="bad")]
        assert self._assert_parity(native, python, ok) == [None]
        out = self._assert_parity(native, python, bad)
        assert out[0] is not None

    def test_checkpoint_restore_rebuilds_table(self):
        native, python = self._pair()
        self._train(native, python)
        state = native.state_dict()
        fresh = self._build()
        fresh.load_state_dict(state)
        fresh._trained = 8
        payloads = [self._msg(variables=("v0", "w0"), log_id="ok"),
                    self._msg(variables=("NEW", "w0"), log_id="bad")]
        out = fresh.process_batch(payloads)
        assert out[0] is None and out[1] is not None

    def test_reconfigure_remapping_watched_fields_invalidates_table(self):
        """A reconfigure that remaps watched fields onto the SAME plan and
        seen counts must not reuse the old table — that would wrongly prove
        rows alert-free against the pre-reconfigure field positions."""
        native = self._build(**{"global": {"gi": {"variables": [{"pos": 0}]}},
                                "events": {}})
        train = [self._msg(variables=(f"v{i % 3}", "CONST"), log_id=str(i))
                 for i in range(8)]
        native.process_batch(train)
        native.process_batch([self._msg(variables=("v0", "x"), log_id="warm")])
        assert native._scan_kernel is not None
        # remap the single watcher from position 0 to position 1: same plan
        # count, same seen count — only the field changed
        native.config = native.config.model_copy(update={
            "global_": {"gi": type(native.config.global_["gi"])(
                variables=[{"pos": 1}])}})
        native.apply_config()
        out = native.process_batch(
            [self._msg(variables=("v0", "NEVER-SEEN"), log_id="bad")])
        assert out[0] is not None, "stale table suppressed the alert"

    def test_live_state_restore_invalidates_table(self):
        native, python = self._pair()
        self._train(native, python)
        native.process_batch([self._msg(variables=("v0", "w0"), log_id="warm")])
        assert native._scan_kernel is not None
        # restore DIFFERENT seen-sets with identical counts onto the live
        # instance: the old table must not answer for the new state
        state = native.state_dict()
        state["seen"] = {k: [f"other-{i}" for i in range(len(v))]
                         for k, v in state["seen"].items()}
        native.load_state_dict(state)
        out = native.process_batch(
            [self._msg(variables=("v0", "w0"), log_id="now-unknown")])
        assert out[0] is not None, "pre-restore table suppressed the alert"

    def test_invalid_utf8_in_unwatched_field_counts_error(self):
        """Invalid UTF-8 in a string field the scan does not watch (logID)
        must still surface as a decode error — upb rejects it at parse, and
        a verdict-0 shortcut would silently undercount."""
        native, python = self._pair()
        self._train(native, python)
        ok = self._msg(variables=("v0", "w0"), log_id="x")
        # splice an invalid-UTF-8 logID (field 8) onto an otherwise
        # all-seen message
        bad = ok + b"\x42\x02\xff\xfe"
        counts = {"native": 0, "python": 0}
        native.count_processing_errors = (
            lambda n, w, _c=counts: _c.__setitem__("native", _c["native"] + n))
        python.count_processing_errors = (
            lambda n, w, _c=counts: _c.__setitem__("python", _c["python"] + n))
        a = native.process_batch([bad])
        b = python.process_batch([bad])
        assert a == b == [None]
        assert counts["native"] == counts["python"] == 1


class TestLogsDecodeEmitFuzz:
    """Differential fuzz for the PR-7 zero-copy host path: randomized
    LogSchema corpora (unicode, truncation, duplicate fields, raw lines,
    invalid UTF-8 edge rows, JSON records, ragged headers) must decode
    byte-exactly vs the pb2 path (dm_parse_logs_*), and the native
    ParserSchema emitter must serialize byte-exactly vs pb2
    SerializeToString — both as units and end-to-end through
    MatcherParser's hybrid batch path vs the pure-pb2 reference."""

    _TEXT_POOLS = (
        "abcdefXYZ0189 =.:/",
        "céäßøñ 日本語ログ",
        "Ωπ𝔘🚀",
        " \t\x1c",
        "A" * 30,
    )

    def _rand_text(self, rng, max_len=40):
        pool = rng.choice(self._TEXT_POOLS)
        return "".join(rng.choice(pool) for _ in range(rng.randrange(max_len)))

    def _corpus(self, rng, n):
        from detectmateservice_tpu.schemas import LogSchema

        payloads = []
        for i in range(n):
            kind = rng.random()
            if kind < 0.45:        # valid envelope, random unicode fields
                payloads.append(LogSchema(
                    logID=self._rand_text(rng, 12),
                    log=f"type=SYSCALL msg=audit(1700.{i}): pid={i} "
                        + self._rand_text(rng),
                    logSource=self._rand_text(rng, 10),
                    hostname=self._rand_text(rng, 10)).serialize())
            elif kind < 0.55:      # truncated envelope
                raw = LogSchema(logID=str(i),
                                log=self._rand_text(rng, 60)).serialize()
                payloads.append(raw[:rng.randrange(1, max(2, len(raw)))])
            elif kind < 0.62:      # duplicate wire fields: last-wins
                a = LogSchema(log="first " + self._rand_text(rng, 10))
                b = LogSchema(log="last " + self._rand_text(rng, 10),
                              logID=str(i))
                payloads.append(a.serialize() + b.serialize())
            elif kind < 0.72:      # raw line (trailing-newline variants)
                line = ("type=LOGIN msg=audit(9.%d): %s"
                        % (i, self._rand_text(rng))).encode()
                payloads.append(line + (b"\n" if rng.random() < 0.5 else b""))
            elif kind < 0.78:      # invalid UTF-8 edge rows
                payloads.append(b"\xff\xfe " + self._rand_text(rng).encode()
                                + b" \x80\x81")
            elif kind < 0.88:      # JSON records (valid / damaged)
                if rng.random() < 0.8:
                    payloads.append(
                        ('{"message": "type=J msg=audit(7.%d): %s", '
                         '"logID": "%d", "hostname": "h"}\n'
                         % (i, self._rand_text(rng, 20).replace('"', "")
                            .replace("\\", ""), i)).encode())
                else:
                    payloads.append(b'{"broken json' + str(i).encode())
            elif kind < 0.94:      # blank-ish lines
                payloads.append(rng.choice(
                    [b" \t ", b"\n", b"\x1c\x1d", " ".encode()]))
            else:                  # wrong-wire-type field numbers
                payloads.append(b"\x10\x05" + self._rand_text(rng, 8).encode())
        return [p for p in payloads if p]

    @pytest.mark.parametrize("accept_raw", [False, True])
    def test_fuzz_decode_matches_ingest_payload(self, accept_raw):
        from detectmateservice_tpu.library.parsers.template_matcher import (
            decode_ingest_payload,
        )
        from detectmateservice_tpu.schemas import SchemaError

        rng = random.Random(0x10C5)
        payloads = self._corpus(rng, 600)
        view = matchkern.parse_logs_batch(payloads, accept_raw)
        n_native = 0
        for i, payload in enumerate(payloads):
            st = int(view.status[i])
            assert view.raw(i) == payload
            if st in (1, 2):
                msg = decode_ingest_payload(payload, accept_raw)
                assert view.log(i) == msg.log, f"row {i} log diverged"
                assert view.log_id(i) == msg.logID, f"row {i} logID diverged"
                n_native += 1
            elif st == 0:
                # JSON-to-Python rows only exist in accept_raw mode and
                # always start with '{'
                assert accept_raw and payload[:1] == b"{"
            else:
                assert st == -1
                if not accept_raw:
                    # strict-mode flag: the pb2 path must also reject it
                    with pytest.raises(SchemaError):
                        decode_ingest_payload(payload, accept_raw)
        assert n_native > len(payloads) // 2, "corpus must mostly ride native"

    def test_fuzz_logs_frames_matches_batch(self):
        from detectmateservice_tpu.engine.framing import pack_batch

        rng = random.Random(0xF4A3)
        payloads = self._corpus(rng, 300)
        frames = []
        expected = []
        i = 0
        while i < len(payloads):
            take = rng.randrange(1, 9)
            chunk = payloads[i:i + take]
            i += take
            if rng.random() < 0.3:
                frames.append(chunk[0])            # plain single message
                expected.extend(chunk[:1])
            else:
                frames.append(pack_batch(chunk))
                expected.extend(chunk)
        frames.insert(3, b"\xd7DM\x01\x7f\x01")    # corrupt batch frame
        fview = matchkern.parse_logs_frames(frames, True)
        bview = matchkern.parse_logs_batch(expected, True)
        assert fview.n_corrupt_frames == 1
        assert len(fview) == len(expected)
        assert list(fview.status) == list(bview.status)
        for i in range(len(expected)):
            assert fview.raw(i) == expected[i]
            if fview.status[i] in (1, 2):
                assert fview.log(i) == bview.log(i)
                assert fview.log_id(i) == bview.log_id(i)

    def test_fuzz_emit_byte_exact_vs_pb2(self):
        import os as _os

        from detectmateservice_tpu.schemas import SCHEMA_VERSION
        from detectmateservice_tpu.schemas import schemas_pb2 as pb

        rng = random.Random(0xE317)
        n = 300
        emitter = matchkern.ParserEmitter(SCHEMA_VERSION, "matcher_parser",
                                          "FuzzEmit")
        event_ids, templates, variables, log_ids, kv_items = [], [], [], [], []
        for i in range(n):
            event_ids.append(rng.choice([-1, 0, 1, i, 2**31 - 1, -2**31]))
            templates.append(self._rand_text(rng).encode())
            variables.append([self._rand_text(rng, 20).encode()
                              for _ in range(rng.randrange(6))])
            log_ids.append(self._rand_text(rng, 12).encode())
            seen = {}
            for _ in range(rng.randrange(5)):
                seen[self._rand_text(rng, 8)] = self._rand_text(rng, 12)
            if rng.random() < 0.2:
                seen[""] = ""                      # empty key AND value
            kv_items.append([(k.encode(), v.encode())
                             for k, v in seen.items()])
        now = 1_754_300_000
        rand_hex = _os.urandom(16 * n).hex().encode()
        arena, offs = emitter.emit(event_ids, templates, variables, log_ids,
                                   kv_items, now, rand_hex)
        offs = offs.tolist()
        n_byte_exact = 0
        native_rows, pb2_rows = [], []
        for i in range(n):
            got = arena[offs[i]:offs[i + 1]].tobytes()
            ref = pb.ParserSchema()
            setattr(ref, "__version__", SCHEMA_VERSION)
            ref.parserType = "matcher_parser"
            ref.parserID = "FuzzEmit"
            ref.EventID = event_ids[i]
            ref.template = templates[i].decode()
            if variables[i]:
                ref.variables.extend(v.decode() for v in variables[i])
            ref.parsedLogID = rand_hex[32 * i:32 * i + 32].decode()
            ref.logID = log_ids[i].decode()
            ref.log = "FuzzEmit"
            for k, v in kv_items[i]:
                ref.logFormatVariables[k.decode()] = v.decode()
            ref.receivedTimestamp = now
            ref.parsedTimestamp = now
            want = ref.SerializeToString()
            native_rows.append(got)
            pb2_rows.append(want)
            if len(kv_items[i]) <= 1:
                # byte-exactness is only well-defined up to one map entry:
                # upb serializes map entries in internal hash order (its own
                # bytes are not canonical for multi-entry maps — the same
                # reason the fused kernel's contract is field-level there)
                assert got == want, f"row {i} diverged"
                n_byte_exact += 1
            back = pb.ParserSchema()
            back.ParseFromString(got)
            assert back == ref, f"row {i} field-diverged"
        assert n_byte_exact > n // 4
        # downstream featurization must be blind to map wire order: the
        # token rows of the native bytes and the pb2 bytes are identical
        nat_tok, nat_ok = matchkern.featurize_batch(native_rows, 24, 4096)
        pb2_tok, pb2_ok = matchkern.featurize_batch(pb2_rows, 24, 4096)
        np.testing.assert_array_equal(nat_ok, pb2_ok)
        np.testing.assert_array_equal(nat_tok, pb2_tok)

    @pytest.mark.parametrize("accept_raw", [False, True])
    def test_fuzz_hybrid_batch_matches_pb2_reference(self, tmp_path,
                                                     accept_raw):
        """End-to-end: MatcherParser's hybrid batch path (native decode
        spans + native emit) is field-identical to the pure-pb2 reference
        over the whole fuzz corpus, errors counted identically."""
        parser = TestParseBatchKernelParity()._parser(
            tmp_path, accept_raw_lines=accept_raw,
            templates=["type=<*> msg=audit(<*>): <*>", "pid=<*>"])
        assert parser._logs_native is not None
        rng = random.Random(0xAB12 + accept_raw)
        payloads = self._corpus(rng, 500)
        errors = []
        parser.count_processing_errors = lambda n, what: errors.append(n)
        hybrid = parser._process_batch_python(list(payloads))
        n_err_hybrid = sum(errors)
        errors.clear()
        ref = parser._process_batch_pb2(list(payloads))
        n_err_ref = sum(errors)
        assert len(hybrid) == len(ref)
        fields = TestParseBatchKernelParity._fields
        for i, (a, b) in enumerate(zip(hybrid, ref)):
            assert fields(a) == fields(b), f"row {i} diverged"
        assert n_err_hybrid == n_err_ref

    def test_time_format_config_uses_logs_kernel_frames(self, tmp_path):
        """time_format keeps the fused kernel off, but frame expansion +
        LogSchema decode + ParserSchema serialize still run natively; the
        outputs stay field-identical to the pb2 reference."""
        from detectmateservice_tpu.engine.framing import pack_batch
        from detectmateservice_tpu.library.parsers.template_matcher import (
            MatcherParser,
        )

        tf = tmp_path / "templates.txt"
        tf.write_text("arch=<*> syscall=<*>\n")
        parser = MatcherParser(config={"parsers": {"MatcherParser": {
            "method_type": "matcher_parser", "auto_config": False,
            "log_format": "type=<Type> msg=audit(<Time>): <Content>",
            "time_format": "%s-ignored",
            "params": {"path_templates": str(tf)}}}})
        assert parser._parse_native is None      # fused kernel gated off
        assert parser._logs_native is not None   # decode kernel still on
        payloads = TestParseBatchKernelParity().audit_payloads(48)
        frames = [pack_batch(payloads[:24]), pack_batch(payloads[24:])]
        outs, n_msgs, _ = parser.process_frames(frames)
        assert n_msgs == 48
        ref = parser._process_batch_pb2(list(payloads))
        fields = TestParseBatchKernelParity._fields
        assert [fields(a) for a in outs] == [fields(b) for b in ref]

    def test_native_parse_off_forces_pb2_path(self, tmp_path):
        from detectmateservice_tpu.library.parsers.template_matcher import (
            MatcherParser,
        )

        parser = MatcherParser(config={"parsers": {"MatcherParser": {
            "method_type": "matcher_parser", "auto_config": False,
            "log_format": "type=<Type> msg=audit(<Time>): <Content>",
            "params": {"native_parse": False}}}})
        assert parser._parse_native is None
        assert parser._logs_native is None
        payloads = TestParseBatchKernelParity().audit_payloads(8)
        out = parser.process_batch(list(payloads))
        ref = parser._process_batch_pb2(list(payloads))
        fields = TestParseBatchKernelParity._fields
        assert [fields(a) for a in out] == [fields(b) for b in ref]

    def test_parse_row_counters_partition_the_batch(self, tmp_path):
        from detectmateservice_tpu.engine import metrics as m

        parser = TestParseBatchKernelParity()._parser(
            tmp_path, accept_raw_lines=True,
            templates=["type=<*> msg=audit(<*>): <*>"])
        labels = parser.metrics_labels
        native_c = m.PARSE_NATIVE_ROWS().labels(**labels)
        fallback_c = m.PARSE_FALLBACK_ROWS().labels(**labels)
        before = native_c._value.get() + fallback_c._value.get()
        payloads = TestParseBatchKernelParity().audit_payloads(20)
        payloads.append(b'{"message": "type=J msg=audit(1.1): x"}\n')
        parser.process_batch(list(payloads))
        after = native_c._value.get() + fallback_c._value.get()
        assert after - before == len(payloads)
