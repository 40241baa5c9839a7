"""Self-test corpus for dmlint (detectmateservice_tpu/analysis).

Three layers, per the analyzer-suite contract:

* **known-bad corpus** — one minimal snippet per rule family (unguarded
  attribute, lock-order cycle, blocking-under-lock, hot-loop allocation,
  unregistered series, undocumented setting, unregistered marker, …), each
  asserting the rule fires EXACTLY once (firing twice means unstable
  fingerprints; zero means the rule rotted),
* **clean corpus** — idiomatic threaded code that must produce zero
  findings (the analyzer's precision contract: serializer locks,
  construction-time helpers, lock-inherited private methods),
* **the real tree** — `detectmate-lint` over this repository must exit 0
  with every suppression justified (the CI gate, run in-process here so a
  regression fails the test suite before it fails CI).
"""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from detectmateservice_tpu.analysis import (
    affinity,
    basic,
    contracts,
    durability,
    hotloop,
    locks,
    markers,
    robustness,
)
from detectmateservice_tpu.analysis.cli import (
    default_repo_root,
    main,
    run,
    to_sarif,
)
from detectmateservice_tpu.analysis.findings import (
    load_baseline,
    scan_pragmas,
    write_baseline,
)

REPO = Path(__file__).resolve().parent.parent


def lock_findings(src: str, rule: str):
    return [f for f in locks.check_module("snippet.py", src) if f.rule == rule]


def hot_findings(src: str, rule: str):
    return [f for f in hotloop.check_module("snippet.py", src) if f.rule == rule]


# ---------------------------------------------------------------------------
# known-bad corpus: each rule fires exactly once
# ---------------------------------------------------------------------------
class TestKnownBadCorpus:
    def test_unguarded_attribute_fires_once(self):
        src = """
import threading

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []

    def put(self, item):
        with self._lock:
            self._items.append(item)

    def size(self):
        return len(self._items)
"""
        found = lock_findings(src, "DM-L001")
        assert len(found) == 1
        assert "Worker._items" in found[0].message
        assert "size" in found[0].message

    def test_blocking_under_lock_fires_once(self):
        src = """
import threading, time

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self.state = 0

    def step(self):
        with self._lock:
            self.state += 1
            time.sleep(0.5)
"""
        found = lock_findings(src, "DM-L002")
        assert len(found) == 1
        assert "sleep" in found[0].message

    def test_lock_order_cycle_fires_once(self):
        src = """
import threading

class Transfer:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def forward(self):
        with self._a:
            with self._b:
                pass

    def backward(self):
        with self._b:
            with self._a:
                pass
"""
        found = lock_findings(src, "DM-L003")
        assert len(found) == 1
        assert "cycle" in found[0].message

    def test_hot_loop_metric_allocation_fires_once(self):
        src = """
class Loop:
    def run(self, m, labels):
        # dmlint: hot-loop
        while True:
            m.DATA_READ_BYTES().labels(**labels).inc()
"""
        found = hot_findings(src, "DM-H001")
        # the chained expression trips both the registry-getter and the
        # .labels() pattern at the same call site — they dedupe to distinct
        # keys; assert the labels-pattern fires exactly once
        labels_hits = [f for f in found if ".labels" in f.message or "labels" in f.key]
        assert len(labels_hits) == 1

    def test_hot_loop_info_logging_fires_once(self):
        src = """
class Loop:
    def run(self, logger):
        # dmlint: hot-loop
        while True:
            logger.info("tick %s", 1)
"""
        assert len(hot_findings(src, "DM-H002")) == 1

    def test_hot_loop_regex_compile_fires_once(self):
        src = """
import re

class Loop:
    def run(self, lines):
        # dmlint: hot-loop
        for line in lines:
            pat = re.compile("x+")
            pat.match(line)
"""
        assert len(hot_findings(src, "DM-H003")) == 1

    def test_hot_loop_sleep_fires_once_and_except_path_is_cold(self):
        src = """
import time

class Loop:
    def run(self):
        # dmlint: hot-loop
        while True:
            time.sleep(0.1)
            try:
                pass
            except Exception:
                time.sleep(5)   # cold path: must NOT be flagged
"""
        assert len(hot_findings(src, "DM-H004")) == 1

    def test_unregistered_series_fires_once(self, tmp_path):
        self._make_contract_repo(tmp_path, alerts_extra="""
      - alert: Ghost
        expr: ghost_series_total > 0
""")
        found = [f for f in contracts.check_metrics_contract(tmp_path)
                 if f.rule == "DM-C001"]
        assert len(found) == 1
        assert "ghost_series_total" in found[0].message

    def test_undocumented_setting_fires_once(self, tmp_path):
        self._make_contract_repo(tmp_path, settings_extra="""
    secret_knob: int = 3
""")
        found = [f for f in contracts.check_settings_contract(tmp_path)
                 if f.rule == "DM-C005"]
        assert len(found) == 1
        assert "secret_knob" in found[0].message

    def test_rejected_example_key_fires_once(self, tmp_path):
        self._make_contract_repo(tmp_path)
        (tmp_path / "examples").mkdir()
        (tmp_path / "examples" / "demo_settings.yaml").write_text(
            "documented_knob: 1\nmistyped_knob: 2\n")
        found = [f for f in contracts.check_settings_contract(tmp_path)
                 if f.rule == "DM-C006"]
        assert len(found) == 1
        assert "mistyped_knob" in found[0].message

    def test_unregistered_marker_fires_once(self, tmp_path):
        (tmp_path / "pyproject.toml").write_text(
            '[tool.pytest.ini_options]\nmarkers = [\n    "slow: heavy",\n]\n')
        tests = tmp_path / "tests"
        tests.mkdir()
        (tests / "test_x.py").write_text(
            "import pytest\n\n"
            "@pytest.mark.slwo\ndef test_a():\n    pass\n\n"
            "@pytest.mark.slow\ndef test_b():\n    pass\n\n"
            "@pytest.mark.parametrize('v', [1])\ndef test_c(v):\n    pass\n")
        found = markers.check_markers(tmp_path)
        assert len(found) == 1
        assert "slwo" in found[0].message

    def test_undocumented_route_fires_once(self, tmp_path):
        self._make_routes_repo(
            tmp_path,
            routes='Route("GET", "/admin/demo", None, "demo"),\n'
                   'Route("POST", "/admin/secret", None, "undocumented"),',
            usage="| `GET /admin/demo` | demo |\n")
        found = [f for f in contracts.check_routes_contract(tmp_path)
                 if f.rule == "DM-C007"]
        assert len(found) == 1
        assert "POST /admin/secret" in found[0].message

    def test_phantom_documented_route_fires_once(self, tmp_path):
        self._make_routes_repo(
            tmp_path,
            routes='Route("GET", "/admin/demo", None, "demo"),',
            usage="| `GET /admin/demo` | demo |\n"
                  "| `POST /admin/ghost` | never declared |\n")
        found = [f for f in contracts.check_routes_contract(tmp_path)
                 if f.rule == "DM-C008"]
        assert len(found) == 1
        assert "POST /admin/ghost" in found[0].message

    @staticmethod
    def _make_routes_repo(tmp_path, routes: str, usage: str):
        web = tmp_path / "detectmateservice_tpu" / "web"
        web.mkdir(parents=True)
        (web / "router.py").write_text(f"ROUTES = (\n{routes}\n)\n")
        docs = tmp_path / "docs"
        docs.mkdir()
        (docs / "usage.md").write_text(usage)

    @staticmethod
    def _make_contract_repo(tmp_path, alerts_extra="", settings_extra=""):
        """Minimal artifact tree the contract checker can traverse."""
        pkg = tmp_path / "detectmateservice_tpu"
        (pkg / "engine").mkdir(parents=True)
        (pkg / "engine" / "metrics.py").write_text(
            'REGISTERED_SERIES = {}\n\n\n'
            'def _series(cls, name, doc, labels=(), **kw):\n'
            '    REGISTERED_SERIES[name] = cls\n'
            '    return lambda: None\n\n\n'
            'DEMO = _series(None, "demo_series_total", "demo")\n')
        (pkg / "settings.py").write_text(
            "class ServiceSettings:\n"
            "    documented_knob: int = 1\n"
            + (settings_extra or "    pass\n"))
        ops = tmp_path / "ops"
        ops.mkdir()
        (ops / "alerts.yml").write_text(
            "groups:\n  - name: demo\n    rules:\n"
            "      - alert: DemoHigh\n"
            "        expr: rate(demo_series_total[5m]) > 1\n" + alerts_extra)
        (ops / "grafana_dashboard.json").write_text(json.dumps({
            "panels": [{"title": "demo",
                        "targets": [{"expr": "rate(demo_series_total[1m])"}]}]}))
        docs = tmp_path / "docs"
        docs.mkdir()
        (docs / "prometheus.md").write_text("`demo_series_total` — demo\n")
        (docs / "configuration.md").write_text("`documented_knob` — demo\n")


# ---------------------------------------------------------------------------
# known-bad corpus: thread affinity (DM-A)
# ---------------------------------------------------------------------------
class TestAffinityKnownBad:
    def test_cross_thread_call_fires_once(self):
        """The PR 9 review bug, distilled: the supervisor thread reaching an
        engine-owned router method through a typed seam."""
        router = """
class MiniRouter:
    # dmlint: thread(engine)
    def tick(self):
        pass

    # dmlint: thread(any)
    def apply_probe(self, result):
        pass
"""
        supervisor = """
class MiniSupervisor:
    def __init__(self, router: "MiniRouter"):
        self._router = router

    # dmlint: thread(supervisor)
    def poll_once(self):
        self._router.apply_probe(None)   # any-owned: fine
        self._router.tick()              # engine-owned: the bug
"""
        found = [f for f in affinity.check_project([
            ("detectmateservice_tpu/a.py", router),
            ("detectmateservice_tpu/b.py", supervisor)])
            if f.rule == "DM-A001"]
        assert len(found) == 1
        assert "MiniRouter.tick" in found[0].message
        assert "supervisor" in found[0].message

    def test_shared_unguarded_attribute_fires_once(self):
        src = """
class Shared:
    def __init__(self):
        self._count = 0

    # dmlint: thread(engine)
    def bump(self):
        self._count += 1

    # dmlint: thread(admin)
    def read(self):
        return self._count
"""
        found = [f for f in affinity.check_project(
            [("detectmateservice_tpu/c.py", src)]) if f.rule == "DM-A002"]
        assert len(found) == 1
        assert "Shared._count" in found[0].message

    def test_off_thread_socket_write_fires_once(self):
        """Modeled directly on the PR 9 review finding: supervisor code
        mutating a replica's socket."""
        src = """
class BadSupervisor:
    # dmlint: thread(supervisor)
    def poll(self, replica):
        replica.sock.send(b"probe")
"""
        found = [f for f in affinity.check_project(
            [("detectmateservice_tpu/d.py", src)]) if f.rule == "DM-A003"]
        assert len(found) == 1
        assert "supervisor" in found[0].message

    def test_spool_write_path_off_engine_fires_once(self):
        src = """
class IngressSpool:
    # dmlint: thread(engine)
    def append(self, frame):
        pass


class BadAdmin:
    def __init__(self):
        self._spool = IngressSpool()

    # dmlint: thread(admin)
    def handler(self, frame):
        self._spool.append(frame)
"""
        found = affinity.check_project([("detectmateservice_tpu/e.py", src)])
        # the call is BOTH a foreign-owned call (A001) and a spool
        # write-path reach (A003); assert the spool rule fires exactly once
        spool_hits = [f for f in found if f.rule == "DM-A003"]
        assert len(spool_hits) == 1
        assert "spool" in spool_hits[0].message.lower()


# ---------------------------------------------------------------------------
# known-bad corpus: durability discipline (DM-D)
# ---------------------------------------------------------------------------
class TestDurabilityKnownBad:
    def test_bare_json_dump_manifest_write_fires_once(self):
        src = """
import json


def commit_manifest(fh, doc):
    json.dump(doc, fh)
"""
        found = durability.check_module("detectmateservice_tpu/wal/m.py", src)
        assert [f.rule for f in found] == ["DM-D001"]

    def test_bare_final_path_open_fires_once(self):
        src = """
def save(path, data):
    with open(path, "w") as fh:
        fh.write(data)
"""
        found = durability.check_module("detectmateservice_tpu/wal/s.py", src)
        assert [f.rule for f in found] == ["DM-D001"]

    def test_rename_without_fsync_fires_once(self):
        src = """
import os


def commit(tmp, final):
    os.replace(tmp, final)
"""
        found = durability.check_module("detectmateservice_tpu/wal/r.py", src)
        assert [f.rule for f in found] == ["DM-D002"]

    def test_buffered_wal_append_fires_once(self):
        src = """
def open_segment(path):
    return open(path, "ab")
"""
        found = durability.check_module("detectmateservice_tpu/wal/a.py", src)
        assert [f.rule for f in found] == ["DM-D003"]

    def test_non_persistence_paths_are_out_of_scope(self):
        src = "import json\n\n\ndef f(fh):\n    json.dump({}, fh)\n"
        assert durability.check_module(
            "detectmateservice_tpu/engine/engine.py", src) == []


# ---------------------------------------------------------------------------
# known-bad corpus: robustness discipline (DM-R)
# ---------------------------------------------------------------------------
class TestRobustnessKnownBad:
    def test_swallowed_exception_fires_once(self):
        """The dmfault motivating bug, distilled: the pre-dmfault engine
        loop swallowing a processor error and acking the frame anyway."""
        src = """
def dispatch(processor, frames, acks):
    try:
        processor.process(frames)
    except Exception:
        pass
    acks.advance(len(frames))
"""
        found = robustness.check_module(
            "detectmateservice_tpu/engine/x.py", src)
        assert [f.rule for f in found] == ["DM-R001"]
        assert "swallows" in found[0].message

    def test_tuple_catch_including_broad_fires_once(self):
        src = """
def tick(obj):
    try:
        obj.poll()
    except (ValueError, Exception):
        return None
"""
        found = robustness.check_module("detectmateservice_tpu/y.py", src)
        assert [f.rule for f in found] == ["DM-R001"]

    def test_fingerprint_is_line_stable(self):
        """Moving the handler down a line must not change the fingerprint
        (fingerprints key baseline suppressions across refactors)."""
        src = "def f(x):\n    try:\n        x()\n    except Exception:\n        pass\n"
        shifted = "\n\n" + src
        (a,) = robustness.check_module("detectmateservice_tpu/z.py", src)
        (b,) = robustness.check_module("detectmateservice_tpu/z.py", shifted)
        assert a.fingerprint == b.fingerprint

    def test_two_swallows_in_one_scope_get_distinct_keys(self):
        src = """
def f(x):
    try:
        x()
    except Exception:
        pass
    try:
        x()
    except Exception:
        pass
"""
        found = robustness.check_module("detectmateservice_tpu/w.py", src)
        assert len(found) == 2
        assert found[0].key != found[1].key


class TestRobustnessClean:
    def test_logged_counted_raised_or_used_is_clean(self):
        src = """
import logging

log = logging.getLogger(__name__)


def a(x):
    try:
        x()
    except Exception:
        log.warning("a failed")


def b(x, m):
    try:
        x()
    except Exception:
        m.ERRORS().inc()


def c(x):
    try:
        x()
    except Exception:
        raise


def d(x):
    try:
        x()
    except Exception as exc:
        return str(exc)


def e(x, stats):
    try:
        x()
    except Exception:
        stats.dropped += 1
"""
        assert robustness.check_module(
            "detectmateservice_tpu/clean.py", src) == []

    def test_narrow_and_bare_excepts_are_out_of_scope(self):
        # narrow catches are legitimate; bare except is DM-B002's finding
        src = """
def f(x):
    try:
        x()
    except ValueError:
        pass
    try:
        x()
    except:
        pass
"""
        assert robustness.check_module(
            "detectmateservice_tpu/n.py", src) == []

    def test_tests_and_scripts_are_out_of_scope(self):
        src = "def f(x):\n    try:\n        x()\n    except Exception:\n        pass\n"
        assert robustness.check_module("tests/test_x.py", src) == []
        assert robustness.check_module("scripts/soak.py", src) == []

    def test_pragma_suppresses(self):
        src = """
def f(x):
    try:
        x()
    # dmlint: ignore[DM-R001] probe teardown: failure means already closed
    except Exception:
        pass
"""
        pragmas = scan_pragmas(src)
        assert robustness.check_module(
            "detectmateservice_tpu/p.py", src, pragmas=pragmas) == []


# ---------------------------------------------------------------------------
# known-bad corpus: event contract (DM-E, both directions)
# ---------------------------------------------------------------------------
class TestEventContractKnownBad:
    @staticmethod
    def _make_event_repo(tmp_path, registry, emit_kind, gated=None,
                         documented=None):
        pkg = tmp_path / "detectmateservice_tpu"
        (pkg / "engine").mkdir(parents=True)
        entries = "\n".join(f'    "{k}": "doc",' for k in registry)
        (pkg / "engine" / "health.py").write_text(
            "EVENT_KINDS = {\n" + entries + "\n}\n")
        (pkg / "emitter.py").write_text(
            "def emit(monitor):\n"
            f'    monitor.emit_event({{"kind": "{emit_kind}"}})\n')
        docs = tmp_path / "docs"
        docs.mkdir()
        documented = registry if documented is None else documented
        (docs / "prometheus.md").write_text(
            "\n".join(f"| `{k}` | doc |" for k in documented) + "\n")
        scripts = tmp_path / "scripts"
        scripts.mkdir()
        gates = "\n".join(
            f'    check("{k}", "{k}" in kinds)' for k in (gated or []))
        (scripts / "soak.py").write_text(
            "def gate(kinds, check):\n" + (gates or "    pass") + "\n")

    def test_unregistered_emitted_kind_fires_once(self, tmp_path):
        self._make_event_repo(tmp_path, registry=["known_kind"],
                              emit_kind="ghost_kind",
                              documented=["known_kind", "ghost_kind"])
        found = contracts.check_events_contract(tmp_path)
        e001 = [f for f in found if f.rule == "DM-E001"]
        assert len(e001) == 1 and "ghost_kind" in e001[0].message

    def test_registered_but_never_emitted_kind_fires_once(self, tmp_path):
        self._make_event_repo(tmp_path,
                              registry=["emitted_kind", "rotted_kind"],
                              emit_kind="emitted_kind")
        found = contracts.check_events_contract(tmp_path)
        e002 = [f for f in found if f.rule == "DM-E002"]
        assert len(e002) == 1 and "rotted_kind" in e002[0].message

    def test_undocumented_kind_fires_once(self, tmp_path):
        self._make_event_repo(tmp_path, registry=["emitted_kind"],
                              emit_kind="emitted_kind", documented=[])
        found = contracts.check_events_contract(tmp_path)
        e003 = [f for f in found if f.rule == "DM-E003"]
        assert len(e003) == 1 and "emitted_kind" in e003[0].message

    def test_gated_but_never_emitted_kind_fires_once(self, tmp_path):
        self._make_event_repo(tmp_path, registry=["emitted_kind"],
                              emit_kind="emitted_kind",
                              gated=["emitted_kind", "never_emitted"])
        found = contracts.check_events_contract(tmp_path)
        e004 = [f for f in found if f.rule == "DM-E004"]
        assert len(e004) == 1 and "never_emitted" in e004[0].message

    def test_clean_event_repo_is_clean(self, tmp_path):
        self._make_event_repo(tmp_path, registry=["emitted_kind"],
                              emit_kind="emitted_kind",
                              gated=["emitted_kind"])
        assert contracts.check_events_contract(tmp_path) == []


# ---------------------------------------------------------------------------
# analyzer precision: the clean corpus produces zero findings
# ---------------------------------------------------------------------------
class TestCleanCorpus:
    CLEAN = """
import threading, time

MODULE_LOCK = threading.Lock()
_things = []


class Clean:
    def __init__(self):
        self._lock = threading.Lock()
        self._state = {}
        self._sock = object()
        self._setup()          # construction-time helper: exempt

    def _setup(self):
        self._state["k"] = 1   # unguarded but pre-publication

    def update(self, k, v):
        with self._lock:
            self._state[k] = v

    def read(self, k):
        with self._lock:
            return self._state.get(k)

    def _locked_only_helper(self):
        # called exclusively under the lock: inherits the guard
        self._state["h"] = 2

    def bump(self):
        with self._lock:
            self._locked_only_helper()

    def send(self, data):
        # serializer with: the lock exists to serialize this one call
        with self._lock:
            self._sock.sendall(data)

    def run(self, items):
        # dmlint: hot-loop
        for item in items:
            self.update("k", item)
"""

    def test_zero_lock_findings(self):
        assert locks.check_module("clean.py", self.CLEAN) == []

    def test_zero_hot_loop_findings(self):
        assert hotloop.check_module("clean.py", self.CLEAN) == []

    def test_zero_basic_findings(self):
        assert basic.check_source("clean.py", self.CLEAN) == []

    def test_pragma_suppresses_with_justification(self):
        src = """
import threading

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []

    def put(self, item):
        with self._lock:
            self._items.append(item)

    def size(self):
        # dmlint: ignore[DM-L001] sampling: a stale length only skews a gauge
        return len(self._items)
"""
        assert lock_findings(src, "DM-L001") == []

    def test_bare_pragma_is_itself_reported(self):
        index = scan_pragmas("x = 1  # dmlint: ignore[DM-L001]\n")
        assert index.bare_ignores == [1]

    def test_guarded_by_pragma_establishes_guard(self):
        src = """
import threading

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        # dmlint: guarded-by(_lock)
        self._flag = False

    def read(self):
        return self._flag
"""
        found = lock_findings(src, "DM-L001")
        assert len(found) == 1 and "read" in found[0].message

    AFFINITY_CLEAN = """
import threading


class CleanRouter:
    def __init__(self):
        self._lock = threading.Lock()
        self._requeue = []
        self._policy = "round_robin"

    # dmlint: thread(engine)
    def dispatch(self, sock, wire):
        sock.send(wire)             # engine-owned socket op: fine
        self._push(wire)            # propagation: _push inherits engine

    def _push(self, wire):
        with self._lock:
            self._requeue.append(wire)

    # dmlint: thread(supervisor)
    def apply(self, result):
        with self._lock:            # lock-guarded cross-domain state: fine
            self._requeue.append(result)

    # dmlint: thread(any)
    def snapshot(self):
        with self._lock:
            return list(self._requeue)

    # dmlint: thread(supervisor)
    def read_policy(self):
        return self._policy         # init-only binding: no guard needed
"""

    def test_zero_affinity_findings_on_clean_corpus(self):
        assert affinity.check_project(
            [("detectmateservice_tpu/clean.py", self.AFFINITY_CLEAN)]) == []

    def test_affinity_ignore_pragma_suppresses(self):
        src = """
class Shared:
    def __init__(self):
        self._count = 0

    # dmlint: thread(engine)
    def bump(self):
        self._count += 1

    # dmlint: thread(admin)
    def read(self):
        # dmlint: ignore[DM-A002] GIL-atomic int read; staleness only skews a gauge
        return self._count
"""
        assert affinity.check_project(
            [("detectmateservice_tpu/s.py", src)]) == []

    DURABILITY_CLEAN = """
import json
import os


def fsync_dir(directory):
    fd = os.open(directory, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def write_json_atomic(path, doc):
    tmp = path.with_name(path.name + ".tmp")
    with open(tmp, "w", encoding="utf-8") as fh:
        fh.write(json.dumps(doc))
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)
    fsync_dir(path.parent)


def open_segment(path):
    return open(path, "ab", buffering=0)


def read_manifest(path):
    return json.loads(open(path).read())
"""

    def test_zero_durability_findings_on_clean_corpus(self):
        assert durability.check_module(
            "detectmateservice_tpu/wal/clean.py", self.DURABILITY_CLEAN) == []


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------
class TestBaseline:
    def test_todo_justification_fails_the_gate(self, tmp_path):
        from detectmateservice_tpu.analysis.findings import Finding

        path = tmp_path / "dmlint-baseline.json"
        write_baseline(path, [Finding("DM-L001", "a.py", 3, "m", key="K")])
        baseline, meta = load_baseline(path)
        assert baseline == {}          # TODO entries never suppress
        assert [m.rule for m in meta] == ["DM-X001"]

    def test_justified_entry_suppresses(self, tmp_path):
        path = tmp_path / "dmlint-baseline.json"
        path.write_text(json.dumps({"suppressions": [{
            "rule": "DM-L001", "fingerprint": "DM-L001:a.py:K",
            "justification": "benign: documented handoff race"}]}))
        baseline, meta = load_baseline(path)
        assert baseline == {"DM-L001:a.py:K": "benign: documented handoff race"}
        assert meta == []

    def test_stale_entry_is_reported(self, tmp_path):
        # a baseline entry matching nothing must fail the whole-repo run
        src_dir = tmp_path / "detectmateservice_tpu"
        src_dir.mkdir()
        (tmp_path / "clean.py").write_text("x = 1\n")
        path = tmp_path / "dmlint-baseline.json"
        path.write_text(json.dumps({"suppressions": [{
            "rule": "DM-L001", "fingerprint": "DM-L001:gone.py:K",
            "justification": "the code this covered was deleted"}]}))
        result = run(tmp_path, paths=None, baseline_path=path)
        stale = [f for f in result["active"] if f.rule == "DM-X002"]
        assert len(stale) == 1


# ---------------------------------------------------------------------------
# the real tree
# ---------------------------------------------------------------------------
class TestRealTree:
    def test_repo_root_derivation(self):
        assert default_repo_root() == REPO

    def test_repo_is_clean_with_every_suppression_justified(self):
        """THE acceptance gate: detectmate-lint exits 0 on this repository
        and every baseline entry both matches a live finding and carries a
        real justification (DM-X001/DM-X002 otherwise surface as active)."""
        result = run(REPO)
        active = result["active"]
        assert active == [], "\n".join(f.render() for f in active)
        # the suppressions that do exist are justified (none TODO)
        baseline = result["baseline"]
        assert all(why and not why.upper().startswith("TODO")
                   for why in baseline.values())

    def test_cli_exit_code_contract(self, capsys):
        assert main([]) == 0
        captured = capsys.readouterr()
        assert "finding(s)" in captured.err

    def test_known_series_set_matches_runtime_registry(self):
        """The contract checker's AST-parsed series set must equal the
        runtime REGISTERED_SERIES — if the declaration idiom in metrics.py
        changes shape, the checker must break loudly, not skip silently."""
        from detectmateservice_tpu.engine import metrics as m

        parsed = contracts.declared_series(
            REPO / "detectmateservice_tpu" / "engine" / "metrics.py")
        assert set(parsed) == set(m.REGISTERED_SERIES)

    def test_settings_fields_match_runtime_model(self):
        from detectmateservice_tpu.settings import ServiceSettings

        parsed = contracts.settings_fields(
            REPO / "detectmateservice_tpu" / "settings.py")
        assert set(parsed) == set(ServiceSettings.model_fields)

    def test_declared_routes_match_runtime_table(self):
        """The route checker's AST-parsed table must equal the runtime
        ROUTES declarations — if the declaration idiom in web/router.py
        changes shape, the checker must break loudly, not skip silently."""
        from detectmateservice_tpu.web.router import ROUTES

        parsed = contracts.declared_routes(
            REPO / "detectmateservice_tpu" / "web" / "router.py")
        assert set(parsed) == {f"{r.method} {r.path}" for r in ROUTES}

    def test_event_registry_matches_runtime_and_emit_sites(self):
        """The AST-parsed EVENT_KINDS must equal the runtime registry, and
        every kind the AST walker extracts from the emit sites must be
        registered — the DM-E gate's own parity pin (if the declaration
        idiom changes shape, break loudly, not silently)."""
        from detectmateservice_tpu.engine.health import EVENT_KINDS

        parsed = contracts.declared_event_kinds(
            REPO / "detectmateservice_tpu" / "engine" / "health.py")
        assert set(parsed) == set(EVENT_KINDS)
        emitted = contracts.emitted_event_kinds(REPO)
        assert set(emitted) == set(EVENT_KINDS)

    def test_soak_gated_kind_extraction_sees_the_known_gates(self):
        gated = contracts.soak_gated_kinds(REPO / "scripts" / "soak.py")
        assert {"replica_drain", "model_canary_holdback"} <= set(gated)

    def test_affinity_sees_the_real_seams(self):
        """The pragma sweep landed: the spool/router engine seams and the
        supervisor/watchdog/rollout entry points are machine-readable."""
        from detectmateservice_tpu.analysis.cli import iter_py_files

        files = []
        for path in iter_py_files(REPO):
            rel = path.resolve().relative_to(REPO).as_posix()
            if rel.startswith("detectmateservice_tpu/"):
                files.append((rel, path.read_text(encoding="utf-8")))
        project = affinity._build_project(files, set())
        assert project.ownership["IngressSpool"]["append"] == "engine"
        assert project.ownership["IngressSpool"]["tick"] == "engine"
        assert project.ownership["ReplicaRouter"]["dispatch"] == "engine"
        assert project.ownership["ReplicaRouter"]["tick"] == "engine"
        assert project.ownership["ReplicaRouter"]["apply_probe"] == "any"
        sup = next(c for c in project.classes
                   if c.name == "ReplicaSupervisor")
        assert sup.methods["poll_once"].declared == "supervisor"
        # the supervisor's router seam is TYPED, so a future off-thread
        # call there resolves (the PR 9 regression stays detectable)
        assert sup.attr_types["_router"] == "ReplicaRouter"

    def test_marker_lint_sees_registered_markers(self):
        regs = markers.registered_markers(REPO / "pyproject.toml")
        assert "slow" in regs

    def test_shim_is_invocable(self):
        """scripts/static_check.py keeps working and stays standalone."""
        import subprocess
        import sys

        proc = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "static_check.py"),
             "--list-rules"],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0
        assert "DM-L001" in proc.stdout


# ---------------------------------------------------------------------------
# SARIF output + diff-aware mode (the CI annotation surface)
# ---------------------------------------------------------------------------
class TestSarifAndDiffMode:
    def test_sarif_schema_shape(self):
        from detectmateservice_tpu.analysis.findings import Finding

        finding = Finding("DM-A001", "pkg/mod.py", 42, "off-thread call",
                          hint="move it", key="K")
        doc = to_sarif([finding], suppressed=[
            Finding("DM-L001", "pkg/other.py", 7, "benign race", key="S")])
        assert doc["version"] == "2.1.0"
        assert "sarif-schema-2.1.0" in doc["$schema"]
        (run_doc,) = doc["runs"]
        driver = run_doc["tool"]["driver"]
        assert driver["name"] == "detectmate-lint"
        rule_ids = {r["id"] for r in driver["rules"]}
        assert {"DM-A001", "DM-D001", "DM-E001"} <= rule_ids
        active, suppressed = run_doc["results"]
        assert active["ruleId"] == "DM-A001"
        assert active["level"] == "error"
        loc = active["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "pkg/mod.py"
        assert loc["region"]["startLine"] == 42
        assert active["partialFingerprints"]["dmlintFingerprint/v1"] \
            == finding.fingerprint
        assert "move it" in active["message"]["text"]
        # baseline-suppressed findings ride along marked suppressed, so
        # code scanning shows them as dismissed instead of resurfacing them
        assert suppressed["suppressions"][0]["kind"] == "external"
        json.dumps(doc)    # must be plain-JSON serializable

    def test_cli_sarif_output_parses(self, capsys):
        assert main(["--format", "sarif"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["runs"][0]["tool"]["driver"]["name"] == "detectmate-lint"

    def test_changed_mode_filters_to_diffed_files(self, capsys):
        """--changed HEAD exits clean on a tree whose full gate is clean
        (the filter can only shrink the finding set)."""
        assert main(["--changed", "HEAD"]) == 0

    def test_changed_files_helper_handles_bad_ref(self):
        from detectmateservice_tpu.analysis.cli import changed_files

        assert changed_files(REPO, "no-such-ref-anywhere") is None


# ---------------------------------------------------------------------------
# sanitizer wiring (static checks; the instrumented run is CI's
# native-sanitize job / scripts/native_sanitize.sh)
# ---------------------------------------------------------------------------
class TestSanitizerWiring:
    def test_build_script_knows_sanitize_modes(self):
        text = (REPO / "native" / "build.sh").read_text()
        assert "--sanitize=" in text
        assert "thread" in text and "address" in text

    def test_runner_script_exists_and_covers_both_modes(self):
        text = (REPO / "scripts" / "native_sanitize.sh").read_text()
        assert "libasan" in text and "libtsan" in text
        assert "test_native_kernels.py" in text
        assert "test_native_transport.py" in text

    def test_ci_has_sanitize_job(self):
        import yaml

        doc = yaml.safe_load((REPO / ".github" / "workflows" / "ci.yml").read_text())
        assert "native-sanitize" in doc["jobs"]
        steps = " ".join(str(s.get("run", ""))
                         for s in doc["jobs"]["native-sanitize"]["steps"])
        assert "native_sanitize.sh" in steps

    def test_ci_static_job_uploads_sarif_and_runs_diff_aware_on_prs(self):
        import yaml

        doc = yaml.safe_load((REPO / ".github" / "workflows" / "ci.yml").read_text())
        static = doc["jobs"]["static"]
        assert static["permissions"]["security-events"] == "write"
        runs = " ".join(str(s.get("run", "")) for s in static["steps"])
        uses = " ".join(str(s.get("uses", "")) for s in static["steps"])
        assert "--changed origin/" in runs       # PR fail-fast mode
        assert "--format sarif" in runs
        assert "upload-sarif" in uses
        # the full unfiltered gate still runs (push-to-main branch)
        conds = [str(s.get("if", "")) for s in static["steps"]
                 if "static_check.py" in str(s.get("run", ""))
                 and "--changed" not in str(s.get("run", ""))
                 and "sarif" not in str(s.get("run", ""))]
        assert any("pull_request" in c for c in conds)

    def test_precommit_hook_is_diff_aware(self):
        import yaml

        doc = yaml.safe_load((REPO / ".pre-commit-config.yaml").read_text())
        local = next(r for r in doc["repos"] if r["repo"] == "local")
        hook = next(h for h in local["hooks"] if h["id"] == "detectmate-lint")
        assert "--changed HEAD" in hook["entry"]
