"""Chip bring-up contracts (PR 21), checked on the CPU.

What must hold before a chip run means anything:

* the compile cache is placed from outside — ``JAX_COMPILATION_CACHE_DIR``
  wins and the program then never updates ``jax_compilation_cache_dir``;
  unset, the cache goes to one fixed in-checkout path, never a temp name;
* ``backend: tpu`` without a TPU is a boot failure, not a CPU run;
* kernel routing (compiled vs interpret-mode Pallas, flash vs einsum) follows
  the platform of the device the scorer was placed on;
* a kept AOT executable that rejects its arguments raises — no quiet retrace;
* the host twin's and the native featurizer's absence is visible;
* ``chip_smoke.py`` runs green in its explicit CPU rehearsal and exits
  non-zero, printing no result, without a chip; ``bench.py`` likewise prints
  no rate.
"""
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np
import pytest

from detectmateservice_tpu.engine import device_obs
from detectmateservice_tpu.library.common.core import LibraryError
from detectmateservice_tpu.utils import backend, profiling

REPO = Path(__file__).resolve().parent.parent


def _run(args, timeout=300, **env):
    """A repo script in a child interpreter, CPU-only like this sandbox."""
    full_env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    full_env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, *args], cwd=str(REPO),
                          capture_output=True, text=True, timeout=timeout,
                          env=full_env)


def make_detector(**overrides):
    from detectmateservice_tpu.library.detectors import JaxScorerDetector

    base = {"method_type": "jax_scorer", "auto_config": False,
            "model": "mlp", "data_use_training": 16, "seq_len": 8, "dim": 16,
            "vocab_size": 512, "max_batch": 16, "train_batch_size": 8,
            "async_fit": False}
    base.update(overrides)
    return JaxScorerDetector(config={"detectors": {"JaxScorerDetector": base}})


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------
class TestCacheDirResolution:
    def test_env_names_exactly_the_directory(self, monkeypatch):
        monkeypatch.setenv(profiling.CACHE_DIR_ENV, "/srv/xla-cache")
        assert profiling.resolve_cache_dir() == ("/srv/xla-cache", True)
        # the compile_cache_dir setting does not override it, and nothing
        # (fingerprint, pid) is appended
        assert profiling.resolve_cache_dir("/etc/from/settings") == (
            "/srv/xla-cache", True)

    def test_unset_is_one_fixed_in_checkout_path(self, monkeypatch):
        monkeypatch.delenv(profiling.CACHE_DIR_ENV, raising=False)
        first, from_env = profiling.resolve_cache_dir()
        assert not from_env
        assert first == str(REPO / ".jax_cache")
        assert profiling.resolve_cache_dir()[0] == first   # never moves
        assert not first.startswith(tempfile.gettempdir())
        assert str(os.getpid()) not in first
        assert profiling.resolve_cache_dir("/var/lib/dm/xla") == (
            "/var/lib/dm/xla", False)

    @pytest.fixture()
    def fresh_cache_state(self, monkeypatch):
        """enable_compilation_cache decides once per process: rewind that,
        record (not apply) the jax config updates it makes, and give it a
        throwaway ledger to arm."""
        import jax

        updates = []
        monkeypatch.setattr(profiling, "_cache_enabled", False)
        monkeypatch.setattr(profiling, "_cache_dir", None)
        monkeypatch.setattr(jax.config, "update",
                            lambda key, value: updates.append((key, value)))
        prev = device_obs.activate(device_obs.CompileLedger())
        yield updates
        device_obs.activate(prev)

    def test_env_set_makes_no_cache_dir_update(self, monkeypatch, tmp_path,
                                               fresh_cache_state):
        placed = str(tmp_path / "placed")
        monkeypatch.setenv(profiling.CACHE_DIR_ENV, placed)
        assert profiling.enable_compilation_cache("/from/settings") == placed
        assert os.path.isdir(placed)
        assert "jax_compilation_cache_dir" not in dict(fresh_cache_state)
        assert device_obs.get_ledger().cache_armed

    def test_setting_places_it_when_env_is_unset(self, monkeypatch, tmp_path,
                                                 fresh_cache_state):
        monkeypatch.delenv(profiling.CACHE_DIR_ENV, raising=False)
        wanted = str(tmp_path / "from-settings")
        assert profiling.enable_compilation_cache(wanted) == wanted
        assert dict(fresh_cache_state)["jax_compilation_cache_dir"] == wanted

    def test_off_on_cpu_when_nothing_names_a_directory(self, monkeypatch,
                                                       fresh_cache_state):
        monkeypatch.delenv(profiling.CACHE_DIR_ENV, raising=False)
        assert profiling.enable_compilation_cache() is None
        assert fresh_cache_state == []
        assert not device_obs.get_ledger().cache_armed

    def test_unusable_directory_raises_naming_the_variable(
            self, monkeypatch, tmp_path, fresh_cache_state):
        """An installed package's default sits beside site-packages; a
        directory the process cannot create is a loud failure that says how
        to place the cache, and the failure does not stick as "decided"."""
        monkeypatch.delenv(profiling.CACHE_DIR_ENV, raising=False)
        blocker = tmp_path / "not-a-directory"
        blocker.write_text("")
        bad = str(blocker / ".jax_cache")     # ENOTDIR even for root
        with pytest.raises(profiling.CompileCacheError) as err:
            profiling.enable_compilation_cache(bad)
        assert profiling.CACHE_DIR_ENV in str(err.value)
        assert bad in str(err.value)
        assert fresh_cache_state == []          # no half-applied config
        assert not device_obs.get_ledger().cache_armed
        # a second call is a second attempt, not a silent None
        with pytest.raises(profiling.CompileCacheError):
            profiling.enable_compilation_cache(bad)
        good = str(tmp_path / "good")
        assert profiling.enable_compilation_cache(good) == good

    def test_read_only_directory_is_refused(self, monkeypatch, tmp_path,
                                            fresh_cache_state):
        monkeypatch.delenv(profiling.CACHE_DIR_ENV, raising=False)
        ro = tmp_path / "ro"
        ro.mkdir()
        monkeypatch.setattr(os, "access", lambda path, mode: False)
        with pytest.raises(profiling.CompileCacheError, match="not writable"):
            profiling.enable_compilation_cache(str(ro))

    def test_no_temp_names_in_cache_paths_outside_tests(self):
        """`tempfile` may not mint a cache directory anywhere but here."""
        offenders = []
        files = [REPO / "bench.py", REPO / "chip_smoke.py",
                 *(REPO / "scripts").glob("*.py"),
                 *(REPO / "detectmateservice_tpu").rglob("*.py")]
        for path in files:
            for n, line in enumerate(path.read_text().splitlines(), 1):
                if ("mkdtemp" in line or "gettempdir" in line) and (
                        "cache" in line.lower() or "dmwarm" in line.lower()):
                    offenders.append(f"{path.relative_to(REPO)}:{n}")
        assert not offenders, offenders


class TestCacheCounters:
    def test_hits_and_misses_come_from_jax_events_not_durations(self):
        ledger = device_obs.CompileLedger()
        ledger.record_cache_lookup(True)           # not armed: silent
        assert ledger.snapshot()["compile_cache"] == {
            "armed": False, "hits": 0, "misses": 0}
        ledger.arm_cache_counters()
        # jax wraps compile-or-get-cached in ONE duration event: a fast
        # real compile must not read as a hit
        ledger.record_compile(0.001, bucket=8, backend="cpu", where="warmup")
        assert ledger.snapshot()["compile_cache"]["hits"] == 0
        ledger.record_cache_lookup(True)
        ledger.record_cache_lookup(False)
        ledger.record_cache_lookup(False)
        assert ledger.snapshot()["compile_cache"] == {
            "armed": True, "hits": 1, "misses": 2}


# ---------------------------------------------------------------------------
# backend: a requirement, not a hint
# ---------------------------------------------------------------------------
class TestBackendPin:
    def test_tpu_requested_on_a_cpu_process_raises(self):
        import jax

        try:
            backend.request_platform("tpu")
            with pytest.raises(backend.BackendUnavailable, match="'tpu'"):
                backend.apply_platform_pin()
        finally:
            backend.request_platform(None)
            jax.config.update("jax_platforms", "cpu")

    def test_auto_resolves_and_reports_what_jax_found(self):
        backend.request_platform("auto")
        assert backend.requested_platform() == "auto"
        assert backend.apply_platform_pin() == "cpu"

    def test_backend_tpu_without_a_tpu_fails_in_a_fresh_process(self):
        proc = _run(["-c", (
            "from detectmateservice_tpu.utils import backend\n"
            "backend.request_platform('tpu')\n"
            "backend.apply_platform_pin()\n")])
        assert proc.returncode != 0
        assert "BackendUnavailable" in proc.stderr
        assert "backend 'tpu'" in proc.stderr


# ---------------------------------------------------------------------------
# kernel routing follows the scorer's resolved platform
# ---------------------------------------------------------------------------
class TestKernelRouting:
    @pytest.fixture()
    def flash_calls(self, monkeypatch):
        import jax.numpy as jnp

        from detectmateservice_tpu.ops import flash

        calls = []

        def fake_flash(q, k, v, key_mask=None, *, interpret):
            calls.append(interpret)
            return jnp.zeros_like(q)

        monkeypatch.setattr(flash, "flash_attention", fake_flash)
        return calls

    def test_attention_routes_by_the_platform_it_is_given(self, flash_calls):
        import jax.numpy as jnp

        from detectmateservice_tpu.ops.attention import (FLASH_MIN_SEQ,
                                                         attention)

        q = jnp.zeros((1, 1, FLASH_MIN_SEQ, 8), jnp.float32)
        attention(q, q, q, impl="auto", platform="tpu")     # long → flash
        attention(q, q, q, impl="flash", platform="cpu")    # forced on CPU
        assert flash_calls == [False, True]   # compiled on tpu, interpret
        # only because the platform IS the cpu
        attention(q, q, q, impl="auto", platform="cpu")     # einsum
        short = q[:, :, :16]
        attention(short, short, short, impl="auto", platform="tpu")
        assert len(flash_calls) == 2

    def test_scorer_decides_platform_once_at_construction(self, monkeypatch):
        import jax.numpy as jnp

        from detectmateservice_tpu.models.mlp import (MLPScorer,
                                                      MLPScorerConfig)
        from detectmateservice_tpu.ops import scorehead

        assert MLPScorer(MLPScorerConfig()).config.platform == "cpu"
        seen = []
        monkeypatch.setattr(
            scorehead, "candidate_lse",
            lambda rows, emb, interpret: seen.append(interpret)
            or jnp.zeros(rows.shape[0]))
        rows, emb = jnp.zeros((4, 8)), jnp.zeros((16, 8))
        for platform in ("tpu", "cpu"):
            scorer = MLPScorer(MLPScorerConfig(platform=platform))
            assert scorer.config.platform == platform
            scorer._pallas_lse_rows(rows, emb)
        assert seen == [False, True]

    def test_detector_hands_its_device_platform_to_the_model(self):
        det = make_detector()
        det._ensure_scorer()
        assert det._exec.platform == det._exec.devices[0].platform == "cpu"
        assert det._scorer.config.platform == "cpu"
        info = det.device_info()
        assert info["platform"] == "cpu" and info["device_count"] >= 1
        assert info["device_kind"] == det._exec.devices[0].device_kind
        assert info["scorer"]["model"] == "mlp"
        assert info["host_twin"]["state"] == "pending"   # mirrors at fit
        assert info["native_featurize"]["loaded"] is True
        assert "compile_cache_dir" in info

    def test_mesh_scorer_names_every_device(self):
        import jax

        n = len(jax.devices())
        det = make_detector(mesh_shape={"data": n}, max_batch=2 * n)
        det._ensure_scorer()
        info = det.device_info()
        assert info["mesh"] == {"data": n}
        assert info["scorer_devices"] == [str(d) for d in jax.devices()]
        assert info["host_twin"]["state"] == "unsupported"

    def test_mesh_script_reads_shards_on_every_device(self):
        """scripts/chip_mesh.py (the builder's multi-chip evidence) at tiny
        size over the virtual CPU devices."""
        import importlib.util

        import jax

        spec = importlib.util.spec_from_file_location(
            "chip_mesh", REPO / "scripts" / "chip_mesh.py")
        chip_mesh = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(chip_mesh)
        n = len(jax.devices())
        report = chip_mesh.check_mesh(
            dict(vocab_size=512, dim=16, depth=1, heads=2, seq_len=8), 4 * n)
        assert report["ok"], report
        assert len(report["batch"]["shards"]) == n
        assert all(shape == [4, 8] for _, shape in report["batch"]["shards"])

    def test_device_spec_names_a_device_or_fails(self):
        from detectmateservice_tpu.library.detectors.device_executor import (
            resolve_device)

        assert resolve_device("cpu:1").id == 1
        with pytest.raises(LibraryError, match="cpu:99"):
            resolve_device("cpu:99")
        with pytest.raises(LibraryError, match="tpu:0"):
            resolve_device("tpu:0")


# ---------------------------------------------------------------------------
# nothing on the scoring path fails quietly
# ---------------------------------------------------------------------------
class TestNoQuietFallbacks:
    def test_rejected_aot_arguments_raise_instead_of_retracing(self):
        det = make_detector(host_score_max_batch=0)
        det.setup_io()
        bucket = det.config.max_batch
        assert ("score", bucket, False) in det._exec.kept_programs()
        # float rows where the kept executable was compiled for the narrow
        # integer wire format
        with pytest.raises(TypeError, match="Argument types differ"):
            det._exec.run("score", np.zeros((bucket, det.config.seq_len),
                                            np.float32))

    def test_missing_cpu_backend_is_a_visible_twin_failure(self, monkeypatch):
        import jax

        real_devices = jax.devices

        def devices(backend=None):
            if backend == "cpu":
                raise RuntimeError("Unknown backend cpu")
            return real_devices(backend)

        det = make_detector()
        monkeypatch.setattr(jax, "devices", devices)
        det._ensure_scorer()
        state = det.device_info()["host_twin"]["state"]
        assert state.startswith("failed: no CPU backend"), state
        assert det._cpu_device is None

    def test_host_twin_states_off_and_ready(self):
        off = make_detector(host_score_max_batch=0)
        off._ensure_scorer()
        assert off.device_info()["host_twin"]["state"] == "off"
        det = make_detector()
        det.setup_io()
        from detectmateservice_tpu.schemas import ParserSchema

        det.process_batch([ParserSchema(
            EventID=1, template="user <*> in", variables=[f"u{i}"],
            logID=str(i)).serialize() for i in range(16)])
        det.flush_final()
        assert det.device_info()["host_twin"]["state"] == "ready"
        assert 1 in det.device_info()["host_twin"]["warm_buckets"]

    def test_native_featurize_absence_is_logged_and_reported(
            self, monkeypatch, caplog):
        import detectmateservice_tpu.utils as utils_pkg

        monkeypatch.setitem(sys.modules,
                            "detectmateservice_tpu.utils.matchkern", None)
        monkeypatch.delattr(utils_pkg, "matchkern", raising=False)
        with caplog.at_level("WARNING"):
            det = make_detector()
        assert "native featurize library unavailable" in caplog.text
        assert det._matchkern() is None
        native = det.device_info()["native_featurize"]
        assert native["loaded"] is False and native["error"]

    def test_admin_xla_carries_the_device_block(self):
        ledger = device_obs.CompileLedger()
        assert "device" not in ledger.snapshot()
        ledger.set_device_info_provider(lambda: {"platform": "tpu"})
        assert ledger.snapshot()["device"] == {"platform": "tpu"}


# ---------------------------------------------------------------------------
# the launchers
# ---------------------------------------------------------------------------
class TestLaunchers:
    def test_chip_smoke_cpu_rehearsal_runs_green(self):
        proc = _run(["chip_smoke.py", "--rehearse-cpu"], timeout=600)
        assert proc.returncode == 0, proc.stderr[-3000:]
        report_line, verdict_line = proc.stdout.strip().splitlines()[-2:]
        # the last line is the verdict alone: exactly these keys, no more
        verdict = json.loads(verdict_line)
        assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
        assert set(verdict["device"]) == {"platform", "kind", "count"}
        assert verdict["device"]["platform"] == "cpu"
        assert isinstance(verdict["device"]["kind"], str)
        assert type(verdict["device"]["count"]) is int
        result = json.loads(report_line)["report"]
        assert result["claim"] is None and list(result)[-1] == "claim"
        assert result["device"] == verdict["device"]
        assert result["scorer"]["model"] == "logbert"
        assert result["batches"]["device_path"] > 0
        assert result["batches"]["host_path"] > 0
        assert result["rows"]["alerted_anomalies"] > 0
        assert result["featurize_rows"]["native"] > 0
        assert result["compiles"]["unexpected_after_warmup"] == 0
        # the cache is off by default on the CPU: skipped, never "passed"
        assert result["second_boot_cache_check"] == "skipped"

    def test_unflagged_chip_smoke_fails_without_a_chip(self):
        proc = _run(["chip_smoke.py"], timeout=300)
        assert proc.returncode != 0
        assert proc.stdout.strip() == "", "a result line was printed"
        assert "backend 'tpu'" in proc.stderr

    def test_bench_without_a_chip_prints_no_rate(self):
        proc = _run(["bench.py"], timeout=300)
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""
        assert "no accelerator" in proc.stderr

    def test_no_hard_exit_in_the_repo(self):
        """Interpreter teardown must run everywhere: if the attached
        runtime aborts in it, that is a finding to record, not to hide."""
        needle = "os._" + "exit"
        files = [REPO / "bench.py", REPO / "chip_smoke.py",
                 REPO / "__graft_entry__.py"]
        for sub in ("scripts", "detectmateservice_tpu", "tests", "examples"):
            files.extend((REPO / sub).rglob("*.py"))
        offenders = [str(p.relative_to(REPO)) for p in files
                     if needle in p.read_text()]
        assert not offenders, offenders
