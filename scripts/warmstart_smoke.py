"""CI smoke for dmwarm AOT warm-start serving: two sequential CPU boots
sharing ONE persistent compile-cache dir.

Each boot runs in its own child interpreter (``--boot``), because
``enable_compilation_cache`` is deliberately once-per-process — exactly the
replica-restart shape the feature exists for. The directory reaches the
children the way a deployment places it: ``JAX_COMPILATION_CACHE_DIR``. Run
by hand it is a fixed sub-directory of the in-checkout cache, emptied first
(the path is part of what the second boot must find again, so it is never a
temp name). Boot #1 starts against an empty cache: its warm-up AOT-compiles
the whole warm bucket set (misses populate the shared dir) and the first
dispatch afterwards must record **zero** ledger compiles — the boot→ACTIVE
honesty gate. Boot #2 repeats the identical boot against the now-warm cache
and must additionally show ``hits > 0`` with ``misses == 0`` and a lower
warm-up wall time.

Exit 0 only when:

* both boots reach ``warmup_complete`` before their first dispatch and
  that dispatch records zero ledger compiles (AOT executables serve it);
* boot #2's compile cache counters show ``hits > 0`` and ``misses == 0``;
* boot #2's warm-up wall time is below boot #1's;
* neither boot records an unexpected recompile.

``--out`` writes both boots' full ledger rings + the verdict as JSON (the
CI artifact, same pattern as shed-smoke).
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

MARKER = "@@WARMSTART "

# small enough to boot in seconds on one CPU core, big enough that the warm
# set spans the small/train/max bucket ladder like a real scorer
BOOT_CONFIG = {
    "method_type": "jax_scorer", "auto_config": False, "model": "mlp",
    "data_use_training": 32, "train_epochs": 1, "threshold_sigma": 4.0,
    "seq_len": 16, "dim": 32, "max_batch": 64, "pipeline_depth": 2,
    "dtype": "float32",
}


def boot() -> None:
    """One replica boot: arm the shared cache (placed by
    JAX_COMPILATION_CACHE_DIR), AOT warm-up, first dispatch, report the
    ledger story. Runs in a child interpreter."""
    import numpy as np

    from detectmateservice_tpu.engine import device_obs
    from detectmateservice_tpu.library.detectors import JaxScorerDetector
    from detectmateservice_tpu.utils.profiling import enable_compilation_cache

    armed = enable_compilation_cache()
    ledger = device_obs.get_ledger()
    det = JaxScorerDetector(
        config={"detectors": {"JaxScorerDetector": dict(BOOT_CONFIG)}})
    t0 = time.perf_counter()
    det.setup_io()
    warmup_s = time.perf_counter() - t0
    warm_snap = ledger.snapshot()
    # the acceptance dispatch: every bucket was AOT-compiled at setup_io,
    # so this must not add a single compile event to the ledger
    det.score_tokens(np.zeros((BOOT_CONFIG["max_batch"],
                               BOOT_CONFIG["seq_len"]), np.int32))
    after = ledger.snapshot()
    payload = {
        "armed_dir": armed,
        "warmup_s": round(warmup_s, 3),
        "warmup_complete_before_dispatch": warm_snap["warmup_complete"],
        "phases": after["warmup_phases"],
        "cache": after["compile_cache"],
        "compiles_at_warmup": warm_snap["totals"]["compiles"],
        "dispatch_compiles": (after["totals"]["compiles"]
                              - warm_snap["totals"]["compiles"]),
        "unexpected": after["totals"]["unexpected"],
        "ledger_ring": after["compiles"],
    }
    sys.stdout.write(MARKER + json.dumps(payload) + "\n")
    sys.stdout.flush()


def run_boot(cache_dir: str, timeout_s: float = 600.0) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--boot"],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    for line in proc.stdout.splitlines():
        if line.startswith(MARKER):
            return json.loads(line[len(MARKER):])
    raise SystemExit(
        f"boot child produced no result (rc={proc.returncode}):\n"
        f"{proc.stderr[-2000:]}")


def main() -> int:
    out_path = None
    if "--out" in sys.argv:
        out_path = sys.argv[sys.argv.index("--out") + 1]
    from detectmateservice_tpu.utils.profiling import DEFAULT_CACHE_DIR

    cache_dir = os.path.join(DEFAULT_CACHE_DIR, "warmstart_smoke")
    shutil.rmtree(cache_dir, ignore_errors=True)  # boot #1 must be cold

    print(f"warmstart_smoke: shared cache dir {cache_dir}")
    cold = run_boot(cache_dir)
    print(f"  boot#1 (cold): warmup {cold['warmup_s']}s, "
          f"cache {cold['cache']}, dispatch_compiles "
          f"{cold['dispatch_compiles']}")
    warm = run_boot(cache_dir)
    print(f"  boot#2 (warm): warmup {warm['warmup_s']}s, "
          f"cache {warm['cache']}, dispatch_compiles "
          f"{warm['dispatch_compiles']}")

    checks = []

    def check(name: str, ok: bool, detail: str = "") -> None:
        checks.append({"name": name, "ok": bool(ok), "detail": detail})
        print(f"  [{'ok' if ok else 'FAIL'}] {name}"
              + (f" — {detail}" if detail else ""))

    for tag, b in (("cold", cold), ("warm", warm)):
        check(f"{tag}_cache_armed", b["armed_dir"] is not None,
              str(b["armed_dir"]))
        check(f"{tag}_warmup_complete_before_dispatch",
              b["warmup_complete_before_dispatch"])
        check(f"{tag}_zero_dispatch_compiles", b["dispatch_compiles"] == 0,
              f"dispatch_compiles={b['dispatch_compiles']}")
        check(f"{tag}_zero_unexpected", b["unexpected"] == 0,
              f"unexpected={b['unexpected']}")
        check(f"{tag}_aot_phase_recorded", "aot" in b["phases"],
              str(b["phases"]))
    check("warm_boot_cache_hits", warm["cache"]["hits"] > 0,
          f"hits={warm['cache']['hits']}")
    check("warm_boot_zero_misses", warm["cache"]["misses"] == 0,
          f"misses={warm['cache']['misses']}")
    check("warm_boot_faster", warm["warmup_s"] < cold["warmup_s"],
          f"{warm['warmup_s']}s vs {cold['warmup_s']}s")

    ok = all(c["ok"] for c in checks)
    verdict = {
        "ok": ok,
        "cache_dir": cache_dir,
        "checks": checks,
        "cold": cold,
        "warm": warm,
    }
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fh:
            json.dump(verdict, fh, indent=1)
        print(f"warmstart_smoke: verdict -> {out_path}")
    print(f"warmstart_smoke: {'OK' if ok else 'FAILED'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if len(sys.argv) > 1 and sys.argv[1] == "--boot":
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        boot()
    else:
        sys.exit(main())
