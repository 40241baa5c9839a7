"""The detector's process: the program's own entry,
``detectmateservice_tpu.cli.main`` — what ``python -m detectmateservice_tpu.cli``
runs — with one reading added that the program does not export.

On the attached v5e jax's allocator statistics (``Device.memory_stats()``,
the program's ``device_hbm_bytes`` gauge) count the arrays a process holds and
not the scratch memory of a running executable: sampled twice a second through
a window in which 16384-row calls ran back to back they never passed 281 MB,
while XLA's buffer assignment for that executable holds 2.5 GiB of
temporaries (PERF.md section 2, PR 23). So for every executable this process
compiles ahead of time (``jax.stages.Lowered.compile``: the program's warm
set, its widest bucket among them) XLA's own account,
``Compiled.memory_analysis()``, is written to ``--programs`` as one JSON line,
with the shapes of its integer arguments (the token batch names the bucket).
That happens where the program compiles, during boot; nothing is added to a
scoring call. On the way out the allocator's own statistics are written too,
so that a runtime that does count scratch memory shows.

    python3 benchmark/lib/stage_main.py --programs <file> --settings <yaml>
"""
from __future__ import annotations

import atexit
import json
import os
import sys
import time


def _platform_of(compiled) -> str:
    import jax

    for sharding in jax.tree_util.tree_leaves(compiled.input_shardings):
        for device in sharding.device_set:
            return str(device.platform)
    return ""


def record_compiles(path: str) -> None:
    """Wrap ``jax.stages.Lowered.compile`` so that each executable's memory
    account is appended to ``path``. Importing jax here initialises no
    backend: the program still pins its platform before its first jax op."""
    import jax
    import jax.stages

    original = jax.stages.Lowered.compile
    state = {"exit_hooked": False}

    def write(record: dict) -> None:
        with open(path, "a", encoding="utf-8") as fh:
            fh.write(json.dumps(record) + "\n")

    def at_exit() -> None:
        try:
            stats = [dict(d.memory_stats() or {}) for d in jax.local_devices()]
            write({"allocator_at_exit": [
                {k: int(v) for k, v in s.items()
                 if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
                for s in stats]})
        except Exception:  # noqa: BLE001 — a reading, never a reason to fail
            pass

    def compile_and_record(self, *args, **kwargs):
        compiled = original(self, *args, **kwargs)
        try:
            if not state["exit_hooked"]:
                # registered after jax's own exit hooks, so it runs before
                # they take the backend down
                atexit.register(at_exit)
                state["exit_hooked"] = True
            stats = (compiled.memory_analysis()
                     or compiled.runtime_executable()
                     .get_compiled_memory_stats())
            leaves = jax.tree_util.tree_leaves(compiled.args_info)
            write({
                "t": time.monotonic(),
                "platform": _platform_of(compiled),
                "int_args": [list(leaf.shape) for leaf in leaves
                             if "int" in str(leaf.dtype)],
                "temp_bytes": int(stats.temp_size_in_bytes),
                "argument_bytes": int(stats.argument_size_in_bytes),
                "output_bytes": int(stats.output_size_in_bytes),
                "alias_bytes": int(stats.alias_size_in_bytes),
                "code_bytes": int(stats.generated_code_size_in_bytes),
            })
        except Exception:  # noqa: BLE001 — a reading, never a reason to fail
            pass
        return compiled

    jax.stages.Lowered.compile = compile_and_record


def main(argv) -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    # run as a script, this directory leads sys.path: its modules (prom,
    # schedule, stages ...) must not shadow anything the program imports
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    if len(argv) >= 2 and argv[0] == "--programs":
        record_compiles(argv[1])
        argv = argv[2:]
    from detectmateservice_tpu import cli

    return cli.main(argv)


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
