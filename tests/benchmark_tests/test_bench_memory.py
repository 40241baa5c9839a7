"""Peak device memory: the allocator's reading of the drained process plus
the scratch memory of the widest executable the window dispatched, and the
stage entry that writes XLA's account of each executable down."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import json
import os
import subprocess
import sys

import pytest

from bench_helpers import REPO
from benchmark.lib import memory, prom

GIB = 2 ** 30
PROGRAMS = [
    {"platform": "tpu", "int_args": [[32, 32]], "temp_bytes": 70 << 20},
    {"platform": "tpu", "int_args": [[32768, 32]], "temp_bytes": 5 * GIB},
    {"platform": "tpu", "int_args": [[16384, 32]], "temp_bytes": 2 * GIB},
    {"platform": "cpu", "int_args": [[32768, 32]], "temp_bytes": 9 * GIB},
]


def _selected(**counts) -> prom.Series:
    return {("detector_bucket_selected_total",
             (("bucket", bucket.lstrip("b")), ("path", path))): float(n)
            for key, n in counts.items()
            for bucket, path in [key.split("_")]}


@pytest.mark.parametrize("buckets,scratch,bucket", [
    ([32768], 5 * GIB, 32768),          # the widest bucket, dispatched
    ([32, 16384], 2 * GIB, 16384),      # compiled wider, never dispatched
    ([8192], 0, 0),                     # compiled inside a jitted call
    ([], 0, 0),
])
def test_only_dispatched_buckets_add_scratch(buckets, scratch, bucket):
    held = memory.peak(300e6, PROGRAMS, [], "tpu", buckets)
    assert held["scratch_bytes"] == scratch
    assert held["scratch_bucket"] == bucket
    assert held["peak_bytes"] == int(300e6) + scratch


def test_an_allocator_that_counts_scratch_is_taken_at_its_word():
    at_exit = [{"bytes_in_use": 1, "peak_bytes_in_use": 7 * GIB},
               {"bytes_in_use": 1, "peak_bytes_in_use": 6 * GIB}]
    held = memory.peak(300e6, PROGRAMS, at_exit, "tpu", [32768])
    assert held["peak_bytes"] == 7 * GIB


def test_the_other_platforms_executables_do_not_count():
    assert memory.temp_bytes(PROGRAMS, "tpu", 32768) == 5 * GIB
    assert memory.temp_bytes(PROGRAMS, "cpu", 32768) == 9 * GIB


def test_dispatched_buckets_are_those_whose_counter_rose():
    before = _selected(b512_device=4, b32768_device=2, b32_host=9)
    after = _selected(b512_device=4, b32768_device=40, b32_host=99,
                      b1024_device=1)
    assert memory.dispatched_buckets(before, after) == [1024, 32768]


def test_a_stage_that_wrote_nothing_reads_as_nothing(tmp_path):
    assert memory.read_programs(str(tmp_path / "absent.jsonl")) == ([], [])


def test_stage_main_writes_each_ahead_of_time_compile(tmp_path):
    """The entry the detector boots through, driven with a stand-in for the
    program's ``cli``: one ahead-of-time compile is written with its scratch
    bytes and the token batch's shape, and the way out adds the allocator's
    line where the backend has one (the CPU has none)."""
    pkg = tmp_path / "detectmateservice_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "cli.py").write_text(
        "import sys\n"
        "def main(argv):\n"
        "    import jax, jax.numpy as jnp, numpy as np\n"
        "    assert 'stages' not in sys.modules  # lib/ is off sys.path\n"
        "    f = jax.jit(lambda w, t: jnp.tanh(w[t.astype(jnp.int32)] @ w.T))\n"
        "    f.lower(jnp.ones((64, 8)), np.zeros((16, 4), np.uint16)).compile()\n"
        "    print('argv', argv)\n"
        "    return 0\n")
    out = tmp_path / "programs.jsonl"
    child = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmark", "lib", "stage_main.py"),
         "--programs", str(out), "--settings", "x.yaml"],
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path)),
        capture_output=True, text=True, timeout=300, cwd=str(tmp_path))
    assert child.returncode == 0, child.stderr[-2000:]
    assert "argv ['--settings', 'x.yaml']" in child.stdout
    programs, _ = memory.read_programs(str(out))
    assert len(programs) == 1
    assert programs[0]["int_args"] == [[16, 4]]
    assert programs[0]["platform"] == "cpu"
    assert programs[0]["temp_bytes"] >= 0
    assert memory.temp_bytes(programs, "cpu", 16) == programs[0]["temp_bytes"]
    json.dumps(programs)
