"""The two trace readers that take their parameters from the metric's file
(``scope_share``, ``kernel_roofline_share``), by hand on small reduced
traces, and the three metrics of ``logbert-256x4`` that are such files."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import os

import pytest

from bench_helpers import REPO, read_json
from benchmark.layer_metrics import scope_share
from benchmark.lib import calls, layers

SCORER = {"model": "logbert", "vocab_size": 32768, "dim": 256, "depth": 4,
          "heads": 4, "seq_len": 32}
PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
CALL = "jit__score_impl(11)"
SMALL = "jit__score_impl(12)"


def spec_of(name: str) -> dict:
    return read_json(os.path.join(REPO, "benchmark", "layer_metrics",
                                  name + ".json"))


def reduced_trace() -> dict:
    """Five whole 32768-row calls of 0.3 s and two 1024-row calls."""
    return {
        "devices": 1, "busy_s": 1.52, "window_s": 4.0,
        "modules": {
            CALL: {"count": 6, "total_s": 1.6, "median_s": 0.3,
                   "whole_count": 5, "whole_total_s": 1.5},
            SMALL: {"count": 2, "total_s": 0.02, "median_s": 0.01,
                    "whole_count": 2, "whole_total_s": 0.02},
            "jit_other(3)": {"count": 1, "total_s": 0.5, "median_s": 0.5,
                             "whole_count": 1, "whole_total_s": 0.5}},
        "module_scopes": {
            CALL: {"LogBERT.hidden/blocks_0/layer0/attn/qkv": 0.2,
                   "LogBERT.hidden/blocks_0/layer0/attn": 0.25,
                   "LogBERT.hidden/blocks_1/layer1/attn/attn_einsum": 0.45,
                   "LogBERT.hidden/blocks_1/layer1/ffn/mlp_in": 0.12,
                   "LogBERT.hidden/embed/tok_embed": 0.02,
                   "head/nll/lse_pallas": 0.44, "head/nll": 0.01,
                   "no scope": 0.01},
            SMALL: {"LogBERT.hidden/blocks_0/layer0/attn": 0.01,
                    "head/nll/lse_pallas": 0.01},
            "jit_other(3)": {"layer0/attn": 0.5}},
        "kernels": {"lse_pallas": {
            CALL: {"seconds": 0.44, "count": 4},
            SMALL: {"seconds": 0.01, "count": 2}}},
    }


def context(buckets=(1024, 32768)) -> dict:
    return {"trace": reduced_trace(), "capture_buckets": list(buckets),
            "scorer": dict(SCORER), "peak": dict(PEAK)}


@pytest.mark.parametrize("path, pattern, held", [
    ("LogBERT.hidden/blocks_0/layer0/attn/qkv", "layer*/attn", True),
    ("LogBERT.hidden/blocks_0/layer0/attn", "layer*/attn", True),
    ("layer12/attn", "layer*/attn", True),
    ("LogBERT.hidden/blocks_0/layer0/ffn/attn_like", "layer*/attn", False),
    ("LogBERT.hidden/blocks_0/layer0", "layer*/attn", False),
    ("head/nll/lse_pallas", "head/nll*", True),
    ("head/nll", "head/nll*", True),
    ("head/candidates", "head/nll*", False),
    ("no scope", "*", True),
])
def test_a_scope_lies_under_a_pattern(path, pattern, held):
    assert scope_share.under(path, pattern) is held


def test_scope_share_by_hand():
    # of the scoring calls' 1.52 s: attention 0.2 + 0.25 + 0.45 + 0.01, the
    # head 0.44 + 0.01 + 0.01; the other module's attention is not a call's
    ctx = context()
    assert layers.evaluate(spec_of("attn_share_of_call"), ctx) == \
        pytest.approx(100 * 0.91 / 1.52)
    assert layers.evaluate(spec_of("head_share_of_call"), ctx) == \
        pytest.approx(100 * 0.46 / 1.52)
    both = dict(spec_of("attn_share_of_call"),
                scopes=["layer*/attn", "layer*/ffn"])
    assert layers.evaluate(both, ctx) == pytest.approx(100 * 1.03 / 1.52)


def test_a_scope_nothing_ran_under_is_left_out_not_zero():
    nothing = dict(spec_of("attn_share_of_call"), scopes=["decoder/cross"])
    assert layers.evaluate(nothing, context()) is None
    ctx = context()
    del ctx["trace"]["module_scopes"]      # no xplane_pb2: no scopes
    assert layers.evaluate(spec_of("attn_share_of_call"), ctx) is None
    assert layers.evaluate(spec_of("attn_share_of_call"),
                           {"trace": None}) is None


def test_kernel_roofline_share_by_hand():
    # one call's head: 2 * rows * 32 * 32768 * 256 operations at 197e12 / s
    big = 2 * 32768 * 32 * 32768 * 256 / 197e12
    small = 2 * 1024 * 32 * 32768 * 256 / 197e12
    assert big == pytest.approx(0.0893, rel=1e-3)
    share = layers.evaluate(spec_of("lse_pallas_roofline"), context())
    assert share == pytest.approx(100 * (4 * big + 2 * small) / 0.45)
    assert share < 100
    only = context(buckets=[32768])
    del only["trace"]["modules"][SMALL]
    del only["trace"]["kernels"]["lse_pallas"][SMALL]
    assert layers.evaluate(spec_of("lse_pallas_roofline"), only) == \
        pytest.approx(100 * 4 * big / 0.44)


def test_a_kernel_that_did_not_run_is_left_out():
    spec = dict(spec_of("lse_pallas_roofline"), kernel="flash_fwd")
    assert layers.evaluate(spec, context()) is None
    # calls that do not pair up with the buckets dispatched: no number
    assert layers.evaluate(spec_of("lse_pallas_roofline"),
                           context(buckets=[32768])) is None
    assert layers.evaluate(spec_of("lse_pallas_roofline"),
                           {"trace": {"devices": 0}}) is None


def test_scoring_calls_pair_modules_with_buckets_by_duration():
    paired = calls.scoring_calls(context())
    assert [(name, bucket) for name, _, bucket in paired] == [
        (SMALL, 1024), (CALL, 32768)]
    assert calls.scoring_calls(context(buckets=[])) is None
    assert calls.scoring_calls({"trace": {"modules": {}}}) is None


def test_step_roofline_share_reads_the_same_calls():
    from benchmark.flops import logbert

    ops_big, _ = logbert.ops_and_bytes(SCORER, 32768)
    ops_small, _ = logbert.ops_and_bytes(SCORER, 1024)
    share = layers.evaluate(spec_of("step_roofline_share"), context())
    assert share == pytest.approx(
        100 * (5 * ops_big + 2 * ops_small) / 197e12 / 1.52)


@pytest.mark.parametrize("name", ["attn_share_of_call", "head_share_of_call",
                                  "lse_pallas_roofline"])
def test_the_three_metrics_are_data_files_in_the_manifest(name):
    entries = {m["name"]: m for m in
               read_json(os.path.join(REPO, "BENCHMARK.json"))["per_layer"]}
    entry, spec = entries[name], spec_of(name)
    assert spec["kind"] == "trace"
    assert spec["reducer"] in ("scope_share", "kernel_roofline_share")
    assert not os.path.exists(os.path.join(REPO, "benchmark", "layer_metrics",
                                           name + ".py"))
    assert entry["layer"] == spec["layer"] == "kernels"
    assert entry["moves"] == spec["moves"] == "alert_p50_ms"
    assert entry["unit"] == spec["unit"] == "%"
    assert entry["source"] == "device_trace"
    assert "logbert-256x4.steady" in entry["workloads"]
