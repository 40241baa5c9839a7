"""The fourth configuration, ``qwen3-next-80b-a3b-ep16`` (a gated-delta-rule,
gated-attention, sparse-expert scorer cut to one of sixteen chips' share),
and its cell ``qwen3-next-80b-a3b-ep16.steady64``: its manifest entries and
its own metrics' files (the kernel's roofline among them since PR 40), the
configuration's file against the source's published ``config.json``,
``flops/moe_delta.py`` against a hand count and against the built scorer's
leaves, the reference's control and its recurrence, and the cell's path end
to end on the CPU at a tiny size (``backend: cpu`` set by the test). What
holds of the manifest for any number of configurations and of metrics that
list their own cells alone is in ``test_bench_room.py``, read from the
cells' own files (``family_metrics``) — the table this file kept until
PR 40 is data now, a file a cell."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import importlib
import json
import os
import time

import numpy as np
import pytest

from bench_helpers import (REPO, entry_of, metrics_due, read_json, temp_root,
                           write_json)
from benchmark.flops import moe_delta as flops
from benchmark.lib import manifest

CONFIG, CELL = "qwen3-next-80b-a3b-ep16", "qwen3-next-80b-a3b-ep16.steady64"
# the per-layer metrics this family alone reports
OWN_METRICS = {"delta_share_of_call", "gated_delta_roofline"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the source's config.json as the model-configs catalog gives it
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
CUT = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}
TINY_ARCH = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=32, partial_rotary_factor=0.25, rope_theta=1e7,
    full_attention_interval=4, linear_conv_kernel_dim=4,
    linear_key_head_dim=16, linear_value_head_dim=16, linear_num_key_heads=2,
    linear_num_value_heads=4, moe_intermediate_size=48,
    shared_expert_intermediate_size=48, num_experts_per_tok=3,
    rms_norm_eps=1e-6, num_hidden_layers=4, num_experts=2, router_experts=8,
    expert_offset=2)


@pytest.fixture(scope="module")
def config():
    return read_json(os.path.join(REPO, "benchmark", "configs",
                                  CONFIG + ".json"))


@pytest.fixture(scope="module")
def listed():
    return read_json(os.path.join(REPO, "BENCHMARK.json"))


def scorer_of(config):
    (block,) = config["stages"]["detector"]["component"]["detectors"].values()
    return block


# -- the manifest's entries for this configuration and its cell -----------------

def test_the_cell_loads_with_its_traffic_and_both_end_to_end_metrics(listed):
    loaded = manifest.load_cell(REPO, CELL)
    assert loaded["entry"]["chips"] == 1
    assert loaded["traffic"]["name"] == "steady64"
    assert loaded["config"]["name"] == CONFIG
    assert loaded["cell"]["name"] == CELL
    assert [m["name"] for m in loaded["end_to_end"]] == ["setup_s",
                                                         "alert_p50_ms"]
    assert OWN_METRICS < {s["name"] for s in loaded["per_layer"]}
    assert {s["name"] for s in loaded["per_layer"]} == metrics_due(
        REPO, listed, CELL)


def test_the_manifest_entries_keep_the_contracts_lengths(listed):
    entry = entry_of(listed, "configs", CONFIG)
    cell = entry_of(listed, "workloads", CELL)
    for text in (entry["source"], entry["why"], cell["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "steady64",
                    "chips": 1, "why": cell["why"]}
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert "model_type qwen3_next" in entry["source"]
    assert len(json.dumps(listed)) < 64 * 1024


def test_the_own_metrics_are_data_for_readers_that_are_there():
    for name in OWN_METRICS | {"expert_busiest_share"}:
        spec = read_json(os.path.join(REPO, "benchmark", "layer_metrics",
                                      name + ".json"))
        assert spec["name"] == name and spec["layer"] == "kernels"
    delta = read_json(os.path.join(REPO, "benchmark", "layer_metrics",
                                   "delta_share_of_call.json"))
    assert (delta["kind"], delta["reducer"], delta["scopes"]) == (
        "trace", "scope_share", ["layer*/delta"])
    busiest = read_json(os.path.join(REPO, "benchmark", "layer_metrics",
                                     "expert_busiest_share.json"))
    assert busiest["kind"] == "prom-delta" and busiest["scale"] == 100
    assert busiest["numerator"]["series"] == (
        "detector_moe_busiest_expert_assignments_total")
    assert busiest["denominator"]["series"] == (
        "detector_moe_held_assignments_total")


def test_the_kernels_roofline_is_its_bytes_over_its_device_time(config):
    """``gated_delta_roofline``: a data file for ``kernel_roofline_share``
    over ``delta_ops_and_bytes`` — one kernel call is one layer's core,
    bound by what it moves. A hand-made reduced trace: two whole 1024-row
    calls of three delta layers, 4 ms a kernel call."""
    from benchmark.lib import layers

    spec = read_json(os.path.join(REPO, "benchmark", "layer_metrics",
                                  "gated_delta_roofline.json"))
    assert (spec["kind"], spec["reducer"], spec["kernel"], spec["least"]) == (
        "trace", "kernel_roofline_share", "gated_delta",
        "delta_ops_and_bytes")
    scorer = scorer_of(config)
    ops, nbytes = flops.delta_ops_and_bytes(scorer, 1024)
    # q | k | v in and o out in bfloat16, two gates a value head in float32
    tokens = 1024 * 32
    assert nbytes == tokens * (2 * (8192 + 4096) + 4 * 2 * 32) == 813694976
    assert ops / 197e12 < nbytes / 819e9 == pytest.approx(0.99352e-3,
                                                          rel=1e-4)
    module = "jit__score_impl(5)"
    trace = {"modules": {module: {"count": 2, "total_s": 0.39,
                                  "median_s": 0.195, "whole_count": 2,
                                  "whole_total_s": 0.39}},
             "kernels": {"gated_delta": {module: {"seconds": 6 * 4e-3,
                                                  "count": 6}},
                         "lse_pallas": {module: {"seconds": 0.0278,
                                                 "count": 2}}}}
    ctx = {"trace": trace, "capture_buckets": [1024], "scorer": scorer,
           "peak": {"flops_per_s": 197e12, "bytes_per_s": 819e9}}
    assert layers.evaluate(spec, ctx) == pytest.approx(24.838, rel=1e-4)
    # a trace in which the kernel did not run (the CPU's and a mesh's route
    # is the chunked form; another family's cell) reports nothing, never 0
    del trace["kernels"]["gated_delta"]
    assert layers.evaluate(spec, ctx) is None
    assert layers.evaluate(spec, dict(ctx, trace={})) is None


# -- the configuration's file ------------------------------------------------

def test_the_published_keys_are_the_catalogs():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct"]
    assert row["config"] == PUBLISHED
    assert row["source_url"] in read_json(os.path.join(
        REPO, "benchmark", "configs", CONFIG + ".json"))["source"]


def test_the_file_holds_the_published_config_but_for_the_three_cuts(config,
                                                                    listed):
    assert config["reduced"] == list(CUT)
    for key, published in PUBLISHED.items():
        assert config[key] == CUT.get(key, published), key
    for key, here in CUT.items():
        assert config["cut"][key]["published"] == PUBLISHED[key]
        assert config["cut"][key]["here"] == here
    (entry,) = [c for c in listed["configs"] if c["name"] == CONFIG]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert manifest.reduced_breaches(entry, config) == []
    assert "sixteen chips share each layer" in config["deployment"]
    for other in ("logbert-256x4", "kanana2-30b-a3b-ep8", "lfm2-24b-a2b-ep8"):
        assert config["guarantees"] == read_json(os.path.join(
            REPO, "benchmark", "configs", other + ".json"))["guarantees"]
    assert {"seq_len", "layer_equations", "initializer_range", "mtp",
            "fused_projection_columns", "learning_rate",
            "router_of_a_share"} <= set(config["assumed"])
    assert config["changed"]["from"].startswith("lfm2-24b-a2b-ep8")
    check = config["check"]
    assert 0 < check["rms_limit_nats"] < check["tolerance_nats"] <= 0.1
    assert check["tolerance_reason"] and "float8_e4m3fn" in config[
        "precision"]["control"]
    assert "stated" in config["precision"]


def test_the_scorers_arch_is_the_published_widths_and_the_share(config):
    scorer = scorer_of(config)
    arch = scorer["arch"]
    assert scorer["model"] == "moe_delta" and scorer["vocab_size"] == 18992
    for key, value in arch.items():
        if key not in ("router_experts", "expert_offset"):
            assert value == config[key], key
    assert (arch["router_experts"], arch["num_experts"],
            arch["expert_offset"]) == (512, 32, 0)
    assert scorer["max_batch"] == 1024 and scorer["dtype"] == "auto"
    assert scorer["host_score_max_batch"] == 0 and scorer["seq_len"] == 32
    assert scorer["batch_deadline_ms"] == 2000.0
    assert scorer["data_use_training"] == 2048 and scorer["score_vocab"] == 0
    assert config["warmup_buckets"] == [256, 512, 1024]
    # no width is reduced
    widths = ("hidden_size", "head_dim", "moe_intermediate_size",
              "shared_expert_intermediate_size", "num_attention_heads",
              "num_key_value_heads", "linear_conv_kernel_dim",
              "linear_key_head_dim", "linear_value_head_dim",
              "linear_num_key_heads", "linear_num_value_heads",
              "num_experts_per_tok", "partial_rotary_factor",
              "full_attention_interval")
    assert all(arch[k] == PUBLISHED[k] for k in widths)
    from detectmateservice_tpu.models.moe_delta import MoEDeltaArch

    typed = MoEDeltaArch.from_mapping(arch)
    assert typed.layer_types == ("linear_attention",) * 3 + (
        "full_attention",)
    assert typed.rotary_dim == 64
    spec = typed.expert_spec
    assert (spec.held, spec.router_experts, spec.top_k, spec.shared,
            spec.shared_gate, spec.scoring_func) == (32, 512, 10, 1, True,
                                                     "softmax")


def test_the_cell_and_its_traffic_state_what_they_offer(listed):
    cell = read_json(os.path.join(REPO, "benchmark", "cells", CELL + ".json"))
    (entry,) = [w for w in listed["workloads"] if w["name"] == CELL]
    assert cell["why"] == entry["why"]
    assert cell["rate_lines_per_s"] > 0 and cell["rate_lines_per_s"] % 5 == 0
    assert "knee" in cell["rate_from"] and "0.6" in cell["rate_from"]
    assert f"{cell['rate_lines_per_s']:,}" in entry["why"]
    assert "16x" in entry["why"] and "sixteen-chip" in cell["who"]
    assert cell["measured"]
    config = manifest.load_cell(REPO, CELL)["config"]
    assert config["traffic_source"]["pool_lines"] % 64 == 0


# -- flops/moe_delta.py against a hand count ------------------------------------

def test_parameters_and_operations_against_a_hand_count(config):
    scorer = dict(scorer_of(config), seq_len=32)
    d = 2048
    # in_proj 2048 -> 12288, ba 2048 -> 64, out 4096 -> 2048; taps, A_log,
    # dt_bias, the output norm, the layer's two norms
    delta = (d * 12288 + d * 64 + 4096 * d + 8192 * 4 + 32 + 32 + 128
             + 2 * d)
    # q with gates 16 x 512, k and v 512 each; per-head norms; two norms
    attn = d * (8192 + 1024) + 4096 * d + 2 * 256 + 2 * d
    unit = 3 * d * 512                                  # 3.146 M
    moe = d * 512 + 512 + 32 * unit + unit + d          # router, bias, held, shared, its gate
    assert (delta, attn, unit, moe) == (33722560, 27267584, 3145728,
                                        104860160)
    by_hand = 2 * 18992 * d + d + 3 * delta + attn + 4 * moe
    assert flops.params_count(scorer) == by_hand == 625669184
    # 7.51 GB resident at 12 bytes, 10.01 GB in the donated step at 16
    assert round(12 * by_hand / 1e9, 2) == 7.51
    assert round(16 * by_hand / 1e9, 2) == 10.01
    core = 16.5 * 2 * (16 * 128 + 32 * 128)             # one chunk a line
    assert core == 202752 < 3 * 32 * 128 * 128
    macs = flops.macs_per_token(scorer)
    assert macs == (3 * (d * 12288 + d * 64 + 4096 * d + core)
                    + d * 9216 + 4096 * d + 2 * 32 * 4096
                    + 4 * (d * 512 + unit + d) + 18992 * d)
    assert round(2 * macs / 1e6) == 370                 # MFLOP a token
    even = flops.macs_per_token(scorer, even_routing=True)
    assert even - macs == pytest.approx(4 * 10 * 32 / 512 * unit)
    ops, nbytes = flops.ops_and_bytes(scorer, 1024)
    assert ops == 2 * 1024 * 32 * macs
    assert nbytes == 4 * 625669184 + 1024 * 32 * 2 + 1024 * 4
    # compute-bound on the v5e: 61.5 ms of matmul against 3.1 ms of bytes
    assert ops / 197e12 == pytest.approx(0.0615, rel=1e-2)
    head_ops, head_bytes = flops.head_ops_and_bytes(scorer, 1024)
    assert head_ops == 2 * 1024 * 32 * 18992 * d < ops
    assert head_bytes == 2 * 1024 * 32 * d + 2 * 18992 * d + 4 * 1024 * 32
    core_ops, core_bytes = flops.delta_ops_and_bytes(scorer, 1024)
    assert core_ops == 2 * 32768 * core
    # q, k, v in and o out once in bfloat16, the gates in float32: 24.8 KB
    # a token, 0.99 ms at 819 GB/s, and memory-bound
    assert core_bytes == 32768 * (2 * (8192 + 4096) + 4 * 64)
    assert core_bytes / 819e9 == pytest.approx(0.00099, rel=1e-2)
    assert core_ops / 197e12 < core_bytes / 819e9 / 10


def test_the_count_is_the_built_scorers_leaves():
    import jax

    from detectmateservice_tpu.models.moe_delta import (
        MoEDeltaArch, MoEDeltaConfig, MoEDeltaScorer)

    def leaves(arch, vocab):
        scorer = MoEDeltaScorer(MoEDeltaConfig(
            arch=MoEDeltaArch.from_mapping(arch), vocab_size=vocab,
            seq_len=32))
        shapes = jax.eval_shape(lambda: scorer.init(jax.random.PRNGKey(0))[0])
        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(shapes))

    assert leaves(TINY_ARCH, 64) == flops.params_count(
        {"arch": TINY_ARCH, "vocab_size": 64})
    # at the published widths, by shapes alone
    full = scorer_of(read_json(os.path.join(REPO, "benchmark", "configs",
                                            CONFIG + ".json")))
    assert leaves(full["arch"], full["vocab_size"]) == flops.params_count(
        full) == 625669184


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmark", "reference", "moe_delta.py"),
              encoding="utf-8") as fh:
        source = fh.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import jax",
                       "import jax.numpy as jnp", "import numpy as np"]
    assert "detectmateservice_tpu" not in source.replace(
        "``detectmateservice_tpu.models`` or ``.ops``", "")
    assert "lax.scan" in source and "solve_triangular" not in source


def _tiny_params(seed=0, vocab=64):
    rng = np.random.default_rng(seed)
    a = TINY_ARCH
    d, m = a["hidden_size"], a["moe_intermediate_size"]
    nrm = lambda *shape: rng.normal(size=shape).astype(np.float32) * 0.1  # noqa: E731
    params = {"tok_embed": {"embedding": nrm(vocab, d)},
              "lm_head": nrm(vocab, d), "final_norm": nrm(d)}
    for i in range(a["num_hidden_layers"]):
        lay = {"input_norm": nrm(d), "post_norm": nrm(d),
               "router": nrm(d, 8) * 10,
               "router_bias": np.zeros(8, np.float32),
               "experts_gate": nrm(2, d, m), "experts_up": nrm(2, d, m),
               "experts_down": nrm(2, m, d),
               "shared_gate_proj": {"kernel": nrm(d, m)},
               "shared_up_proj": {"kernel": nrm(d, m)},
               "shared_down_proj": {"kernel": nrm(m, d)},
               "shared_gate": nrm(d, 1) * 5}
        if (i + 1) % a["full_attention_interval"]:
            lay.update(in_proj={"kernel": nrm(d, 2 * 32 + 2 * 64) * 3},
                       ba_proj={"kernel": nrm(d, 8) * 5},
                       conv_weight=nrm(2 * 32 + 64, 4) * 5,
                       A_log=np.log(rng.uniform(0.1, 16, 4)).astype(
                           np.float32),
                       dt_bias=np.ones(4, np.float32),
                       out_norm=np.ones(16, np.float32),
                       out_proj={"kernel": nrm(64, d)})
        else:
            lay.update(qkv_proj={"kernel": nrm(d, (8 + 2 + 2) * 32)},
                       q_norm=nrm(32), k_norm=nrm(32),
                       out_proj={"kernel": nrm(4 * 32, d)})
        params[f"layers_{i}"] = lay
    return {"params": params}


def test_the_references_lower_control_changes_the_scores():
    import jax.numpy as jnp

    reference = importlib.import_module("benchmark.reference.moe_delta")
    rng = np.random.default_rng(1)
    tokens = rng.integers(3, 64, size=(6, 16)).astype(np.int32)
    tokens[:, 0] = 2
    tokens[4, 7:] = 0
    params = _tiny_params()
    scorer = {"arch": TINY_ARCH}
    plain = reference.score(params, tokens, scorer, block_rows=4)
    again = reference.score(params, tokens, scorer, block_rows=8)
    lowered = reference.score(params, tokens, scorer, block_rows=4,
                              lower=jnp.float8_e4m3fn)
    assert np.allclose(plain, again, atol=1e-5)       # blocks change nothing
    assert np.abs(plain - lowered).max() > 1e-3
    # the share: with no expert held the scores differ
    none = reference.score(params, tokens,
                           {"arch": dict(TINY_ARCH, num_experts=0)},
                           block_rows=4)
    assert np.abs(plain - none).max() > 1e-4


def test_the_references_recurrence_is_the_delta_rule_step_by_step():
    """``delta_rule`` against a loop in numpy float64: decay, the delta
    correction, the write, the read."""
    reference = importlib.import_module("benchmark.reference.moe_delta")
    rng = np.random.default_rng(2)
    n, s, h, dk, dv = 2, 6, 3, 4, 5
    q, k = (rng.normal(size=(n, s, h, dk)) for _ in range(2))
    v = rng.normal(size=(n, s, h, dv))
    g = -rng.uniform(0.1, 2.0, size=(n, s, h))
    beta = rng.uniform(0.1, 0.9, size=(n, s, h))
    out = np.asarray(reference.delta_rule(
        *(np.asarray(x, np.float32) for x in (q, k, v, g, beta))))
    for i in range(n):
        for j in range(h):
            state = np.zeros((dk, dv))
            for t in range(s):
                state = state * np.exp(g[i, t, j])
                u = beta[i, t, j] * (v[i, t, j] - state.T @ k[i, t, j])
                state = state + np.outer(k[i, t, j], u)
                np.testing.assert_allclose(out[i, t, j],
                                           state.T @ q[i, t, j], atol=1e-4)


def test_the_references_convolution_is_four_shifted_multiply_adds():
    reference = importlib.import_module("benchmark.reference.moe_delta")
    rng = np.random.default_rng(2)
    u = rng.normal(size=(2, 7, 4)).astype(np.float32)
    w = rng.normal(size=(4, 4)).astype(np.float32)
    out = np.asarray(reference.short_conv(u, w))
    for t in range(7):
        want = sum(w[:, j] * u[:, t - 3 + j] for j in range(4)
                   if t - 3 + j >= 0)
        np.testing.assert_allclose(out[:, t], want, rtol=1e-6, atol=1e-6)


# -- the cell's path on the CPU, tiny ------------------------------------------

def test_a_traced_run_of_the_tiny_cell_is_correct_and_reads_the_counters(
        tmp_path, capsys):
    from benchmark import run

    root, cell = temp_root(tmp_path, config_name=CONFIG, model="moe_delta",
                           traffic="steady64", rate=1500, like=CELL,
                           reduced={key: {"published": 1, "here": 1,
                                          "why": "tiny"} for key in CUT})
    assert cell == "tiny-moe_delta.steady64"
    path = os.path.join(root, "benchmark", "configs", "tiny-moe_delta.json")
    tiny = read_json(path)
    scorer_of(tiny).update(arch=TINY_ARCH)
    tiny["check"].update(extra_alerted_sample=64)
    write_json(path, tiny)
    loaded = manifest.load_cell(root, cell)
    assert loaded["traffic"]["frame_lines"] == 64
    assert OWN_METRICS | {"moe_share_of_call", "expert_held_share",
                          "expert_busiest_share"} <= {
        s["name"] for s in loaded["per_layer"]}
    result = run.run_cell(root, cell, 2147483647 + 13, 3.0, True,
                          platform="cpu", t_start=time.monotonic())
    printed = capsys.readouterr().out
    assert result["correct"] is True and result["failed"] == 0, printed
    metrics = result["metrics"]
    assert {"expert_held_share", "expert_busiest_share", "batch_occupancy",
            "dispatch_ready_ms.lat", "row_hold_mean_ms"} <= set(metrics)
    # 2 of 8 experts held: a quarter of the assignments under even routing,
    # and the busier of the two takes at least half of those
    assert 5.0 < metrics["expert_held_share"]["value"] < 60.0
    assert 50.0 <= metrics["expert_busiest_share"]["value"] <= 100.0
    assert result["compared"]["compiles_after_warmup"]["value"] == 0
    assert result["compared"]["dropped_lines"]["value"] == 0
    # the kernel's roofline reads nothing where no kernel ran (the CPU's
    # route is the chunked form): left out, never 0
    assert "gated_delta_roofline" not in metrics
