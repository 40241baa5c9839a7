"""The third configuration, ``lfm2-24b-a2b-ep8`` (a gated-short-convolution,
grouped-query, sparse-expert scorer cut to one of eight chips' share), and
its cell ``lfm2-24b-a2b-ep8.steady64``: its manifest entries, the
configuration's file against the source's published ``config.json``,
``flops/moe_conv.py`` against a hand count, the reference's control, and the
cell's path end to end on the CPU at a tiny size (``backend: cpu`` set by
the test). What holds of the manifest for any number of configurations and
traffic mixes is in ``test_bench_room.py``, read from the cells' own files
(``family_metrics``, PR 40; until then this file restated
``test_bench_moe_mla.py``'s pins and was restated by
``test_bench_moe_delta.py``'s)."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import importlib
import json
import os
import time

import numpy as np
import pytest

from bench_helpers import (REPO, entry_of, metrics_due, read_json, temp_root,
                           write_json)
from benchmark.flops import moe_conv as flops
from benchmark.lib import manifest

CONFIG, CELL = "lfm2-24b-a2b-ep8", "lfm2-24b-a2b-ep8.steady64"
# the per-layer metrics this family alone reports
OWN_METRICS = {"conv_share_of_call", "gated_conv_roofline"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the source's config.json as the model-configs catalog gives it
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 9 + ["full_attention",
                                                      "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
CUT = {"num_hidden_layers": 8, "num_experts": 8, "vocab_size": 8192}
TINY_ARCH = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    conv_L_cache=3, conv_bias=False, intermediate_size=96,
    moe_intermediate_size=48, num_experts_per_tok=2, num_dense_layers=1,
    norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True,
    norm_eps=1e-5, rope_parameters={"rope_theta": 1e6,
                                    "rope_type": "default"},
    num_hidden_layers=4,
    layer_types=["conv", "full_attention", "conv", "conv"],
    num_experts=2, router_experts=8, expert_offset=2)


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

def test_the_manifest_entries_keep_the_contracts_lengths(listed):
    entry = entry_of(listed, "configs", CONFIG)
    cell = entry_of(listed, "workloads", CELL)
    for text in (entry["source"], entry["why"], cell["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "steady64",
                    "chips": 1, "why": cell["why"]}
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


# -- the configuration's file ------------------------------------------------

def test_the_published_keys_are_the_catalogs():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    (row,) = [r for r in rows if r["name"] == "LFM2-24B-A2B"]
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
    assert manifest.reduced_breaches(entry, config) == []
    assert "eight chips share each layer" in config["deployment"]
    for other in ("logbert-256x4", "kanana2-30b-a3b-ep8"):
        assert config["guarantees"] == read_json(os.path.join(
            REPO, "benchmark", "configs", other + ".json"))["guarantees"]
    assert {"seq_len", "tie_word_embeddings", "hidden_act",
            "initializer_range", "expert_bias", "learning_rate"} <= set(
        config["assumed"])
    check = config["check"]
    assert 0 < check["rms_limit_nats"] < check["tolerance_nats"] <= 0.1
    assert check["tolerance_reason"] and "float8_e4m3fn" in config[
        "precision"]["control"]


def test_the_scorers_arch_is_the_published_widths_and_the_share(config):
    scorer = scorer_of(config)
    arch = scorer["arch"]
    assert scorer["model"] == "moe_conv" and scorer["vocab_size"] == 8192
    for key, value in arch.items():
        if key in ("router_experts", "expert_offset"):
            continue
        # the file's top level; layer_types stays whole there
        assert value == (config[key][:8] if key == "layer_types"
                         else config[key]), key
    assert arch["layer_types"] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv"]
    assert (arch["router_experts"], arch["num_experts"],
            arch["expert_offset"]) == (64, 8, 0)
    assert scorer["max_batch"] == 1024 and scorer["dtype"] == "auto"
    assert scorer["host_score_max_batch"] == 0
    assert scorer["batch_deadline_ms"] == 2000.0
    assert scorer["data_use_training"] == 2048 and scorer["score_vocab"] == 0
    assert config["warmup_buckets"] == [256, 512, 1024]
    # no width is reduced
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_key_value_heads", "conv_L_cache",
              "num_experts_per_tok", "num_dense_layers")
    assert all(arch[k] == PUBLISHED[k] for k in widths)
    from detectmateservice_tpu.models.moe_conv import MoEConvArch

    typed = MoEConvArch.from_mapping(arch)
    assert typed.head_dim == 64 and typed.expert_spec.held == 8
    assert typed.layer_types.count("conv") == 6


def test_the_cell_and_its_traffic_state_what_they_offer(listed):
    cell = read_json(os.path.join(REPO, "benchmark", "cells", CELL + ".json"))
    (entry,) = [w for w in listed["workloads"] if w["name"] == CELL]
    assert cell["why"] == entry["why"]
    assert cell["rate_lines_per_s"] > 0 and cell["rate_lines_per_s"] % 5 == 0
    assert "knee" in cell["rate_from"] and "0.6" in cell["rate_from"]
    assert f"{cell['rate_lines_per_s']:,}" in entry["why"]
    steady = read_json(os.path.join(REPO, "benchmark", "traffic",
                                    "steady.json"))
    ours = read_json(os.path.join(REPO, "benchmark", "traffic",
                                  "steady64.json"))
    assert ours["frame_lines"] == 64 and ours["name"] == "steady64"
    same = ("loop", "arrival", "anomaly_share", "ramp_s", "saturating")
    assert all(ours[key] == steady[key] for key in same)
    # sixteen frames a 1024-row batch; the pool is whole frames
    config = manifest.load_cell(REPO, CELL)["config"]
    assert config["traffic_source"]["pool_lines"] % 64 == 0


# -- flops/moe_conv.py against a hand count ------------------------------------

def test_parameters_and_operations_against_a_hand_count(config):
    scorer = dict(scorer_of(config), seq_len=32)
    d = 2048
    conv = 4 * d * d + 3 * d + 2 * d                  # 16.79 M
    attn = d * 48 * 64 + d * d + 2 * 64 + 2 * d       # 10.49 M
    unit = 3 * d * 1536                               # 9.44 M
    dense = 3 * d * 11776                             # 72.35 M
    expert = d * 64 + 64 + 8 * unit                   # 75.63 M
    assert (conv, attn, unit, dense, expert) == (
        16787456, 10489984, 9437184, 72351744, 75628608)
    by_hand = 8192 * d + d + 6 * conv + 2 * attn + 2 * dense + 6 * expert
    assert flops.params_count(scorer) == by_hand == 736959104
    macs = flops.macs_per_token(scorer)
    assert macs == (6 * 4 * d * d + 2 * (d * 48 * 64 + d * d + 2 * 32 * d)
                    + 2 * dense + 6 * d * 64 + 8192 * d)
    assert round(2 * macs / 1e6) == 568               # MFLOP a token
    even = flops.macs_per_token(scorer, even_routing=True)
    assert even - macs == pytest.approx(6 * 0.5 * unit)   # 28.3 M
    assert round(2 * even / 1e6) == 625
    ops, nbytes = flops.ops_and_bytes(scorer, 1024)
    assert ops == 2 * 1024 * 32 * macs
    assert nbytes == 4 * 736959104 + 1024 * 32 * 2 + 1024 * 4
    # compute-bound on the v5e: 94.5 ms of matmul against 3.6 ms of bytes
    assert ops / 197e12 == pytest.approx(0.0945, rel=1e-2)
    head_ops, head_bytes = flops.head_ops_and_bytes(scorer, 1024)
    assert head_ops == 2 * 1024 * 32 * 8192 * d < ops
    assert head_bytes == 2 * 1024 * 32 * d + 2 * 8192 * d + 4 * 1024 * 32
    conv_ops, conv_bytes = flops.conv_ops_and_bytes(scorer, 1024)
    assert conv_ops == 7 * 32768 * d
    # 403 MB in, 134 MB out: 0.66 ms at 819 GB/s, and memory-bound
    assert conv_bytes == 2 * 4 * 32768 * d + 4 * 3 * d
    assert conv_bytes / 819e9 == pytest.approx(0.000655, rel=1e-2)
    assert conv_ops / 197e12 < conv_bytes / 819e9 / 100


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmark", "reference", "moe_conv.py"),
              encoding="utf-8") as fh:
        source = fh.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import jax",
                       "import jax.numpy as jnp", "import numpy as np"]
    assert "detectmateservice_tpu" not in source.replace(
        "``detectmateservice_tpu.models`` or ``.ops``", "")


def _tiny_params(seed=0, vocab=64):
    rng = np.random.default_rng(seed)
    a = TINY_ARCH
    d, m = a["hidden_size"], a["moe_intermediate_size"]
    nrm = lambda *shape: rng.normal(size=shape).astype(np.float32) * 0.1  # noqa: E731
    params = {"tok_embed": {"embedding": nrm(vocab, d)},
              "final_norm": np.ones(d, np.float32)}
    for i, kind in enumerate(a["layer_types"]):
        lay = {"operator_norm": np.ones(d, np.float32),
               "ffn_norm": np.ones(d, np.float32),
               "out_proj": {"kernel": nrm(d, d)}}
        if kind == "conv":
            lay.update(in_proj={"kernel": nrm(d, 3 * d)},
                       conv_weight=nrm(d, 3) * 5)
        else:
            lay.update(qkv_proj={"kernel": nrm(d, (4 + 2 + 2) * 16)},
                       q_norm=np.ones(16, np.float32),
                       k_norm=np.ones(16, np.float32))
        if i < a["num_dense_layers"]:
            lay.update(gate_proj={"kernel": nrm(d, 96)},
                       up_proj={"kernel": nrm(d, 96)},
                       down_proj={"kernel": nrm(96, d)})
        else:
            lay.update(router=nrm(d, 8) * 10,
                       router_bias=np.zeros(8, np.float32),
                       experts_gate=nrm(2, d, m), experts_up=nrm(2, d, m),
                       experts_down=nrm(2, m, d))
        params[f"layers_{i}"] = lay
    return {"params": params}


def test_the_references_lower_control_changes_the_scores():
    import jax.numpy as jnp

    reference = importlib.import_module("benchmark.reference.moe_conv")
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


def test_the_references_convolution_is_three_shifted_multiply_adds():
    reference = importlib.import_module("benchmark.reference.moe_conv")
    rng = np.random.default_rng(2)
    u = rng.normal(size=(2, 6, 4)).astype(np.float32)
    w = rng.normal(size=(4, 3)).astype(np.float32)
    out = np.asarray(reference.short_conv(u, w))
    for t in range(6):
        want = sum(w[:, j] * u[:, t - 2 + j] for j in range(3)
                   if t - 2 + j >= 0)
        np.testing.assert_allclose(out[:, t], want, rtol=1e-6, atol=1e-6)


# -- the cell's path on the CPU, tiny ------------------------------------------

def test_a_traced_run_of_the_tiny_cell_is_correct_and_reads_the_counters(
        tmp_path, capsys):
    from benchmark import run

    root, cell = temp_root(tmp_path, config_name=CONFIG, model="moe_conv",
                           traffic="steady64", rate=1500, like=CELL,
                           reduced={key: {"published": 1, "here": 1,
                                          "why": "tiny"} for key in CUT})
    assert cell == "tiny-moe_conv.steady64"
    path = os.path.join(root, "benchmark", "configs", "tiny-moe_conv.json")
    tiny = read_json(path)
    scorer_of(tiny).update(arch=TINY_ARCH)
    tiny["check"].update(extra_alerted_sample=64)
    write_json(path, tiny)
    loaded = manifest.load_cell(root, cell)
    assert loaded["traffic"]["frame_lines"] == 64
    # the tiny cell is asked for what its family's cell reports
    assert OWN_METRICS < {s["name"] for s in loaded[
        "per_layer"]} == metrics_due(REPO, read_json(os.path.join(
            REPO, "BENCHMARK.json")), CELL)
    result = run.run_cell(root, cell, 2147483647 + 11, 3.0, True,
                          platform="cpu", t_start=time.monotonic())
    printed = capsys.readouterr().out
    assert result["correct"] is True and result["failed"] == 0, printed
    assert "num_hidden_layers 1 -> 1" in printed
    metrics = result["metrics"]
    assert {"expert_held_share", "expert_busiest_share", "batch_occupancy",
            "dispatch_ready_ms.lat", "row_hold_mean_ms"} <= set(metrics)
    # 2 of 8 experts held: a quarter of the assignments under even routing
    assert 5.0 < metrics["expert_held_share"]["value"] < 60.0
    # the busier of the two held experts: half when balanced, all at most
    assert 50.0 <= metrics["expert_busiest_share"]["value"] <= 100.0
    assert result["compared"]["compiles_after_warmup"]["value"] == 0
    assert result["compared"]["dropped_lines"]["value"] == 0
    # the kernel's roofline reads nothing where no kernel ran (the CPU's
    # route is XLA's): left out, never 0
    assert "gated_conv_roofline" not in metrics
