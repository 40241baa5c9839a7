"""The sixth configuration, ``ling3-flash-125b-a5b-tp4`` (a vector-decay
delta-rule, gated-latent-attention, group-routed sparse-expert scorer cut to
one chip's tensor share of four and expert share of sixty-four), and its cell
``ling3-flash-125b-a5b-tp4.steady64``: its manifest entries and its own
metrics' files, the configuration's file against the catalog's row key by
key, ``flops/moe_kda.py`` against a hand count and against the built
scorer's leaves, the reference's control, its recurrence, its rotation and
its grouped choice, and the cell's path end to end on the CPU at a tiny size
(``backend: cpu`` set by the test). What holds of the manifest for any number
of configurations is in ``test_bench_room.py``."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import importlib
import json
import os
import time

import numpy as np
import pytest

from bench_helpers import (REPO, entry_of, metrics_due, read_json, temp_root,
                           write_json)
from benchmark.flops import moe_kda as flops
from benchmark.lib import manifest

CONFIG = "ling3-flash-125b-a5b-tp4"
CELL = CONFIG + ".steady64"
# the per-layer metrics this family alone reports
OWN_METRICS = {"kda_share_of_call", "route_share_of_call"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MODEL = "Ling-3.0-flash"
CUT = {"num_hidden_layers": 7, "first_k_dense_replace": 1,
       "expert_swiglu_limit_list": [0] * 7,
       "share_expert_swiglu_limit_list": [0] * 7, "num_experts": 8,
       "num_attention_heads": 8, "num_key_value_heads": 8,
       "vocab_size": 19648}
SHARE_KEYS = ("router_experts", "expert_offset", "tensor_parallel",
              "tensor_rank")
TINY_ARCH = dict(
    hidden_size=64, num_hidden_layers=4, layer_group_size=3,
    first_k_dense_replace=1, num_attention_heads=4, head_dim=16,
    short_conv_kernel_size=4, kda_lower_bound=-5, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=24, rope_theta=6000000,
    intermediate_size=96, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=40, num_experts=2,
    num_experts_per_tok=3, n_group=4, topk_group=2,
    routed_scaling_factor=2.5, rms_norm_eps=1e-6, router_experts=16,
    expert_offset=2, tensor_parallel=2, tensor_rank=1)


@pytest.fixture(scope="module")
def config():
    return read_json(os.path.join(REPO, "benchmark", "configs",
                                  CONFIG + ".json"))


@pytest.fixture(scope="module")
def listed():
    return read_json(os.path.join(REPO, "BENCHMARK.json"))


@pytest.fixture(scope="module")
def published():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    (row,) = [r for r in rows if r["name"] == MODEL]
    return row


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
    due = {s["name"] for s in loaded["per_layer"]}
    assert OWN_METRICS < due and due == metrics_due(REPO, listed, CELL)
    assert {"attn_share_of_call", "moe_share_of_call", "head_share_of_call",
            "step_roofline_share", "lse_pallas_roofline",
            "expert_held_share", "expert_busiest_share"} <= due
    # the other families' scope and kernel metrics are not this cell's
    assert not {"delta_share_of_call", "gated_delta_roofline",
                "conv_share_of_call", "gated_conv_roofline",
                "ssm_share_of_call", "latent_share_of_call"} & due


def test_the_manifest_entries_keep_the_contracts_lengths(listed):
    entry = entry_of(listed, "configs", CONFIG)
    cell = entry_of(listed, "workloads", CELL)
    for text in (entry["source"], entry["why"], cell["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "steady64",
                    "chips": 1, "why": cell["why"]}
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert MODEL in entry["source"]
    assert "model_type bailing_hybrid" in entry["source"]
    assert entry["reduced"] == list(CUT)
    assert len(json.dumps(listed)) < 64 * 1024
    # appended behind what was there (and not pinned as the last: the next
    # configuration appends behind these)
    names = [c["name"] for c in listed["configs"]]
    assert names.index(CONFIG) > names.index("nemotron3-super-120b-a12b-tp8")
    cells = [w["name"] for w in listed["workloads"]]
    assert cells.index(CELL) > cells.index(
        "nemotron3-super-120b-a12b-tp8.steady64")
    metrics = [m["name"] for m in listed["per_layer"]]
    assert (metrics.index("latent_share_of_call")
            < metrics.index("kda_share_of_call")
            < metrics.index("route_share_of_call"))
    for name in OWN_METRICS:
        assert entry_of(listed, "per_layer", name) == {
            "name": name, "unit": "%", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "alert_p50_ms", "workloads": [CELL]}
    # no cell asks for four chips
    assert all(w["chips"] == 1 for w in listed["workloads"])


def test_the_own_metrics_are_data_for_a_reader_that_is_there():
    kda = read_json(os.path.join(REPO, "benchmark", "layer_metrics",
                                 "kda_share_of_call.json"))
    router = read_json(os.path.join(REPO, "benchmark", "layer_metrics",
                                    "route_share_of_call.json"))
    assert (kda["kind"], kda["reducer"], kda["scopes"]) == (
        "trace", "scope_share", ["layer*/kda"])
    assert (router["kind"], router["reducer"], router["scopes"]) == (
        "trace", "scope_share", ["layer*/moe/router"])
    for spec in (kda, router):
        assert spec["layer"] == "kernels" and spec["unit"] == "%"
        assert spec["moves"] == "alert_p50_ms"
    from benchmark.lib import layers

    trace = {"module_scopes": {"jit__score_impl(3)": {
        "Model/layers_0/layer0/kda/in_proj": 0.2,
        "Model/layers_0/layer0/kda/core/kda_chunked/solve": 0.1,
        "Model/layers_1/layer1/moe/router/scores": 0.04,
        "Model/layers_1/layer1/moe/router/groups": 0.03,
        "Model/layers_1/layer1/moe/router/top_k": 0.03,
        "Model/layers_1/layer1/moe/experts": 0.3,
        "Model/layers_2/layer2/attn/core": 0.1, "head/nll": 0.2}}}
    assert layers.evaluate(kda, {"trace": trace}) == pytest.approx(30.0)
    assert layers.evaluate(router, {"trace": trace}) == pytest.approx(10.0)
    # a program without such scopes (the parent's, another family's)
    # reports nothing, never 0
    other = {"module_scopes": {"jit__score_impl(3)": {
        "Model/layers_1/layer1/moe/experts": 0.5, "head/nll": 1.0}}}
    assert layers.evaluate(kda, {"trace": other}) is None
    assert layers.evaluate(router, {"trace": {}}) is None


# -- the configuration's file ------------------------------------------------

def test_the_file_holds_the_catalogs_row_key_by_key_but_for_the_eight_cuts(
        config, listed, published):
    row = published["config"]
    assert published["source_url"] in config["source"]
    assert config["reduced"] == list(CUT) and len(CUT) == 8
    for key, value in row.items():
        assert config[key] == CUT.get(key, value), key
    for key, here in CUT.items():
        assert config["cut"][key] == {"published": row[key], "here": here,
                                      "why": config["cut"][key]["why"]}
        assert row[key] != here and config["cut"][key]["why"]
    # the limit lists keep their own first entries, one a kept layer; the
    # published model clamps nothing there
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert row[key][:7] == CUT[key] and len(row[key]) == 42
    assert row["expert_swiglu_limit_list"].index(4) == 35
    assert row["share_expert_swiglu_limit_list"].index(5) == 34
    # one leading dense layer and a whole period of six in the published
    # ratio 5 : 1 (the row's described_as says 3 : 1; its config decides)
    kinds = [(i + 1) % row["layer_group_size"] == 0 for i in range(42)]
    assert sum(kinds) == 7 and kinds[:7] == [False] * 5 + [True, False]
    (entry,) = [c for c in listed["configs"] if c["name"] == CONFIG]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert manifest.reduced_breaches(entry, config) == []
    assert "four chips" in config["deployment"]
    assert "64 chips" in config["deployment"]
    # no width is among the cuts: counts of layers, experts, heads and rows
    assert not [k for k in CUT if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    for other in ("logbert-256x4", "kanana2-30b-a3b-ep8", "lfm2-24b-a2b-ep8",
                  "qwen3-next-80b-a3b-ep16", "nemotron3-super-120b-a12b-tp8"):
        assert config["guarantees"] == read_json(os.path.join(
            REPO, "benchmark", "configs", other + ".json"))["guarantees"]
    assert {"seq_len", "layer_equations", "kda_safe_gate", "use_qk_norm",
            "group_norm_size", "grouped_choice", "initializer_range", "mtp",
            "swiglu_limit", "causal_contract", "router_of_a_share",
            "learning_rate", "partial_results"} <= set(config["assumed"])
    assert config["changed"]["from"].startswith("nemotron3-super-120b")
    check = config["check"]
    assert 0 < check["rms_limit_nats"] < check["tolerance_nats"] <= 0.12
    assert check["tolerance_reason"] and "float8_e4m3fn" in config[
        "precision"]["control"]
    assert "stated" in config["precision"]


def test_the_scorers_arch_is_the_published_widths_and_the_share(config,
                                                                published):
    row = published["config"]
    scorer = scorer_of(config)
    arch = scorer["arch"]
    assert scorer["model"] == "moe_kda" and scorer["vocab_size"] == 19648
    for key, value in arch.items():
        if key not in SHARE_KEYS:
            assert value == config[key], key
    assert [arch[k] for k in SHARE_KEYS] == [512, 0, 4, 0]
    assert scorer["max_batch"] == 1024 and scorer["dtype"] == "auto"
    assert scorer["host_score_max_batch"] == 0 and scorer["seq_len"] == 32
    assert scorer["batch_deadline_ms"] == 2000.0
    assert scorer["data_use_training"] == 2048 and scorer["score_vocab"] == 0
    assert config["warmup_buckets"] == [256, 512, 1024]
    # no width is reduced
    widths = ("hidden_size", "head_dim", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
              "short_conv_kernel_size", "intermediate_size",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size",
              "num_experts_per_tok", "n_group", "topk_group",
              "routed_scaling_factor", "kda_lower_bound", "rms_norm_eps",
              "rope_theta", "layer_group_size")
    assert all(arch[k] == row[k] for k in widths)
    from detectmateservice_tpu.models.moe_kda import MoEKDAArch

    typed = MoEKDAArch.from_mapping(arch)
    assert typed.layer_types == ("kda",) * 5 + ("attn", "kda")
    spec = typed.expert_spec
    assert (spec.held, spec.router_experts, spec.top_k, spec.shared,
            spec.shared_width, spec.latent, spec.gated, spec.scoring_func,
            spec.scaling, spec.width, spec.n_group, spec.topk_group) == (
        8, 512, 8, 1, 768, 0, True, "sigmoid", 2.5, 768, 8, 4)
    # the file's share is what share_of derives from the catalog's config
    derived = MoEKDAArch.share_of(
        row, tensor_parallel=4, tensor_rank=0, experts_held=8,
        num_hidden_layers=7, first_k_dense_replace=1)
    assert MoEKDAArch.from_mapping(derived) == typed


def test_the_cell_and_its_traffic_state_what_they_offer(listed):
    cell = read_json(os.path.join(REPO, "benchmark", "cells", CELL + ".json"))
    (entry,) = [w for w in listed["workloads"] if w["name"] == CELL]
    assert cell["why"] == entry["why"]
    assert cell["rate_lines_per_s"] > 0 and cell["rate_lines_per_s"] % 5 == 0
    assert "knee" in cell["rate_from"] and "0.6" in cell["rate_from"]
    assert len(cell["floods_lines_per_s"]) == 3
    assert sorted(cell["floods_lines_per_s"])[1] == cell["knee_lines_per_s"]
    assert abs(cell["rate_lines_per_s"]
               - 0.6 * cell["knee_lines_per_s"]) <= 2.5
    assert f"{cell['rate_lines_per_s']:,}" in entry["why"]
    assert "4x" in entry["why"] and "8x" in entry["why"]
    assert "7 of 42" in entry["why"]
    assert "64-chip" in cell["who"]
    assert cell["measured"]
    assert cell["family_metrics"] == [
        "moe_share_of_call", "expert_held_share", "expert_busiest_share",
        "kda_share_of_call", "route_share_of_call"]
    config = manifest.load_cell(REPO, CELL)["config"]
    assert config["traffic_source"]["pool_lines"] % 64 == 0


# -- flops/moe_kda.py against a hand count ------------------------------------

def test_parameters_and_operations_against_a_hand_count(config):
    scorer = dict(scorer_of(config), seq_len=32)
    d = 2560
    # in_proj 2560 -> 5 x 1024 (q | k | v | f | z), b 2560 -> 8, out 1024 ->
    # 2560; 4 taps over q | k | v, A_log, dt_bias, the head norm
    kda = d * 5120 + d * 8 + 1024 * d + 3072 * 4 + 8 + 1024 + 128
    # q 2560 -> 8 x 192, kv_down 2560 -> 576, kv_up 512 -> 8 x 256, the
    # head-wise gate 2560 -> 8, out 1024 -> 2560; kv_norm, q_norm, k_norm
    attn = (d * 1536 + d * 576 + 512 * 2048 + d * 8 + 1024 * d
            + 512 + 2 * 192)
    unit = 3 * d * 768                                   # 5.898 M
    dense = 3 * d * 6144
    # router and its bias, the shared expert, the 8 held
    moe = d * 512 + 512 + unit + 8 * unit
    assert (kda, attn, unit, dense, moe) == (
        15762568, 9098112, 5898240, 47185920, 54395392)
    by_hand = (2 * 19648 * d + d + 7 * 2 * d + 6 * kda + attn + dense
               + 6 * moe)
    assert flops.params_count(scorer) == by_hand == 577867952
    # 6.93 GB resident at 12 bytes, 9.25 GB in the donated step at 16
    assert round(12 * by_hand / 1e9, 2) == 6.93
    assert round(16 * by_hand / 1e9, 2) == 9.25
    # 16 held would be 861.0 M, 13.78 GB in the step
    sixteen = dict(scorer, arch=dict(scorer["arch"], num_experts=16))
    assert flops.params_count(sixteen) == by_hand + 6 * 8 * unit == 860983472
    assert round(16 * 860983472 / 1e9, 2) == 13.78
    core = 16.5 * 4 * 1024                              # one chunk a line
    assert core == 67584 < 3 * 1024 * 128
    macs = flops.macs_per_token(scorer)
    assert macs == (6 * (d * 5120 + d * 8 + 1024 * d + core)
                    + (d * 1536 + d * 576 + 512 * 2048 + d * 8 + 1024 * d
                       + 32 * 8 * (192 + 128))
                    + dense + 6 * (d * 512 + unit) + 19648 * d)
    assert round(2 * macs / 1e6) == 490                 # MFLOP a token
    even = flops.macs_per_token(scorer, even_routing=True)
    assert even - macs == pytest.approx(6 * 8 * 8 / 512 * unit)
    ops, nbytes = flops.ops_and_bytes(scorer, 1024)
    assert ops == 2 * 1024 * 32 * macs
    assert round(ops / 1e12, 2) == 16.04
    assert nbytes == 4 * 577867952 + 1024 * 32 * 2 + 1024 * 4
    # compute-bound on the v5e: 81.4 ms of matmul against 2.8 ms of bytes
    assert ops / 197e12 == pytest.approx(0.0814, rel=1e-2)
    assert nbytes / 819e9 == pytest.approx(0.00282, rel=1e-2)
    # by part, in T a 1024-row call: six delta-rule mixers, the expert
    # layers' dense parts, the dense unit, the head, latent attention
    tokens = 2 * 32768 / 1e12
    assert round(tokens * 6 * (d * 5120 + d * 8 + 1024 * d + core), 1) == 6.2
    assert round(tokens * 6 * (d * 512 + unit), 1) == 2.8
    assert round(tokens * dense, 1) == 3.1
    assert round(tokens * 19648 * d, 1) == 3.3
    assert round(tokens * (attn + 32 * 8 * 320), 1) == 0.6
    head_ops, head_bytes = flops.head_ops_and_bytes(scorer, 1024)
    assert head_ops == 2 * 1024 * 32 * 19648 * d < ops
    assert head_bytes == 2 * 1024 * 32 * d + 2 * 19648 * d + 4 * 1024 * 32
    core_ops, core_bytes = flops.kda_core_ops_and_bytes(scorer, 1024)
    assert core_ops == 2 * 32768 * core
    # q, k, v in once in bfloat16, the decay a head and lane and beta a
    # head in float32, o out in float32: 14.4 KB a token, 0.57 ms at 819
    # GB/s, and memory-bound by a factor of 25
    assert core_bytes == 32768 * (6 * 1024 + 4 * 1024 + 4 * 8 + 4 * 1024)
    assert core_bytes / 819e9 == pytest.approx(0.000575, rel=2e-2)
    assert core_ops / 197e12 < core_bytes / 819e9 / 20


def test_the_count_is_the_built_scorers_leaves():
    import jax

    from detectmateservice_tpu.models.moe_kda import (
        MoEKDAArch, MoEKDAConfig, MoEKDAScorer)

    def leaves(arch, vocab):
        scorer = MoEKDAScorer(MoEKDAConfig(
            arch=MoEKDAArch.from_mapping(arch), vocab_size=vocab,
            seq_len=32))
        shapes = jax.eval_shape(lambda: scorer.init(jax.random.PRNGKey(0))[0])
        return sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(shapes))

    assert leaves(TINY_ARCH, 64) == flops.params_count(
        {"arch": TINY_ARCH, "vocab_size": 64})
    # every layer latent attention over dense units, and none
    for changes in (dict(layer_group_size=1, first_k_dense_replace=4),
                    dict(layer_group_size=5, first_k_dense_replace=0)):
        arch = dict(TINY_ARCH, **changes)
        assert leaves(arch, 64) == flops.params_count(
            {"arch": arch, "vocab_size": 64})
    # at the published widths, by shapes alone
    full = scorer_of(read_json(os.path.join(REPO, "benchmark", "configs",
                                            CONFIG + ".json")))
    assert leaves(full["arch"], full["vocab_size"]) == flops.params_count(
        full) == 577867952


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmark", "reference", "moe_kda.py"),
              encoding="utf-8") as fh:
        source = fh.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import jax",
                       "import jax.numpy as jnp", "import numpy as np"]
    assert "detectmateservice_tpu" not in source.replace(
        "``detectmateservice_tpu.models`` or ``.ops``", "")
    assert "lax.scan" in source and "cumsum" not in source
    assert "top_k" not in source.replace("topk_group", "")   # by sorting


def _tiny_params(seed=0, vocab=64):
    rng = np.random.default_rng(seed)
    a = TINY_ARCH
    d, m = a["hidden_size"], a["moe_intermediate_size"]
    h, hd = a["num_attention_heads"], a["head_dim"]
    nope, rope, dv, rank = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                            a["v_head_dim"], a["kv_lora_rank"])
    nrm = lambda *shape: rng.normal(size=shape).astype(np.float32) * 0.1  # noqa: E731
    params = {"tok_embed": {"embedding": nrm(vocab, d)},
              "lm_head": nrm(vocab, d), "final_norm": 1 + nrm(d)}
    for i in range(a["num_hidden_layers"]):
        lay = {"input_norm": 1 + nrm(d), "post_norm": 1 + nrm(d)}
        if (i + 1) % a["layer_group_size"]:
            lay.update(in_proj={"kernel": nrm(d, 5 * h * hd) * 3},
                       b_proj={"kernel": nrm(d, h) * 3},
                       conv_weight=nrm(3 * h * hd, 4) * 5,
                       A_log=np.log(rng.uniform(0.1, 4, size=h)).astype(
                           np.float32),
                       dt_bias=1 + nrm(h, hd), out_norm=1 + nrm(hd),
                       out_proj={"kernel": nrm(h * hd, d)})
        else:
            lay.update(q_proj={"kernel": nrm(d, h * (nope + rope)) * 3},
                       kv_down={"kernel": nrm(d, rank + rope) * 3},
                       kv_norm=1 + nrm(rank),
                       kv_up={"kernel": nrm(rank, h * (nope + dv)) * 3},
                       q_norm=1 + nrm(nope + rope),
                       k_norm=1 + nrm(nope + rope),
                       attn_gate={"kernel": nrm(d, h) * 3},
                       out_proj={"kernel": nrm(h * dv, d)})
        if i < a["first_k_dense_replace"]:
            lay.update(gate_proj={"kernel": nrm(d, 96) * 3},
                       up_proj={"kernel": nrm(d, 96) * 3},
                       down_proj={"kernel": nrm(96, d)})
        else:
            lay.update(router=nrm(d, 16) * 10,
                       router_bias=np.zeros(16, np.float32),
                       experts_gate=nrm(2, d, m) * 3,
                       experts_up=nrm(2, d, m) * 3,
                       experts_down=nrm(2, m, d),
                       shared_gate_proj={"kernel": nrm(d, 40) * 3},
                       shared_up_proj={"kernel": nrm(d, 40) * 3},
                       shared_down_proj={"kernel": nrm(40, d)})
        params[f"layers_{i}"] = lay
    return {"params": params}


def test_the_references_lower_control_changes_the_scores():
    import jax.numpy as jnp

    reference = importlib.import_module("benchmark.reference.moe_kda")
    rng = np.random.default_rng(1)
    tokens = rng.integers(3, 64, size=(6, 16)).astype(np.int32)
    tokens[:, 0] = 2
    tokens[4, 9:] = 0
    params = _tiny_params()
    scorer = {"arch": TINY_ARCH}
    plain = reference.score(params, tokens, scorer)
    again = reference.score(params, tokens, scorer, block_rows=4)
    lowered = reference.score(params, tokens, scorer,
                              lower=jnp.float8_e4m3fn)
    assert np.isfinite(plain).all() and np.isfinite(lowered).all()
    assert np.allclose(plain, again, atol=1e-5)
    assert np.abs(plain - lowered).max() > 1e-3
    # the rounding saturates: float8_e4m3fn has no infinity
    cast = reference.lowered(jnp.float8_e4m3fn)
    assert float(cast(jnp.asarray(1e6))) == 448.0


def test_the_references_recurrence_is_the_delta_rule_step_by_step():
    reference = importlib.import_module("benchmark.reference.moe_kda")
    rng = np.random.default_rng(2)
    n, s, h, d = 2, 6, 2, 4
    q, k, v = (rng.normal(size=(n, s, h, d)).astype(np.float32)
               for _ in range(3))
    g = (-5 * rng.uniform(size=(n, s, h, d))).astype(np.float32)
    beta = rng.uniform(size=(n, s, h)).astype(np.float32)
    got = np.asarray(reference.delta_rule(q, k, v, g, beta))
    want = np.zeros((n, s, h, d))
    for i in range(n):
        state = np.zeros((h, d, d))
        for t in range(s):
            state = state * np.exp(g[i, t].astype(np.float64))[..., None]
            u = beta[i, t][:, None] * (v[i, t] - np.einsum(
                "hkv,hk->hv", state, k[i, t]))
            state = state + k[i, t][:, :, None] * u[:, None, :]
            want[i, t] = np.einsum("hkv,hk->hv", state, q[i, t])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # a lane whose gate sits at the bound forgets: e^-5 a step
    held = np.asarray(reference.delta_rule(
        q, k, v, np.full_like(g, -5.0), beta))
    assert np.abs(held - got).max() > 1e-3


def test_the_references_rotation_turns_interleaved_pairs():
    reference = importlib.import_module("benchmark.reference.moe_kda")
    x = np.zeros((1, 3, 1, 4), np.float32)
    x[..., 0] = 1.0                        # lane 0 of pair (0, 1)
    x[..., 2] = 1.0                        # lane 2 of pair (2, 3)
    out = np.asarray(reference._interleaved_rotary(x, 100.0))
    for t in range(3):
        np.testing.assert_allclose(
            out[0, t, 0], [np.cos(t), np.sin(t), np.cos(t * 100 ** -0.5),
                           np.sin(t * 100 ** -0.5)], rtol=1e-5, atol=1e-6)


def test_the_references_router_groups_then_chooses_by_the_scores():
    reference = importlib.import_module("benchmark.reference.moe_kda")
    arch = dict(TINY_ARCH, num_experts_per_tok=2, n_group=4, topk_group=2,
                routed_scaling_factor=2.0)
    y = np.eye(16, dtype=np.float32)[:1] * 0 + 1.0           # [1, 16]
    router = np.zeros((16, 16), np.float32)
    # groups of four: group 0 holds the single largest score, groups 2 and 3
    # the largest pairs
    logits = np.full(16, -3.0, np.float32)
    logits[0] = 4.0
    logits[[8, 9]] = 2.0
    logits[[12, 13]] = 1.5
    router[0] = logits
    y = np.zeros((1, 16), np.float32)
    y[0, 0] = 1.0
    chosen, w = reference.routing(y, router, np.zeros(16, np.float32), arch)
    scores = 1 / (1 + np.exp(-logits.astype(np.float64)))
    # a group's score is its two largest: group 0 reads 0.982 + 0.047,
    # group 2 reads 1.76, group 3 reads 1.64 — group 0 is set aside and
    # its expert 0, the largest score of all, is not chosen
    assert sorted(np.asarray(chosen)[0].tolist()) == [8, 9]
    np.testing.assert_allclose(np.asarray(w)[0].sum(), 2.0, rtol=1e-6)
    # a selection bias moves the choice, never the weight
    bias = np.zeros(16, np.float32)
    bias[0] = 1.0
    chosen, w = reference.routing(y, router, bias, arch)
    assert sorted(np.asarray(chosen)[0].tolist()) == [0, 8]
    want = scores[[0, 8]] / scores[[0, 8]].sum() * 2.0
    np.testing.assert_allclose(sorted(np.asarray(w)[0]), sorted(want),
                               rtol=1e-5)


# -- the cell's path on the CPU, tiny ------------------------------------------

def test_a_traced_run_of_the_tiny_cell_is_correct_and_reads_the_counters(
        tmp_path, capsys):
    from benchmark import run

    root, cell = temp_root(tmp_path, config_name=CONFIG, model="moe_kda",
                           traffic="steady64", rate=1500, like=CELL,
                           reduced={key: {"published": 1, "here": 1,
                                          "why": "tiny"} for key in CUT})
    assert cell == "tiny-moe_kda.steady64"
    path = os.path.join(root, "benchmark", "configs", "tiny-moe_kda.json")
    tiny = read_json(path)
    scorer_of(tiny).update(arch=TINY_ARCH)
    tiny["check"].update(extra_alerted_sample=64)
    write_json(path, tiny)
    loaded = manifest.load_cell(root, cell)
    assert loaded["traffic"]["frame_lines"] == 64
    assert OWN_METRICS | {"moe_share_of_call", "expert_held_share",
                          "expert_busiest_share", "attn_share_of_call"} <= {
        s["name"] for s in loaded["per_layer"]}
    result = run.run_cell(root, cell, 2147483647 + 44, 3.0, True,
                          platform="cpu", t_start=time.monotonic())
    printed = capsys.readouterr().out
    assert result["correct"] is True and result["failed"] == 0, printed
    metrics = result["metrics"]
    assert {"expert_held_share", "expert_busiest_share", "batch_occupancy",
            "dispatch_ready_ms.lat", "row_hold_mean_ms"} <= set(metrics)
    # 2 of 16 experts held: an eighth of the assignments under even routing,
    # and the busier of the two takes at least half of those
    assert 1.0 < metrics["expert_held_share"]["value"] < 50.0
    assert 50.0 <= metrics["expert_busiest_share"]["value"] <= 100.0
    assert result["compared"]["compiles_after_warmup"]["value"] == 0
    assert result["compared"]["dropped_lines"]["value"] == 0
