"""The fifth configuration, ``nemotron3-super-120b-a12b-tp8`` (a state-space,
grouped-query-attention, latent-sparse-expert scorer cut to one chip's tensor
share of eight and expert share of sixty-four), and its cell
``nemotron3-super-120b-a12b-tp8.steady64``: its manifest entries and its own
metrics' files, the configuration's file against the source's published
``config.json`` (the catalog's row), ``flops/moe_ssm.py`` against a hand
count and against the built scorer's leaves, the reference's control, its
recurrence and its convolution, and the cell's path end to end on the CPU at
a tiny size (``backend: cpu`` set by the test). What holds of the manifest
for any number of configurations is in ``test_bench_room.py``."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import importlib
import json
import os
import time

import numpy as np
import pytest

from bench_helpers import (REPO, entry_of, metrics_due, read_json, temp_root,
                           write_json)
from benchmark.flops import moe_ssm as flops
from benchmark.lib import manifest

CONFIG = "nemotron3-super-120b-a12b-tp8"
CELL = CONFIG + ".steady64"
# the per-layer metrics this family alone reports
OWN_METRICS = {"ssm_share_of_call", "latent_share_of_call"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
MODEL = "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
# the source's config.json as the model-configs catalog gives it
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 2688,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 2688,
    "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False,
    "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8,
    "n_routed_experts": 512, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 22, "num_hidden_layers": 88,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
CUT = {"num_hidden_layers": 11, "hybrid_override_pattern": "MEMEMEM*EME",
       "n_routed_experts": 8, "vocab_size": 16384, "mamba_num_heads": 16,
       "n_groups": 1, "num_attention_heads": 4, "num_key_value_heads": 1}
SHARE_KEYS = ("router_experts", "expert_offset", "tensor_parallel",
              "tensor_rank")
TINY_ARCH = dict(
    hidden_size=64, num_hidden_layers=4, hybrid_override_pattern="ME*E",
    mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=8,
    conv_kernel=4, chunk_size=128, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, n_routed_experts=2,
    num_experts_per_tok=3, moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=40, routed_scaling_factor=2.5,
    layer_norm_epsilon=1e-5, router_experts=8, expert_offset=2,
    tensor_parallel=2, tensor_rank=1)


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
    # the other families' scope and kernel metrics are not this cell's
    assert not {"delta_share_of_call", "gated_delta_roofline",
                "conv_share_of_call", "gated_conv_roofline"} & {
        s["name"] for s in loaded["per_layer"]}


def test_the_manifest_entries_keep_the_contracts_lengths(listed):
    entry = entry_of(listed, "configs", CONFIG)
    cell = entry_of(listed, "workloads", CELL)
    for text in (entry["source"], entry["why"], cell["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "steady64",
                    "chips": 1, "why": cell["why"]}
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert MODEL in entry["source"]
    assert "model_type nemotron_h" in entry["source"]
    assert entry["reduced"] == list(CUT)
    assert len(json.dumps(listed)) < 64 * 1024
    # the manifest's last configuration, cell and two metrics: appended
    assert listed["configs"][-1]["name"] == CONFIG
    assert listed["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in listed["per_layer"][-2:]] == [
        "ssm_share_of_call", "latent_share_of_call"]
    for name in OWN_METRICS:
        assert entry_of(listed, "per_layer", name) == {
            "name": name, "unit": "%", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "alert_p50_ms", "workloads": [CELL]}


def test_the_own_metrics_are_data_for_a_reader_that_is_there():
    ssm = read_json(os.path.join(REPO, "benchmark", "layer_metrics",
                                 "ssm_share_of_call.json"))
    latent = read_json(os.path.join(REPO, "benchmark", "layer_metrics",
                                    "latent_share_of_call.json"))
    assert (ssm["kind"], ssm["reducer"], ssm["scopes"]) == (
        "trace", "scope_share", ["layer*/ssm"])
    assert (latent["kind"], latent["reducer"], latent["scopes"]) == (
        "trace", "scope_share",
        ["layer*/moe/latent_in", "layer*/moe/latent_out"])
    for spec in (ssm, latent):
        assert spec["layer"] == "kernels" and spec["unit"] == "%"
        assert spec["moves"] == "alert_p50_ms"
    from benchmark.lib import layers

    trace = {"module_scopes": {"jit__score_impl(3)": {
        "Model/layers_0/layer0/ssm/in_proj": 0.2,
        "Model/layers_0/layer0/ssm/core/scores": 0.1,
        "Model/layers_1/layer1/moe/latent_in": 0.05,
        "Model/layers_1/layer1/moe/latent_out": 0.05,
        "Model/layers_1/layer1/moe/experts": 0.3,
        "Model/layers_2/layer2/attn/core": 0.1, "head/nll": 0.2}}}
    assert layers.evaluate(ssm, {"trace": trace}) == pytest.approx(30.0)
    assert layers.evaluate(latent, {"trace": trace}) == pytest.approx(10.0)
    # a program without such scopes (the parent's, another family's)
    # reports nothing, never 0
    other = {"module_scopes": {"jit__score_impl(3)": {"head/nll": 1.0}}}
    assert layers.evaluate(ssm, {"trace": other}) is None
    assert layers.evaluate(latent, {"trace": {}}) is None


# -- the configuration's file ------------------------------------------------

def test_the_published_keys_are_the_catalogs():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    (row,) = [r for r in rows if r["name"] == MODEL]
    assert row["config"] == PUBLISHED
    assert row["source_url"] in read_json(os.path.join(
        REPO, "benchmark", "configs", CONFIG + ".json"))["source"]


def test_the_file_holds_the_published_config_but_for_the_eight_cuts(
        config, listed):
    assert config["reduced"] == list(CUT) and len(CUT) == 8
    for key, published in PUBLISHED.items():
        assert config[key] == CUT.get(key, published), key
    for key, here in CUT.items():
        assert config["cut"][key]["published"] == PUBLISHED[key]
        assert config["cut"][key]["here"] == here
    # the pattern's cut is its own first letters, a whole period in the
    # published ratio 40 : 40 : 8
    assert PATTERN.startswith(CUT["hybrid_override_pattern"])
    assert len(PATTERN) == 88 and [PATTERN.count(c) for c in "ME*"] == [
        40, 40, 8]
    assert [CUT["hybrid_override_pattern"].count(c) for c in "ME*"] == [
        5, 5, 1]
    (entry,) = [c for c in listed["configs"] if c["name"] == CONFIG]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert manifest.reduced_breaches(entry, config) == []
    assert "eight chips share each mixer" in config["deployment"]
    assert "64 chips" in config["deployment"]
    # no width is among the cuts: the shared unit is held whole
    assert not [k for k in CUT if k.endswith("_size") and k != "vocab_size"]
    for other in ("logbert-256x4", "kanana2-30b-a3b-ep8", "lfm2-24b-a2b-ep8",
                  "qwen3-next-80b-a3b-ep16"):
        assert config["guarantees"] == read_json(os.path.join(
            REPO, "benchmark", "configs", other + ".json"))["guarantees"]
    assert {"seq_len", "layer_equations", "no_rotary", "initializer_range",
            "mtp", "causal_contract", "router_of_a_share", "learning_rate",
            "partial_results"} <= set(config["assumed"])
    assert config["changed"]["from"].startswith("qwen3-next-80b-a3b-ep16")
    check = config["check"]
    assert 0 < check["rms_limit_nats"] < check["tolerance_nats"] <= 0.1
    assert check["tolerance_reason"] and "float8_e4m3fn" in config[
        "precision"]["control"]
    assert "stated" in config["precision"]


def test_the_scorers_arch_is_the_published_widths_and_the_share(config):
    scorer = scorer_of(config)
    arch = scorer["arch"]
    assert scorer["model"] == "moe_ssm" and scorer["vocab_size"] == 16384
    for key, value in arch.items():
        if key not in SHARE_KEYS:
            assert value == config[key], key
    assert [arch[k] for k in SHARE_KEYS] == [512, 0, 8, 0]
    assert scorer["max_batch"] == 1024 and scorer["dtype"] == "auto"
    assert scorer["host_score_max_batch"] == 0 and scorer["seq_len"] == 32
    assert scorer["batch_deadline_ms"] == 2000.0
    assert scorer["data_use_training"] == 2048 and scorer["score_vocab"] == 0
    assert config["warmup_buckets"] == [256, 512, 1024]
    # no width is reduced
    widths = ("hidden_size", "head_dim", "mamba_head_dim", "ssm_state_size",
              "conv_kernel", "chunk_size", "moe_intermediate_size",
              "moe_latent_size", "moe_shared_expert_intermediate_size",
              "num_experts_per_tok",
              "routed_scaling_factor", "layer_norm_epsilon")
    assert all(arch[k] == PUBLISHED[k] for k in widths)
    from detectmateservice_tpu.models.moe_ssm import MoESSMArch

    typed = MoESSMArch.from_mapping(arch)
    assert typed.layer_types == ("ssm", "moe") * 3 + ("ssm", "attn", "moe",
                                                       "ssm", "moe")
    assert typed.ssm_inner == 1024
    spec = typed.expert_spec
    assert (spec.held, spec.router_experts, spec.top_k, spec.shared,
            spec.shared_width, spec.latent, spec.gated, spec.scoring_func,
            spec.scaling, spec.width) == (8, 512, 22, 1, 5376, 1024, False,
                                          "sigmoid", 5.0, 2688)
    # the file's share is what share_of derives from the published config
    derived = MoESSMArch.share_of(
        {k: v for k, v in PUBLISHED.items()}, tensor_parallel=8,
        tensor_rank=0, experts_held=8, num_hidden_layers=11)
    assert MoESSMArch.from_mapping(derived) == typed


def test_the_cell_and_its_traffic_state_what_they_offer(listed):
    cell = read_json(os.path.join(REPO, "benchmark", "cells", CELL + ".json"))
    (entry,) = [w for w in listed["workloads"] if w["name"] == CELL]
    assert cell["why"] == entry["why"]
    assert cell["rate_lines_per_s"] > 0 and cell["rate_lines_per_s"] % 5 == 0
    assert "knee" in cell["rate_from"] and "0.6" in cell["rate_from"]
    assert f"{cell['rate_lines_per_s']:,}" in entry["why"]
    assert "8x" in entry["why"] and "11 of 88" in entry["why"]
    assert "64-chip" in cell["who"]
    assert cell["measured"]
    assert cell["family_metrics"] == [
        "moe_share_of_call", "expert_held_share", "expert_busiest_share",
        "ssm_share_of_call", "latent_share_of_call"]
    config = manifest.load_cell(REPO, CELL)["config"]
    assert config["traffic_source"]["pool_lines"] % 64 == 0


# -- flops/moe_ssm.py against a hand count ------------------------------------

def test_parameters_and_operations_against_a_hand_count(config):
    scorer = dict(scorer_of(config), seq_len=32)
    d = 4096
    # in_proj 4096 -> 1024 z + 1024 x + 128 B + 128 C + 16 dt, out 1024 ->
    # 4096; taps and their bias, dt_bias, A_log, D, the gated norm, the norm
    ssm = (d * 2320 + 1024 * d + 1280 * 4 + 1280 + 3 * 16 + 1024 + d)
    # 4 query heads and 1 key/value head of 128; the norm
    attn = d * (512 + 2 * 128) + 512 * d + d
    unit = 2 * 1024 * 2688                              # 5.505 M
    # router and its bias, the latent's two, the shared unit whole (as
    # much as the 8 held experts together)
    moe = (d * 512 + 512 + 2 * d * 1024 + 2 * d * 5376 + 8 * unit + d)
    assert (ssm, attn, unit, moe) == (13708592, 5246976, 5505024, 98570752)
    by_hand = 2 * 16384 * d + d + 5 * ssm + attn + 5 * moe
    assert flops.params_count(scorer) == by_hand == 700865520
    # 8.41 GB resident at 12 bytes, 11.21 GB in the donated step at 16
    assert round(12 * by_hand / 1e9, 2) == 8.41
    assert round(16 * by_hand / 1e9, 2) == 11.21
    # 16 held would be 921.1 M, 14.74 GB in the step: over the chip's 14.5
    sixteen = dict(scorer, arch=dict(scorer["arch"], n_routed_experts=16))
    assert flops.params_count(sixteen) == by_hand + 5 * 8 * unit == 921066480
    core = 16.5 * (128 + 1024)                          # one chunk a line
    assert core == 19008 < 2 * 1024 * 128
    macs = flops.macs_per_token(scorer)
    assert macs == (5 * (d * 2320 + 1024 * d + core)
                    + d * 768 + 512 * d + 2 * 32 * 512
                    + 5 * (d * 512 + 2 * d * 1024 + 2 * d * 5376)
                    + 16384 * d)
    assert round(2 * macs / 1e6) == 827                 # MFLOP a token
    even = flops.macs_per_token(scorer, even_routing=True)
    assert even - macs == pytest.approx(5 * 22 * 8 / 512 * unit)
    ops, nbytes = flops.ops_and_bytes(scorer, 1024)
    assert ops == 2 * 1024 * 32 * macs
    assert nbytes == 4 * 700865520 + 1024 * 32 * 2 + 1024 * 4
    # compute-bound on the v5e: 137.6 ms of matmul against 3.4 ms of bytes
    assert ops / 197e12 == pytest.approx(0.1376, rel=1e-2)
    assert nbytes / 819e9 == pytest.approx(0.00342, rel=1e-2)
    head_ops, head_bytes = flops.head_ops_and_bytes(scorer, 1024)
    assert head_ops == 2 * 1024 * 32 * 16384 * d < ops
    assert head_bytes == 2 * 1024 * 32 * d + 2 * 16384 * d + 4 * 1024 * 32
    core_ops, core_bytes = flops.ssm_core_ops_and_bytes(scorer, 1024)
    assert core_ops == 2 * 32768 * core
    # x, B, C in once in bfloat16, the time steps in float32, o out in
    # float32: 6.7 KB a token, 0.27 ms at 819 GB/s, and memory-bound
    assert core_bytes == 32768 * (2 * 1280 + 4 * 16 + 4 * 1024)
    assert core_bytes / 819e9 == pytest.approx(0.00027, rel=2e-2)
    assert core_ops / 197e12 < core_bytes / 819e9 / 10


def test_the_count_is_the_built_scorers_leaves():
    import jax

    from detectmateservice_tpu.models.moe_ssm import (
        MoESSMArch, MoESSMConfig, MoESSMScorer)

    def leaves(arch, vocab):
        scorer = MoESSMScorer(MoESSMConfig(
            arch=MoESSMArch.from_mapping(arch), vocab_size=vocab,
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
        full) == 700865520


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmark", "reference", "moe_ssm.py"),
              encoding="utf-8") as fh:
        source = fh.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import jax",
                       "import jax.numpy as jnp", "import numpy as np"]
    assert "detectmateservice_tpu" not in source.replace(
        "``detectmateservice_tpu.models`` or ``.ops``", "")
    assert "lax.scan" in source and "cumsum" not in source


def _tiny_params(seed=0, vocab=64):
    rng = np.random.default_rng(seed)
    a = TINY_ARCH
    d, m, lat = a["hidden_size"], a["moe_intermediate_size"], a[
        "moe_latent_size"]
    h, g, ns = a["mamba_num_heads"], a["n_groups"], a["ssm_state_size"]
    inner = h * a["mamba_head_dim"]
    conv = inner + 2 * g * ns
    heads, groups, hd = (a["num_attention_heads"], a["num_key_value_heads"],
                         a["head_dim"])
    nrm = lambda *shape: rng.normal(size=shape).astype(np.float32) * 0.1  # noqa: E731
    params = {"tok_embed": {"embedding": nrm(vocab, d)},
              "lm_head": nrm(vocab, d), "final_norm": 1 + nrm(d)}
    for i, letter in enumerate(a["hybrid_override_pattern"]):
        lay = {"norm": 1 + nrm(d)}
        if letter == "M":
            lay.update(in_proj={"kernel": nrm(d, inner + conv + h) * 3},
                       conv_weight=nrm(conv, 4) * 5, conv_bias=nrm(conv),
                       dt_bias=nrm(h) * 10,
                       A_log=np.log(np.arange(1, h + 1)).astype(np.float32),
                       D=1 + nrm(h), out_norm=1 + nrm(inner),
                       out_proj={"kernel": nrm(inner, d)})
        elif letter == "*":
            lay.update(qkv_proj={"kernel": nrm(d, (heads + 2 * groups) * hd)
                                 * 3},
                       out_proj={"kernel": nrm(heads * hd, d)})
        else:
            lay.update(router=nrm(d, 8) * 10,
                       router_bias=np.zeros(8, np.float32),
                       latent_in={"kernel": nrm(d, lat) * 3},
                       latent_out={"kernel": nrm(lat, d)},
                       experts_up=nrm(2, lat, m) * 3,
                       experts_down=nrm(2, m, lat),
                       shared_up_proj={"kernel": nrm(d, 40) * 3},
                       shared_down_proj={"kernel": nrm(40, d)})
        params[f"layers_{i}"] = lay
    return {"params": params}


def test_the_references_lower_control_changes_the_scores():
    import jax.numpy as jnp

    reference = importlib.import_module("benchmark.reference.moe_ssm")
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
                           {"arch": dict(TINY_ARCH, n_routed_experts=0)},
                           block_rows=4)
    assert np.abs(plain - none).max() > 1e-4


def test_the_references_recurrence_is_the_state_space_step_by_step():
    """``state_space`` against a loop in numpy float64: decay, the write,
    the read, the skip."""
    reference = importlib.import_module("benchmark.reference.moe_ssm")
    rng = np.random.default_rng(2)
    n, s, h, p, ns = 2, 6, 3, 4, 5
    x = rng.normal(size=(n, s, h, p))
    b, c = (rng.normal(size=(n, s, h, ns)) for _ in range(2))
    delta = rng.uniform(0.01, 1.0, size=(n, s, h))
    a = -rng.uniform(0.5, 4.0, size=(h,))
    d = rng.normal(size=(h,))
    out = np.asarray(reference.state_space(
        *(np.asarray(t, np.float32) for t in (x, b, c, delta, a, d))))
    for i in range(n):
        for j in range(h):
            state = np.zeros((p, ns))
            for t in range(s):
                state = (state * np.exp(delta[i, t, j] * a[j])
                         + delta[i, t, j] * np.outer(x[i, t, j], b[i, t, j]))
                np.testing.assert_allclose(
                    out[i, t, j], state @ c[i, t, j] + d[j] * x[i, t, j],
                    atol=1e-4)


def test_the_references_convolution_is_four_shifted_multiply_adds():
    reference = importlib.import_module("benchmark.reference.moe_ssm")
    rng = np.random.default_rng(2)
    u = rng.normal(size=(2, 7, 4)).astype(np.float32)
    w = rng.normal(size=(4, 4)).astype(np.float32)
    out = np.asarray(reference.short_conv(u, w))
    for t in range(7):
        want = sum(w[:, j] * u[:, t - 3 + j] for j in range(4)
                   if t - 3 + j >= 0)
        np.testing.assert_allclose(out[:, t], want, rtol=1e-6, atol=1e-6)


def test_the_references_router_takes_the_scores_not_the_biased_choice():
    """Selection by ``s + bias``, weights from ``s`` alone, normalised over
    the chosen and scaled."""
    reference = importlib.import_module("benchmark.reference.moe_ssm")
    rng = np.random.default_rng(3)
    y = rng.normal(size=(5, 8)).astype(np.float32)
    router = rng.normal(size=(8, 6)).astype(np.float32)
    bias = np.array([0, 0, 0, 0, 0, 10.0], np.float32)
    arch = {"num_experts_per_tok": 2, "routed_scaling_factor": 5}
    chosen, w = (np.asarray(t) for t in reference.routing(y, router, bias,
                                                         arch))
    s = 1 / (1 + np.exp(-(y @ router)))
    assert (chosen == 5).any(axis=-1).all()             # the bias decides
    np.testing.assert_allclose(w.sum(-1), 5.0, rtol=1e-5)
    picked = np.take_along_axis(s, chosen, axis=-1)
    np.testing.assert_allclose(w, 5 * picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)


# -- the cell's path on the CPU, tiny ------------------------------------------

def test_a_traced_run_of_the_tiny_cell_is_correct_and_reads_the_counters(
        tmp_path, capsys):
    from benchmark import run

    root, cell = temp_root(tmp_path, config_name=CONFIG, model="moe_ssm",
                           traffic="steady64", rate=1500, like=CELL,
                           reduced={key: {"published": 1, "here": 1,
                                          "why": "tiny"} for key in CUT})
    assert cell == "tiny-moe_ssm.steady64"
    path = os.path.join(root, "benchmark", "configs", "tiny-moe_ssm.json")
    tiny = read_json(path)
    scorer_of(tiny).update(arch=TINY_ARCH)
    tiny["check"].update(extra_alerted_sample=64)
    write_json(path, tiny)
    loaded = manifest.load_cell(root, cell)
    assert loaded["traffic"]["frame_lines"] == 64
    assert OWN_METRICS | {"moe_share_of_call", "expert_held_share",
                          "expert_busiest_share"} <= {
        s["name"] for s in loaded["per_layer"]}
    result = run.run_cell(root, cell, 2147483647 + 41, 3.0, True,
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
