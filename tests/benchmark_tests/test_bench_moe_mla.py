"""The second configuration, ``kanana2-30b-a3b-ep8`` (a sparse-expert,
latent-attention scorer cut to one of eight chips' share), and its cell
``kanana2-30b-a3b-ep8.steady64`` (64-line frames since PR 40; until then
``.steady``, whose four 256-line frames a batch spread its median by 16%):
the configuration's file against the source's published ``config.json``,
``flops/moe_mla.py`` against a hand count, the reference's control, and the
cell's path end to end on the CPU at a tiny size (``backend: cpu`` set by
the test). What holds of the manifest for any number of configurations —
every generic list has every cell, a family's metric the cells whose
files name it, a further configuration follows by additions — is in
``test_bench_room.py``, read from the cells' files (``family_metrics``)."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import importlib
import os
import time

import numpy as np
import pytest

from bench_helpers import (REPO, entry_of, metrics_due, read_json, temp_root,
                           write_json)
from benchmark.flops import moe_mla as flops
from benchmark.lib import manifest

CONFIG, CELL = "kanana2-30b-a3b-ep8", "kanana2-30b-a3b-ep8.steady64"
# the source's config.json as the model-configs catalog gives it
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
TINY_ARCH = dict(
    hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32, q_lora_rank=None,
    intermediate_size=96, moe_intermediate_size=48, n_shared_experts=1,
    num_experts_per_tok=2, first_k_dense_replace=1, norm_topk_prob=True,
    routed_scaling_factor=2.448, scoring_func="sigmoid", rope_theta=1e6,
    rope_interleave=True, rms_norm_eps=1e-6, num_hidden_layers=3,
    n_routed_experts=2, router_experts=8, expert_offset=2)


@pytest.fixture(scope="module")
def config():
    return read_json(os.path.join(REPO, "benchmark", "configs",
                                  CONFIG + ".json"))


def scorer_of(config):
    (block,) = config["stages"]["detector"]["component"]["detectors"].values()
    return block


# -- the configuration's file ------------------------------------------------

def test_the_file_holds_the_published_config_but_for_the_three_cuts(config):
    cut = {"num_hidden_layers": 6, "n_routed_experts": 16,
           "vocab_size": 16032}
    assert config["reduced"] == list(cut)
    for key, published in PUBLISHED.items():
        assert config[key] == cut.get(key, published), key
    for key, here in cut.items():
        assert config["cut"][key]["published"] == PUBLISHED[key]
        assert config["cut"][key]["here"] == here
    (entry,) = [c for c in read_json(os.path.join(
        REPO, "BENCHMARK.json"))["configs"] if c["name"] == CONFIG]
    assert manifest.reduced_breaches(entry, config) == []
    assert "eight chips share each layer" in config["deployment"]
    logbert = read_json(os.path.join(REPO, "benchmark", "configs",
                                     "logbert-256x4.json"))
    assert config["guarantees"] == logbert["guarantees"]


def test_the_scorers_arch_is_the_published_widths_and_the_share(config):
    scorer = scorer_of(config)
    arch = scorer["arch"]
    assert scorer["model"] == "moe_mla" and scorer["vocab_size"] == 16032
    for key, value in arch.items():
        if key in ("router_experts", "expert_offset"):
            continue
        assert value == config[key], key      # the file's top level
    assert (arch["router_experts"], arch["n_routed_experts"],
            arch["expert_offset"]) == (128, 16, 0)
    assert scorer["max_batch"] == 1024
    assert scorer["host_score_max_batch"] == 0
    assert config["warmup_buckets"] == [256, 512, 1024]
    # no width is reduced
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "num_experts_per_tok", "n_shared_experts")
    assert all(arch[k] == PUBLISHED[k] for k in widths)
    from detectmateservice_tpu.models.moe_mla import MoEMLAArch

    assert MoEMLAArch.from_mapping(arch).expert_layers == 5


def test_the_cell_states_its_rate_and_where_it_comes_from():
    cell = read_json(os.path.join(REPO, "benchmark", "cells",
                                  CELL + ".json"))
    listed = read_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = entry_of(listed, "workloads", CELL)
    assert entry == {"name": CELL, "config": CONFIG, "traffic": "steady64",
                     "chips": 1, "why": cell["why"]}
    assert 1 <= len(entry["why"]) <= 200
    assert cell["rate_lines_per_s"] > 0 and "knee" in cell["rate_from"]
    assert f"{cell['rate_lines_per_s']:,}" in cell["why"]
    # the cell it replaced is gone, and the configuration keeps one cell
    assert [w["name"] for w in listed["workloads"]
            if w["config"] == CONFIG] == [CELL]
    assert not os.path.exists(os.path.join(
        REPO, "benchmark", "cells", CONFIG + ".steady.json"))
    traffic = read_json(os.path.join(REPO, "benchmark", "traffic",
                                     "steady64.json"))
    assert (traffic["frame_lines"], traffic["arrival"]) == (64, "exponential")


# -- flops/moe_mla.py against a hand count ------------------------------------

def test_parameters_and_operations_against_a_hand_count(config):
    scorer = scorer_of(config)
    attn = (2048 * 32 * 192 + 2048 * 576 + 512 + 512 * 32 * 256
            + 32 * 128 * 2048 + 2 * 2048)
    assert attn == 26350080                               # 26.35 M a layer
    unit = 3 * 2048 * 768                                 # 4.72 M
    expert_layer = attn + 2 * unit + 2048 * 128 + 128 + 16 * unit
    assert flops.params_count(scorer) == (
        2 * 16032 * 2048 + 2048 + attn + 3 * 2048 * 6144
        + 5 * expert_layer) == 687502976
    macs = flops.macs_per_token(scorer)
    by_hand = (6 * (attn - 512 - 2 * 2048 + 32 * 32 * (192 + 128))
               + 3 * 2048 * 6144 + 5 * (2 * unit + 2048 * 128)
               + 16032 * 2048)
    assert macs == by_hand
    assert round(2 * macs / 1e6) == 558                   # MFLOP a token
    even = flops.macs_per_token(scorer, even_routing=True)
    assert even - macs == pytest.approx(5 * 0.75 * unit)  # 17.7 M
    assert round(2 * even / 1e6) == 594
    ops, nbytes = flops.ops_and_bytes(scorer, 1024)
    assert ops == 2 * 1024 * 32 * macs
    assert nbytes == 4 * 687502976 + 1024 * 32 * 2 + 1024 * 4
    # compute-bound on the v5e: 92.9 ms of matmul against 3.4 ms of bytes
    assert ops / 197e12 == pytest.approx(0.0929, rel=1e-2)
    head_ops, head_bytes = flops.head_ops_and_bytes(scorer, 1024)
    assert head_ops == 2 * 1024 * 32 * 16032 * 2048 < ops
    assert head_bytes == (2 * 1024 * 32 * 2048 + 2 * 16032 * 2048
                          + 4 * 1024 * 32)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmark", "reference", "moe_mla.py"),
              encoding="utf-8") as fh:
        source = fh.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import jax",
                       "import jax.numpy as jnp", "import numpy as np"]


def _tiny_params(seed=0, vocab=64):
    rng = np.random.default_rng(seed)
    a = TINY_ARCH
    d, h, m = a["hidden_size"], a["num_attention_heads"], 48
    nrm = lambda *shape: rng.normal(size=shape).astype(np.float32) * 0.1  # noqa: E731
    params = {"tok_embed": {"embedding": nrm(vocab, d)},
              "lm_head": nrm(vocab, d), "final_norm": np.ones(d, np.float32)}
    for i in range(a["num_hidden_layers"]):
        lay = {"attn_norm": np.ones(d, np.float32),
               "ffn_norm": np.ones(d, np.float32),
               "kv_norm": np.ones(32, np.float32),
               "q_proj": {"kernel": nrm(d, h * 24)},
               "kv_down": {"kernel": nrm(d, 40)},
               "kv_up": {"kernel": nrm(32, h * 32)},
               "out_proj": {"kernel": nrm(h * 16, d)}}
        if i < 1:
            lay.update(gate_proj={"kernel": nrm(d, 96)},
                       up_proj={"kernel": nrm(d, 96)},
                       down_proj={"kernel": nrm(96, d)})
        else:
            lay.update(router=nrm(d, 8) * 10, router_bias=np.zeros(8, np.float32),
                       experts_gate=nrm(2, d, m), experts_up=nrm(2, d, m),
                       experts_down=nrm(2, m, d),
                       shared_gate_proj={"kernel": nrm(d, m)},
                       shared_up_proj={"kernel": nrm(d, m)},
                       shared_down_proj={"kernel": nrm(m, d)})
        params[f"layers_{i}"] = lay
    return {"params": params}


def test_the_references_lower_control_changes_the_scores():
    import jax.numpy as jnp

    reference = importlib.import_module("benchmark.reference.moe_mla")
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
    # the share: with no expert held but the shared one the scores differ
    none = reference.score(params, tokens,
                           {"arch": dict(TINY_ARCH, n_routed_experts=0)},
                           block_rows=4)
    assert np.abs(plain - none).max() > 1e-4


# -- the cell's path on the CPU, tiny ------------------------------------------

def test_a_traced_run_of_the_tiny_cell_is_correct_and_reads_the_counters(
        tmp_path, capsys):
    from benchmark import run

    root, cell = temp_root(tmp_path, config_name=CONFIG, model="moe_mla",
                           traffic="steady64", rate=1500, like=CELL, reduced={
                               key: {"published": 1, "here": 1, "why": "tiny"}
                               for key in ("num_hidden_layers",
                                           "n_routed_experts", "vocab_size")})
    path = os.path.join(root, "benchmark", "configs", "tiny-moe_mla.json")
    tiny = read_json(path)
    scorer_of(tiny).update(arch=TINY_ARCH)
    tiny["check"].update(extra_alerted_sample=64)
    write_json(path, tiny)
    result = run.run_cell(root, cell, 7, 3.0, True, platform="cpu",
                          t_start=time.monotonic())
    printed = capsys.readouterr().out
    assert result["correct"] is True and result["failed"] == 0, printed
    assert "num_hidden_layers 1 -> 1" in printed
    metrics = result["metrics"]
    assert {"expert_held_share", "expert_busiest_share", "batch_occupancy",
            "dispatch_ready_ms.lat"} <= set(metrics)
    # the tiny cell is asked for what its family's cell reports
    assert {s["name"] for s in manifest.load_cell(root, cell)[
        "per_layer"]} == metrics_due(
            REPO, read_json(os.path.join(REPO, "BENCHMARK.json")), CELL)
    # 2 of 8 experts held: a quarter of the assignments under even routing
    # (a share's router is not trained, so the fit leaves it there)
    assert 10.0 < metrics["expert_held_share"]["value"] < 45.0
    # the busier of the two held experts: half when balanced, all at most
    assert 50.0 <= metrics["expert_busiest_share"]["value"] <= 100.0
    assert result["compared"]["compiles_after_warmup"]["value"] == 0
