"""The scorer families ``JaxScorerDetector`` can build, in one table.

Each family says three things of a detector configuration: why it cannot
run it (by name, at construction, before anything is traced), how to build
its scorer, and whether a CPU twin of it can score small batches. Importing
this module imports no jax: the builders import their model on use.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional


class ScorerFamily(NamedTuple):
    # (detector config, model_kw: platform and an explicit dtype) -> scorer
    build: Callable[[Any, Dict[str, Any]], Any]
    # detector config -> why this family cannot run it, or None
    refuses: Callable[[Any], Optional[str]]
    # detector config -> whether the host CPU twin can run this scorer
    host_twin: Callable[[Any], bool]


def _no_arch(cfg) -> Optional[str]:
    if cfg.arch is not None:
        return ("'arch' is the moe_mla, moe_conv, moe_delta, moe_ssm and "
                f"moe_kda families' shape key; model {cfg.model!r} takes "
                "dim/depth/heads")
    return None


def _build_mlp(cfg, model_kw):
    from ...models.mlp import MLPScorer, MLPScorerConfig

    return MLPScorer(MLPScorerConfig(
        vocab_size=cfg.vocab_size, dim=cfg.dim, seq_len=cfg.seq_len,
        head_impl=cfg.head_impl, **model_kw))


def _build_gru(cfg, model_kw):
    from ...models.gru import GRUScorer, GRUScorerConfig

    return GRUScorer(GRUScorerConfig(
        vocab_size=cfg.vocab_size, dim=cfg.dim, depth=cfg.depth,
        seq_len=cfg.seq_len, score_topk=cfg.score_topk,
        score_vocab=cfg.score_vocab, head_impl=cfg.head_impl, **model_kw))


def _build_logbert(cfg, model_kw):
    from ...models.logbert import LogBERTConfig, LogBERTScorer

    return LogBERTScorer(LogBERTConfig(
        vocab_size=cfg.vocab_size, dim=cfg.dim, depth=cfg.depth,
        heads=cfg.heads, seq_len=cfg.seq_len, score_topk=cfg.score_topk,
        attn_impl=cfg.attn_impl, score_vocab=cfg.score_vocab,
        head_impl=cfg.head_impl, **model_kw))


def _build_moe_mla(cfg, model_kw):
    from ...models.moe_mla import MoEMLAArch, MoEMLAConfig, MoEMLAScorer

    return MoEMLAScorer(MoEMLAConfig(
        arch=MoEMLAArch.from_mapping(cfg.arch), vocab_size=cfg.vocab_size,
        seq_len=cfg.seq_len, score_topk=cfg.score_topk,
        attn_impl=cfg.attn_impl, head_impl=cfg.head_impl, **model_kw))


def _build_moe_conv(cfg, model_kw):
    from ...models.moe_conv import MoEConvArch, MoEConvConfig, MoEConvScorer

    return MoEConvScorer(MoEConvConfig(
        arch=MoEConvArch.from_mapping(cfg.arch), vocab_size=cfg.vocab_size,
        seq_len=cfg.seq_len, score_topk=cfg.score_topk,
        attn_impl=cfg.attn_impl, head_impl=cfg.head_impl, **model_kw))


def _build_moe_delta(cfg, model_kw):
    from ...models.moe_delta import (MoEDeltaArch, MoEDeltaConfig,
                                     MoEDeltaScorer)

    return MoEDeltaScorer(MoEDeltaConfig(
        arch=MoEDeltaArch.from_mapping(cfg.arch), vocab_size=cfg.vocab_size,
        seq_len=cfg.seq_len, score_topk=cfg.score_topk,
        attn_impl=cfg.attn_impl, head_impl=cfg.head_impl, **model_kw))


def _build_moe_ssm(cfg, model_kw):
    from ...models.moe_ssm import MoESSMArch, MoESSMConfig, MoESSMScorer

    return MoESSMScorer(MoESSMConfig(
        arch=MoESSMArch.from_mapping(cfg.arch), vocab_size=cfg.vocab_size,
        seq_len=cfg.seq_len, score_topk=cfg.score_topk,
        attn_impl=cfg.attn_impl, head_impl=cfg.head_impl, **model_kw))


def _build_moe_kda(cfg, model_kw):
    from ...models.moe_kda import MoEKDAArch, MoEKDAConfig, MoEKDAScorer

    return MoEKDAScorer(MoEKDAConfig(
        arch=MoEKDAArch.from_mapping(cfg.arch), vocab_size=cfg.vocab_size,
        seq_len=cfg.seq_len, score_topk=cfg.score_topk,
        attn_impl=cfg.attn_impl, head_impl=cfg.head_impl, **model_kw))


def _expert_family_refuses(attn_impls: tuple, attn_why: str
                           ) -> Callable[[Any], Optional[str]]:
    """What no sparse-expert family can do yet, by the key that asks for it
    (ROADMAP: the expert layer across chips, a sliced vocabulary's
    cross-shard logsumexp, quantized experts, a candidate head), and an
    ``attn_impl`` outside ``attn_impls``, the routes that compute the
    family's form of attention (``attn_why`` names it)."""
    def refuses(cfg) -> Optional[str]:
        if not isinstance(cfg.arch, dict):
            return (f"model {cfg.model!r} takes its shape from the mapping "
                    "'arch' (the published config.json keys; "
                    "docs/configuration.md)")
        if cfg.mesh_shape:
            return (f"mesh_shape: the {cfg.model} scorer runs on one device; "
                    "parallel/mesh.py has no expert axis and no rule for it")
        if cfg.dtype == "int8w":
            return "dtype 'int8w': models/quant.py does not quantize experts"
        if cfg.score_vocab > 0:
            return (f"score_vocab > 0: the {cfg.model} scorer has the exact "
                    "head only")
        if cfg.attn_impl not in attn_impls:
            return (f"attn_impl {cfg.attn_impl!r}: {attn_why} (expected one "
                    f"of {list(attn_impls)})")
        return None
    return refuses


FAMILIES: Dict[str, ScorerFamily] = {
    "mlp": ScorerFamily(_build_mlp, _no_arch, lambda cfg: True),
    "gru": ScorerFamily(_build_gru, _no_arch, lambda cfg: True),
    # a forced kernel would run on the twin in interpret mode and ring
    # attention is bound to the accelerator mesh: device-only
    "logbert": ScorerFamily(
        _build_logbert, _no_arch,
        lambda cfg: cfg.attn_impl not in ("flash", "short", "ring")),
    # their scoring calls return counts beside the scores, and a CPU mirror
    # of a model sized for a chip's memory is no latency path
    "moe_mla": ScorerFamily(
        _build_moe_mla,
        _expert_family_refuses(
            ("auto", "einsum", "short"),
            "latent attention is causal with two q·k widths and a value "
            "width of its own, which the einsum route and the "
            "short-sequence kernel compute"),
        lambda cfg: False),
    "moe_conv": ScorerFamily(
        _build_moe_conv,
        _expert_family_refuses(
            ("auto", "einsum"),
            "grouped-query attention (fewer key/value heads than query "
            "heads, causal) is computed by the grouped einsum"),
        lambda cfg: False),
    "moe_delta": ScorerFamily(
        _build_moe_delta,
        _expert_family_refuses(
            ("auto", "einsum"),
            "gated grouped-query attention (fewer key/value heads than "
            "query heads, causal, rotary on a head's first lanes) is "
            "computed by the grouped einsum"),
        lambda cfg: False),
    "moe_ssm": ScorerFamily(
        _build_moe_ssm,
        _expert_family_refuses(
            ("auto", "einsum"),
            "grouped-query attention (fewer key/value heads than query "
            "heads, causal, no rotary positions) is computed by the "
            "grouped einsum"),
        lambda cfg: False),
    "moe_kda": ScorerFamily(
        _build_moe_kda,
        _expert_family_refuses(
            ("auto", "einsum"),
            "latent attention behind per-head query and key norms (every "
            "head's rope part its own, causal) is computed by the einsum "
            "over whole heads"),
        lambda cfg: False),
}
