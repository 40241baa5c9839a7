"""DeepLog-style recurrent (GRU) next-token anomaly scorer (flax).

Third rung of the scorer ladder (mlp → gru → logbert). A causal next-token
language model over the hashed token stream: each position's token is
predicted from the learned prefix state, so the anomaly score is the true
autoregressive NLL of the sequence — the DeepLog formulation — rather than
the bag (mlp) or masked-LM pseudo-NLL (logbert). The reference has no
accelerator or sequence model at all (SURVEY.md §0 "no training, no
GPU/accelerator code"); this family exists because recurrent scorers catch
*order* anomalies (a valid token in the wrong place) that the bag model is
blind to, at ~1/4 of the transformer's FLOPs for short log sequences.

TPU-first design notes:
* fixed [B, S] int32 inputs; the time loop is ``flax.linen.RNN`` (lax.scan
  under jit — traced once, no Python-level unrolling, static shapes),
* per-step matmuls are [B, D]x[D, 3D] — batched and MXU-tiled; bfloat16
  activations with fp32 logits/log-softmax accumulation,
* weight-tied output head (``embed.attend``) keeps HBM traffic at one
  embedding table,
* the scan carries [B, D] per layer — tiny versus the transformer's
  [B, S, S] attention intermediates, so very large micro-batches fit.

Interface-compatible with MLPScorer/LogBERTScorer (score / train_step /
_score_impl / _token_nlls_impl / _normscore_impl / init), so the detector
(`library/detectors/jax_scorer.py`) and parallel.ShardedScorer compose with
it unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from .base import SequenceScorerBase
from .tokenizer import PAD_ID


@dataclasses.dataclass(frozen=True)
class GRUScorerConfig:
    vocab_size: int = 32768
    dim: int = 128
    depth: int = 1                    # stacked GRU layers
    seq_len: int = 32
    dtype: Any = jnp.bfloat16
    learning_rate: float = 2e-3
    # 0 = mean NLL over observed tokens; k > 0 = mean of the k most
    # surprising (same knob as LogBERTConfig.score_topk)
    score_topk: int = 0
    # candidate-vocab approximate NLL (same knob as LogBERTConfig.score_vocab)
    score_vocab: int = 0
    # scoring-head implementation (same knob as LogBERTConfig)
    head_impl: str = "auto"
    # platform of the device the scorer is placed on ("tpu" | "cpu"); set by
    # the executor, "" = the process default backend (models/base.py)
    platform: str = ""


class GRULM(nn.Module):
    config: GRUScorerConfig

    def setup(self) -> None:
        cfg = self.config
        self.tok_embed = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype)
        self.bos_embed = self.param(
            "bos_embed", nn.initializers.normal(0.02), (cfg.dim,))
        self.rnns = [nn.RNN(nn.GRUCell(features=cfg.dim, dtype=cfg.dtype))
                     for _ in range(cfg.depth)]
        self.final_ln = nn.LayerNorm(dtype=cfg.dtype)

    def hidden(self, tokens: jax.Array) -> jax.Array:
        """[B, S] int32 → [B, S, D] fp32 causal hidden states (pre-head).

        Position t's state is computed from tokens[<t] plus a learned BOS
        embedding, so every position (including 0) has a real prediction and
        the per-position NLLs line up 1:1 with the input tokens — the same
        alignment contract positional_z_max and the calibration pass assume.
        Exposed separately for the chunked NLL path (models/base.py)."""
        cfg = self.config
        with jax.named_scope("embed"):
            emb = self.tok_embed(tokens)             # [B, S, D]
            # teacher-forced shift-right: the input at step t is token t-1
            x = jnp.concatenate(
                [jnp.broadcast_to(self.bos_embed.astype(cfg.dtype),
                                  (tokens.shape[0], 1, cfg.dim)),
                 emb[:, :-1]], axis=1)
        for i, rnn in enumerate(self.rnns):
            with jax.named_scope(f"layer{i}/rnn"):
                x = rnn(x)                           # lax.scan over time
        return self.final_ln(x).astype(jnp.float32)

    def __call__(self, tokens: jax.Array) -> jax.Array:
        """[B, S] int32 → [B, S, V] fp32 causal next-token logits
        (weight-tied einsum head, bf16 multiplies / fp32 accumulation —
        see LogBERT.__call__)."""
        cfg = self.config
        return jnp.einsum("bsd,vd->bsv", self.hidden(tokens).astype(cfg.dtype),
                          self.tok_embed.embedding.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def causal_lm_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Mean next-token NLL over all non-PAD positions (scalar)."""
    logprobs = jax.nn.log_softmax(logits, axis=-1)
    tok_lp = jnp.take_along_axis(logprobs, tokens[..., None], axis=-1)[..., 0]
    mask = (tokens != PAD_ID).astype(jnp.float32)
    return -(tok_lp * mask).sum() / jnp.maximum(mask.sum(), 1.0)


class GRUScorer(SequenceScorerBase):
    """Causal GRU LM scorer (jit wiring + NLL scoring from
    SequenceScorerBase; this class owns only the model and its loss)."""

    name = "gru"

    def __init__(self, config: Optional[GRUScorerConfig] = None):
        super().__init__(config or GRUScorerConfig())

    def _build_model(self) -> GRULM:
        return GRULM(self.config)

    def _train_impl(self, params, opt_state, rng, tokens):
        del rng  # teacher forcing is deterministic; no corruption step
        tokens = tokens.astype(jnp.int32)

        def loss_fn(p):
            return causal_lm_loss(self.model.apply(p, tokens), tokens)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = self.optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss
