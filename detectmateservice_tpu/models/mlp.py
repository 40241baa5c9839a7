"""Embedding + MLP bag-of-tokens NLL scorer — the lightweight TPU scorer.

First-rung model of the scorer ladder (SURVEY.md §7 step 5: "First scorer:
embedding+MLP; then the LogBERT-style Transformer"). A CBOW-style log-linear
language model: masked mean-pool of token embeddings → small MLP → weight-tied
logits over the vocab; the anomaly score is the mean NLL of the sequence's
observed tokens. Tokens never seen in training keep unaligned random
embeddings and draw low probability, so novelty shows up directly as surprise
— the same signal LogBERT's pseudo-NLL gives, at a fraction of the FLOPs
(one [B,D]×[D,V] matmul per batch, MXU-friendly).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from .base import ScorerBase, positional_z_max
from .tokenizer import PAD_ID


@dataclasses.dataclass(frozen=True)
class MLPScorerConfig:
    vocab_size: int = 32768
    dim: int = 128
    hidden: int = 256
    seq_len: int = 32
    dtype: Any = jnp.bfloat16
    learning_rate: float = 3e-3
    # scoring-head path: "auto"/"einsum" = weight-tied attend + log_softmax
    # ([B, V] logits materialize); "pallas" = fused online-logsumexp kernel
    # (ops/scorehead.py) + direct target dots — no [B, V] tensor in HBM
    head_impl: str = "auto"
    # platform of the device the scorer is placed on ("tpu" | "cpu"); set by
    # the executor, "" = the process default backend (models/base.py)
    platform: str = ""


class EmbedMLPModel(nn.Module):
    config: MLPScorerConfig

    def setup(self) -> None:
        cfg = self.config
        # explicit names preserve the param-tree layout of the original
        # nn.compact formulation (checkpoint compatibility, tree version 1)
        self.tok_embed = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype,
                                  name="tok_embed")
        self.fc1 = nn.Dense(cfg.hidden, dtype=cfg.dtype, name="Dense_0")
        self.fc2 = nn.Dense(cfg.dim, dtype=cfg.dtype, name="Dense_1")

    def hidden(self, tokens: jax.Array) -> jax.Array:
        """[B, S] int32 → [B, D] context vector (pre-head). Exposed via
        ``apply(..., method="hidden")`` so the pallas head can compute the
        logsumexp without materializing the [B, V] logits."""
        cfg = self.config
        with jax.named_scope("embed"):
            emb = self.tok_embed(tokens)
            mask = (tokens != PAD_ID).astype(cfg.dtype)[..., None]
            pooled = (emb * mask).sum(1) / jnp.maximum(mask.sum(1), 1.0)
        with jax.named_scope("layer0/ffn"):
            return self.fc2(nn.gelu(self.fc1(pooled)))

    def __call__(self, tokens: jax.Array) -> jax.Array:
        """[B, S] int32 → [B, V] fp32 logits (context token distribution)."""
        hidden = self.hidden(tokens).astype(jnp.float32)
        with jax.named_scope("head/nll"):
            return self.tok_embed.attend(hidden)


def _masked_mean_nll(tok_lp: jax.Array, tokens: jax.Array) -> jax.Array:
    """[B, S] per-token log-probs → [B] mean NLL over non-PAD positions.
    The single home for the reduction both head implementations share —
    the parity tests and threshold calibration assume they stay locked."""
    mask = (tokens != PAD_ID).astype(jnp.float32)
    return -(tok_lp * mask).sum(-1) / jnp.maximum(mask.sum(-1), 1.0)


def bag_nll(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Mean NLL of each sequence's non-PAD tokens under its single context
    distribution → [B] fp32."""
    with jax.named_scope("head/nll"):
        logprobs = jax.nn.log_softmax(logits, axis=-1)           # [B, V]
        tok_lp = jnp.take_along_axis(logprobs, tokens, axis=-1)  # [B, S]
        return _masked_mean_nll(tok_lp, tokens)


class MLPScorer(ScorerBase):
    """Bag-of-tokens scorer. Jit wiring/init/score/train_step come from
    ScorerBase; the impls are custom because the model emits ONE context
    distribution per sequence ([B, V] logits), not per-position [B, S, V]."""

    name = "mlp"

    def __init__(self, config: Optional[MLPScorerConfig] = None):
        super().__init__(config or MLPScorerConfig())

    def _build_model(self) -> EmbedMLPModel:
        return EmbedMLPModel(self.config)

    def _pallas_token_logprobs(self, params, tokens: jax.Array) -> jax.Array:
        """[B, S] per-token log-probs via the fused head: lse from the
        online kernel (no [B, V] logits in HBM), target logits from direct
        h·emb[token] dots; bf16 multiplies with fp32 accumulation, like
        the sequence heads."""
        dtype = self.config.dtype
        h = self.model.apply(params, tokens, method="hidden").astype(dtype)
        with jax.named_scope("head/nll"):
            emb = params["params"]["tok_embed"]["embedding"].astype(dtype)
            lse = self._pallas_lse_rows(h, emb)                     # [B]
            tgt = jnp.einsum("bsd,bd->bs", emb[tokens], h,
                             preferred_element_type=jnp.float32)
            return tgt - lse[:, None]

    def _use_pallas_head(self, tokens: jax.Array) -> bool:
        return self._head_route(False, tokens.shape[0],
                                self.config.vocab_size) == "pallas"

    def _score_impl(self, params, tokens: jax.Array) -> jax.Array:
        # tokens may arrive as uint16 (the half-width wire format the
        # detector uploads to cut host→device bandwidth); compute in int32
        tokens = tokens.astype(jnp.int32)
        if self._use_pallas_head(tokens):
            return _masked_mean_nll(
                self._pallas_token_logprobs(params, tokens), tokens)
        return bag_nll(self.model.apply(params, tokens), tokens)

    def _token_nlls_impl(self, params, tokens: jax.Array) -> jax.Array:
        """[B, S] per-position NLL under the bag context distribution."""
        tokens = tokens.astype(jnp.int32)
        if self._use_pallas_head(tokens):
            tok_lp = self._pallas_token_logprobs(params, tokens)
        else:
            logits = self.model.apply(params, tokens)
            with jax.named_scope("head/nll"):
                logprobs = jax.nn.log_softmax(logits, axis=-1)
                tok_lp = jnp.take_along_axis(logprobs, tokens, axis=-1)  # [B, S]
        return -tok_lp * (tokens != PAD_ID).astype(jnp.float32)

    def _normscore_impl(self, params, tokens: jax.Array,
                        mu: jax.Array, sigma: jax.Array) -> jax.Array:
        tokens = tokens.astype(jnp.int32)
        return positional_z_max(self._token_nlls_impl(params, tokens),
                                tokens, mu, sigma)

    def _train_impl(self, params, opt_state, rng, tokens):
        del rng  # no stochastic corruption in the bag model
        tokens = tokens.astype(jnp.int32)

        def loss_fn(p):
            return bag_nll(self.model.apply(p, tokens), tokens).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = self.optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss
