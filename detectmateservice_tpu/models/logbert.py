"""LogBERT-style Transformer anomaly scorer (flax).

The neural scorer the reference lacks (its ML is classical; SURVEY.md §2.9
"the TPU build adds the neural scorer") and the BASELINE.json config #3
("detector w/ LogBERT-style Transformer anomaly scorer (jit, batch=32)").

Design, TPU-first:
* fixed [B, S] int32 inputs from the hashing tokenizer — no dynamic shapes,
* bfloat16 activations with fp32 logits/softmax accumulation (MXU-friendly),
* masked-token training on normal traffic (optax adamw); anomaly score at
  inference is the pseudo-negative-log-likelihood of the observed tokens, so
  one forward pass scores a whole micro-batch,
* attention goes through ops/attention so the blockwise/ring/pallas variants
  can be swapped in.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from ..ops.attention import self_attention, self_attention_route
from .base import SequenceScorerBase, positional_z_max, token_nll  # noqa: F401 — token_nll/positional_z_max re-exported for compat
from .tokenizer import MASK_ID, PAD_ID


@dataclasses.dataclass(frozen=True)
class LogBERTConfig:
    vocab_size: int = 32768
    dim: int = 256
    depth: int = 4
    heads: int = 4
    mlp_ratio: int = 4
    seq_len: int = 32
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    mask_prob: float = 0.15
    learning_rate: float = 1e-3
    # 0 = mean NLL over all observed tokens; k > 0 = mean of the k most
    # surprising tokens (sharper for single-field anomalies)
    score_topk: int = 0
    # 0 = exact full-vocab NLL; 0 < C < vocab_size = candidate-vocab
    # approximation (models/base.py _token_nlls_candidate): ~V/C fewer head
    # FLOPs
    score_vocab: int = 0
    # "auto" = per traced call, from platform, shape and mesh size
    # (ops/attention.py attention_route): on a TPU the flash kernel for long
    # sequences and, on one device, the short kernel for whole sequences up
    # to 128 tokens; einsum otherwise. "einsum" | "flash" | "short" |
    # "blockwise" | "ring" force a path
    attn_impl: str = "auto"
    # scoring-head implementation: "einsum" = S-chunked einsum + logsumexp
    # over materialized logits; "pallas" = fused online-logsumexp kernel
    # that keeps the [N, V] / [N, C] logits in VMEM (ops/scorehead.py);
    # "auto" = per traced call, from platform, head kind, shape and mesh
    # size (models/base.py head_route): the kernel for the exact head on
    # one TPU, einsum everywhere else
    head_impl: str = "auto"
    # platform of the device the scorer is placed on ("tpu" | "cpu"); set by
    # the executor, "" = the process default backend (models/base.py)
    platform: str = ""


class Block(nn.Module):
    config: LogBERTConfig
    # position in the stack: names the device scopes (``layer<i>/attn``,
    # ``layer<i>/ffn``) a profiler capture groups operations by; metadata
    # only — the parameter tree is named by the parent's attribute
    layer: int = 0

    @nn.compact
    def __call__(self, x: jax.Array, pad_mask: jax.Array) -> jax.Array:
        """``x``: ``[B, S, D]``, or token-major ``[B * S, D]`` (what
        ``LogBERT.hidden`` hands over where the short kernel runs);
        ``pad_mask`` ``[B, S]`` either way."""
        cfg = self.config
        b, s = pad_mask.shape
        with jax.named_scope(f"layer{self.layer}/attn"):
            y = nn.LayerNorm(dtype=cfg.dtype)(x)
            qkv = nn.Dense(3 * cfg.dim, dtype=cfg.dtype, name="qkv")(y)
            # the route decides the layout: the short kernel reads the
            # projection as it lies, the others get head-major copies
            out = self_attention(qkv.reshape(b, s, 3 * cfg.dim), cfg.heads,
                                 key_mask=pad_mask, impl=cfg.attn_impl,
                                 platform=cfg.platform or None)
            x = x + nn.Dense(cfg.dim, dtype=cfg.dtype, name="proj")(
                out.reshape(x.shape))
        with jax.named_scope(f"layer{self.layer}/ffn"):
            y = nn.LayerNorm(dtype=cfg.dtype)(x)
            y = nn.Dense(cfg.dim * cfg.mlp_ratio, dtype=cfg.dtype, name="mlp_in")(y)
            y = nn.gelu(y)
            y = nn.Dense(cfg.dim, dtype=cfg.dtype, name="mlp_out")(y)
            return x + y


class LogBERT(nn.Module):
    config: LogBERTConfig

    def setup(self) -> None:
        cfg = self.config
        self.tok_embed = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype)
        self.pos_embed = self.param(
            "pos_embed", nn.initializers.normal(0.02), (cfg.seq_len, cfg.dim)
        )
        self.blocks = [Block(cfg, layer=i) for i in range(cfg.depth)]
        self.final_ln = nn.LayerNorm(dtype=cfg.dtype)

    def hidden(self, tokens: jax.Array) -> jax.Array:
        """[B, S] int32 → [B, S, D] fp32 final hidden states (pre-head).

        Exposed separately (``apply(..., method="hidden")``) so the scorer
        can compute NLLs in sequence chunks without ever materializing the
        [B, S, V] logits tensor — at V=32k and large micro-batches that
        tensor alone exceeds HBM (models/base.py chunked NLL)."""
        cfg = self.config
        b, s = tokens.shape
        pad_mask = tokens != PAD_ID
        with jax.named_scope("embed"):
            x = self.tok_embed(tokens) + self.pos_embed[
                None, :s].astype(cfg.dtype)
        # Where the short kernel runs, the whole stack runs token-major. A
        # [b, s, ·] activation is laid out sequence-major by XLA on a TPU
        # ({2,0,1}: the LayerNorm statistics want b in the lanes), and the
        # kernel's row-major operands were copied there and back in every
        # layer: 6.2 ms of a layer's 17 (PERF.md section 6, PR 28). A
        # [b * s, ·] array has one layout
        if self_attention_route(b, s, cfg.heads, cfg.dim // cfg.heads,
                                cfg.attn_impl,
                                cfg.platform or None) == "short":
            x = x.reshape(b * s, cfg.dim)
        for blk in self.blocks:
            x = blk(x, pad_mask)
        return self.final_ln(x).astype(jnp.float32).reshape(b, s, cfg.dim)

    def __call__(self, tokens: jax.Array) -> jax.Array:
        """[B, S] int32 → [B, S, V] fp32 logits (weight-tied head).

        The head is an explicit einsum with bf16 multiplies and fp32
        accumulation (MXU-native) rather than ``Embed.attend`` (bf16
        accumulation): fp32 logits keep the loss numerics stable and the
        formulation matches the chunked scoring path bit-for-bit.
        """
        cfg = self.config
        return jnp.einsum("bsd,vd->bsv", self.hidden(tokens).astype(cfg.dtype),
                          self.tok_embed.embedding.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def masked_lm_loss(logits: jax.Array, targets: jax.Array, mask: jax.Array) -> jax.Array:
    logprobs = jax.nn.log_softmax(logits, axis=-1)
    tok_lp = jnp.take_along_axis(logprobs, targets[..., None], axis=-1)[..., 0]
    mask = mask.astype(jnp.float32)
    return -(tok_lp * mask).sum() / jnp.maximum(mask.sum(), 1.0)


class LogBERTScorer(SequenceScorerBase):
    """Masked-LM transformer scorer (jit wiring + NLL scoring from
    SequenceScorerBase; this class owns only the model and its loss)."""

    name = "logbert"

    def __init__(self, config: Optional[LogBERTConfig] = None):
        super().__init__(config or LogBERTConfig())

    def _build_model(self) -> LogBERT:
        return LogBERT(self.config)

    def _train_impl(self, params, opt_state, rng, tokens):
        cfg = self.config
        tokens = tokens.astype(jnp.int32)

        def loss_fn(p):
            mask_rng, _ = jax.random.split(rng)
            maskable = tokens != PAD_ID
            mask = (
                jax.random.uniform(mask_rng, tokens.shape) < cfg.mask_prob
            ) & maskable
            corrupted = jnp.where(mask, MASK_ID, tokens)
            logits = self._apply(p, corrupted)
            return masked_lm_loss(logits, tokens, mask)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = self.optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss
