"""Plain reference for the ``mlp`` scorer (embedding + MLP bag-of-tokens
language model), float32 ``jax.numpy`` under matmul precision ``highest``.

Imports nothing from ``detectmateservice_tpu.models`` or ``.ops``. Leaves, by
the checkpoint's names: ``tok_embed/embedding [V, D]``, ``Dense_0`` and
``Dense_1`` ``{kernel, bias}``.

    pooled = mean over non-PAD positions of E[token]
    c      = gelu_tanh(pooled W0 + b0) W1 + b1
    logits = c E^T                          (one distribution per line)
    score  = mean over non-PAD positions of (logsumexp(logits) - logits[token])
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PAD_ID = 0


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _scores(p, tokens, lower=None):
    cast = (lambda a: a) if lower is None else (
        lambda a: a.astype(lower).astype(jnp.float32))
    tokens = jnp.asarray(tokens, jnp.int32)
    emb = jnp.asarray(p["tok_embed"]["embedding"], jnp.float32)
    keep = (tokens != PAD_ID).astype(jnp.float32)
    count = jnp.maximum(keep.sum(-1), 1.0)
    pooled = (emb[tokens] * keep[..., None]).sum(1) / count[:, None]
    w0, w1 = p["Dense_0"], p["Dense_1"]
    hid = _gelu_tanh(cast(pooled) @ cast(jnp.asarray(w0["kernel"], jnp.float32))
                     + jnp.asarray(w0["bias"], jnp.float32))
    ctx = (cast(hid) @ cast(jnp.asarray(w1["kernel"], jnp.float32))
           + jnp.asarray(w1["bias"], jnp.float32))
    logits = cast(ctx) @ cast(emb).T
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, tokens, axis=-1)
    return ((lse[:, None] - tgt) * keep).sum(-1) / count


def score(params: dict, tokens: np.ndarray, scorer: dict,
          block_rows: int = 1024, lower=None) -> np.ndarray:
    """``lower`` is for the control alone: both inputs of every matrix
    multiplication rounded to that type (``float8_e4m3fn``)."""
    p = params["params"] if "params" in params else params
    tokens = np.asarray(tokens, np.int32)
    out = np.zeros((len(tokens),), np.float32)
    with jax.default_matmul_precision("highest"):
        for start in range(0, len(tokens), block_rows):
            chunk = tokens[start:start + block_rows]
            out[start:start + len(chunk)] = np.asarray(
                _scores(p, chunk, lower))
    return out
