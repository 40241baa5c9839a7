"""Plain reference for the ``logbert`` scorer: the forward pass and the
observed-token NLL, written from the layer equations in float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``.

Imports nothing from ``detectmateservice_tpu.models`` or ``.ops``. It reads a
parameter tree by the names the checkpoint gives its leaves:

    tok_embed/embedding [V, D]      pos_embed [S, D]
    blocks_<i>/LayerNorm_0, LayerNorm_1 {scale, bias}
    blocks_<i>/qkv, proj, mlp_in, mlp_out {kernel, bias}
    final_ln {scale, bias}

Equations (pre-LN transformer encoder, weight-tied head):

    x0 = E[tokens] + P
    per block:  y = LN(x); q,k,v = split(y Wqkv + b)      (heads of D/H)
                a = softmax(q k^T / sqrt(D/H) + pad mask) v
                x = x + a Wproj + b
                y = LN(x); x = x + gelu_tanh(y Win + b) Wout + b
    h = LN(x);  logits = h E^T
    score(line) = mean over non-PAD positions of (logsumexp(logits) - logits[token])

Departure from the published LogBERT: none in the encoder; the score is this
repo's (mean NLL of every observed token in one unmasked pass), not LogBERT's
masked-key top-g rule. PAD = 0. LayerNorm epsilon 1e-6 (flax's default, which
the program's modules take).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PAD_ID = 0
LN_EPS = 1e-6


def _ln(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def token_nlls(params: dict, tokens, heads: int, lower=None):
    """[N, S] int tokens -> [N, S] per-position NLL (PAD positions 0).

    ``lower`` is for the control alone: the same equations with both inputs
    of every matrix multiplication rounded to that type (``float8_e4m3fn``,
    the precision below the configuration's bfloat16)."""
    p = params["params"] if "params" in params else params
    cast = (lambda a: a) if lower is None else (
        lambda a: a.astype(lower).astype(jnp.float32))
    tokens = jnp.asarray(tokens, jnp.int32)
    emb = jnp.asarray(p["tok_embed"]["embedding"], jnp.float32)
    n, s = tokens.shape
    d = emb.shape[1]
    hd = d // heads
    keep = tokens != PAD_ID
    x = emb[tokens] + jnp.asarray(p["pos_embed"], jnp.float32)[None, :s]
    depth = sum(1 for name in p if name.startswith("blocks_"))
    for i in range(depth):
        blk = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), p[f"blocks_{i}"])
        y = _ln(x, blk["LayerNorm_0"])
        qkv = cast(y) @ cast(blk["qkv"]["kernel"]) + blk["qkv"]["bias"]
        q, k, v = (t.reshape(n, s, heads, hd).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        att = jnp.einsum("bhsd,bhtd->bhst", cast(q), cast(k)) / np.sqrt(hd)
        att = jnp.where(keep[:, None, None, :], att, -1e30)
        att = jax.nn.softmax(att, axis=-1)
        out = jnp.einsum("bhst,bhtd->bhsd", cast(att), cast(v))
        out = out.transpose(0, 2, 1, 3).reshape(n, s, d)
        x = x + cast(out) @ cast(blk["proj"]["kernel"]) + blk["proj"]["bias"]
        y = _ln(x, blk["LayerNorm_1"])
        y = _gelu_tanh(cast(y) @ cast(blk["mlp_in"]["kernel"])
                       + blk["mlp_in"]["bias"])
        x = x + cast(y) @ cast(blk["mlp_out"]["kernel"]) + blk["mlp_out"]["bias"]
    h = _ln(x, jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), p["final_ln"]))
    logits = jnp.einsum("bsd,vd->bsv", cast(h), cast(emb))
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    return (lse - tgt) * keep.astype(jnp.float32)


def score(params: dict, tokens: np.ndarray, scorer: dict,
          block_rows: int = 64, lower=None) -> np.ndarray:
    """[N, S] tokens -> [N] float32 scores, in blocks of rows so that the
    [rows, S, V] logits fit the host (the last block is padded with PAD
    rows, so one traced program serves every block)."""
    tokens = np.asarray(tokens, np.int32)
    out = np.zeros((len(tokens),), np.float32)
    heads = int(scorer.get("heads", 4))
    with jax.default_matmul_precision("highest"):
        block = jax.jit(lambda p, t: token_nlls(p, t, heads, lower))
        for start in range(0, len(tokens), block_rows):
            chunk = tokens[start:start + block_rows]
            real = len(chunk)
            if real < block_rows:
                chunk = np.concatenate([chunk, np.zeros(
                    (block_rows - real, tokens.shape[1]), np.int32)])
            nll = np.asarray(block(params, chunk))[:real]
            count = np.maximum((chunk[:real] != PAD_ID).sum(-1), 1)
            out[start:start + real] = nll.sum(-1) / count
    return out
