"""Least work of one ``logbert`` scoring call: operations and bytes the
algorithm needs at the dispatched shapes, whatever the program spends.

Operations: the matrix multiplications of the dense encoder and the exact
full-vocabulary head, two per multiply-add, at every one of the S positions
(PAD included: the dense formulation computes them). LayerNorm, softmax,
gelu and the head's V exponentials per position are left out, so the count
is a lower bound and a share of the roofline can only read low, never over.

Bytes: every parameter once in float32 as the checkpoint holds it, the token
batch in (uint16 on the wire), the scores out. No logits, no activations.
"""
from __future__ import annotations


def params_count(scorer: dict) -> int:
    d, v, s = scorer["dim"], scorer["vocab_size"], scorer["seq_len"]
    ratio = scorer.get("mlp_ratio", 4)
    block = (2 * 2 * d                       # two LayerNorms
             + d * 3 * d + 3 * d             # qkv
             + d * d + d                     # proj
             + d * ratio * d + ratio * d     # mlp_in
             + ratio * d * d + d)            # mlp_out
    return v * d + s * d + scorer["depth"] * block + 2 * d


def ops_per_row(scorer: dict) -> int:
    d, v, s = scorer["dim"], scorer["vocab_size"], scorer["seq_len"]
    ratio = scorer.get("mlp_ratio", 4)
    per_token_block = (2 * d * 3 * d + 2 * d * d + 2 * 2 * d * ratio * d
                       + 2 * 2 * s * d)      # QK^T and AV over S keys
    return s * (scorer["depth"] * per_token_block + 2 * d * v)


def ops_and_bytes(scorer: dict, rows: int) -> tuple:
    ops = rows * ops_per_row(scorer)
    nbytes = (4 * params_count(scorer) + rows * scorer["seq_len"] * 2
              + rows * 4)
    return ops, nbytes


def head_ops_and_bytes(scorer: dict, rows: int) -> tuple:
    """Least work of the exact head's logsumexp kernel (``lse_pallas``) for
    one call: the logits' matrix multiplication, rows x S positions against
    the V x D embedding, two operations per multiply-add. The V exponentials
    per position are left out, so the count is a lower bound. Bytes: hidden
    states and embedding once in bfloat16, as the kernel is given them, and
    one float32 per position out."""
    d, v, s = scorer["dim"], scorer["vocab_size"], scorer["seq_len"]
    ops = 2 * rows * s * v * d
    nbytes = 2 * rows * s * d + 2 * v * d + 4 * rows * s
    return ops, nbytes
