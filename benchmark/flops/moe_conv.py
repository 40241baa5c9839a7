"""Least work of one ``moe_conv`` scoring call: operations and bytes the
algorithm needs at the dispatched shapes, whatever the program spends.

Operations: the matrix multiplications, two per multiply-add, at every one of
the S positions (PAD included: the dense parts compute them) — a gated short
convolution's two projections, grouped-query attention's fused q·k·v and
output projections and its score and value products over S keys, the leading
dense feed-forwards, and in every expert layer the router; then the tied
head. **The routed experts' part is counted as zero**, as
``flops/moe_mla.py`` counts it: how many assignments fall on the experts
held here is the routing's to decide, so a count that has to hold at any
routing can claim none of it (there is no shared expert to count instead).
Under even routing the held experts add ``num_experts_per_tok x num_experts
/ router_experts`` expert units a token and layer (``even_routing=True``).
RMSNorm, rotary positions, softmax, the gates' and the convolution's
elementwise products, the router's sigmoid and top-k, the sort and the
head's V exponentials per position are left out too, so a share of the
roofline can only read low, never over.

Bytes: every parameter once in float32 as the checkpoint holds it, the token
batch in (uint16 on the wire), the scores out. No activations.
"""
from __future__ import annotations


def _shape(scorer: dict) -> dict:
    a = dict(scorer["arch"])
    a.setdefault("router_experts", a["num_experts"])
    return a


def _kinds(a: dict) -> tuple:
    """(convolution layers, attention layers) of the stack."""
    convs = sum(kind == "conv" for kind in a["layer_types"])
    return convs, len(a["layer_types"]) - convs


def _attn_weights(a: dict) -> int:
    """Weights of grouped-query attention's projections (= multiply-adds a
    token): the fused q·k·v and the output."""
    d, h, g = (a["hidden_size"], a["num_attention_heads"],
               a["num_key_value_heads"])
    return d * (h + 2 * g) * (d // h) + d * d


def _conv_weights(a: dict) -> int:
    """Weights of a gated short convolution's two projections."""
    return 4 * a["hidden_size"] ** 2


def params_count(scorer: dict) -> int:
    a, v = _shape(scorer), scorer["vocab_size"]
    d = a["hidden_size"]
    convs, attns = _kinds(a)
    # an operator with its taps or per-head norms, and the layer's two norms
    conv = _conv_weights(a) + d * a["conv_L_cache"] + 2 * d
    attn = _attn_weights(a) + 2 * (d // a["num_attention_heads"]) + 2 * d
    n_dense = a["num_dense_layers"]
    expert = (d * a["router_experts"] + a["router_experts"]   # router, bias
              + a["num_experts"] * 3 * d * a["moe_intermediate_size"])
    return (v * d + d + convs * conv + attns * attn
            + n_dense * 3 * d * a["intermediate_size"]
            + (a["num_hidden_layers"] - n_dense) * expert)


def macs_per_token(scorer: dict, even_routing: bool = False) -> float:
    """Multiply-adds of one position through body and head."""
    a, v, s = _shape(scorer), scorer["vocab_size"], scorer["seq_len"]
    d = a["hidden_size"]
    convs, attns = _kinds(a)
    # the projections, and q.k^T and a.v over S keys (H heads of d / H)
    attn = _attn_weights(a) + 2 * s * d
    unit = 3 * d * a["moe_intermediate_size"]
    routed = (a["num_experts_per_tok"] * a["num_experts"]
              / a["router_experts"] * unit) if even_routing else 0.0
    n_dense = a["num_dense_layers"]
    return (convs * _conv_weights(a) + attns * attn
            + n_dense * 3 * d * a["intermediate_size"]
            + (a["num_hidden_layers"] - n_dense) * (
                d * a["router_experts"] + routed)
            + v * d)


def ops_and_bytes(scorer: dict, rows: int) -> tuple:
    """A lower bound at any routing (the module's docstring)."""
    tokens = rows * scorer["seq_len"]
    ops = 2 * tokens * macs_per_token(scorer)
    nbytes = 4 * params_count(scorer) + tokens * 2 + rows * 4
    return ops, nbytes


def head_ops_and_bytes(scorer: dict, rows: int) -> tuple:
    """Least work of the exact head's logsumexp kernel (``lse_pallas``) for
    one call: the logits' matrix multiplication, rows x S positions against
    the V x D embedding, two operations per multiply-add. The V exponentials
    per position are left out, so the count is a lower bound. Bytes: hidden
    states and embedding once in bfloat16, as the kernel is given them, and
    one float32 per position out."""
    d = _shape(scorer)["hidden_size"]
    v, s = scorer["vocab_size"], scorer["seq_len"]
    ops = 2 * rows * s * v * d
    nbytes = 2 * rows * s * d + 2 * v * d + 4 * rows * s
    return ops, nbytes


def conv_ops_and_bytes(scorer: dict, rows: int) -> tuple:
    """Least work of ONE call of the fused gated-convolution kernel
    (``gated_conv``: one convolution layer's elementwise core): ``B ⊙ x̃``,
    K multiplies and K - 1 adds of the taps and ``C ⊙ v`` per token and
    channel; bytes, which bound it — B, C and x̃ in and the result out once
    in bfloat16, the taps in float32."""
    a = _shape(scorer)
    d, k = a["hidden_size"], a["conv_L_cache"]
    tokens = rows * scorer["seq_len"]
    return (2 * k + 1) * tokens * d, 2 * 4 * tokens * d + 4 * k * d
