"""Least work of one ``moe_mla`` scoring call: operations and bytes the
algorithm needs at the dispatched shapes, whatever the program spends.

Operations: the matrix multiplications, two per multiply-add, at every one of
the S positions (PAD included: the dense parts compute them) — latent
attention's four projections and its score and value products over S keys,
the leading dense feed-forward, and in every expert layer the router and the
shared experts; then the untied head. **The routed experts' part is counted
as zero**: how many assignments fall on the experts held here is the
routing's to decide (none, under a router that sends every token elsewhere),
so a count that has to hold at any routing can claim none of it. Under even
routing the held experts add ``num_experts_per_tok x n_routed_experts /
router_experts`` expert units a token and layer (``even_routing=True``): for
the benchmark's share 594 MFLOP a token where this bound counts 559.
RMSNorm, rotary positions, softmax, the router's sigmoid and top-k, the sort
and the head's V exponentials per position are left out too, so a share of
the roofline can only read low, never over.

Bytes: every parameter once in float32 as the checkpoint holds it, the token
batch in (uint16 on the wire), the scores out. No activations.
"""
from __future__ import annotations


def _shape(scorer: dict) -> dict:
    a = dict(scorer["arch"])
    a.setdefault("router_experts", a["n_routed_experts"])
    return a


def _attn_weights(a: dict) -> int:
    """Weights of latent attention's four projections (= multiply-adds a
    token): q_proj, kv_down, kv_up, out_proj."""
    d, h = a["hidden_size"], a["num_attention_heads"]
    return (d * h * (a["qk_nope_head_dim"] + a["qk_rope_head_dim"])
            + d * (a["kv_lora_rank"] + a["qk_rope_head_dim"])
            + a["kv_lora_rank"] * h * (a["qk_nope_head_dim"]
                                       + a["v_head_dim"])
            + h * a["v_head_dim"] * d)


def params_count(scorer: dict) -> int:
    a, v = _shape(scorer), scorer["vocab_size"]
    d = a["hidden_size"]
    # the projections, kv_norm and the layer's two norms
    attn = _attn_weights(a) + a["kv_lora_rank"] + 2 * d
    unit = 3 * d * a["moe_intermediate_size"]
    dense = attn + 3 * d * a["intermediate_size"]
    expert = (attn + a["n_shared_experts"] * unit
              + d * a["router_experts"] + a["router_experts"]  # router, bias
              + a["n_routed_experts"] * unit)
    n_dense = a["first_k_dense_replace"]
    return (2 * v * d + d + n_dense * dense
            + (a["num_hidden_layers"] - n_dense) * expert)


def macs_per_token(scorer: dict, even_routing: bool = False) -> float:
    """Multiply-adds of one position through body and head."""
    a, v, s = _shape(scorer), scorer["vocab_size"], scorer["seq_len"]
    d, h = a["hidden_size"], a["num_attention_heads"]
    qk = a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
    # the projections, and q.k^T and a.v over S keys
    attn = _attn_weights(a) + h * s * (qk + a["v_head_dim"])
    unit = 3 * d * a["moe_intermediate_size"]
    routed = (a["num_experts_per_tok"] * a["n_routed_experts"]
              / a["router_experts"] * unit) if even_routing else 0.0
    n_dense = a["first_k_dense_replace"]
    return (a["num_hidden_layers"] * attn
            + n_dense * 3 * d * a["intermediate_size"]
            + (a["num_hidden_layers"] - n_dense) * (
                a["n_shared_experts"] * unit + d * a["router_experts"]
                + routed)
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
    the V x D head, two operations per multiply-add. The V exponentials per
    position are left out, so the count is a lower bound. Bytes: hidden
    states and head once in bfloat16, as the kernel is given them, and one
    float32 per position out."""
    d = _shape(scorer)["hidden_size"]
    v, s = scorer["vocab_size"], scorer["seq_len"]
    ops = 2 * rows * s * v * d
    nbytes = 2 * rows * s * d + 2 * v * d + 4 * rows * s
    return ops, nbytes
