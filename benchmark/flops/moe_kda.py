"""Least work of one ``moe_kda`` scoring call: operations and bytes the
algorithm needs at the dispatched shapes, whatever the program spends.

Operations: the matrix multiplications, two per multiply-add, at every one of
the S positions (PAD included: the dense parts compute them) — a delta-rule
layer's two input projections and its output projection and its core in
whichever of its two forms needs less (``_kda_core_macs``: the recurrence's
three products with each head's ``d x d`` state, or the one-chunk closed
form's four causal products over the line, which at 32 positions is a sixth
of it); latent attention's four projections, its head-wise gate, its output
projection and its score and value products over S keys; the dense layers'
gated unit; in an expert layer the router and the shared expert; then the
untied head. **The routed experts' part is counted as zero**, as the other
expert families' counts do: how many assignments fall on the experts held
here is the routing's to decide, so a count that has to hold at any routing
can claim none of it. Under even routing the held experts add
``num_experts_per_tok x num_experts / router_experts`` expert units a token
and expert layer (``even_routing=True``). RMSNorm (the per-head ones too),
rotary positions, softmax, the convolution's taps and SiLU, the gates and
decays with their exponentials about each sub-block's reference point, the
L2 norms, the triangular inverse of the chunked form, the router's sigmoid,
group scores and top-k, the sort and the head's V exponentials per position
are left out too, so a share of the roofline can only read low, never over.

``arch`` counts what THIS chip holds (its heads and experts; kv_down, the
router, the shared expert and the dense unit whole: models/moe_kda.py), so
every count here is the chip's own.

Bytes: every parameter once in float32 as the checkpoint holds it, the token
batch in (uint16 on the wire), the scores out. No activations.
"""
from __future__ import annotations


def _shape(scorer: dict) -> dict:
    a = dict(scorer["arch"])
    a.setdefault("router_experts", a["num_experts"])
    return a


def _kinds(a: dict) -> tuple:
    """(delta-rule, attention, dense, expert) layers of the stack."""
    layers = a["num_hidden_layers"]
    attns = layers // a["layer_group_size"]
    dense = min(a["first_k_dense_replace"], layers)
    return layers - attns, attns, dense, layers - dense


def _kda_width(a: dict) -> int:
    """A delta-rule layer's channels here: heads x head width."""
    return a["num_attention_heads"] * a["head_dim"]


def _kda_weights(a: dict) -> int:
    """Weights of a delta-rule layer's projections (= multiply-adds a
    token): q | k | v | f | z, b, and the output."""
    d, width = a["hidden_size"], _kda_width(a)
    return d * 5 * width + d * a["num_attention_heads"] + width * d


def _kda_core_macs(a: dict, s: int) -> float:
    """Multiply-adds a position of the delta rule's core, the lesser of its
    two forms: position by position (``S'ᵀk``, ``k uᵀ``, ``Sᵀq`` a head) or
    the whole line as one chunk (the decayed ``k kᵀ`` and ``q kᵀ``, the
    inverse's and the scores' products with ``u`` a head, each over the (S
    + 1) / 2 positions a causal row holds on average)."""
    width = _kda_width(a)
    return min(3 * width * a["head_dim"], (s + 1) / 2 * 4 * width)


def _attn_weights(a: dict) -> int:
    """Weights of latent attention's projections: queries, the latent and
    the shared rope key down, keys and values up, the head-wise gate, and
    the output."""
    d, h = a["hidden_size"], a["num_attention_heads"]
    nope, rope, dv, rank = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                            a["v_head_dim"], a["kv_lora_rank"])
    return (d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + dv) + d * h + h * dv * d)


def _unit(a: dict, width: int) -> int:
    """One gated unit at ``width``: gate, up and down."""
    return 3 * a["hidden_size"] * width


def params_count(scorer: dict) -> int:
    a, v = _shape(scorer), scorer["vocab_size"]
    d, h = a["hidden_size"], a["num_attention_heads"]
    kdas, attns, dense, experts = _kinds(a)
    # a mixer with its taps, A_log, dt_bias and its head norm
    kda = (_kda_weights(a) + 3 * _kda_width(a) * a["short_conv_kernel_size"]
           + h + _kda_width(a) + a["head_dim"])
    # kv_norm, q_norm and k_norm
    attn = (_attn_weights(a) + a["kv_lora_rank"]
            + 2 * (a["qk_nope_head_dim"] + a["qk_rope_head_dim"]))
    expert = (d * a["router_experts"] + a["router_experts"]   # router, bias
              + a["num_experts"] * _unit(a, a["moe_intermediate_size"])
              + _unit(a, a["moe_shared_expert_intermediate_size"]))
    # every layer's two norms, the final norm, embedding and head
    return (2 * v * d + d + a["num_hidden_layers"] * 2 * d + kdas * kda
            + attns * attn + dense * _unit(a, a["intermediate_size"])
            + experts * expert)


def macs_per_token(scorer: dict, even_routing: bool = False) -> float:
    """Multiply-adds of one position through body and head."""
    a, v, s = _shape(scorer), scorer["vocab_size"], scorer["seq_len"]
    d = a["hidden_size"]
    kdas, attns, dense, experts = _kinds(a)
    kda = _kda_weights(a) + _kda_core_macs(a, s)
    # the projections, and q.k^T (nope + rope wide) and a.v over S keys
    attn = _attn_weights(a) + s * a["num_attention_heads"] * (
        a["qk_nope_head_dim"] + a["qk_rope_head_dim"] + a["v_head_dim"])
    routed = (a["num_experts_per_tok"] * a["num_experts"]
              / a["router_experts"] * _unit(a, a["moe_intermediate_size"])
              ) if even_routing else 0.0
    return (kdas * kda + attns * attn
            + dense * _unit(a, a["intermediate_size"])
            + experts * (d * a["router_experts"] + _unit(
                a, a["moe_shared_expert_intermediate_size"]) + routed)
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


def kda_core_ops_and_bytes(scorer: dict, rows: int) -> tuple:
    """Least work of ONE delta-rule layer's core (the recurrence between the
    convolution and the output norm: the scope ``layer<i>/kda/core``) for
    one call: ``_kda_core_macs`` a position; bytes, which bound it — q, k
    and v in once in bfloat16, the decay a head and lane and β a head in
    float32, o out once in float32 as the head norm reads it. What a kernel
    for the core would be held to (``kda_core_roofline``); until there is
    one, PERF.md sets the scope's device time against it."""
    a = _shape(scorer)
    width = _kda_width(a)
    tokens = rows * scorer["seq_len"]
    ops = 2 * tokens * _kda_core_macs(a, scorer["seq_len"])
    return ops, tokens * (2 * 3 * width + 4 * width
                          + 4 * a["num_attention_heads"] + 4 * width)
