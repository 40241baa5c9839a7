"""Least work of one ``moe_delta`` scoring call: operations and bytes the
algorithm needs at the dispatched shapes, whatever the program spends.

Operations: the matrix multiplications, two per multiply-add, at every one of
the S positions (PAD included: the dense parts compute them) — a delta
layer's two input projections and its output projection and its core in
whichever of its two forms needs less (``_delta_core_macs``: the recurrence's
three products with each value head's ``Dk x Dv`` state, or the one-chunk
closed form's four causal products over the line, which at 32 positions is
an eighth of it); gated attention's fused projection (queries, their gates,
keys, values), its output projection and its score and value products over
S keys; in every layer the router, the shared expert's gate and the shared expert; then the
untied head. **The routed experts' part is counted as zero**, as
``flops/moe_mla.py`` and ``flops/moe_conv.py`` count it: how many
assignments fall on the experts held here is the routing's to decide, so a
count that has to hold at any routing can claim none of it. Under even
routing the held experts add ``num_experts_per_tok x num_experts /
router_experts`` expert units a token and layer (``even_routing=True``).
RMSNorm, rotary positions, softmax, the convolution's taps and SiLU, the
gates and decays, the L2 norms, the triangular inverse of the chunked form,
the router's softmax and top-k, the sort and the head's V exponentials per
position are left out too, so a share of the roofline can only read low,
never over.

Bytes: every parameter once in float32 as the checkpoint holds it, the token
batch in (uint16 on the wire), the scores out. No activations.
"""
from __future__ import annotations


def _shape(scorer: dict) -> dict:
    a = dict(scorer["arch"])
    a.setdefault("router_experts", a["num_experts"])
    return a


def _kinds(a: dict) -> tuple:
    """(delta layers, attention layers) of the stack."""
    attns = a["num_hidden_layers"] // a["full_attention_interval"]
    return a["num_hidden_layers"] - attns, attns


def _delta_widths(a: dict) -> tuple:
    """(q + k + v channels, the convolution's; value channels)."""
    keys = a["linear_num_key_heads"] * a["linear_key_head_dim"]
    values = a["linear_num_value_heads"] * a["linear_value_head_dim"]
    return 2 * keys + values, values


def _delta_weights(a: dict) -> int:
    """Weights of a delta layer's projections (= multiply-adds a token):
    q | k | v | z, b | a, and the output."""
    d = a["hidden_size"]
    mixed, values = _delta_widths(a)
    return (d * (mixed + values) + d * 2 * a["linear_num_value_heads"]
            + values * d)


def _delta_core_macs(a: dict, s: int) -> float:
    """Multiply-adds a position of the delta rule's core, the lesser of its
    two forms: position by position (``S'ᵀk``, ``k uᵀ``, ``Sᵀq`` a value
    head) or the whole line as one chunk (``k kᵀ`` and ``q kᵀ`` a key head,
    the inverse's and the decayed scores' products with ``u`` a value head,
    each over the (S + 1) / 2 positions a causal row holds on average)."""
    hk, hv = a["linear_num_key_heads"], a["linear_num_value_heads"]
    dk, dv = a["linear_key_head_dim"], a["linear_value_head_dim"]
    return min(3 * hv * dk * dv, (s + 1) / 2 * 2 * (hk * dk + hv * dv))


def _attn_weights(a: dict) -> int:
    """Weights of gated attention's projections: queries with their gates,
    keys and values fused, and the output."""
    d, h, g, hd = (a["hidden_size"], a["num_attention_heads"],
                   a["num_key_value_heads"], a["head_dim"])
    return d * (2 * h + 2 * g) * hd + h * hd * d


def _shared_weights(a: dict) -> int:
    """The shared expert's gated unit and its per-token gate."""
    d = a["hidden_size"]
    return 3 * d * a["shared_expert_intermediate_size"] + d


def params_count(scorer: dict) -> int:
    a, v = _shape(scorer), scorer["vocab_size"]
    d = a["hidden_size"]
    deltas, attns = _kinds(a)
    mixed, _ = _delta_widths(a)
    # a mixer with its taps, gates' parameters and norms, and the layer's
    # two norms
    delta = (_delta_weights(a) + mixed * a["linear_conv_kernel_dim"]
             + 2 * a["linear_num_value_heads"] + a["linear_value_head_dim"]
             + 2 * d)
    attn = _attn_weights(a) + 2 * a["head_dim"] + 2 * d
    expert = (d * a["router_experts"] + a["router_experts"]   # router, bias
              + a["num_experts"] * 3 * d * a["moe_intermediate_size"]
              + _shared_weights(a))
    return (2 * v * d + d + deltas * delta + attns * attn
            + a["num_hidden_layers"] * expert)


def macs_per_token(scorer: dict, even_routing: bool = False) -> float:
    """Multiply-adds of one position through body and head."""
    a, v, s = _shape(scorer), scorer["vocab_size"], scorer["seq_len"]
    d = a["hidden_size"]
    deltas, attns = _kinds(a)
    delta = _delta_weights(a) + _delta_core_macs(a, s)
    # the projections, and q.k^T and a.v over S keys (H heads of head_dim)
    attn = _attn_weights(a) + 2 * s * a["num_attention_heads"] * a["head_dim"]
    unit = 3 * d * a["moe_intermediate_size"]
    routed = (a["num_experts_per_tok"] * a["num_experts"]
              / a["router_experts"] * unit) if even_routing else 0.0
    return (deltas * delta + attns * attn
            + a["num_hidden_layers"] * (d * a["router_experts"]
                                        + _shared_weights(a) + routed)
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


def delta_ops_and_bytes(scorer: dict, rows: int) -> tuple:
    """Least work of ONE delta layer's core (the recurrence between the
    convolution and the output norm) for one call, which is one call of the
    kernel ``gated_delta`` (``gated_delta_roofline``): ``_delta_core_macs`` a
    position; bytes, which bound it — what the kernel has to move: a line's
    q, k and v in and o out once in bfloat16, the two gates a value head in
    float32. The ``[128, 128]`` intermediates of a tile (decay-masked
    ``k kᵀ``, its inverse, ``q kᵀ``) never leave the chip's fast memory and
    are not counted, so the share can only read low."""
    a = _shape(scorer)
    mixed, values = _delta_widths(a)
    tokens = rows * scorer["seq_len"]
    ops = 2 * tokens * _delta_core_macs(a, scorer["seq_len"])
    return ops, (2 * tokens * (mixed + values)
                 + 4 * tokens * 2 * a["linear_num_value_heads"])
