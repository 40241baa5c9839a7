"""Least work of one ``moe_ssm`` scoring call: operations and bytes the
algorithm needs at the dispatched shapes, whatever the program spends.

Operations: the matrix multiplications, two per multiply-add, at every one of
the S positions (PAD included: the dense parts compute them) — a state-space
layer's input and output projections and its core in whichever of its two
forms needs less (``_ssm_core_macs``: the recurrence's write and read of
each head's ``P x N`` state, or the one-chunk closed form's ``C Bᵀ`` a group
and masked product with ``x`` a head over the line, which at 32 positions is
a fourteenth of it); attention's fused projection, its output projection and
its score and value products over S keys; in an expert layer the router, the
latent's two projections and the shared unit; then the untied head. **The
routed experts' part is counted as zero**, as the other expert families'
counts do: how many assignments fall on the experts held here is the
routing's to decide, so a count that has to hold at any routing can claim
none of it. Under even routing the held experts add ``num_experts_per_tok x
n_routed_experts / router_experts`` expert units a token and expert layer
(``even_routing=True``). RMSNorm, softmax, the convolution's taps, bias and
SiLU, the time steps and decays, the gated norm, the router's sigmoid and
top-k, the sort and the head's V exponentials per position are left out too,
so a share of the roofline can only read low, never over.

``arch`` counts what THIS chip holds (its heads, groups and experts; the
shared unit whole: models/moe_ssm.py), so every count here is the chip's
own.

Bytes: every parameter once in float32 as the checkpoint holds it, the token
batch in (uint16 on the wire), the scores out. No activations.
"""
from __future__ import annotations


def _shape(scorer: dict) -> dict:
    a = dict(scorer["arch"])
    a.setdefault("router_experts", a["n_routed_experts"])
    return a


def _kinds(a: dict) -> tuple:
    """(state-space, attention, expert) layers of the stack."""
    pattern = a["hybrid_override_pattern"]
    return pattern.count("M"), pattern.count("*"), pattern.count("E")


def _ssm_widths(a: dict) -> tuple:
    """(x channels, x | B | C channels: the convolution's)."""
    inner = a["mamba_num_heads"] * a["mamba_head_dim"]
    return inner, inner + 2 * a["n_groups"] * a["ssm_state_size"]


def _ssm_weights(a: dict) -> int:
    """Weights of a state-space layer's projections (= multiply-adds a
    token): z | x | B | C | dt, and the output."""
    d = a["hidden_size"]
    inner, conv = _ssm_widths(a)
    return d * (inner + conv + a["mamba_num_heads"]) + inner * d


def _ssm_core_macs(a: dict, s: int) -> float:
    """Multiply-adds a position of the state-space core, the lesser of its
    two forms: position by position (``x Bᵀ`` into and ``S C`` out of each
    head's state) or the whole line as one chunk (``C Bᵀ`` a group and the
    masked scores' product with ``x`` a head, each over the (S + 1) / 2
    positions a causal row holds on average)."""
    inner, _ = _ssm_widths(a)
    state = a["n_groups"] * a["ssm_state_size"]
    return min(2 * inner * a["ssm_state_size"], (s + 1) / 2 * (state + inner))


def _attn_weights(a: dict) -> int:
    """Weights of attention's projections: q | k | v fused, and the
    output."""
    d, h, g, hd = (a["hidden_size"], a["num_attention_heads"],
                   a["num_key_value_heads"], a["head_dim"])
    return d * (h + 2 * g) * hd + h * hd * d


def _expert_dense_weights(a: dict) -> int:
    """What every token passes in an expert layer: the router, the latent's
    two projections and the shared unit (non-gated: two matrices)."""
    d = a["hidden_size"]
    return (d * a["router_experts"] + 2 * d * a["moe_latent_size"]
            + 2 * d * a["moe_shared_expert_intermediate_size"])


def _unit(a: dict) -> int:
    """One routed expert: up and down, in the latent."""
    return 2 * a["moe_latent_size"] * a["moe_intermediate_size"]


def params_count(scorer: dict) -> int:
    a, v = _shape(scorer), scorer["vocab_size"]
    d = a["hidden_size"]
    ssms, attns, moes = _kinds(a)
    inner, conv = _ssm_widths(a)
    # a mixer with its taps and their bias, dt_bias, A_log and D, the gated
    # norm, and the layer's norm
    ssm = (_ssm_weights(a) + conv * a["conv_kernel"] + conv
           + 3 * a["mamba_num_heads"] + inner + d)
    attn = _attn_weights(a) + d
    moe = (_expert_dense_weights(a) + a["router_experts"]       # the bias
           + a["n_routed_experts"] * _unit(a) + d)
    return 2 * v * d + d + ssms * ssm + attns * attn + moes * moe


def macs_per_token(scorer: dict, even_routing: bool = False) -> float:
    """Multiply-adds of one position through body and head."""
    a, v, s = _shape(scorer), scorer["vocab_size"], scorer["seq_len"]
    ssms, attns, moes = _kinds(a)
    ssm = _ssm_weights(a) + _ssm_core_macs(a, s)
    # the projections, and q.k^T and a.v over S keys (H heads of head_dim)
    attn = _attn_weights(a) + 2 * s * a["num_attention_heads"] * a["head_dim"]
    routed = (a["num_experts_per_tok"] * a["n_routed_experts"]
              / a["router_experts"] * _unit(a)) if even_routing else 0.0
    return (ssms * ssm + attns * attn
            + moes * (_expert_dense_weights(a) + routed)
            + v * a["hidden_size"])


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


def ssm_core_ops_and_bytes(scorer: dict, rows: int) -> tuple:
    """Least work of ONE state-space layer's core (the scan between the
    convolution and the gated norm: the scope ``layer<i>/ssm/core``) for one
    call: ``_ssm_core_macs`` a position; bytes, which bound it — x, B and C
    in once in bfloat16, the time step a head in float32, o out once in
    float32 as the gated norm reads it. What a kernel for the core would be
    held to (``ssd_roofline``); until there is one, PERF.md sets the scope's
    device time against it."""
    a = _shape(scorer)
    inner, conv = _ssm_widths(a)
    tokens = rows * scorer["seq_len"]
    ops = 2 * tokens * _ssm_core_macs(a, scorer["seq_len"])
    return ops, tokens * (2 * conv + 4 * a["mamba_num_heads"] + 4 * inner)
