"""Plain reference for the ``moe_mla`` scorer: a causal language model of
latent-attention (MLA) blocks, one leading dense gated feed-forward and then
expert layers (sigmoid-scored router, shared experts, routed experts), and
the observed-token NLL — written from the layer equations in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``.

Imports nothing from ``detectmateservice_tpu.models`` or ``.ops``. One loop
over layers, one over the held experts, every expert computed densely over
all tokens and weighted by the routing (no sort, no grouped matmul, no
kernel); rows in blocks only so that it fits the host. It reads a parameter
tree by the names the checkpoint gives its leaves:

    tok_embed/embedding [V, D]   lm_head [V, D]   final_norm [D]
    layers_<i>/attn_norm, ffn_norm [D]   kv_norm [kv_lora_rank]
    layers_<i>/q_proj, kv_down, kv_up, out_proj {kernel}
    dense layer:  gate_proj, up_proj, down_proj {kernel}
    expert layer: router [D, E], router_bias [E],
                  experts_gate, experts_up [held, D, M], experts_down [held, M, D],
                  shared_gate_proj, shared_up_proj, shared_down_proj {kernel}

Equations (``arch`` holds the published config.json keys; ``norm`` is
RMSNorm with ``rms_norm_eps``; H heads):

    inp = [CLS, tokens[:-1]];  x = E[inp]
    per layer:
      y = norm(x)
      q = y Wq -> per head q_nope (qk_nope_head_dim) | q_rope (qk_rope_head_dim)
      y Wkva -> c = norm(first kv_lora_rank), k_rope = the rest (one for all heads)
      c Wkvb -> per head k_nope | v (v_head_dim)
      rotary positions on q_rope and k_rope, pairs (2i, 2i+1), theta = rope_theta
      a = softmax(q k^T / sqrt(nope + rope) + causal and PAD mask) v;  x += a Wo
      y = norm(x)
      layer < first_k_dense_replace:  x += Wdown(silu(Wgate y) * Wup y)
      else: s = sigmoid(y Wr) over all router_experts (float32)
            chosen = the num_experts_per_tok largest of s + router_bias
            w = s[chosen] / (sum + 1e-20) * routed_scaling_factor
            x += sum over chosen AND held i of w_i E_i(y) + Shared(y)
    h = norm(x);  logits = h lm_head^T
    score(line) = mean over non-PAD positions of (logsumexp(logits) - logits[token])

Departures from the published description, each shared with the program:

* No cache and no decode: this system scores every position of a line in one
  pass and never generates.
* The share: this chip holds ``n_routed_experts`` of the ``router_experts``
  routed experts (from ``expert_offset``) and a slice of the vocabulary. The
  router scores all experts and the weights normalise over all chosen ones;
  what the absent experts would add is left out, and that partial result goes
  on to the next layer. A sliced vocabulary is a smaller vocabulary.
* The shift: position t is predicted from the tokens before t (input t is
  token t-1, input 0 is CLS), so NLLs line up with the tokens; rotary
  position t is the input's place.
* No balance update: ``router_bias`` (e_score_correction_bias) is read as
  the checkpoint holds it (zeros).

``lower`` (the control) rounds both inputs of every matrix multiplication the
configuration states in bfloat16. The router is stated in float32 and stays
there: a control that failed by re-routing alone would say nothing of the
multiplies.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PAD_ID = 0
CLS_ID = 2


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate(x, theta):
    """[..., S, R]: pair (2i, 2i+1) at position t turns by t * theta^(-2i/R);
    the pairs stay where they are."""
    s, r = x.shape[-2], x.shape[-1]
    freq = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    angle = np.arange(s, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)
    sin = jnp.asarray(np.sin(angle), jnp.float32)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return turned.reshape(x.shape)


def routing(y, router, bias, arch):
    """[N, D] -> ([N, K] expert ids over all experts, [N, K] weights)."""
    logits = y @ router
    if arch["scoring_func"] == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:
        s = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(s + bias, arch["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if arch["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return chosen, w * arch["routed_scaling_factor"]


def token_nlls(params: dict, tokens, arch: dict, lower=None,
               with_routing: bool = False):
    """[N, S] int tokens -> [N, S] per-position NLL (PAD positions 0); with
    ``with_routing`` also the chosen experts of every expert layer,
    ``[layers, N, S, K]`` (PAD positions -1), for the counters' test."""
    p = params["params"] if "params" in params else params
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    cast = (lambda a: a) if lower is None else (
        lambda a: a.astype(lower).astype(jnp.float32))
    mm = lambda a, b: cast(a) @ cast(b)  # noqa: E731
    eps = arch["rms_norm_eps"]
    heads, nope, rope = (arch["num_attention_heads"],
                         arch["qk_nope_head_dim"], arch["qk_rope_head_dim"])
    vdim, rank = arch["v_head_dim"], arch["kv_lora_rank"]
    offset = arch.get("expert_offset", 0)
    tokens = jnp.asarray(tokens, jnp.int32)
    n, s = tokens.shape
    inp = jnp.concatenate([jnp.full((n, 1), CLS_ID, jnp.int32),
                           tokens[:, :-1]], axis=1)
    keep = tokens != PAD_ID
    see = (inp != PAD_ID)[:, None, None, :] & jnp.tril(
        jnp.ones((s, s), bool))[None, None]
    x = f32(p["tok_embed"]["embedding"])[inp]
    chosen_by_layer = []
    for i in range(arch["num_hidden_layers"]):
        lay = p[f"layers_{i}"]
        y = _norm(x, f32(lay["attn_norm"]), eps)
        q = mm(y, f32(lay["q_proj"]["kernel"])).reshape(
            n, s, heads, nope + rope).transpose(0, 2, 1, 3)
        kva = mm(y, f32(lay["kv_down"]["kernel"]))
        c = _norm(kva[..., :rank], f32(lay["kv_norm"]), eps)
        kv = mm(c, f32(lay["kv_up"]["kernel"])).reshape(
            n, s, heads, nope + vdim).transpose(0, 2, 1, 3)
        k_rope = _rotate(kva[..., rank:][:, None], arch["rope_theta"])
        q = jnp.concatenate(
            [q[..., :nope], _rotate(q[..., nope:], arch["rope_theta"])], -1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (n, heads, s, rope))],
            -1)
        att = jnp.einsum("bhsd,bhtd->bhst", cast(q), cast(k)) / np.sqrt(
            nope + rope)
        att = jax.nn.softmax(jnp.where(see, att, -1e30), axis=-1)
        out = jnp.einsum("bhst,bhtd->bhsd", cast(att), cast(kv[..., nope:]))
        out = out.transpose(0, 2, 1, 3).reshape(n, s, heads * vdim)
        x = x + mm(out, f32(lay["out_proj"]["kernel"]))
        y = _norm(x, f32(lay["ffn_norm"]), eps)

        def gated(y, gate, up, down):
            return mm(jax.nn.silu(mm(y, f32(gate))) * mm(y, f32(up)),
                      f32(down))

        if i < arch["first_k_dense_replace"]:
            x = x + gated(y, lay["gate_proj"]["kernel"],
                          lay["up_proj"]["kernel"],
                          lay["down_proj"]["kernel"])
            continue
        chosen, w = routing(y, f32(lay["router"]), f32(lay["router_bias"]),
                            arch)
        chosen = jnp.where(keep[..., None], chosen, -1)
        chosen_by_layer.append(chosen)
        moe = gated(y, lay["shared_gate_proj"]["kernel"],
                    lay["shared_up_proj"]["kernel"],
                    lay["shared_down_proj"]["kernel"])
        for e in range(arch["n_routed_experts"]):     # the held experts
            w_e = (w * (chosen == offset + e)).sum(-1)          # [N, S]
            moe = moe + w_e[..., None] * gated(
                y, lay["experts_gate"][e], lay["experts_up"][e],
                lay["experts_down"][e])
        x = x + moe
    h = _norm(x, f32(p["final_norm"]), eps)
    logits = jnp.einsum("bsd,vd->bsv", cast(h), cast(f32(p["lm_head"])))
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    nll = (lse - tgt) * keep.astype(jnp.float32)
    if with_routing:
        return nll, jnp.stack(chosen_by_layer)
    return nll


def score(params: dict, tokens: np.ndarray, scorer: dict,
          block_rows: int = 32, lower=None) -> np.ndarray:
    """[N, S] tokens -> [N] float32 scores, in blocks of rows so that the
    activations and the [rows, S, V] logits fit the host (the last block is
    padded with PAD rows, so one traced program serves every block)."""
    tokens = np.asarray(tokens, np.int32)
    out = np.zeros((len(tokens),), np.float32)
    arch = dict(scorer["arch"])
    with jax.default_matmul_precision("highest"):
        block = jax.jit(lambda p, t: token_nlls(p, t, arch, lower))
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
