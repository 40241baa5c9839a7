"""Plain reference for the ``moe_conv`` scorer: a causal language model whose
layers differ in kind by a published list (``layer_types``) — gated short
convolutions and grouped-query attention — over a dense gated feed-forward
in the leading layers and routed experts (sigmoid-scored router, no shared
expert) in the rest, with a tied head, and the observed-token NLL — written
out of the layer equations in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.

Imports nothing from ``detectmateservice_tpu.models`` or ``.ops``. One loop
over layers, one over the held experts, every expert computed densely over
all tokens and weighted by the routing (no sort, no grouped matmul, no
kernel); the convolution as three shifted multiply-adds per line on
``[rows, S, D]``; query heads against key/value heads repeated for them;
rows in blocks only so that it fits the host. It reads a parameter tree by
the names the checkpoint gives its leaves:

    tok_embed/embedding [V, D] (also the head)   final_norm [D]
    layers_<i>/operator_norm, ffn_norm [D]
    conv layer:      in_proj {kernel [D, 3D]}, conv_weight [D, K], out_proj {kernel}
    attention layer: qkv_proj {kernel [D, (H + 2G) d]}, q_norm, k_norm [d], out_proj {kernel}
    dense layer:     gate_proj, up_proj, down_proj {kernel}
    expert layer:    router [D, E], router_bias [E],
                     experts_gate, experts_up [held, D, M], experts_down [held, M, D]

Equations (``arch`` holds the published config.json keys; ``norm`` is
RMSNorm with ``norm_eps``; H query heads, G key/value heads of d = D / H;
K = ``conv_L_cache``):

    inp = [CLS, tokens[:-1]];  x = E[inp]
    per layer i:
      y = norm(x)
      layer_types[i] == "conv":
        B | C | xt = y Win;  u = B * xt
        v[t] = sum_j w[:, j] * u[t - (K-1) + j]   (u left of the line = 0)
        x += (C * v) Wout
      layer_types[i] == "full_attention":
        q | k | v = y Wqkv;  q = norm_q(q), k = norm_k(k) per head over d
        rotary positions on q and k over the whole head, rotate-half:
          lanes (i, i + d/2) turn by t * theta^(-2i/d)
        key/value head g serves query heads g*H/G .. (g+1)*H/G - 1
        a = softmax(q k^T / sqrt(d) + causal and PAD mask) v;  x += a Wo
      y = norm(x)
      i < num_dense_layers:  x += W2(silu(W1 y) * W3 y)
      else: s = sigmoid(y Wr) over all router_experts (float32)
            chosen = the num_experts_per_tok largest of s + router_bias
            w = s[chosen] / (sum + 1e-6) * routed_scaling_factor
            x += sum over chosen AND held e of w_e E_e(y)
    h = norm(x);  logits = h E^T
    score(line) = mean over non-PAD positions of (logsumexp(logits) - logits[token])

Departures from the published description, each shared with the program:

* No cache and no decode: this system scores every position of a line in one
  pass and never generates; the convolution's K-deep state is never kept.
* The share: this chip holds ``num_experts`` of the ``router_experts`` routed
  experts (from ``expert_offset``) and a slice of the vocabulary. The router
  scores all experts and the weights normalise over all chosen ones; what
  the absent experts would add is left out, and that partial result goes on
  to the next layer. A sliced vocabulary is a smaller vocabulary.
* The shift: position t is predicted from the tokens before t (input t is
  token t-1, input 0 is CLS), so NLLs line up with the tokens; rotary
  position t is the input's place.
* q, k and v come from one projection, the published three side by side.
* No balance update: ``router_bias`` (the published ``expert_bias``) is read
  as the checkpoint holds it (zeros).

``lower`` (the control) rounds both inputs of every matrix multiplication the
configuration states in bfloat16. The router is stated in float32 and stays
there, as do the gates' and the convolution's elementwise products.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PAD_ID = 0
CLS_ID = 2


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate_half(x, theta):
    """[N, S, heads, d]: lanes (i, i + d/2) at position t turn by
    t * theta^(-2i/d)."""
    s, d = x.shape[1], x.shape[-1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(s, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], -1)


def short_conv(u, weight):
    """Depthwise causal convolution over positions: ``u`` [N, S, D],
    ``weight`` [D, K] → ``v[:, t] = Σ_j weight[:, j] * u[:, t-(K-1)+j]``,
    zeros left of the line."""
    taps = weight.shape[1]
    out = jnp.zeros_like(u)
    for j in range(taps):
        shift = taps - 1 - j
        moved = jnp.pad(u, ((0, 0), (shift, 0), (0, 0)))[:, :u.shape[1]]
        out = out + moved * weight[:, j]
    return out


def routing(y, router, bias, arch):
    """[N, D] -> ([N, K] expert ids over all experts, [N, K] weights)."""
    s = jax.nn.sigmoid(y @ router)
    _, chosen = jax.lax.top_k(s + bias, arch["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if arch["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
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
    eps = arch["norm_eps"]
    heads, groups = arch["num_attention_heads"], arch["num_key_value_heads"]
    d_model = arch["hidden_size"]
    d = d_model // heads
    theta = arch["rope_parameters"]["rope_theta"]
    offset = arch.get("expert_offset", 0)
    tokens = jnp.asarray(tokens, jnp.int32)
    n, s = tokens.shape
    inp = jnp.concatenate([jnp.full((n, 1), CLS_ID, jnp.int32),
                           tokens[:, :-1]], axis=1)
    keep = tokens != PAD_ID
    see = (inp != PAD_ID)[:, None, None, :] & jnp.tril(
        jnp.ones((s, s), bool))[None, None]
    x = f32(p["tok_embed"]["embedding"])[inp]

    def gated(y, gate, up, down):
        return mm(jax.nn.silu(mm(y, f32(gate))) * mm(y, f32(up)), f32(down))

    chosen_by_layer = []
    for i in range(arch["num_hidden_layers"]):
        lay = p[f"layers_{i}"]
        y = _norm(x, f32(lay["operator_norm"]), eps)
        if arch["layer_types"][i] == "conv":
            gate_in, gate_out, xt = jnp.split(
                mm(y, f32(lay["in_proj"]["kernel"])), 3, axis=-1)
            v = short_conv(gate_in * xt, f32(lay["conv_weight"]))
            x = x + mm(gate_out * v, f32(lay["out_proj"]["kernel"]))
        else:
            qkv = mm(y, f32(lay["qkv_proj"]["kernel"]))
            q = qkv[..., :heads * d].reshape(n, s, heads, d)
            k = qkv[..., heads * d:(heads + groups) * d].reshape(
                n, s, groups, d)
            v = qkv[..., (heads + groups) * d:].reshape(n, s, groups, d)
            q = _rotate_half(_norm(q, f32(lay["q_norm"]), eps), theta)
            k = _rotate_half(_norm(k, f32(lay["k_norm"]), eps), theta)
            # each key/value head repeated for its query heads
            k = jnp.repeat(k, heads // groups, axis=2)
            v = jnp.repeat(v, heads // groups, axis=2)
            att = jnp.einsum("bshd,bthd->bhst", cast(q), cast(k)) / np.sqrt(d)
            att = jax.nn.softmax(jnp.where(see, att, -1e30), axis=-1)
            out = jnp.einsum("bhst,bthd->bshd", cast(att), cast(v))
            x = x + mm(out.reshape(n, s, heads * d),
                       f32(lay["out_proj"]["kernel"]))
        y = _norm(x, f32(lay["ffn_norm"]), eps)
        if i < arch["num_dense_layers"]:
            x = x + gated(y, lay["gate_proj"]["kernel"],
                          lay["up_proj"]["kernel"],
                          lay["down_proj"]["kernel"])
            continue
        chosen, w = routing(y, f32(lay["router"]), f32(lay["router_bias"]),
                            arch)
        chosen = jnp.where(keep[..., None], chosen, -1)
        chosen_by_layer.append(chosen)
        moe = jnp.zeros_like(x)
        for e in range(arch["num_experts"]):          # the held experts
            w_e = (w * (chosen == offset + e)).sum(-1)          # [N, S]
            moe = moe + w_e[..., None] * gated(
                y, lay["experts_gate"][e], lay["experts_up"][e],
                lay["experts_down"][e])
        x = x + moe
    h = _norm(x, f32(p["final_norm"]), eps)
    logits = jnp.einsum("bsd,vd->bsv", cast(h),
                        cast(f32(p["tok_embed"]["embedding"])))
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
