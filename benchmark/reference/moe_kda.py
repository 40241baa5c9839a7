"""Plain reference for the ``moe_kda`` scorer: a causal language model whose
layers differ in kind by a published rule — every ``layer_group_size``-th
mixes positions with latent attention behind per-head query/key norms and a
head-wise output gate, the others with the delta rule whose decay is a
vector a head (Kimi Delta Attention) — over a leading dense gated unit, then
routed experts chosen group-first (sigmoid scores, selection bias) beside
one shared expert, with an untied head, and the observed-token NLL — written
out of the layer equations in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.

Imports nothing from ``detectmateservice_tpu.models`` or ``.ops``. One loop
over layers, one over the held experts, every expert computed densely over
all tokens and weighted by the routing (no sort of assignments, no grouped
matmul, no kernel); the delta rule as a ``lax.scan`` over positions, one
state update a step (no chunks, no sub-blocks, no triangular solve); the
convolution as shifted multiply-adds per line on ``[rows, S, C]``;
attention a dense softmax over ``nope + rope``-wide heads; the grouped
choice by sorting; rows in blocks only so that it fits the host. It reads a
parameter tree by the names the checkpoint gives its leaves:

    tok_embed/embedding [V, D]   lm_head [V, D]   final_norm [D]
    layers_<i>/input_norm, post_norm [D]
    delta-rule layer: in_proj {kernel [D, 5·H·d]} (q | k | v | f | z),
                      b_proj {kernel [D, H]}, conv_weight [3·H·d, K],
                      A_log [H], dt_bias [H, d], out_norm [d], out_proj {kernel}
    attention layer:  q_proj {kernel [D, H·(nope + rope)]} (head by head),
                      kv_down {kernel [D, kv_lora_rank + rope]}, kv_norm [kv_lora_rank],
                      kv_up {kernel [kv_lora_rank, H·(nope + dv)]} (head by head),
                      q_norm, k_norm [nope + rope], attn_gate {kernel [D, H]},
                      out_proj {kernel}
    dense layer:      gate_proj, up_proj, down_proj {kernel}
    expert layer:     router [D, E], router_bias [E] (zeros),
                      experts_gate, experts_up [held, D, M], experts_down [held, M, D],
                      shared_gate_proj, shared_up_proj, shared_down_proj {kernel}

Equations (``arch`` holds the published config.json keys, the counts of
heads and experts this chip's; ``norm(x; w) = w x rsqrt(mean(x^2) +
rms_norm_eps)``; H heads held, d = head_dim):

    inp = [CLS, tokens[:-1]];  x = E[inp]
    per layer i:
      y = norm(x; input_norm)
      (i + 1) % layer_group_size != 0  (delta rule, a vector of decays a head):
        q | k | v | f | z = y Win;  b = y Wb
        q, k, v = silu(conv_K(q | k | v))   (depthwise, causal, zeros left of the line)
        q = q / sqrt(sum q^2 + 1e-6) d^-0.5;  k = k / sqrt(sum k^2 + 1e-6)   per head
        g = kda_lower_bound sigmoid(exp(A_log_h) (f + dt_bias))   in (kda_lower_bound, 0), [H, d]
        beta = sigmoid(b);  S_0 = 0 [d, d] per head
        per position t:  S' = Diag(exp(g_t)) S;  u = beta_t (v_t - S'^T k_t)
                         S = S' + k_t u^T;  o_t = S^T q_t
        x += (norm(o; out_norm) over each head's d * sigmoid(z)) Wo
      else  (latent attention, normed and gated):
        q = y Wq -> per head q_nope | q_rope
        c | k_rope = y Wkva;  c = norm(c; kv_norm);  per head k_nope | v = c Wkvb
        q_h = norm(q_nope | q_rope; q_norm),  k_h = norm(k_nope | k_rope; k_norm)   over nope + rope
        rotary positions, interleaved pairs (2i, 2i+1) of the rope lanes turn by t theta^(-2i/rope)
        a = softmax(q k^T / sqrt(nope + rope) + causal and PAD mask) v
        x += (a_h sigmoid(y Wgate)_h) Wo
      y = norm(x; post_norm)
      i < first_k_dense_replace:  x += W2(silu(W1 y) * W3 y)
      else:
        s = sigmoid(y Wr) over all router_experts;  c = s + router_bias
        groups of router_experts / n_group consecutive experts, a group's score
          the sum of its two largest c; the topk_group best groups stand
        chosen = the num_experts_per_tok largest c among the standing groups
        w = s[chosen] / (sum + 1e-20) routed_scaling_factor
        x += sum over chosen AND held e of w_e E_e(y) + Shared(y)    E, Shared: W2(silu(W1 y) * W3 y)
    h = norm(x; final_norm);  logits = h lm_head^T
    score(line) = mean over non-PAD positions of (logsumexp(logits) - logits[token])

Departures from the published description, each shared with the program:

* No cache and no decode: this system scores every position of a line in one
  pass and never generates; neither the convolution's K-deep state nor the
  delta rule's outlives a line. PAD lies right of a line's tokens, so no PAD
  position feeds a real one through the recurrence.
* No multi-token-prediction layer (the published mtp_loss_scaling_factor is
  0), and no clamp in the gated units (the published limit lists are 0 for
  every layer kept).
* The share: this chip holds ``num_experts`` of the ``router_experts`` routed
  experts (from ``expert_offset``), a slice of the vocabulary, and its part
  of a tensor-parallel group's heads (``arch`` counts what is held; kv_down,
  the router, the shared expert and the dense unit are whole). The router
  scores all experts and the weights normalise over all chosen ones; what
  the absent experts and the absent heads would add is left out, and that
  partial result goes on to the next layer. A sliced vocabulary is a smaller
  vocabulary.
* The shift: position t is predicted from the tokens before t (input t is
  token t-1, input 0 is CLS), so NLLs line up with the tokens; rotary
  position t is the input's place.
* The residual stream is float32; the delta rule's five projections are one
  matrix ordered by kind.
* Three readings are the family's conventions and no key of the published
  file: the lower-bound gate's form, the per-head query/key norm before the
  rotation (the key's over k_nope | k_rope), and group_norm_size 1 as a norm
  a head.

``lower`` (the control) rounds both inputs of every matrix multiplication the
configuration states in bfloat16: the projections, the delta rule's products
of keys, queries and ``u`` with the state, attention's two products, the
dense unit, the experts, the shared expert and the head. The router, the
gates and decays, the convolution, the norms and the state itself are stated
in float32 and stay there. The rounding saturates at the format's largest
finite value (``lowered``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PAD_ID = 0
CLS_ID = 2


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def kinds(arch: dict) -> list:
    """(mixer, feed-forward) of every layer: ("kda" | "attn", "ffn" |
    "moe")."""
    return [("attn" if (i + 1) % arch["layer_group_size"] == 0 else "kda",
             "ffn" if i < arch["first_k_dense_replace"] else "moe")
            for i in range(arch["num_hidden_layers"])]


def short_conv(u, weight):
    """Depthwise causal convolution over positions: ``u`` [N, S, C],
    ``weight`` [C, K] → ``v[:, t] = Σ_j weight[:, j] * u[:, t-(K-1)+j]``,
    zeros left of the line."""
    taps = weight.shape[1]
    out = jnp.zeros_like(u)
    for j in range(taps):
        shift = taps - 1 - j
        moved = jnp.pad(u, ((0, 0), (shift, 0), (0, 0)))[:, :u.shape[1]]
        out = out + moved * weight[:, j]
    return out


def delta_rule(q, k, v, g, beta, cast=lambda t: t):
    """The recurrence, one position a step: ``q``, ``k`` (normalised) and
    the log decay ``g`` [N, S, H, d], ``v`` [N, S, H, dv], ``beta`` [N, S,
    H] → ``o`` [N, S, H, dv]."""
    n, _, h, d = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("nhkv,nhk->nhv", state, cast(k_t))
        u_t = b_t[..., None] * (v_t - seen)
        state = state + cast(k_t)[..., :, None] * cast(u_t)[..., None, :]
        return state, jnp.einsum("nhkv,nhk->nhv", state, cast(q_t))

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    _, out = jax.lax.scan(
        step, jnp.zeros((n, h, d, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(out, 0, 1)


def _interleaved_rotary(x, theta):
    """[N, S, heads, r]: the pair of lanes (2i, 2i + 1) at position t turns
    by t * theta^(-2i/r)."""
    s, r = x.shape[1], x.shape[-1]
    freq = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    angle = np.arange(s, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def routing(y, router, bias, arch):
    """[.., D] -> ([.., K] expert ids over all experts, [.., K] weights):
    the grouped choice, by sorting."""
    s = jax.nn.sigmoid(y @ router)
    c = s + bias
    groups, keep = arch["n_group"], arch["topk_group"]
    per = c.shape[-1] // groups
    by_group = c.reshape(*c.shape[:-1], groups, per)
    group_score = jnp.sort(by_group, axis=-1)[..., -min(2, per):].sum(-1)
    # a group's place among the groups, best first (ties: the lower index)
    place = jnp.argsort(jnp.argsort(-group_score, axis=-1, stable=True),
                        axis=-1, stable=True)
    standing = jnp.repeat(place < keep, per, axis=-1)
    chosen = jnp.argsort(-jnp.where(standing, c, -jnp.inf), axis=-1,
                         stable=True)[..., :arch["num_experts_per_tok"]]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, (w / (w.sum(-1, keepdims=True) + 1e-20)
                    * arch["routed_scaling_factor"])


def lowered(dtype):
    """Round to ``dtype`` and back, saturating at its largest finite value:
    float8_e4m3fn has no infinity — unsaturated, an overflow reads NaN and
    the control scores nothing."""
    top = float(jnp.finfo(dtype).max)
    return lambda t: jnp.clip(t, -top, top).astype(dtype).astype(jnp.float32)


def mixer(lay: dict, kind: str, y, arch: dict, see, cast=lambda t: t):
    """What the sub-layer of ``kind`` ("kda", "attn", "ffn" or "moe") with
    the leaves ``lay`` adds to the residual for its normed input ``y`` [N,
    S, D] → ``(addend [N, S, D], chosen experts [N, S, K] or None)``;
    ``see`` [N, 1, S, S] is attention's causal and PAD mask."""
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    mm = lambda t, w: cast(t) @ cast(f32(w))  # noqa: E731
    eps = arch["rms_norm_eps"]
    n, s = y.shape[:2]
    h = arch["num_attention_heads"]

    def gated(t, prefix):
        return mm(jax.nn.silu(mm(t, lay[prefix + "gate_proj"]["kernel"]))
                  * mm(t, lay[prefix + "up_proj"]["kernel"]),
                  lay[prefix + "down_proj"]["kernel"])

    if kind == "kda":
        d = arch["head_dim"]
        width = h * d
        qkvfz = mm(y, lay["in_proj"]["kernel"])
        conv = jax.nn.silu(short_conv(qkvfz[..., :3 * width],
                                      f32(lay["conv_weight"])))
        q, k, v = (conv[..., j * width:(j + 1) * width].reshape(n, s, h, d)
                   for j in range(3))
        f = qkvfz[..., 3 * width:4 * width].reshape(n, s, h, d)
        z = qkvfz[..., 4 * width:].reshape(n, s, h, d)
        q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / np.sqrt(d)
        k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
        g = arch["kda_lower_bound"] * jax.nn.sigmoid(
            jnp.exp(f32(lay["A_log"]))[:, None] * (f + f32(lay["dt_bias"])))
        beta = jax.nn.sigmoid(mm(y, lay["b_proj"]["kernel"]))
        o = delta_rule(q, k, v, g, beta, cast)
        o = _norm(o, f32(lay["out_norm"]), eps) * jax.nn.sigmoid(z)
        return mm(o.reshape(n, s, width), lay["out_proj"]["kernel"]), None
    if kind == "attn":
        nope, rope, dv = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                          arch["v_head_dim"])
        rank = arch["kv_lora_rank"]
        q = mm(y, lay["q_proj"]["kernel"]).reshape(n, s, h, nope + rope)
        kva = mm(y, lay["kv_down"]["kernel"])
        c = _norm(kva[..., :rank], f32(lay["kv_norm"]), eps)
        kv = mm(c, lay["kv_up"]["kernel"]).reshape(n, s, h, nope + dv)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            kva[..., None, rank:], (n, s, h, rope))], axis=-1)
        q = _norm(q, f32(lay["q_norm"]), eps)
        k = _norm(k, f32(lay["k_norm"]), eps)
        q, k = (jnp.concatenate([t[..., :nope], _interleaved_rotary(
            t[..., nope:], arch["rope_theta"])], axis=-1) for t in (q, k))
        att = jnp.einsum("bshd,bthd->bhst", cast(q), cast(k)) / np.sqrt(
            nope + rope)
        att = jax.nn.softmax(jnp.where(see, att, -1e30), axis=-1)
        out = jnp.einsum("bhst,bthd->bshd", cast(att), cast(kv[..., nope:]))
        out = out * jax.nn.sigmoid(mm(y, lay["attn_gate"]["kernel"]))[
            ..., None]
        return mm(out.reshape(n, s, h * dv), lay["out_proj"]["kernel"]), None
    if kind == "ffn":
        return gated(y, ""), None
    offset = arch.get("expert_offset", 0)
    chosen, w = routing(y, f32(lay["router"]), f32(lay["router_bias"]), arch)
    moe = gated(y, "shared_")
    for e in range(arch["num_experts"]):                   # the held experts
        w_e = (w * (chosen == offset + e)).sum(-1)                  # [N, S]
        moe = moe + w_e[..., None] * mm(
            jax.nn.silu(mm(y, lay["experts_gate"][e]))
            * mm(y, lay["experts_up"][e]), lay["experts_down"][e])
    return moe, chosen


def token_nlls(params: dict, tokens, arch: dict, lower=None,
               with_routing: bool = False):
    """[N, S] int tokens -> [N, S] per-position NLL (PAD positions 0); with
    ``with_routing`` also the chosen experts of every expert layer,
    ``[expert layers, N, S, K]`` (PAD positions -1), for the counters'
    test."""
    p = params["params"] if "params" in params else params
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    cast = (lambda t: t) if lower is None else lowered(lower)
    eps = arch["rms_norm_eps"]
    tokens = jnp.asarray(tokens, jnp.int32)
    n, s = tokens.shape
    inp = jnp.concatenate([jnp.full((n, 1), CLS_ID, jnp.int32),
                           tokens[:, :-1]], axis=1)
    keep = tokens != PAD_ID
    see = (inp != PAD_ID)[:, None, None, :] & jnp.tril(
        jnp.ones((s, s), bool))[None, None]
    x = f32(p["tok_embed"]["embedding"])[inp]
    chosen_by_layer = []
    for i, (mix, ffn) in enumerate(kinds(arch)):
        lay = p[f"layers_{i}"]
        out, _ = mixer(lay, mix, _norm(x, f32(lay["input_norm"]), eps), arch,
                       see, cast)
        x = x + out
        out, chosen = mixer(lay, ffn, _norm(x, f32(lay["post_norm"]), eps),
                            arch, see, cast)
        x = x + out
        if chosen is not None:
            chosen_by_layer.append(jnp.where(keep[..., None], chosen, -1))
    hid = _norm(x, f32(p["final_norm"]), eps)
    logits = jnp.einsum("bsd,vd->bsv", cast(hid), cast(f32(p["lm_head"])))
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
