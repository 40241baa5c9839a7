"""Plain reference for the ``moe_delta`` scorer: a causal language model whose
layers differ in kind by a published rule — every
``full_attention_interval``-th is gated grouped-query attention, the others
the gated delta rule, a linear attention with a state carried over a line's
positions — over routed experts (softmax-scored router) and one gated
shared expert in every layer, with an untied head, and the observed-token
NLL — written out of the layer equations in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.

Imports nothing from ``detectmateservice_tpu.models`` or ``.ops``. One loop
over layers, one over the held experts, every expert computed densely over
all tokens and weighted by the routing (no sort, no grouped matmul, no
kernel); the delta rule as a ``lax.scan`` over positions, one state update
a step (no chunks, no triangular solve); the convolution as shifted
multiply-adds per line on ``[rows, S, C]``; query heads against key/value
heads repeated for them; rows in blocks only so that it fits the host. It
reads a parameter tree by the names the checkpoint gives its leaves:

    tok_embed/embedding [V, D]   lm_head [V, D]   final_norm [D]
    layers_<i>/input_norm, post_norm [D]
    delta layer:     in_proj {kernel [D, 2·Hk·Dk + 2·Hv·Dv]} (q | k | v | z),
                     ba_proj {kernel [D, 2·Hv]} (b | a), conv_weight [2·Hk·Dk + Hv·Dv, K],
                     A_log, dt_bias [Hv], out_norm [Dv], out_proj {kernel}
    attention layer: qkv_proj {kernel [D, (2H + 2G) d]} (q | gate | k | v),
                     q_norm, k_norm [d], out_proj {kernel}
    every layer:     router [D, E], router_bias [E] (zeros: the model has none),
                     experts_gate, experts_up [held, D, M], experts_down [held, M, D],
                     shared_gate_proj, shared_up_proj, shared_down_proj {kernel},
                     shared_gate [D, 1]

Equations (``arch`` holds the published config.json keys; ``norm(x; w) = x
rsqrt(mean(x^2) + rms_norm_eps) (1 + w)``; Hk key and Hv value heads of Dk
= Dv in a delta layer, H query and G key/value heads of d in an attention
layer):

    inp = [CLS, tokens[:-1]];  x = E[inp]
    per layer i:
      y = norm(x; input_norm)
      (i + 1) % full_attention_interval != 0  (gated delta rule):
        q | k | v | z = y Win;  b | a = y Wba
        c = silu(conv_K(concat(q, k, v)))   (depthwise, causal, zeros left of the line)
        q, k, v = split(c);  beta = sigmoid(b)
        g = -exp(A_log) softplus(a + dt_bias)
        q = q / sqrt(sum q^2 + 1e-6) Dk^-0.5;  k = k / sqrt(sum k^2 + 1e-6)   per head
        value head h reads key head h // (Hv / Hk);  S_0 = 0 [Dk, Dv] per value head
        per position t:  S' = exp(g_t) S;  u = beta_t (v_t - S'^T k_t)
                         S = S' + k_t u^T;  o_t = S^T q_t
        o = out_norm * o rsqrt(mean(o^2) + eps) * silu(z)   per head (plain weight)
        x += o Wout
      else  (gated full attention):
        q | gate | k | v = y Wqkv;  q = norm(q; q_norm), k = norm(k; k_norm) per head over d
        rotary positions, rotate-half, on lanes 0 .. partial_rotary_factor d - 1:
          lanes (i, i + r/2) turn by t theta^(-2i/r)
        key/value head g serves query heads g H/G .. (g+1) H/G - 1
        a = softmax(q k^T / sqrt(d) + causal and PAD mask) v
        x += (a * sigmoid(gate)) Wo
      y = norm(x; post_norm)
      s = softmax(y Wr) over all router_experts (float32)
      chosen = the num_experts_per_tok largest of s;  w = s[chosen] / sum
      x += sum over chosen AND held e of w_e E_e(y)
           + sigmoid(y w_s) * Shared(y)          E, Shared: W2(silu(W1 y) * W3 y)
    h = norm(x; final_norm);  logits = h lm_head^T
    score(line) = mean over non-PAD positions of (logsumexp(logits) - logits[token])

Departures from the published description, each shared with the program:

* No cache and no decode: this system scores every position of a line in one
  pass and never generates; neither the convolution's K-deep state nor the
  delta rule's outlives a line. PAD lies right of a line's tokens, so no PAD
  position feeds a real one through the recurrence.
* No multi-token-prediction module: it is no part of the scoring pass.
* The share: this chip holds ``num_experts`` of the ``router_experts`` routed
  experts (from ``expert_offset``) and a slice of the vocabulary. The router
  scores all experts and the weights normalise over all chosen ones; what
  the absent experts would add is left out, and that partial result goes on
  to the next layer. A sliced vocabulary is a smaller vocabulary.
* The shift: position t is predicted from the tokens before t (input t is
  token t-1, input 0 is CLS), so NLLs line up with the tokens; rotary
  position t is the input's place.
* The fused projections' columns are ordered by kind (q | k | v | z, b | a,
  q | gate | k | v), not interleaved by key-head group as published: with
  seeded weights it changes nothing.

``lower`` (the control) rounds both inputs of every matrix multiplication the
configuration states in bfloat16: the projections, the delta rule's products
of keys, queries and ``u`` with the state, the attention's two products, the
experts and the head. The router, the shared expert's gate, the gates and
decays, the convolution and the state itself are stated in float32 and stay
there.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PAD_ID = 0
CLS_ID = 2


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate_half(x, theta, r):
    """[N, S, heads, d]: lanes (i, i + r/2) of the first ``r`` at position t
    turn by t * theta^(-2i/r); lanes r .. d - 1 stay."""
    s = x.shape[1]
    freq = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    angle = np.arange(s, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    first, second = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin, x[..., r:]], -1)


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


def delta_rule(q, k, v, g, beta, cast=lambda a: a):
    """The recurrence, one position a step: ``q``, ``k`` [N, S, Hv, Dk]
    (normalised, a key head repeated for its value heads), ``v`` [N, S, Hv,
    Dv], ``g`` (log decay) and ``beta`` [N, S, Hv] → ``o`` [N, S, Hv, Dv]."""
    n, _, hv, dk = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        seen = jnp.einsum("nhkv,nhk->nhv", state, cast(k_t))
        u_t = b_t[..., None] * (v_t - seen)
        state = state + cast(k_t)[..., :, None] * cast(u_t)[..., None, :]
        return state, jnp.einsum("nhkv,nhk->nhv", state, cast(q_t))

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, out = jax.lax.scan(
        step, jnp.zeros((n, hv, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(out, 0, 1)


def routing(y, router, arch):
    """[N, D] -> ([N, K] expert ids over all experts, [N, K] weights)."""
    s = jax.nn.softmax(y @ router, axis=-1)
    w, chosen = jax.lax.top_k(s, arch["num_experts_per_tok"])
    return chosen, w / w.sum(-1, keepdims=True)


def token_nlls(params: dict, tokens, arch: dict, lower=None,
               with_routing: bool = False):
    """[N, S] int tokens -> [N, S] per-position NLL (PAD positions 0); with
    ``with_routing`` also the chosen experts of every layer, ``[layers, N,
    S, K]`` (PAD positions -1), for the counters' test."""
    p = params["params"] if "params" in params else params
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    cast = (lambda a: a) if lower is None else (
        lambda a: a.astype(lower).astype(jnp.float32))
    mm = lambda a, b: cast(a) @ cast(b)  # noqa: E731
    eps = arch["rms_norm_eps"]
    heads, groups = arch["num_attention_heads"], arch["num_key_value_heads"]
    d = arch["head_dim"]
    rot = int(d * arch["partial_rotary_factor"])
    hk, hv = arch["linear_num_key_heads"], arch["linear_num_value_heads"]
    dk, dv = arch["linear_key_head_dim"], arch["linear_value_head_dim"]
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
        y = _norm(x, 1.0 + f32(lay["input_norm"]), eps)
        if (i + 1) % arch["full_attention_interval"]:
            qkvz = mm(y, f32(lay["in_proj"]["kernel"]))
            ba = mm(y, f32(lay["ba_proj"]["kernel"]))
            mixed = 2 * hk * dk + hv * dv
            c = jax.nn.silu(short_conv(qkvz[..., :mixed],
                                       f32(lay["conv_weight"])))
            q = c[..., :hk * dk].reshape(n, s, hk, dk)
            k = c[..., hk * dk:2 * hk * dk].reshape(n, s, hk, dk)
            v = c[..., 2 * hk * dk:].reshape(n, s, hv, dv)
            z = qkvz[..., mixed:].reshape(n, s, hv, dv)
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(f32(lay["A_log"])) * jax.nn.softplus(
                ba[..., hv:] + f32(lay["dt_bias"]))
            q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / np.sqrt(dk)
            k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
            # each key head repeated for its value heads
            q = jnp.repeat(q, hv // hk, axis=2)
            k = jnp.repeat(k, hv // hk, axis=2)
            o = delta_rule(q, k, v, g, beta, cast)
            o = _norm(o, f32(lay["out_norm"]), eps) * jax.nn.silu(z)
            x = x + mm(o.reshape(n, s, hv * dv),
                       f32(lay["out_proj"]["kernel"]))
        else:
            qkv = mm(y, f32(lay["qkv_proj"]["kernel"]))
            q = qkv[..., :heads * d].reshape(n, s, heads, d)
            gate = qkv[..., heads * d:2 * heads * d]
            k = qkv[..., 2 * heads * d:(2 * heads + groups) * d].reshape(
                n, s, groups, d)
            v = qkv[..., (2 * heads + groups) * d:].reshape(n, s, groups, d)
            q = _rotate_half(_norm(q, 1.0 + f32(lay["q_norm"]), eps),
                             arch["rope_theta"], rot)
            k = _rotate_half(_norm(k, 1.0 + f32(lay["k_norm"]), eps),
                             arch["rope_theta"], rot)
            # each key/value head repeated for its query heads
            k = jnp.repeat(k, heads // groups, axis=2)
            v = jnp.repeat(v, heads // groups, axis=2)
            att = jnp.einsum("bshd,bthd->bhst", cast(q), cast(k)) / np.sqrt(d)
            att = jax.nn.softmax(jnp.where(see, att, -1e30), axis=-1)
            out = jnp.einsum("bhst,bthd->bshd", cast(att), cast(v))
            out = out.reshape(n, s, heads * d) * jax.nn.sigmoid(gate)
            x = x + mm(out, f32(lay["out_proj"]["kernel"]))
        y = _norm(x, 1.0 + f32(lay["post_norm"]), eps)
        chosen, w = routing(y, f32(lay["router"]), arch)
        chosen = jnp.where(keep[..., None], chosen, -1)
        chosen_by_layer.append(chosen)
        moe = jax.nn.sigmoid(y @ f32(lay["shared_gate"])) * gated(
            y, lay["shared_gate_proj"]["kernel"],
            lay["shared_up_proj"]["kernel"], lay["shared_down_proj"]["kernel"])
        for e in range(arch["num_experts"]):          # the held experts
            w_e = (w * (chosen == offset + e)).sum(-1)          # [N, S]
            moe = moe + w_e[..., None] * gated(
                y, lay["experts_gate"][e], lay["experts_up"][e],
                lay["experts_down"][e])
        x = x + moe
    h = _norm(x, 1.0 + f32(p["final_norm"]), eps)
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
