"""Plain reference for the ``moe_ssm`` scorer: a causal language model whose
every layer is one sub-layer, its kind read off a published pattern string —
``M`` a Mamba-2 state-space mixer, ``*`` causal grouped-query attention
without rotary positions, ``E`` an expert layer whose routed experts are
non-gated ``relu²`` units in a latent narrower than the residual beside one
shared unit — with an untied head, and the observed-token NLL — written out
of the layer equations in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.

Imports nothing from ``detectmateservice_tpu.models`` or ``.ops``. One loop
over layers, one over the held experts, every expert computed densely over
all tokens and weighted by the routing (no sort, no grouped matmul, no
kernel); the state-space recurrence as a ``lax.scan`` over positions, one
state update a step (no chunks, no closed form); the convolution as shifted
multiply-adds per line on ``[rows, S, C]``; query heads against key/value
heads repeated for them; rows in blocks only so that it fits the host. It
reads a parameter tree by the names the checkpoint gives its leaves:

    tok_embed/embedding [V, D]   lm_head [V, D]   final_norm [D]
    layers_<i>/norm [D]
    M layer: in_proj {kernel [D, 2·H·P + 2·G·N + H]} (z | x | B | C | dt),
             conv_weight [H·P + 2·G·N, K], conv_bias [H·P + 2·G·N],
             dt_bias, A_log, D [H], out_norm [H·P], out_proj {kernel [H·P, D]}
    * layer: qkv_proj {kernel [D, (Hq + 2·Hkv) d]} (q | k | v), out_proj {kernel}
    E layer: router [D, E], router_bias [E], latent_in {kernel [D, L]},
             experts_up [held, L, M], experts_down [held, M, L],
             latent_out {kernel [L, D]},
             shared_up_proj {kernel [D, Ms]}, shared_down_proj {kernel [Ms, D]}

Equations (``arch`` holds the published config.json keys at this chip's
counts; ``norm(x; w) = w x rsqrt(mean(x^2) + layer_norm_epsilon)``):

    inp = [CLS, tokens[:-1]];  x = E[inp]
    per layer i, y = norm(x; norm_i), by hybrid_override_pattern[i]:
      M  (H heads of P, G groups of state N; head h reads group h // (H / G)):
        z | xBC | dt = y Win
        xBC = silu(conv_K(xBC) + conv_bias)   (depthwise, causal, zeros left of the line)
        x_ | B | C = xBC;  delta = softplus(dt + dt_bias);  A = -exp(A_log)
        S_0 = 0 [P, N] per head
        per position t:  S = exp(delta_t A) S + delta_t x_t B_t^T;  o_t = S C_t + D x_t
        o = out_norm * rms_g(o * silu(z))     rms over each group's H P / G channels
        x += o Wout
      *  (Hq query and Hkv key/value heads of d; no rotary positions):
        q | k | v = y Wqkv;  key/value head g serves query heads g Hq/Hkv .. (g+1) Hq/Hkv - 1
        a = softmax(q k^T / sqrt(d) + causal and PAD mask) v;  x += a Wo
      E:
        s = sigmoid(y Wr) over all router_experts (float32)
        chosen = the num_experts_per_tok largest of s + router_bias
        w = s[chosen] / (sum + 1e-20) * routed_scaling_factor
        l = y Wlat_in
        r = sum over chosen AND held e of w_e Wdown_e relu(Wup_e l)^2
        x += r Wlat_out + relu(y Wsup)^2 Wsdown
    h = norm(x; final_norm);  logits = h lm_head^T
    score(line) = mean over non-PAD positions of (logsumexp(logits) - logits[token])

Departures from the published description, each shared with the program:

* No cache and no decode: this system scores every position of a line in one
  pass and never generates; neither the convolution's K-deep state nor the
  state-space layer's outlives a line. PAD lies right of a line's tokens, so
  no PAD position feeds a real one through the recurrence.
* No multi-token-prediction module: it is no part of the scoring pass.
* The share: this chip holds ``n_routed_experts`` of the ``router_experts``
  routed experts (from ``expert_offset``), a slice of the vocabulary, and
  its part of a tensor-parallel group's heads and groups (``arch`` counts
  what is held; the shared unit is whole). The router scores all experts and
  the weights normalise over all chosen ones; what the absent experts and
  the absent heads would add is left out, and that partial result goes on to
  the next layer. A sliced vocabulary is a smaller vocabulary.
* The shift: position t is predicted from the tokens before t (input t is
  token t-1, input 0 is CLS), so NLLs line up with the tokens.
* The residual stream is float32 (the published residual_in_fp32 is false).
* Attention's three projections are one matrix (q | k | v).

``lower`` (the control) rounds both inputs of every matrix multiplication the
configuration states in bfloat16: the projections (the latent's two among
them), the recurrence's products of x with B and of the state with C, the
attention's two products, the experts, the shared unit and the head. The
router, the time steps, decays and the state itself, the convolution and the
norms are stated in float32 and stay there. The rounding saturates at the
format's largest finite value (``lowered``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PAD_ID = 0
CLS_ID = 2
KINDS = {"M": "ssm", "*": "attn", "E": "moe"}


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


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


def state_space(x, b, c, delta, a, d, cast=lambda t: t):
    """The recurrence, one position a step: ``x`` [N, S, H, P], ``b``, ``c``
    [N, S, H, Ns] (a group's repeated for its heads), ``delta`` [N, S, H]
    (> 0), ``a`` (< 0) and ``d`` [H] → ``o`` [N, S, H, P]."""
    n, _, h, p = x.shape

    def step(state, xs):
        x_t, b_t, c_t, d_t = xs
        state = (state * jnp.exp(d_t * a)[..., None, None]
                 + cast(d_t[..., None] * x_t)[..., :, None]
                 * cast(b_t)[..., None, :])
        return state, jnp.einsum("nhps,nhs->nhp", cast(state), cast(c_t))

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x, b, c, delta))
    _, out = jax.lax.scan(
        step, jnp.zeros((n, h, p, b.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(out, 0, 1) + d[:, None] * x


def routing(y, router, bias, arch):
    """[N, D] -> ([N, K] expert ids over all experts, [N, K] weights)."""
    s = jax.nn.sigmoid(y @ router)
    _, chosen = jax.lax.top_k(s + bias, arch["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, (w / (w.sum(-1, keepdims=True) + 1e-20)
                    * arch["routed_scaling_factor"])


def lowered(dtype):
    """Round to ``dtype`` and back, saturating at its largest finite value:
    a squared activation of a fitted model passes float8_e4m3fn's 448, and
    that format has no infinity — unsaturated, the overflow reads NaN and
    the control scores nothing."""
    top = float(jnp.finfo(dtype).max)
    return lambda t: jnp.clip(t, -top, top).astype(dtype).astype(jnp.float32)


def mixer(lay: dict, letter: str, y, arch: dict, see, cast=lambda t: t):
    """What the layer of kind ``letter`` with the leaves ``lay`` adds to the
    residual for its normed input ``y`` [N, S, D] → ``(addend [N, S, D],
    chosen experts [N, S, K] or None)``; ``see`` [N, 1, S, S] is attention's
    causal and PAD mask."""
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    mm = lambda t, w: cast(t) @ cast(f32(w))  # noqa: E731
    eps = arch["layer_norm_epsilon"]
    n, s = y.shape[:2]

    def relu2(t, up, down):
        return mm(jnp.square(jax.nn.relu(mm(t, up))), down)

    if KINDS[letter] == "ssm":
        h, hp, g, ns = (arch["mamba_num_heads"], arch["mamba_head_dim"],
                        arch["n_groups"], arch["ssm_state_size"])
        inner = h * hp
        zxbcdt = mm(y, lay["in_proj"]["kernel"])
        z = zxbcdt[..., :inner]
        xbc = jax.nn.silu(short_conv(
            zxbcdt[..., inner:2 * inner + 2 * g * ns],
            f32(lay["conv_weight"])) + f32(lay["conv_bias"]))
        delta = jax.nn.softplus(zxbcdt[..., 2 * inner + 2 * g * ns:]
                                + f32(lay["dt_bias"]))
        xs = xbc[..., :inner].reshape(n, s, h, hp)
        # each group's B and C repeated for its heads
        b, c = (jnp.repeat(part.reshape(n, s, g, ns), h // g, axis=2)
                for part in (xbc[..., inner:inner + g * ns],
                             xbc[..., inner + g * ns:]))
        o = state_space(xs, b, c, delta, -jnp.exp(f32(lay["A_log"])),
                        f32(lay["D"]), cast)
        o = (o.reshape(n, s, inner) * jax.nn.silu(z)).reshape(
            n, s, g, inner // g)
        o = _norm(o, f32(lay["out_norm"]).reshape(g, inner // g), eps)
        return mm(o.reshape(n, s, inner), lay["out_proj"]["kernel"]), None
    if KINDS[letter] == "attn":
        heads, groups, d = (arch["num_attention_heads"],
                            arch["num_key_value_heads"], arch["head_dim"])
        qkv = mm(y, lay["qkv_proj"]["kernel"])
        q = qkv[..., :heads * d].reshape(n, s, heads, d)
        k = qkv[..., heads * d:(heads + groups) * d].reshape(n, s, groups, d)
        v = qkv[..., (heads + groups) * d:].reshape(n, s, groups, d)
        # each key/value head repeated for its query heads
        k = jnp.repeat(k, heads // groups, axis=2)
        v = jnp.repeat(v, heads // groups, axis=2)
        att = jnp.einsum("bshd,bthd->bhst", cast(q), cast(k)) / np.sqrt(d)
        att = jax.nn.softmax(jnp.where(see, att, -1e30), axis=-1)
        out = jnp.einsum("bhst,bthd->bshd", cast(att), cast(v))
        return mm(out.reshape(n, s, heads * d),
                  lay["out_proj"]["kernel"]), None
    offset = arch.get("expert_offset", 0)
    chosen, w = routing(y, f32(lay["router"]), f32(lay["router_bias"]), arch)
    latent = mm(y, lay["latent_in"]["kernel"])
    routed = jnp.zeros_like(latent)
    for e in range(arch["n_routed_experts"]):              # the held experts
        w_e = (w * (chosen == offset + e)).sum(-1)                  # [N, S]
        routed = routed + w_e[..., None] * relu2(
            latent, lay["experts_up"][e], lay["experts_down"][e])
    return (mm(routed, lay["latent_out"]["kernel"])
            + relu2(y, lay["shared_up_proj"]["kernel"],
                    lay["shared_down_proj"]["kernel"])), chosen


def token_nlls(params: dict, tokens, arch: dict, lower=None,
               with_routing: bool = False):
    """[N, S] int tokens -> [N, S] per-position NLL (PAD positions 0); with
    ``with_routing`` also the chosen experts of every expert layer,
    ``[expert layers, N, S, K]`` (PAD positions -1), for the counters'
    test."""
    p = params["params"] if "params" in params else params
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    cast = (lambda t: t) if lower is None else lowered(lower)
    eps = arch["layer_norm_epsilon"]
    tokens = jnp.asarray(tokens, jnp.int32)
    n, s = tokens.shape
    inp = jnp.concatenate([jnp.full((n, 1), CLS_ID, jnp.int32),
                           tokens[:, :-1]], axis=1)
    keep = tokens != PAD_ID
    see = (inp != PAD_ID)[:, None, None, :] & jnp.tril(
        jnp.ones((s, s), bool))[None, None]
    x = f32(p["tok_embed"]["embedding"])[inp]
    chosen_by_layer = []
    for i, letter in enumerate(arch["hybrid_override_pattern"]):
        lay = p[f"layers_{i}"]
        out, chosen = mixer(lay, letter, _norm(x, f32(lay["norm"]), eps),
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
