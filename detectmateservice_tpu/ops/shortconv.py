"""The gated short convolution's elementwise core, token-major.

A gated-short-convolution operator (models/moe_conv.py) mixes positions
without attention: ``[B | C | x̃] = W_in·y``, ``u = B ⊙ x̃``, a depthwise
causal convolution of ``u`` over the line's positions with a kernel of a
few taps, ``Op = W_out·(C ⊙ v)``. The two projections are matmuls; this
file is what lies between them:

    v[t] = Σ_j w[:, j] ⊙ u[t − (K − 1) + j]      (zeros left of the line)
    out  = C ⊙ v

over ``bcx`` ``[tokens, 3·D]`` (the projection's own layout: B, C and x̃ as
column blocks) and ``weight`` ``[D, K]``. The activations are token-major
(``[B·S, ·]``, as the stacks have run since PR 28), so a shift over
positions is a shift over rows, and a row whose place in its line is
under the shift would read the line before it: those terms are zeroed
by the row's place ``t mod S``, never by a pad between lines.

It is memory-bound: at 1024 rows of 32 tokens and D = 2048 it reads 403 MB
and writes 134 MB in bfloat16, 0.66 ms at the v5e's 819 GB/s, between two
matmuls of 2.1 and 0.7 ms. Products and sums in float32 from the
projection's bfloat16, cast back for ``W_out``.

Two forms, told apart by :func:`conv_route`:

* ``xla`` — plain ``jax.numpy``: the shifts are pads and slices that XLA
  fuses as it sees fit. What the CPU, a mesh and the fit's 32-row step
  run, and what the kernel's backward differentiates.
* ``fused`` — one Pallas kernel (``gated_conv``): a grid step owns a block
  of whole lines by a block of channels, reads B, C and x̃ once from the one
  buffer (three column-block views, no split copied), shifts ``u`` down the
  sublanes in VMEM (``pltpu.roll``) and writes ``out`` once.

:func:`causal_conv_silu` is the same tap walk with no gate on either side
and SiLU behind it (a linear-attention layer's short convolution,
models/moe_delta.py, and with a bias a state-space mixer's,
models/moe_ssm.py), in the plain form only.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import current_placement

LANES = 128
# tokens from which ``auto`` takes the kernel on one TPU: the smallest
# served bucket (256 rows of 32 tokens); the fit's 32-row step keeps XLA's
# form, whose backward it needs anyway
FUSED_MIN_TOKENS = 8192
# a grid step's block: whole lines, at most this many tokens, by this many
# channels (3 inputs and 1 output in bfloat16, double-buffered: 4 MB)
_BLOCK_TOKENS = 512
_BLOCK_CHANNELS = 512


def conv_route(impl: str, platform: str, tokens: int, seq: int, width: int,
               taps: int, mesh_devices: int = 1) -> str:
    """``"fused"`` or ``"xla"`` for one traced call. ``impl`` other than
    ``"auto"`` forces; ``auto`` takes the kernel on ONE TPU (GSPMD does not
    partition a Pallas call) from ``FUSED_MIN_TOKENS`` tokens where the
    shapes tile: channels in whole lane groups, lines in whole 8-row
    sublane tiles that divide the block, more positions than taps."""
    if impl != "auto":
        return impl
    if (platform == "tpu" and mesh_devices == 1
            and tokens >= FUSED_MIN_TOKENS and fits(tokens, seq, width,
                                                    taps)):
        return "fused"
    return "xla"


def fits(tokens: int, seq: int, width: int, taps: int) -> bool:
    return (width % LANES == 0 and seq % 8 == 0 and taps <= seq
            and tokens % seq == 0)


def _block_tokens(tokens: int, seq: int) -> int:
    lines = max(1, min(_BLOCK_TOKENS, tokens) // seq)
    while (tokens // seq) % lines:
        lines -= 1
    return lines * seq


def gated_short_conv(bcx: jax.Array, weight: jax.Array, seq: int,
                     impl: str = "auto", platform: str = "") -> jax.Array:
    """``bcx`` [tokens, 3·D] (B | C | x̃), ``weight`` [D, K], lines of
    ``seq`` tokens → ``C ⊙ conv_K(B ⊙ x̃)`` [tokens, D] in ``bcx``'s dtype.
    Differentiable in both operands."""
    tokens, width = bcx.shape[0], bcx.shape[1] // 3
    placed = current_placement()
    route = conv_route(impl, platform or jax.default_backend(), tokens, seq,
                       width, weight.shape[1], placed.mesh_devices)
    if placed.conv_routes is not None:
        placed.conv_routes[tokens // seq] = route
    with jax.named_scope(f"conv_{route}"):
        if route == "fused":
            if not fits(tokens, seq, width, weight.shape[1]):
                raise ValueError(
                    f"conv impl 'fused': {tokens} tokens in lines of {seq} "
                    f"by {width} channels do not tile (channels in "
                    f"multiples of {LANES}, lines of 8 positions)")
            return _fused(bcx, weight, seq, platform == "cpu")
        if route != "xla":
            raise ValueError(f"conv impl {route!r}: expected 'auto', 'xla' "
                             "or 'fused'")
        return gated_conv_xla(bcx, weight, seq)


def causal_taps(u: jax.Array, weight: jax.Array, seq: int) -> jax.Array:
    """The tap walk both plain forms share: ``u`` [tokens, D], ``weight``
    [D, K] → float32 ``v[t] = Σ_j w[:, j] ⊙ u[t − (K − 1) + j]`` as K
    shifted multiply-adds over rows, each masked by the row's place in its
    line. The rows are shifted in ``u``'s own dtype and widened tap by tap:
    widened first, XLA stores a float32 copy of a bfloat16 ``u`` and reads it
    K times (10.1 ms at 32768 tokens by 8,192 channels on the v5e)."""
    tokens, taps = u.shape[0], weight.shape[1]
    place = (jnp.arange(tokens, dtype=jnp.int32) % seq)[:, None]
    w = weight.astype(jnp.float32)
    v = u.astype(jnp.float32) * w[:, taps - 1]
    for shift in range(1, taps):
        # back[t] = u[t - shift]
        back = jnp.pad(u, ((shift, 0), (0, 0)))[:tokens].astype(jnp.float32)
        v = v + jnp.where(place >= shift, back, 0.0) * w[:, taps - 1 - shift]
    return v


def gated_conv_xla(bcx: jax.Array, weight: jax.Array, seq: int) -> jax.Array:
    """The plain form: the tap walk between the two gates."""
    b, c, x = (part.astype(jnp.float32) for part in jnp.split(bcx, 3, -1))
    return (c * causal_taps(b * x, weight, seq)).astype(bcx.dtype)


def causal_conv_silu(x: jax.Array, weight: jax.Array, seq: int,
                     bias: Optional[jax.Array] = None) -> jax.Array:
    """``silu(conv_K(x) + bias)``: the depthwise causal convolution of ``x``
    [tokens, D] with ``weight`` [D, K] over each line's positions (zeros
    left of the line), plus ``bias`` [D] where the layer has one, then SiLU
    — a linear-attention layer's short convolution (models/moe_delta.py,
    no bias) and a state-space mixer's (models/moe_ssm.py, with one), no
    gate on either side.
    Products, sums and the activation in float32, the result in ``x``'s
    dtype. XLA's fusion: at 1024 rows of 32 tokens and 8,192 channels it
    has to read and write 537 MB each in bfloat16, 1.3 ms at the v5e's
    819 GB/s, behind a projection of 8.4 ms."""
    with jax.named_scope("conv_xla"):
        v = causal_taps(x, weight, seq)
        if bias is not None:
            v = v + bias.astype(jnp.float32)
        return jax.nn.silu(v).astype(x.dtype)


def _kernel(b_ref, c_ref, x_ref, w_ref, o_ref, *, seq: int, taps: int):
    u = b_ref[...].astype(jnp.float32) * x_ref[...].astype(jnp.float32)
    place = jax.lax.broadcasted_iota(jnp.int32, u.shape, 0) % seq
    v = u * w_ref[taps - 1:taps, :]
    for shift in range(1, taps):
        # the block holds whole lines, so what the roll wraps round lands
        # on rows whose place is under the shift and is zeroed with them
        back = pltpu.roll(u, shift, 0)
        v = v + (jnp.where(place >= shift, back, 0.0)
                 * w_ref[taps - 1 - shift:taps - shift, :])
    o_ref[...] = (c_ref[...].astype(jnp.float32) * v).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("seq", "interpret"))
def gated_conv(bcx: jax.Array, weight: jax.Array, seq: int,
               interpret: bool = False) -> jax.Array:
    """The kernel. jitted, so that a stack's layers share one trace of its
    body (tracing a Pallas body once a layer cost 1.4 s a bucket inside the
    serving process: PERF.md section 6, PR 28)."""
    tokens, width = bcx.shape[0], bcx.shape[1] // 3
    taps = weight.shape[1]
    rows = _block_tokens(tokens, seq)
    cols = _BLOCK_CHANNELS if width % _BLOCK_CHANNELS == 0 else LANES
    per_part = width // cols

    def part(k: int) -> pl.BlockSpec:
        return pl.BlockSpec((rows, cols),
                            lambda i, j, k=k: (i, k * per_part + j))

    return pl.pallas_call(
        functools.partial(_kernel, seq=seq, taps=taps),
        grid=(tokens // rows, per_part),
        in_specs=[part(0), part(1), part(2),
                  pl.BlockSpec((taps, cols), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((rows, cols), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((tokens, width), bcx.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name="gated_conv",
    )(bcx, bcx, bcx, weight.astype(jnp.float32).T)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _fused(bcx, weight, seq, interpret):
    return gated_conv(bcx, weight, seq, interpret)


def _fused_fwd(bcx, weight, seq, interpret):
    return gated_conv(bcx, weight, seq, interpret), (bcx, weight)


def _fused_bwd(seq, interpret, saved, grad):
    # exact: the plain form recomputed from the operands. The fit's 32-row
    # step takes that form anyway; a backward kernel would buy nothing
    return jax.vjp(lambda b, w: gated_conv_xla(b, w, seq), *saved)[1](grad)


_fused.defvjp(_fused_fwd, _fused_bwd)
