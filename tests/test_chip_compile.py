"""Compiles for a DESCRIBED TPU v5e, no chip attached (the
on-chip-measurement guide's third rehearsal): the kernels of the
sparse-expert scorer's main path at the published widths, its widest
scoring program with latent attention's two-width kernel, and ``logbert``'s
widest scoring program with the short-sequence attention kernel, so that what the
chip's compiler refuses — a tile that does not fit, a shape a kernel cannot
take — fails here and costs no chip time. Nothing runs; no time or result
is read.

The topology is described inside a fixture of THIS file only (one process
may load the TPU's library; a second file would be skipped in silence on
another worker). Where it cannot be described the tests skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

os.environ.setdefault("TPU_LOG_DIR", "disabled")

TOKENS, D, M, HELD, ROUTER, K, VOCAB = 32768, 2048, 768, 16, 128, 6, 16032


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: not here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def shape(dims, dtype, sharding):
    return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)


def colliding_scatters(text):
    """The compiled program's scatters under an expert layer whose indices
    may collide (XLA's row loop on the v5e): the way back to the tokens is
    a segment sum and leaves none."""
    return [line.strip()[:200] for line in text.splitlines()
            if " scatter(" in line and "/moe/" in line
            and "unique_indices=true" not in line]


def test_the_expert_layers_grouped_matmul_compiles_at_published_widths(
        one_chip, no_compile_cache):
    """The chunk walk at the cell's widest bucket (the fit's 32-row train
    batch is one chunk and never meets this size). About 20 s, nearly all
    of it the sort's compile."""
    from detectmateservice_tpu.ops import experts as ops

    def layer(x, experts, weights, gate, up, down):
        out, counts = ops.routed_experts(
            x, ops.Routing(experts, weights), gate, up, down, platform="tpu",
            combine=ops.combine_route("tpu", TOKENS, TOKENS // 2, D))
        return out, counts

    compiled = jax.jit(layer).lower(
        shape((TOKENS, D), jnp.bfloat16, one_chip),
        shape((TOKENS, K), jnp.int32, one_chip),
        shape((TOKENS, K), jnp.float32, one_chip),
        shape((HELD, D, M), jnp.float32, one_chip),
        shape((HELD, D, M), jnp.float32, one_chip),
        shape((HELD, M, D), jnp.float32, one_chip)).compile()
    text = compiled.as_text()
    # the TPU's native grouped matmul, three a live chunk (gate, up, down),
    # inside the chunk walk
    assert text.count("ragged-dot") >= 3
    # a scan over the chunks, the dead ones skipped under a conditional
    assert "while" in text and "conditional" in text
    # the way back: the segment-sum kernel, once for the first chunk (it
    # makes the accumulator) and once inside the walk (the accumulator its
    # operand and its result); no scatter over the [N, D] accumulator
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "segment_sum_add" in line]
    assert len(kernels) == 2, len(kernels)
    assert sum("output_to_operand_aliasing" in line
               for line in kernels) == 1
    assert not [line for line in text.splitlines() if " scatter(" in line
                and f"f32[{TOKENS},{D}]" in line.split(" scatter(")[0]]
    assert ops.chunk_rows_for(TOKENS, K) == TOKENS // 2
    stats = compiled.memory_analysis()
    # the sorted list, one chunk's rows and the float32 accumulator: far
    # under the [N*K, D] buffer (805 MB in bfloat16) a one-shot gather needs
    assert stats.temp_size_in_bytes < 1 << 30


def test_the_router_compiles_in_float32(one_chip, no_compile_cache):
    from detectmateservice_tpu.ops import experts as ops

    def router(x, w, bias, valid):
        return ops.route(x, w, bias, valid, top_k=K, norm_topk_prob=True,
                         scaling=2.448)

    compiled = jax.jit(router).lower(
        shape((TOKENS, D), jnp.float32, one_chip),
        shape((D, ROUTER), jnp.float32, one_chip),
        shape((ROUTER,), jnp.float32, one_chip),
        shape((TOKENS,), jnp.bool_, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_the_fused_head_compiles_at_the_slice_of_the_vocabulary(
        one_chip, no_compile_cache):
    """V = 16032 is no multiple of the kernel's 128-lane tile and D = 2048 is
    eight times ``logbert-256x4``'s: the same ``lse_pallas`` kernel."""
    from detectmateservice_tpu.ops.scorehead import candidate_lse

    compiled = jax.jit(candidate_lse).lower(
        shape((TOKENS, D), jnp.bfloat16, one_chip),
        shape((VOCAB, D), jnp.bfloat16, one_chip)).compile()
    assert "lse_pallas" in compiled.as_text()


def test_logberts_widest_scoring_program_compiles_with_the_short_kernel(
        one_chip, no_compile_cache):
    """``logbert-256x4``'s 32768-row bucket as ``auto`` routes it on one
    TPU: four ``attn_short`` kernels and the fused head, no copy of an
    activation between them (the stack runs token-major), and a scratch
    under the einsum route's 5,930,632,192 bytes (the padded ``[rows, 4,
    32, 32]`` float32 logits and the head-major copies are gone;
    3,265,716,736 when this was written, mostly the feed-forward's
    hidden)."""
    from detectmateservice_tpu.models.logbert import (LogBERTConfig,
                                                      LogBERTScorer)

    scorer = LogBERTScorer(LogBERTConfig(platform="tpu"))
    params = jax.tree_util.tree_map(
        lambda leaf: shape(leaf.shape, leaf.dtype, one_chip),
        jax.eval_shape(lambda: scorer.init(jax.random.PRNGKey(0))[0]))
    compiled = jax.jit(scorer._score_impl).lower(
        params, shape((32768, 32), jnp.uint16, one_chip)).compile()
    assert scorer.attn_routes == {32768: "short"}
    assert scorer.head_routes == {32768: "pallas"}
    text = compiled.as_text()
    assert text.count("attn_short") >= 4 and "lse_pallas" in text
    copies = [line for line in text.splitlines()
              if " copy(" in line and "[32768,32,256]" in line
              or " copy(" in line and "[32768,32,768]" in line]
    assert not copies, copies[:2]
    assert compiled.memory_analysis().temp_size_in_bytes < 3_500_000_000


def test_moe_mlas_widest_scoring_program_holds_no_192_wide_head(
        one_chip, no_compile_cache):
    """``kanana2-30b-a3b-ep8``'s 1024-row bucket as ``auto`` routes it on
    one TPU: six two-width attention kernels and the fused head; nothing of
    shape ``[1024, 32, 32, ·]`` — no 192-wide head, no float32 ``[1024, 32,
    32, 32]`` logits, no head-major copy of q, k or v (the einsum route's
    program holds 798 such arrays) — and a scratch under that route's
    2,719,007,232 bytes at the parent (1,510,098,432 when this was written:
    the dense layer's hidden and the expert walk's chunk). About 20 s."""
    import re

    from benchmark.lib.manifest import read_json
    from detectmateservice_tpu.models.moe_mla import (
        MoEMLAArch, MoEMLAConfig, MoEMLAScorer)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    (block,) = read_json(os.path.join(
        repo, "benchmark", "configs", "kanana2-30b-a3b-ep8.json"))[
        "stages"]["detector"]["component"]["detectors"].values()
    scorer = MoEMLAScorer(MoEMLAConfig(
        arch=MoEMLAArch.from_mapping(block["arch"]),
        vocab_size=block["vocab_size"], seq_len=block["seq_len"],
        platform="tpu"))
    params = jax.tree_util.tree_map(
        lambda leaf: shape(leaf.shape, leaf.dtype, one_chip),
        jax.eval_shape(lambda: scorer.init(jax.random.PRNGKey(0))[0]))
    compiled = jax.jit(scorer._score_impl).lower(
        params, shape((1024, 32), jnp.uint16, one_chip)).compile()
    assert scorer.attn_routes == {1024: "short"}
    assert scorer.head_routes == {1024: "pallas"}
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "attn_short_latent" in line]
    assert len(kernels) == 6, len(kernels)
    assert "lse_pallas" in text
    head_major = sorted(set(re.findall(r"\w+\[1024,32,32,\d+\]", text)))
    assert not head_major, head_major
    wide = [line for line in text.splitlines()
            if " transpose(" in line and re.search(
                r"\[32768,(4096|6144|8192)\]|\[1024,32,(4096|6144|8192)\]",
                line)]
    assert not wide, wide[:2]
    assert "combine segment_sum" in scorer.expert_routes[1024]
    assert text.count("segment_sum_add") and not colliding_scatters(text)
    # what it read before the segment sum (under 1.6e9) plus one
    # token-ordered [16384, 2048] float32 block and the way back's lists
    assert compiled.memory_analysis().temp_size_in_bytes < 1_900_000_000


def test_head_route_takes_the_kernel_at_the_cells_bucket():
    from detectmateservice_tpu.models.base import head_route

    assert head_route("auto", "tpu", True, 1024 * 32, VOCAB) == "pallas"
    assert head_route("auto", "cpu", True, 1024 * 32, VOCAB) == "einsum"


# -- the gated-short-convolution, grouped-query family (moe_conv) -----------

def test_the_gated_convolutions_kernel_compiles_at_the_published_width(
        one_chip, no_compile_cache):
    """``gated_conv`` at the cell's widest bucket: 32768 tokens in lines of
    32, D = 2048, 3 taps; B, C and x̃ are three column-block views of the
    one ``[32768, 6144]`` buffer (no split is copied) and the shift down the
    sublanes stays inside the kernel."""
    from detectmateservice_tpu.ops.shortconv import gated_conv

    compiled = jax.jit(lambda b, w: gated_conv(b, w, 32)).lower(
        shape((TOKENS, 3 * D), jnp.bfloat16, one_chip),
        shape((D, 3), jnp.float32, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gated_conv" in text
    copies = [line for line in text.splitlines()
              if " copy(" in line and "[32768," in line
              or " slice(" in line and "[32768,2048]" in line]
    assert not copies, copies[:2]
    # the transposed taps, nothing of the activations' size
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.fixture(scope="module")
def moe_conv_scorer():
    from benchmark.lib.manifest import read_json
    from detectmateservice_tpu.models.moe_conv import (
        MoEConvArch, MoEConvConfig, MoEConvScorer)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    (block,) = read_json(os.path.join(
        repo, "benchmark", "configs", "lfm2-24b-a2b-ep8.json"))[
        "stages"]["detector"]["component"]["detectors"].values()
    return MoEConvScorer(MoEConvConfig(
        arch=MoEConvArch.from_mapping(block["arch"]),
        vocab_size=block["vocab_size"], seq_len=block["seq_len"],
        platform="tpu"))


def _described(tree, one_chip):
    return jax.tree_util.tree_map(
        lambda leaf: shape(leaf.shape, leaf.dtype, one_chip), tree)


def test_moe_convs_widest_scoring_program_and_its_bytes(
        moe_conv_scorer, one_chip, no_compile_cache):
    """``lfm2-24b-a2b-ep8``'s 1024-row bucket as ``auto`` routes it on one
    TPU: six gated-convolution kernels, the grouped einsum for the two
    attention layers, the fused head; no ``[..., 32, 2]`` pair reshape for
    the rotation and no key/value head repeated for its four query heads
    (a ``[1024, 32, 32, 64]`` key or value would be one); scratch
    1,884,570,624 bytes when this was written, beside 2.95 GB of float32
    parameters. About 20 s."""
    import re

    scorer = moe_conv_scorer
    params = _described(jax.eval_shape(
        lambda: scorer.init(jax.random.PRNGKey(0))[0]), one_chip)
    compiled = jax.jit(scorer._score_impl).lower(
        params, shape((1024, 32), jnp.uint16, one_chip)).compile()
    assert scorer.attn_routes == {1024: "einsum"}
    assert scorer.conv_routes == {1024: "fused"}
    assert scorer.head_routes == {1024: "pallas"}
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "gated_conv" in line]
    assert len(kernels) == 6, len(kernels)
    assert "lse_pallas" in text
    pairs = sorted(set(re.findall(r"\w+\[[\d,]*,32,2\]", text)))
    assert not pairs, pairs
    # keys and values stay 8 heads wide: [1024,32,8,64], never [.., 32, 64]
    # per query head outside q itself and the attention's output
    repeated = [line for line in text.splitlines()
                if re.search(r"broadcast\(.*\[1024,32,8,64\]", line)
                and "[1024,32,8,4,64]" in line]
    assert not repeated, repeated[:2]
    assert "combine segment_sum" in scorer.expert_routes[1024]
    assert text.count("segment_sum_add") and not colliding_scatters(text)
    stats = compiled.memory_analysis()
    # under 2.0e9 before the segment sum, plus one token-ordered
    # [16384, 2048] float32 block and the way back's lists
    assert stats.temp_size_in_bytes < 2_300_000_000
    assert stats.argument_size_in_bytes == pytest.approx(
        4 * 736_959_104, rel=1e-3)


def test_moe_convs_donated_train_step_fits_the_chip(
        moe_conv_scorer, one_chip, no_compile_cache):
    """The boundary fit's 32-row donated train step at the published widths
    and the cut's eight layers: 16 bytes a parameter while a gradient lives.
    XLA's buffer assignment for a described v5e read 8,843,681,280 bytes of
    arguments (parameters and both moments, aliased to the outputs) and
    2,315,088,384 of temporaries = 11.16 GB when this was written; the
    configuration's fall-back to six layers is for a reading above 13.5 GB.
    About 25 s."""
    scorer = moe_conv_scorer
    params, opt_state = _described(jax.eval_shape(
        lambda: scorer.init(jax.random.PRNGKey(0))), one_chip)
    compiled = jax.jit(scorer._train_impl, donate_argnums=(0, 1)).lower(
        params, opt_state, shape((2,), jnp.uint32, one_chip),
        shape((32, 32), jnp.int32, one_chip)).compile()
    assert scorer.conv_routes[32] == "xla"       # the fit keeps XLA's form
    # and takes the segment sum back to the tokens: one kernel a layer,
    # every block of tokens written (one chunk, no accumulator before it)
    kernels = [line for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line and "segment_sum_add" in line]
    assert len(kernels) == 6, len(kernels)
    assert not any("output_to_operand_aliasing" in line for line in kernels)
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == pytest.approx(
        12 * 736_959_104, rel=1e-3)
    held = (stats.argument_size_in_bytes + stats.output_size_in_bytes
            - stats.alias_size_in_bytes + stats.temp_size_in_bytes)
    assert held < 13_500_000_000
    assert held == pytest.approx(11_158_771_200, rel=0.05)


@pytest.fixture(scope="module")
def moe_delta_scorer():
    from benchmark.lib.manifest import read_json
    from detectmateservice_tpu.models.moe_delta import (
        MoEDeltaArch, MoEDeltaConfig, MoEDeltaScorer)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    (block,) = read_json(os.path.join(
        repo, "benchmark", "configs", "qwen3-next-80b-a3b-ep16.json"))[
        "stages"]["detector"]["component"]["detectors"].values()
    return MoEDeltaScorer(MoEDeltaConfig(
        arch=MoEDeltaArch.from_mapping(block["arch"]),
        vocab_size=block["vocab_size"], seq_len=block["seq_len"],
        platform="tpu"))


def test_moe_deltas_widest_scoring_program_and_its_bytes(
        moe_delta_scorer, one_chip, no_compile_cache):
    """``qwen3-next-80b-a3b-ep16``'s 1024-row bucket as ``auto`` routes it
    on one TPU: the delta rule's core as the kernel ``gated_delta``, once a
    delta layer, reading q | k | v in place from the convolution's output
    (no scan over positions or chunks is left in the program, and no
    ``[32, 32, lines × heads]`` intermediate), the grouped einsum for the
    gated attention layer with no ``[..., 32, 2]`` pair reshape for its
    partial rotation, the fused head, the segment sum back from the
    experts; scratch 3,034,655,232 bytes when this was written
    (3,672,502,272 with the core as XLA's batched matmuls), beside 2.50 GB
    of float32 parameters — with the 8.10 GB the fitted detector holds
    (parameters, both moments and the allocator's slack) 11.1 GB of the
    chip's 16. About 45 s."""
    import re

    scorer = moe_delta_scorer
    params = _described(jax.eval_shape(
        lambda: scorer.init(jax.random.PRNGKey(0))[0]), one_chip)
    compiled = jax.jit(scorer._score_impl).lower(
        params, shape((1024, 32), jnp.uint16, one_chip)).compile()
    assert scorer.attn_routes == {1024: "einsum"}
    assert scorer.delta_routes == {1024: "fused"}
    assert scorer.head_routes == {1024: "pallas"}
    assert "32 of 512 experts from 0" in scorer.expert_routes[1024]
    assert "combine segment_sum" in scorer.expert_routes[1024]
    text = compiled.as_text()
    assert "lse_pallas" in text
    assert text.count("segment_sum_add") and not colliding_scatters(text)
    # a pair reshape of the 64 turned lanes would be [1024,32,heads,32,2]
    # ([1024,32,2] alone is the two key heads' norm statistics)
    pairs = sorted(set(re.findall(r"\w+\[1024,32,\d+,32,2\]", text)))
    assert not pairs, pairs
    # one chunk a line: the only loops left are the expert layers' walks
    loops = [line for line in text.splitlines()
             if " while(" in line and "/delta/" in line]
    assert not loops, loops[:2]
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "/core/delta_fused/" in line]
    assert len(kernels) == 3, len(kernels)
    # each reads the convolution's one output three times, no slice of it
    operands = re.findall(r"custom-call\(([^)]*)\)", kernels[0])[0].split(", ")
    assert len(set(operands[:3])) == 1, operands
    matrices = sorted(set(re.findall(r"f32\[32,32,\d+\]", text)))
    assert not matrices, matrices
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 3_400_000_000
    assert stats.argument_size_in_bytes == pytest.approx(
        4 * 625_669_184, rel=1e-3)
    # beside what the fitted detector holds: under the chip's 16 GB
    assert 12 * 625_669_184 + stats.temp_size_in_bytes < 13_000_000_000


@pytest.mark.parametrize("rows", [256, 512])
def test_the_delta_rules_kernel_compiles_at_the_other_served_buckets(
        rows, one_chip, no_compile_cache):
    """``gated_delta`` alone at 256 and 512 rows of the published heads
    (the 1024-row bucket compiles it inside its scoring program above),
    q | k | v in place from one bfloat16 array. About 5 s each."""
    from detectmateservice_tpu.ops.deltarule import Heads, gated_delta

    heads, n = Heads(16, 32, 128, 128), rows * 32
    lowered = jax.jit(lambda m, g, b: gated_delta((m,), g, b, heads, 32)
                      ).lower(shape((n, 8192), jnp.bfloat16, one_chip),
                              shape((n, 32), jnp.float32, one_chip),
                              shape((n, 32), jnp.float32, one_chip))
    assert "tpu_custom_call" in lowered.as_text()
    stats = lowered.compile().memory_analysis()
    assert stats.output_size_in_bytes == n * 4096 * 4
    # the result by head (a relayout only where nothing consumes it) and
    # the gates by tile: nothing of q, k, v is copied
    assert stats.temp_size_in_bytes < n * 4096 * 4 + 4 * n * 32 * 4


def test_moe_deltas_donated_train_step_fits_the_chip(
        moe_delta_scorer, one_chip, no_compile_cache):
    """The boundary fit's 32-row donated train step at the published widths
    and the cut's four layers: 16 bytes a parameter while a gradient lives.
    XLA's buffer assignment for a described v5e read 7,508,054,016 bytes of
    arguments (parameters and both moments, aliased to the outputs) and
    2,842,418,176 of temporaries = 10.35 GB when this was written. The
    delta rule takes its chunked form here too (the scan's reverse pass
    would keep 67 MB of state a position and layer). About 55 s."""
    scorer = moe_delta_scorer
    params, opt_state = _described(jax.eval_shape(
        lambda: scorer.init(jax.random.PRNGKey(0))), one_chip)
    compiled = jax.jit(scorer._train_impl, donate_argnums=(0, 1)).lower(
        params, opt_state, shape((2,), jnp.uint32, one_chip),
        shape((32, 32), jnp.int32, one_chip)).compile()
    assert scorer.delta_routes[32] == "chunked 32"
    kernels = [line for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line and "segment_sum_add" in line]
    assert len(kernels) == 4, len(kernels)
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == pytest.approx(
        12 * 625_669_184, rel=1e-3)
    held = (stats.argument_size_in_bytes + stats.output_size_in_bytes
            - stats.alias_size_in_bytes + stats.temp_size_in_bytes)
    assert held < 12_500_000_000
    assert held == pytest.approx(10_350_473_728, rel=0.05)


# -- the state-space, latent-expert family (moe_ssm) ------------------------

@pytest.fixture(scope="module")
def moe_ssm_scorer():
    from benchmark.lib.manifest import read_json
    from detectmateservice_tpu.models.moe_ssm import (
        MoESSMArch, MoESSMConfig, MoESSMScorer)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    (block,) = read_json(os.path.join(
        repo, "benchmark", "configs", "nemotron3-super-120b-a12b-tp8.json"))[
        "stages"]["detector"]["component"]["detectors"].values()
    return MoESSMScorer(MoESSMConfig(
        arch=MoESSMArch.from_mapping(block["arch"]),
        vocab_size=block["vocab_size"], seq_len=block["seq_len"],
        platform="tpu"))


def held_buffers(text, shape_text):
    """The instructions of a compiled program whose RESULT is an array of
    ``shape_text`` outside every fused computation: what the program holds
    in memory (inside a fusion a shape is a value in flight, no buffer)."""
    found, fused = [], False
    for line in text.splitlines():
        if line and not line.startswith(" "):
            fused = "fused_computation" in line
        elif not fused and f"= {shape_text}" in line:
            found.append(line.strip()[:160])
    return found


def test_moe_ssms_widest_scoring_program_and_its_bytes(
        moe_ssm_scorer, one_chip, no_compile_cache):
    """``nemotron3-super-120b-a12b-tp8``'s 1024-row bucket as ``auto``
    routes it on one TPU, at 8 held experts and the shared unit whole: the
    grouped einsum for the one attention layer, the fused head at D 4096
    and V 16,384, the segment
    sum back from the latent experts (five walks over 44 chunks of 16,384
    of the 720,896 assignment slots, one of them live under even routing);
    the state-space core with no loop over positions or chunks (a line is
    one chunk); and the router's choice at 22 of 512 held nowhere as a
    ``[32768, 22, 512]`` float32 array (1.48 GB: it lives inside one
    fusion). Scratch 1,779,362,816 bytes when this was written, beside 2.80
    GB of float32 parameters — with the 8.41 GB the fitted detector holds
    (parameters and both moments) 10.2 GB of the chip's 16. About 50 s."""
    scorer = moe_ssm_scorer
    params = _described(jax.eval_shape(
        lambda: scorer.init(jax.random.PRNGKey(0))[0]), one_chip)
    compiled = jax.jit(scorer._score_impl).lower(
        params, shape((1024, 32), jnp.uint16, one_chip)).compile()
    assert scorer.attn_routes == {1024: "einsum"}
    assert scorer.head_routes == {1024: "pallas"}
    assert "8 of 512 experts from 0" in scorer.expert_routes[1024]
    assert "chunks of 16384 of 720896 slots" in scorer.expert_routes[1024]
    assert "combine segment_sum" in scorer.expert_routes[1024]
    text = compiled.as_text()
    assert "lse_pallas" in text
    assert text.count("segment_sum_add") and not colliding_scatters(text)
    assert not held_buffers(text, "f32[32768,22,512]")
    assert not held_buffers(text, "pred[32768,22,512]")
    # the only loops are the five expert layers' walks
    loops = [line for line in text.splitlines() if " while(" in line]
    assert len(loops) == 5 and not [
        line for line in loops if "/ssm/" in line], loops[:2]
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 2_300_000_000
    assert stats.argument_size_in_bytes == pytest.approx(
        4 * 700_865_520, rel=1e-3)
    # beside what the fitted detector holds: under 14.5 GB
    assert 12 * 700_865_520 + stats.temp_size_in_bytes < 14_500_000_000


def test_moe_ssms_donated_train_step_fits_the_chip(
        moe_ssm_scorer, one_chip, no_compile_cache):
    """The boundary fit's 32-row donated train step at the published widths
    and the cut's eleven layers with 8 experts held and the shared unit
    whole: 16 bytes a parameter while a gradient lives. XLA's buffer
    assignment for a described v5e read 8,410,452,992 bytes of arguments
    (parameters and both moments, aliased to the outputs) and 3,851,598,336
    of temporaries = 12.26 GB when this was written. The check that decides
    how many experts are held: 16 would be 921,066,480 parameters, 14.74 GB
    at 16 bytes before any temporary, over the 14.5 GB this holds the step
    to. About 70 s."""
    scorer = moe_ssm_scorer
    params, opt_state = _described(jax.eval_shape(
        lambda: scorer.init(jax.random.PRNGKey(0))), one_chip)
    compiled = jax.jit(scorer._train_impl, donate_argnums=(0, 1)).lower(
        params, opt_state, shape((2,), jnp.uint32, one_chip),
        shape((32, 32), jnp.int32, one_chip)).compile()
    # one chunk a layer (22,528 slots): the segment sum, every block written
    kernels = [line for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line and "segment_sum_add" in line]
    assert len(kernels) == 5, len(kernels)
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == pytest.approx(
        12 * 700_865_520, rel=1e-3)
    held = (stats.argument_size_in_bytes + stats.output_size_in_bytes
            - stats.alias_size_in_bytes + stats.temp_size_in_bytes)
    assert held < 14_500_000_000
    assert held == pytest.approx(12_262_069_760, rel=0.05)


# -- the vector-decay delta rule, gated latent attention family (moe_kda) ----

@pytest.fixture(scope="module")
def moe_kda_scorer():
    from benchmark.lib.manifest import read_json
    from detectmateservice_tpu.models.moe_kda import (
        MoEKDAArch, MoEKDAConfig, MoEKDAScorer)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    (block,) = read_json(os.path.join(
        repo, "benchmark", "configs", "ling3-flash-125b-a5b-tp4.json"))[
        "stages"]["detector"]["component"]["detectors"].values()
    return MoEKDAScorer(MoEKDAConfig(
        arch=MoEKDAArch.from_mapping(block["arch"]),
        vocab_size=block["vocab_size"], seq_len=block["seq_len"],
        platform="tpu"))


def test_moe_kdas_widest_scoring_program_and_its_bytes(
        moe_kda_scorer, one_chip, no_compile_cache):
    """``ling3-flash-125b-a5b-tp4``'s 1024-row bucket as ``auto`` routes it
    on one TPU, at 8 heads and 8 experts held: the delta rule's chunked
    closed form in four sub-blocks of 8 positions with no loop over
    positions or chunks (a line is one chunk), the einsum over whole heads
    for the one latent-attention layer (behind the key's norm every head's
    rope part is its own), the fused head at D 2560 and V 19,648, the
    segment sum back from the experts (walks over 16 chunks of 16,384 of
    the 262,144 assignment slots, one of them live under even routing),
    the grouped router's choice at 8 of 512 held nowhere as a ``[32768,
    8, 512]`` array (it lives inside one fusion), and **layers 1-4, one
    kind, as one scan of a block over their stacked leaves**
    (``MoEKDALM._walk``: the program holds their body once — 21.6 MB of
    compile-cache entry where the unrolled stack made 41.0, which is what
    lets the configuration's seven programs stay in the 192 MiB the chip
    tool's machine keeps). Scratch 3,010,892,800 bytes when this was
    written (2,296,401,408 unrolled: the stack is a copy of the four
    layers' leaves), beside 2.31 GB of float32 parameters — with the 6.93
    GB the fitted detector holds (parameters and both moments) 9.9 GB of
    the chip's 16. About 35 s."""
    scorer = moe_kda_scorer
    params = _described(jax.eval_shape(
        lambda: scorer.init(jax.random.PRNGKey(0))[0]), one_chip)
    compiled = jax.jit(scorer._score_impl).lower(
        params, shape((1024, 32), jnp.uint16, one_chip)).compile()
    assert scorer.attn_routes == {1024: "einsum"}
    assert scorer.head_routes == {1024: "pallas"}
    assert scorer.delta_routes == {1024: "kda chunked 32/8"}
    assert "8 of 512 experts from 0" in scorer.expert_routes[1024]
    assert "chunks of 16384 of 262144 slots" in scorer.expert_routes[1024]
    assert "combine segment_sum" in scorer.expert_routes[1024]
    text = compiled.as_text()
    assert "lse_pallas" in text
    assert text.count("segment_sum_add") and not colliding_scatters(text)
    assert not held_buffers(text, "f32[32768,8,512]")
    assert not held_buffers(text, "pred[32768,8,512]")
    # the only loops are the run's scan with its layers' expert walk
    # inside, and the walks of layers 5 and 6
    loops = [line for line in text.splitlines() if " while(" in line]
    assert len(loops) == 4 and not [
        line for line in loops if "/kda/" in line], loops[:2]
    # the three scopes of the grouped router reach the compiled program
    for step in ("scores", "groups", "top_k"):
        assert f"/moe/router/{step}/" in text, step
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 3_500_000_000
    assert stats.argument_size_in_bytes == pytest.approx(
        4 * 577_867_952, rel=1e-3)
    # beside what the fitted detector holds: under 14.5 GB
    assert 12 * 577_867_952 + stats.temp_size_in_bytes < 14_500_000_000


def test_moe_kdas_donated_train_step_fits_the_chip(
        moe_kda_scorer, one_chip, no_compile_cache):
    """The boundary fit's 32-row donated train step at the published widths
    and the cut's seven layers with 8 heads and 8 experts held: 16 bytes a
    parameter while a gradient lives. XLA's buffer assignment for a
    described v5e read 6,934,534,656 bytes of arguments (parameters and
    both moments, aliased to the outputs) and 2,950,903,808 of temporaries
    = 9.89 GB when this was written. Sixteen experts held would be
    860,983,472 parameters, 13.78 GB at 16 bytes before any temporary. The
    delta rule's reverse pass is the chunked form's own (no scan over
    positions is differentiated), and the step walks the layers one by one
    (scanned like the scoring programs it read 6,279,259,648 bytes of
    temporaries: the stacked leaves and their gradient). About 45 s."""
    scorer = moe_kda_scorer
    params, opt_state = _described(jax.eval_shape(
        lambda: scorer.init(jax.random.PRNGKey(0))), one_chip)
    compiled = jax.jit(scorer._train_impl, donate_argnums=(0, 1)).lower(
        params, opt_state, shape((2,), jnp.uint32, one_chip),
        shape((32, 32), jnp.int32, one_chip)).compile()
    assert scorer.delta_routes[32] == "kda chunked 32/8"
    assert " while(" not in compiled.as_text()
    # one chunk a layer (8,192 slots): the segment sum, every block written
    kernels = [line for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line and "segment_sum_add" in line]
    assert len(kernels) == 6, len(kernels)
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == pytest.approx(
        12 * 577_867_952, rel=1e-3)
    held = (stats.argument_size_in_bytes + stats.output_size_in_bytes
            - stats.alias_size_in_bytes + stats.temp_size_in_bytes)
    assert held < 14_500_000_000
    assert held == pytest.approx(9_885_440_512, rel=0.05)
