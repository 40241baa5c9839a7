"""The routed experts' way back to the tokens (ops/experts.py): the
token-ordered segment sum (``dispatch``'s second permutation, the kernel
``segment_sum_add`` in interpret mode on the CPU) held to the scatter-add
form it replaces on the TPU — at every skew, with PAD rows, at four and six
experts a token, in one chunk and through the walk — with its gradient, the
kernel's list of steps, the rule that chooses, and the record of the
choice in ``GET /admin/xla``."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from detectmateservice_tpu.ops import experts as ops  # noqa: E402

N, D, M, E_ALL, HELD, OFFSET = 256, 128, 24, 16, 4, 4


def layer(k, bias_held, pad=0, seed=0, one_expert=False, n=N, valid=None):
    """A seeded expert layer and its routing: ``bias_held`` on the held
    experts' selection (+50 every token on them, -50 none), or on the first
    held expert alone (``one_expert``: it takes every token); the first
    ``pad`` tokens are PAD, or those ``valid`` [n] leaves out."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(D, E_ALL)) * 0.3, jnp.float32)
    bias = np.zeros(E_ALL, np.float32)
    bias[OFFSET:OFFSET + (1 if one_expert else HELD)] = bias_held
    gate, up = (jnp.asarray(rng.normal(size=(HELD, D, M)) * 0.2, jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(HELD, M, D)) * 0.2, jnp.float32)
    if valid is None:
        valid = jnp.arange(n) >= pad
    routing = ops.route(x, router, jnp.asarray(bias), valid, top_k=k,
                        norm_topk_prob=True, scaling=1.5)
    return x, routing, gate, up, down


def both(x, routing, gate, up, down, chunk_rows):
    return [ops.routed_experts(x, routing, gate, up, down, offset=OFFSET,
                               chunk_rows=chunk_rows, combine=way)
            for way in ("scatter_add", "segment_sum")]


SKEWS = {
    "even": dict(bias_held=0.0),
    "every_token_on_held": dict(bias_held=50.0),
    "none_on_held": dict(bias_held=-50.0),
    "one_expert_takes_all": dict(bias_held=50.0, one_expert=True),
    "pad_rows": dict(bias_held=0.0, pad=37),
    "pad_rows_all_held": dict(bias_held=50.0, pad=130),
}


@pytest.mark.parametrize("chunk_rows", [None, 128, 512])
@pytest.mark.parametrize("k", [4, 6])
@pytest.mark.parametrize("skew", sorted(SKEWS))
def test_the_segment_sum_equals_the_scatter_add(skew, k, chunk_rows):
    """One chunk (``None``: the whole list) and the walk; nothing dropped,
    PAD tokens zero, the counts untouched."""
    x, routing, gate, up, down = layer(k, **SKEWS[skew])
    (want, want_counts), (got, counts) = both(x, routing, gate, up, down,
                                              chunk_rows)
    assert np.array_equal(counts, want_counts)
    assert np.allclose(got, want, atol=2e-5, rtol=1e-5)
    if skew == "none_on_held":
        assert int(counts.sum()) == 0 and float(jnp.abs(got).max()) == 0.0
    if skew == "every_token_on_held":
        assert int(counts.sum()) == N * min(k, HELD)
    if skew == "one_expert_takes_all":
        assert int(counts[0]) == N
    pad = SKEWS[skew].get("pad", 0)
    assert float(jnp.abs(got[:pad]).max(initial=0.0)) == 0.0


@pytest.mark.parametrize("chunk_rows", [None, 512])
@pytest.mark.parametrize("unrouted", [(128, 520), (0, 390), (250, 768),
                                      (3, 765)])
def test_a_run_of_unrouted_tokens_reads_zero(unrouted, chunk_rows):
    """PAD lines in a row leave whole blocks of tokens without an addend:
    the first chunk, which makes the accumulator, writes them all the
    same (a block the kernel skips reads NaN in interpret mode and
    whatever the memory held on the chip)."""
    n, (lo, hi) = 768, unrouted
    place = jnp.arange(n)
    x, routing, gate, up, down = layer(4, 50.0, n=n,
                                       valid=(place < lo) | (place >= hi))
    (want, want_counts), (got, counts) = both(x, routing, gate, up, down,
                                              chunk_rows)
    assert int(counts.sum()) == (n - (hi - lo)) * 4
    assert np.array_equal(counts, want_counts)
    assert bool(jnp.isfinite(got).all())
    assert np.allclose(got, want, atol=2e-5, rtol=1e-5)
    assert float(jnp.abs(got[lo:hi]).max()) == 0.0


GAPS = {
    # the reviewer's: rows 127 and 128 two blocks of tokens apart
    "on_a_row_blocks_edge": (384, [100] * 128 + [300]),
    "before_the_first_row": (512, [400] * 130),
    "behind_the_last_row": (512, [5] * 200),
    "two_edges_three_blocks": (1024, [0] * 128 + [512] * 128 + [1023] * 3),
    "inside_a_row_block": (512, [10] * 60 + [500] * 60),
    "no_live_row": (384, []),
}


@pytest.mark.parametrize("gap", sorted(GAPS))
def test_without_an_accumulator_every_block_of_tokens_is_written(gap):
    """``whole``: the blocks of tokens that fall between two row blocks,
    before the first or behind the last have a step too, and the kernel's
    every output row is the sum or zero."""
    tokens, held = GAPS[gap]
    chunk, live = 512, len(held)
    back_token = np.full(chunk, tokens, np.int32)
    back_token[:live] = held
    tb, rb, flags = (np.asarray(a) for a in ops.segment_work(
        jnp.asarray(back_token), jnp.int32(live), tokens, whole=True))
    assert set(tb) == set(range(tokens // 128))
    assert (np.diff(tb) >= 0).all() and (np.diff(rb) >= 0).all()
    assert len(tb) == tokens // 128 + chunk // 128
    assert {(t // 128, r // 128) for r, t in enumerate(held)} <= set(
        zip(tb[flags >= 2], rb[flags >= 2]))
    rng = np.random.default_rng(len(gap))
    y = jnp.asarray(rng.normal(size=(chunk, D)), jnp.float32)
    weight = jnp.asarray(rng.uniform(0.1, 1.0, chunk), jnp.float32)
    got = np.asarray(ops.segment_sum_add(
        None, y.at[live:].set(jnp.nan), jnp.asarray(back_token), weight,
        jnp.int32(live), tokens, interpret=True))
    want = np.zeros((tokens, D))
    np.add.at(want, back_token[:live], np.asarray(y, np.float64)[:live]
              * np.asarray(weight, np.float64)[:live, None])
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < 2e-5
    # with an accumulator the blocks without a row are left as they were
    acc = jnp.asarray(rng.normal(size=(tokens, D)), jnp.float32)
    got = np.asarray(ops.segment_sum_add(
        acc, y.at[live:].set(jnp.nan), jnp.asarray(back_token), weight,
        jnp.int32(live), tokens, interpret=True))
    assert np.abs(got - (want + np.asarray(acc, np.float64))).max() < 2e-5


def test_the_sum_keeps_float32():
    """Addends that bfloat16 cannot hold come back to float32's last bits:
    the kernel splits each into three bfloat16 parts for its 0/1 matmul."""
    rng = np.random.default_rng(7)
    tokens, chunk = 128, 256
    y = jnp.asarray(rng.normal(size=(chunk, D)) * (1 + 1e-4 * rng.normal(
        size=(chunk, D))), jnp.float32)
    back_token = jnp.sort(jnp.asarray(rng.integers(0, tokens, chunk),
                                      jnp.int32))
    acc = jnp.asarray(rng.normal(size=(tokens, D)), jnp.float32)
    weight = jnp.asarray(rng.uniform(0.1, 1.0, chunk), jnp.float32)
    addends = np.asarray(y, np.float64) * np.asarray(weight,
                                                     np.float64)[:, None]
    got = ops.segment_sum_add(acc, y, back_token, weight, jnp.int32(chunk),
                              tokens, interpret=True)
    want = np.asarray(acc, np.float64)
    np.add.at(want, np.asarray(back_token), addends)
    assert np.abs(np.asarray(got) - want).max() < 4e-6
    # rows past ``live`` are zeroed whatever they hold (0 x NaN); without
    # an accumulator every row is written
    half = ops.segment_sum_add(
        None, y.at[128:].set(jnp.nan), back_token.at[128:].set(tokens),
        weight, jnp.int32(128), tokens, interpret=True)
    want = np.zeros((tokens, D))
    np.add.at(want, np.asarray(back_token[:128]), addends[:128])
    assert np.abs(np.asarray(half) - want).max() < 4e-6


@pytest.mark.parametrize("chunk_rows", [None, 256])
@pytest.mark.parametrize("k", [4, 6])
def test_the_gradient_through_the_segment_sum_is_the_scatter_adds(k,
                                                                  chunk_rows):
    """Hidden state, router (through the weights) and experts: the
    kernel's custom backward is the gather it is mathematically."""
    x, _, gate, up, down = layer(k, 0.0, pad=5)
    rng = np.random.default_rng(11)
    router = jnp.asarray(rng.normal(size=(D, E_ALL)) * 0.3, jnp.float32)
    probe = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)

    def loss(way):
        def fn(x, router, gate, down):
            routing = ops.route(x, router, jnp.zeros(E_ALL),
                                jnp.arange(N) >= 5, top_k=k,
                                norm_topk_prob=True, scaling=1.5)
            out, _ = ops.routed_experts(
                x, routing, gate, up, down, offset=OFFSET,
                chunk_rows=chunk_rows, combine=way)
            return (out * probe).sum() + (out ** 2).sum()
        return jax.value_and_grad(fn, argnums=(0, 1, 2, 3))(
            x, router, gate, down)

    (want, want_grads), (got, grads) = loss("scatter_add"), loss(
        "segment_sum")
    assert got == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(grads, want_grads):
        assert bool(jnp.isfinite(g).all())
        assert np.allclose(g, w, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("whole", [False, True])
@pytest.mark.parametrize("live", [0, 1, 127, 128, 129, 300, 512])
def test_the_kernels_steps_cover_every_live_row_once(live, whole):
    """A merge of the token blocks and the row blocks: every live row's
    (token block, row block) pair is a step with rows to add, each token
    block's steps are adjacent and the first is flagged, and the list never
    outgrows ``token blocks + row blocks``; ``whole`` (no accumulator yet)
    gives every token block a step."""
    rng = np.random.default_rng(live)
    tokens, chunk = 384, 512
    back_token = np.full(chunk, tokens, np.int32)
    back_token[:live] = np.sort(rng.integers(0, tokens, live))
    tb, rb, flags = (np.asarray(a) for a in ops.segment_work(
        jnp.asarray(back_token), jnp.int32(live), tokens, whole))
    if whole:
        assert set(tb[flags >= 2]) == set(range(tokens // 128))
    assert len(tb) == tokens // 128 + chunk // 128
    assert tb.min() >= 0 and tb.max() < tokens // 128
    assert rb.min() >= 0 and rb.max() < chunk // 128
    adding = flags >= 2
    steps = list(zip(tb[adding], rb[adding]))
    assert len(set(steps)) == len(steps)
    assert {(t // 128, r // 128) for r, t in enumerate(back_token[:live])
            } <= set(steps)
    first = flags % 2 == 1
    assert first[0]
    assert np.array_equal(first[1:], tb[1:] != tb[:-1])
    # a token block is left once: the accumulator's block is read and
    # written in one stay
    seen = tb[np.concatenate([[True], tb[1:] != tb[:-1]])]
    assert len(set(seen)) == len(seen)


@pytest.mark.parametrize("call,want", [
    (("tpu", 32768, 16384, 2048), "segment_sum"),
    (("tpu", 8192, 4096, 2048), "segment_sum"),
    (("tpu", 1024, 6144, 2048), "segment_sum"),      # the fit's 32-row step
    (("cpu", 32768, 16384, 2048), "scatter_add"),
    (("tpu", 32768, 16384, 2048, 4), "scatter_add"),  # a mesh
    (("tpu", 32768, 16384, 2000), "scatter_add"),     # lanes
    (("tpu", 32768 + 64, 16384 + 32, 2048), "scatter_add"),
    (("tpu", 256, 1024, 2048), "segment_sum"),
    (("tpu", 32, 128, 2048), "scatter_add"),          # one line: no block
])
def test_the_route_follows_platform_and_shape(call, want):
    assert ops.combine_route(*call) == want


def test_an_unknown_combine_is_refused_by_name():
    x, routing, gate, up, down = layer(4, 0.0)
    with pytest.raises(ValueError, match="combine 'gather'"):
        ops.routed_experts(x, routing, gate, up, down, offset=OFFSET,
                           combine="gather")


def test_a_segment_sum_that_does_not_tile_is_refused():
    x, routing, gate, up, down = layer(4, 0.0, n=192)
    with pytest.raises(ValueError, match="192 tokens .* do not tile"):
        ops.routed_experts(x, routing, gate, up, down, offset=OFFSET,
                           combine="segment_sum")


def test_the_second_permutation_orders_each_chunk_by_token():
    """``back`` permutes each chunk's rows within the chunk; their tokens
    ascend over the live rows and the dead ones read N, last."""
    _, routing, *_ = layer(6, 0.0, pad=9)
    chunk = 256
    plan = ops.dispatch(routing, OFFSET, HELD)
    order = ops.token_order(plan, N, chunk)
    back, back_token = np.asarray(order.rows), np.asarray(order.token)
    token_of, n_held = np.asarray(plan.token_of), int(plan.ends[-1])
    weight_of = np.asarray(plan.weight_of)
    for lo in range(0, N * 6, chunk):
        rows = back[lo:lo + chunk]
        assert sorted(rows) == list(range(lo, lo + chunk))
        live = rows < n_held
        assert np.array_equal(back_token[lo:lo + chunk][live],
                              token_of[rows[live]])
        assert (back_token[lo:lo + chunk][~live] == N).all()
        assert (np.diff(back_token[lo:lo + chunk]) >= 0).all()
        assert np.array_equal(np.asarray(order.weight)[lo:lo + chunk],
                              np.where(live, weight_of[rows], 0.0))


# -- the scorers: what each bucket took, and the fit -------------------------

# the families' tiny shapes (tests/test_moe_mla.py, tests/test_moe_conv.py)
# at a hidden width of one lane group, which the kernel's rule asks for
MLA_ARCH = dict(
    hidden_size=128, num_attention_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32, q_lora_rank=None,
    intermediate_size=96, moe_intermediate_size=48, n_shared_experts=1,
    num_experts_per_tok=2, first_k_dense_replace=1, norm_topk_prob=True,
    routed_scaling_factor=2.448, scoring_func="sigmoid", rope_theta=1e6,
    rope_interleave=True, rms_norm_eps=1e-6, num_hidden_layers=3,
    n_routed_experts=4, router_experts=8, expert_offset=2)
CONV_ARCH = dict(
    hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
    conv_L_cache=3, conv_bias=False, intermediate_size=96,
    moe_intermediate_size=48, num_experts_per_tok=2, num_dense_layers=1,
    norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True,
    norm_eps=1e-5, rope_parameters={"rope_theta": 1e6,
                                    "rope_type": "default"},
    num_hidden_layers=4,
    layer_types=["conv", "full_attention", "conv", "conv"],
    num_experts=4, router_experts=8, expert_offset=2)


def _scorers(platform):
    from detectmateservice_tpu.models.moe_conv import (MoEConvArch,
                                                       MoEConvConfig,
                                                       MoEConvScorer)
    from detectmateservice_tpu.models.moe_mla import (MoEMLAArch,
                                                      MoEMLAConfig,
                                                      MoEMLAScorer)

    return [
        MoEMLAScorer(MoEMLAConfig(
            arch=MoEMLAArch.from_mapping(MLA_ARCH),
            vocab_size=64, seq_len=16, dtype=jnp.float32, platform=platform,
            head_impl="einsum", attn_impl="einsum")),
        MoEConvScorer(MoEConvConfig(
            arch=MoEConvArch.from_mapping(CONV_ARCH),
            vocab_size=64, seq_len=16, dtype=jnp.float32, platform=platform,
            head_impl="einsum", conv_impl="xla")),
    ]


@pytest.mark.parametrize("family", [0, 1], ids=["moe_mla", "moe_conv"])
def test_expert_route_names_the_combine_of_each_bucket(family):
    """On a TPU every bucket whose tokens come in whole blocks takes the
    segment sum, the fit's 32 rows among them; on the CPU none does."""
    for platform, want in (("tpu", {4: "scatter_add", 32: "segment_sum",
                                    512: "segment_sum",
                                    1024: "segment_sum"}),
                           ("cpu", {32: "scatter_add", 512: "scatter_add"})):
        scorer = _scorers(platform)[family]
        params = jax.eval_shape(lambda: scorer.init(jax.random.PRNGKey(0))[0])
        for rows in want:
            jax.eval_shape(scorer._score_impl, params,
                           jax.ShapeDtypeStruct((rows, 16), jnp.int32))
        assert {rows: route.rsplit(", combine ", 1)[1]
                for rows, route in scorer.expert_routes.items()} == want
        assert all(route.startswith("sorted ragged_dot, ")
                   for route in scorer.expert_routes.values())


def test_admin_xla_names_the_combine_beside_the_attention():
    """``GET /admin/xla`` → ``buckets.expert_route`` beside ``attn_route``:
    on the CPU every warm bucket reads ``combine scatter_add``; the same
    scorer placed on a TPU reads ``combine segment_sum`` wherever the
    tokens come in whole blocks."""
    from detectmateservice_tpu.engine import device_obs
    from detectmateservice_tpu.library.detectors import JaxScorerDetector

    det = JaxScorerDetector(config={"detectors": {"JaxScorerDetector": {
        "method_type": "jax_scorer", "auto_config": False,
        "model": "moe_mla", "arch": MLA_ARCH, "vocab_size": 256,
        "seq_len": 16, "dtype": "float32", "data_use_training": 32,
        "max_batch": 32, "host_score_max_batch": 0, "async_fit": False,
    }}})
    det.setup_io()
    buckets = device_obs.get_ledger().snapshot()["buckets"]
    routes = buckets["expert_route"]
    assert routes and set(routes) == set(buckets["attn_route"])
    assert all(r.endswith(", combine scatter_add") for r in routes.values())

    on_tpu = type(det._scorer)(dataclasses.replace(
        det._scorer.config, platform="tpu", attn_impl="einsum",
        head_impl="einsum"))
    params = jax.eval_shape(lambda: on_tpu.init(jax.random.PRNGKey(0))[0])
    for rows in (4, 32, 512):
        jax.eval_shape(on_tpu._score_impl, params,
                       jax.ShapeDtypeStruct((rows, 16), jnp.uint16))
    det._scorer = on_tpu
    buckets = device_obs.get_ledger().snapshot()["buckets"]
    assert buckets["expert_route"]["4"].endswith(", combine scatter_add")
    assert buckets["expert_route"]["32"].endswith(", combine segment_sum")
    assert buckets["expert_route"]["512"].endswith(", combine segment_sum")
    assert buckets["attn_route"] == {"4": "einsum", "32": "einsum",
                                     "512": "einsum"}


def _fit(scorer, steps=3):
    params, opt_state = scorer.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = rng.integers(3, 64, size=(32, 16)).astype(np.int32)
    tokens[:, 0] = 2
    tokens[-1, 5:] = 0
    tokens[8:20] = 0            # PAD lines in a row: blocks without a token
    losses = []
    for step in range(steps):
        params, opt_state, loss = scorer.train_step(
            params, opt_state, jax.random.PRNGKey(step), jnp.asarray(tokens))
        losses.append(float(loss))
    return params, losses


@pytest.mark.parametrize("way", ops.WAYS_BACK)
@pytest.mark.parametrize("family", [0, 1], ids=["moe_mla", "moe_conv"])
def test_the_fits_32_row_step_stays_finite_and_takes_one_chunk(family, way,
                                                               monkeypatch):
    """The CPU's scatter-add, and the segment sum as ONE TPU's fit takes it
    (the rule's answer set here, the kernel in interpret mode): one chunk,
    finite, the loss falls, and both ways fit the same parameters."""
    rule = ops.combine_route
    if way == "segment_sum":
        monkeypatch.setattr(ops, "combine_route",
                            lambda platform, *shape: rule("tpu", *shape))
    scorer = _scorers("cpu")[family]
    params, losses = _fit(scorer)
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    assert all(bool(jnp.isfinite(leaf).all())
               for leaf in jax.tree_util.tree_leaves(params))
    spec = scorer.config.arch.expert_spec
    assert ops.chunk_rows_for(32 * 16, spec.top_k) == 32 * 16 * spec.top_k
    jax.eval_shape(scorer._score_impl, params,
                   jax.ShapeDtypeStruct((32, 16), jnp.int32))
    assert scorer.expert_routes[32].endswith(f"slots, combine {way}")
    monkeypatch.undo()
    _, plain = _fit(_scorers("cpu")[family])
    assert losses == pytest.approx(plain, rel=1e-4)
