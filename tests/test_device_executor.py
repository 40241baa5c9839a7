"""The device executor (library/detectors/device_executor.py): one table of
programs keyed (kind, rows, quantized) over two placements.

A warm (kind, bucket) runs the executable ``warm`` kept and fires no compile;
a bucket outside the table takes the jit, whose compile the ledger
attributes to the caller's context; a kept executable handed a wrong dtype
raises instead of retracing. On one device the programs are the scorer's own
jit objects, lowered as the detector always lowered them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from detectmateservice_tpu.engine import device_obs
from detectmateservice_tpu.library.detectors.device_executor import (
    KINDS,
    DeviceExecutor,
    OneDevice,
)
from detectmateservice_tpu.models import quant
from detectmateservice_tpu.models.mlp import MLPScorer, MLPScorerConfig
from detectmateservice_tpu.models.tokenizer import narrow_tokens
from detectmateservice_tpu.parallel.mesh import make_mesh
from detectmateservice_tpu.parallel.sharded import ShardedScorer

SEQ_LEN = 8
WARM, COLD = 8, 4     # a bucket in the table, and one outside it


def build_scorer(platform: str = "cpu") -> MLPScorer:
    return MLPScorer(MLPScorerConfig(vocab_size=512, dim=16, hidden=32,
                                     seq_len=SEQ_LEN, dtype=jnp.float32,
                                     platform=platform))


def make_executor(placement: str, tree: str) -> DeviceExecutor:
    rng = jax.random.PRNGKey(0)
    if placement == "mesh":
        scorer = build_scorer()
        mesh = make_mesh({"data": 2}, devices=jax.devices("cpu")[:2])
        ex = DeviceExecutor(scorer, ShardedScorer(scorer, mesh=mesh, rng=rng))
    else:
        ex = DeviceExecutor.open(build_scorer, rng, device="cpu:0")
    if tree == "int8w":
        ex.install_quantized(quant.quantize_tree(ex.params))
    return ex


def compiles(ledger) -> list:
    return ledger.snapshot(limit=4096)["compiles"]


def total_compiles(ledger) -> int:
    return ledger.snapshot(limit=1)["totals"]["compiles"]


@pytest.mark.parametrize("tree", ["float", "int8w"])
@pytest.mark.parametrize("placement", ["one_device", "mesh"])
@pytest.mark.parametrize("kind", KINDS)
def test_one_table_serves_every_kind_placement_and_tree(kind, placement,
                                                        tree):
    ledger = device_obs.get_ledger()
    device_obs.install_listener()
    ex = make_executor(placement, tree)
    assert ex.backend == ("mesh" if placement == "mesh" else "cpu")
    extra = ()
    if kind == "normscore":
        extra = (np.zeros(SEQ_LEN, np.float32), np.ones(SEQ_LEN, np.float32))
    # there is no quantized token_nlls: the float tree serves it
    quantized = tree == "int8w" and kind != "token_nlls"

    with ledger.context(bucket=WARM, backend=ex.backend, where="warmup",
                        expected=True):
        ex.warm(kind, WARM, *extra)
        ex.warm(kind, WARM, *extra)          # kept already: nothing to do
    assert ex.kept_programs() == [(kind, WARM, quantized)]

    # a warm (kind, bucket) runs the kept executable: no compile fires
    # (lower().compile() does not seed the jit's cache, so the jit would)
    tokens = np.random.default_rng(0).integers(
        1, 512, (WARM, SEQ_LEN)).astype(np.int32)
    before = total_compiles(ledger)
    out, aux = ex.run(kind, tokens, *extra)
    out = np.asarray(out)
    assert total_compiles(ledger) == before
    assert aux is None and np.all(np.isfinite(out))
    assert out.shape == ((WARM, SEQ_LEN) if kind == "token_nlls"
                         else (WARM,))

    # a bucket outside the table takes the jit; its compile is attributed
    # to the caller's context (on a mesh the sharded scorer's own, innermost)
    with ledger.context(bucket=COLD, backend=ex.backend, where="detect",
                        expected=True):
        cold, _ = ex.run(kind, tokens[:COLD], *extra)
    assert total_compiles(ledger) > before
    event = compiles(ledger)[-1]
    assert event["bucket"] == str(COLD) and not event["unexpected"]
    assert event["where"] == ("sharded" if placement == "mesh" else "detect")
    assert ex.kept_programs() == [(kind, WARM, quantized)]
    np.testing.assert_allclose(np.asarray(cold), out[:COLD], rtol=1e-5,
                               atol=1e-5)

    # a kept executable handed float rows (it was compiled for the narrow
    # integer wire format) raises; nothing retraces behind it
    before = total_compiles(ledger)
    with pytest.raises(TypeError):
        ex.run(kind, tokens.astype(np.float32), *extra)
    assert total_compiles(ledger) == before


def test_single_device_programs_are_the_scorers_own_jits(monkeypatch):
    """Module names, HLO and so the persistent compile cache's keys must be
    what ``scorer._score.lower(params, placed_tokens)`` always gave: the
    table holds the scorer's jit objects themselves and lowers them with
    the live tree and the placed batch, wrapped in nothing."""
    ex = make_executor("one_device", "float")
    scorer = ex.scorer
    placement = OneDevice(scorer, jax.devices("cpu")[0],
                          jax.random.PRNGKey(0))
    for kind, jit_fn in (("score", scorer._score),
                         ("normscore", scorer._normscore),
                         ("token_nlls", scorer._token_nlls)):
        assert placement.jits[kind] is jit_fn
        assert ex._jits[(kind, False)] is jit_fn

    lowered = []
    original = jax.stages.Lowered.compile

    def compile_and_keep(self, *args, **kwargs):
        lowered.append(self)
        return original(self, *args, **kwargs)

    # where benchmark/lib/stage_main.py reads each program's scratch size
    monkeypatch.setattr(jax.stages.Lowered, "compile", compile_and_keep)
    ex.warm("score", WARM)
    assert len(lowered) == 1
    placed = jax.device_put(
        narrow_tokens(np.zeros((WARM, SEQ_LEN), np.int32), 512),
        jax.devices("cpu")[0])
    assert placed.dtype == np.uint16
    parent_form = scorer._score.lower(ex.params, placed)
    assert lowered[0].as_text() == parent_form.as_text()
