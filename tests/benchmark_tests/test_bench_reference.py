"""Each plain reference against the program's scorer at a tiny size on the
CPU: float32 agrees tightly, a bfloat16 run of the same scorer does not."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import numpy as np
import pytest

TIGHT = 2e-5    # float32 rounding through a 2-block model, measured ~1e-6


def _tokens():
    rng = np.random.default_rng(0)
    tokens = rng.integers(3, 2048, (48, 32)).astype(np.int32)
    tokens[:, 0] = 2
    tokens[:16, 20:] = 0        # some rows padded
    return tokens


def _tiny(model, dtype):
    """The program's scorer at a tiny size with seeded weights, the plain
    reference for it and the reference's scorer block."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import logbert as ref_logbert, mlp as ref_mlp
    from detectmateservice_tpu.models.logbert import (LogBERTConfig,
                                                       LogBERTScorer)
    from detectmateservice_tpu.models.mlp import MLPScorer, MLPScorerConfig

    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype]
    if model == "logbert":
        scorer = LogBERTScorer(LogBERTConfig(
            vocab_size=2048, dim=32, depth=2, heads=2, seq_len=32,
            dtype=dtype))
        reference, cfg = ref_logbert, {"heads": 2}
    else:
        scorer = MLPScorer(MLPScorerConfig(vocab_size=2048, dim=32,
                                           seq_len=32, dtype=dtype))
        reference, cfg = ref_mlp, {}
    params, _ = scorer.init(jax.random.PRNGKey(1))
    return scorer, params, reference, cfg


def _program_and_reference(model, dtype):
    import jax

    scorer, params, reference, cfg = _tiny(model, dtype)
    tokens = _tokens()
    program = np.asarray(scorer.score(params, tokens))
    plain = reference.score(jax.tree_util.tree_map(np.asarray, params),
                            tokens, cfg)
    return program, plain


@pytest.mark.parametrize("model", ["logbert", "mlp"])
def test_float32_program_agrees_with_the_reference(model):
    program, plain = _program_and_reference(model, "float32")
    assert np.abs(program - plain).max() < TIGHT


@pytest.mark.parametrize("model", ["logbert", "mlp"])
def test_bfloat16_program_fails_the_tight_tolerance(model):
    program, plain = _program_and_reference(model, "bfloat16")
    assert np.abs(program - plain).max() > 10 * TIGHT


def test_reference_modules_import_nothing_of_the_programs_models():
    import ast
    import os

    from bench_helpers import REPO

    for name in ("logbert.py", "mlp.py"):
        path = os.path.join(REPO, "benchmark", "reference", name)
        with open(path, encoding="utf-8") as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                module = getattr(node, "module", None) or ""
                names = [alias.name for alias in node.names]
                assert "detectmateservice_tpu" not in module
                assert not any("detectmateservice_tpu" in n for n in names)


def _control_gap(model):
    """Widest gap of the control: the reference put in the program's place
    with every matrix multiplication's inputs rounded to float8_e4m3fn."""
    import jax
    import jax.numpy as jnp

    _, params, reference, cfg = _tiny(model, "float32")
    params = jax.tree_util.tree_map(np.asarray, params)
    tokens = _tokens()
    plain = reference.score(params, tokens, cfg)
    lowered = reference.score(params, tokens, cfg, lower=jnp.float8_e4m3fn)
    return float(np.abs(lowered - plain).max())


def test_logbert_control_stands_clear_of_the_sound_program():
    """The control of ``logbert-256x4`` at a size a test run holds: float8
    inputs move the score at least three times as far as the program's own
    bfloat16 does (on the chip, at full width: 0.049 against 0.013)."""
    program, plain = _program_and_reference("logbert", "bfloat16")
    sound = float(np.abs(program - plain).max())
    assert _control_gap("logbert") > 3 * sound


def test_mlp_bfloat16_head_hides_a_lower_precision():
    """Why ``mlp-compose`` is kept out of ``BENCHMARK.json`` (PERF.md section
    7): the program's ``mlp`` head rounds logits and log-probabilities to
    bfloat16, and that rounding is as wide as what float8 inputs do to this
    small model — no limit on the score gap separates the two there."""
    program, plain = _program_and_reference("mlp", "bfloat16")
    sound = float(np.abs(program - plain).max())
    assert _control_gap("mlp") < 3 * sound
