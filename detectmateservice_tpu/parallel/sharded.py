"""ShardedScorer: DP×TP execution of a scorer over a device mesh.

Multi-chip scale-out for the detector hot path (SURVEY.md §7 step 6,
BASELINE.json config #5 "8× detector replicas across v5e-8"). Instead of the
reference's N independent processes, one process drives all chips: the batch
is sharded over the ``data`` axis, params are sharded over ``model`` per the
Megatron-style rules (parallel/mesh.py), and ``jit`` + GSPMD insert the ICI
collectives. Training steps psum gradients across ``data`` automatically
(they fall out of jit's partitioning — no hand-written NCCL/MPI analog).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from ..engine import device_obs
from ..models.tokenizer import narrow_tokens
from .mesh import (
    AXIS_DATA,
    AXIS_SEQ,
    LOGBERT_RULES,
    REPLICATED_RULES,
    make_mesh,
    tree_shardings,
)


class ShardedScorer:
    """Wraps a scorer (LogBERTScorer / MLPScorer surface) with mesh placement.

    ``score(tokens)`` and ``train_step(rng, tokens)`` own the params/opt-state
    internally (sharded once at construction) so callers just stream batches.
    """

    def __init__(
        self,
        scorer,
        mesh=None,
        rules: Optional[Sequence] = None,
        rng: Optional[jax.Array] = None,
    ):
        self.scorer = scorer
        self.mesh = mesh if mesh is not None else make_mesh()
        # placement fact for the scorer's kernel routing (models/base.py
        # head_route): GSPMD does not partition a Pallas call, so on more
        # than one device ``head_impl: auto`` keeps the einsum head
        scorer.mesh_devices = int(self.mesh.devices.size)
        if rules is None:
            rules = LOGBERT_RULES if getattr(scorer, "name", "") == "logbert" else REPLICATED_RULES
        # sequence parallelism (long-context): a 'seq' mesh axis shards the
        # token/activation sequence dim; the model's attention runs as ring
        # attention over that axis (ops.attention impl="ring", resolved via
        # the ring_context this wrapper sets around tracing). Each 'data' row
        # runs its own independent ring.
        self._seq_axis = AXIS_SEQ if AXIS_SEQ in self.mesh.shape else None
        if self._seq_axis is not None:
            seq_size = int(self.mesh.shape[AXIS_SEQ])
            seq_len = getattr(getattr(scorer, "config", None), "seq_len", None)
            if seq_len is not None and seq_len % seq_size != 0:
                raise ValueError(
                    f"seq_len {seq_len} must divide by the seq mesh axis "
                    f"({seq_size}) for sequence-parallel scoring")
        # token batches travel in the narrow wire format (uint16 when the
        # vocab fits — models.tokenizer.narrow_tokens has the one rule); the
        # jitted impls cast back to int32 on device
        self._vocab_size = getattr(getattr(scorer, "config", None),
                                   "vocab_size", 1 << 31)
        self._data_axis = AXIS_DATA if AXIS_DATA in self.mesh.shape else None
        # init also traces the model (flax shape inference) so it needs the
        # ring context on a seq mesh — but with the batch axis REPLICATED:
        # flax init runs on a [1, S] dummy, and a batch of 1 cannot shard
        # over a data axis of 2+
        init_rng = rng if rng is not None else jax.random.PRNGKey(0)
        # construction-time tracing/compiles attribute to the mesh init —
        # always an expected phase, whatever context the caller holds
        with device_obs.get_ledger().context(where="sharded_init",
                                             backend="mesh", expected=True):
            if self._seq_axis is None:
                params, opt_state = scorer.init(init_rng)
            else:
                from ..ops.attention import ring_context

                with ring_context(self.mesh, batch_axis=None,
                                  axis_name=self._seq_axis):
                    params, opt_state = scorer.init(init_rng)
        self._param_sharding = tree_shardings(self.mesh, params, rules)
        self._opt_sharding = tree_shardings(self.mesh, opt_state, rules)
        self.params = jax.device_put(params, self._param_sharding)
        self.opt_state = jax.device_put(opt_state, self._opt_sharding)
        # tokens are [B, S]: batch over 'data' when present, sequence over
        # 'seq' when present — so activations start out seq-sharded and the
        # ring's shard_map needs no initial reshard
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._batch_sharding = NamedSharding(
            self.mesh, P(self._data_axis, self._seq_axis))

        self._score = jax.jit(
            scorer._score_impl,
            in_shardings=(self._param_sharding, self._batch_sharding),
        )
        self._token_nlls = jax.jit(
            scorer._token_nlls_impl,
            in_shardings=(self._param_sharding, self._batch_sharding),
        )
        self._normscore = jax.jit(
            scorer._normscore_impl,
            in_shardings=(self._param_sharding, self._batch_sharding, None, None),
        )
        self._train = jax.jit(
            scorer._train_impl,
            in_shardings=(self._param_sharding, self._opt_sharding, None,
                          self._batch_sharding),
            out_shardings=(self._param_sharding, self._opt_sharding, None),
            donate_argnums=(0, 1),
        )
        # dmwarm (PR 17): AOT-compiled executables keyed (kind, padded_B) —
        # the detector's setup_io lowers+compiles the warm bucket set here
        # so mesh dispatch executes without entering the jit compile path
        self._aot: Dict[Tuple[str, int], Any] = {}
        # weight-only int8 serving (models/quant.py): installed by the
        # detector after its parity gate passes; None = float path serves
        self._qparams = None
        self._qscore = None
        self._qnormscore = None

    @property
    def data_parallelism(self) -> int:
        return int(self.mesh.shape.get(AXIS_DATA, 1))

    def install_params(self, params, opt_state) -> None:
        """Hot-swap the served param/opt trees (model rollout): the new
        trees are placed with the SAME shardings the jitted executables
        were compiled against, so every cached executable keeps hitting —
        the swap itself is a reference assignment, never a recompile."""
        self.params = jax.device_put(params, self._param_sharding)
        self.opt_state = jax.device_put(opt_state, self._opt_sharding)

    # -- AOT warm-start (dmwarm) -----------------------------------------
    def aot_compile_bucket(self, kind: str, tokens: np.ndarray,
                           *extra) -> None:
        """Lower+compile one (kind, bucket) sharded executable and KEEP it
        (jax's AOT compile does not seed the jit's dispatch cache). The
        batch pads to the mesh's data-axis multiple first, so the key is
        the padded shape every later dispatch of this bucket produces."""
        jit_fn = {"score": self._score, "normscore": self._normscore,
                  "token_nlls": self._token_nlls}[kind]
        tokens, _ = self._pad_batch(np.asarray(tokens))
        tokens = jax.device_put(tokens, self._batch_sharding)
        args = (self.params, tokens, *extra)
        with device_obs.get_ledger().context(bucket=tokens.shape[0],
                                             backend="mesh",
                                             where="sharded"):
            if self._seq_axis is None:
                self._aot[(kind, tokens.shape[0])] = (
                    jit_fn.lower(*args).compile())
            else:
                from ..ops.attention import ring_context

                with ring_context(self.mesh, batch_axis=self._data_axis,
                                  axis_name=self._seq_axis):
                    self._aot[(kind, tokens.shape[0])] = (
                        jit_fn.lower(*args).compile())

    def _aot_call(self, kind: str, batch: int, *args):
        """The kept executable for (kind, batch), called directly — None
        only when the bucket has none (the caller then takes the jit). An
        argument the executable rejects raises: retracing quietly would
        turn every batch into a compile nobody sees."""
        comp = self._aot.get((kind, batch))
        return None if comp is None else comp(*args)

    # -- weight-only int8 serving (dmwarm) -------------------------------
    def install_quantized(self, qparams) -> None:
        """Install a quantized tree (models/quant.quantize_tree of the live
        params): the int8 payloads shard exactly like their float leaves,
        the per-channel scales along the leaf's last-axis placement. The
        detector's parity gate decides whether this tree ever serves."""
        from ..models.quant import dequantize_tree, quant_shardings

        qshard = quant_shardings(self.params, self._param_sharding,
                                 self.mesh)
        qparams = jax.device_put(qparams, qshard)
        if self._qscore is None:
            scorer = self.scorer
            compute_dtype = scorer.config.dtype

            def _qscore_impl(qp, tokens):
                return scorer._score_impl(
                    dequantize_tree(qp, compute_dtype), tokens)

            def _qnormscore_impl(qp, tokens, mu, sigma):
                return scorer._normscore_impl(
                    dequantize_tree(qp, compute_dtype), tokens, mu, sigma)

            self._qscore = jax.jit(
                _qscore_impl, in_shardings=(qshard, self._batch_sharding))
            self._qnormscore = jax.jit(
                _qnormscore_impl,
                in_shardings=(qshard, self._batch_sharding, None, None))
        self._qparams = qparams

    def clear_quantized(self) -> None:
        """Back to the float path (parity flip, or a fresh candidate swap
        whose requant has not been judged yet)."""
        self._qparams = None

    def _traced(self, fn, *args, bucket: Optional[int] = None):
        """Invoke a jitted fn; on a seq mesh, tracing happens inside
        ring_context so the model's ``attention(impl="ring")`` resolves to
        this mesh. Trace-time only: cached executions skip the context.

        Compiles fired here attribute to the padded batch bucket on the
        mesh backend (engine/device_obs.py); ``expected`` is inherited from
        the caller — the detector's dispatch path marks itself
        unexpected-after-warm-up, its fit/warm-up paths expected."""
        with device_obs.get_ledger().context(bucket=bucket, backend="mesh",
                                             where="sharded"):
            if self._seq_axis is None:
                return fn(*args)
            from ..ops.attention import ring_context

            with ring_context(self.mesh, batch_axis=self._data_axis,
                              axis_name=self._seq_axis):
                return fn(*args)

    def _pad_batch(self, tokens: np.ndarray) -> Tuple[np.ndarray, int]:
        """Pad the batch to a multiple of the data-axis size (and narrow to
        the wire dtype — see __init__)."""
        n = len(tokens)
        dp = self.data_parallelism
        padded = ((n + dp - 1) // dp) * dp
        if padded != n:
            pad = np.zeros((padded - n,) + tokens.shape[1:], tokens.dtype)
            tokens = np.concatenate([tokens, pad])
        return narrow_tokens(tokens, self._vocab_size), n

    def score(self, tokens: np.ndarray) -> np.ndarray:
        tokens, n = self._pad_batch(np.asarray(tokens))
        tokens = jax.device_put(tokens, self._batch_sharding)
        return np.asarray(self._traced(self._score, self.params, tokens,
                                       bucket=len(tokens)))[:n]

    def warm_bucket(self, tokens: np.ndarray) -> None:
        """Pre-compile the sharded score path for this batch shape and block
        until the executable exists. The detector's adaptive batcher warms
        buckets BEFORE their first dispatch use (adaptive warm-set growth,
        post-retirement resurrection), so the compile attributes as an
        expected ``bucket_warm`` — never an unexpected-recompile page."""
        with device_obs.get_ledger().context(bucket=len(tokens),
                                             backend="mesh",
                                             where="bucket_warm",
                                             expected=True):
            jax.block_until_ready(self.score_device(tokens))

    def score_device(self, tokens: np.ndarray) -> jax.Array:
        """Asynchronous scoring: dispatch and return the device array without
        forcing a host readback (rows beyond the caller's real batch are
        padding — the caller slices). Lets the detector's pipelined hot path
        overlap readback with the next batch's featurization. Routing: the
        int8 quantized path when live, then the bucket's AOT executable,
        then the jit (whose compile the ledger attributes)."""
        tokens, _ = self._pad_batch(np.asarray(tokens))
        tokens = jax.device_put(tokens, self._batch_sharding)
        if self._qparams is not None:
            return self._traced(self._qscore, self._qparams, tokens,
                                bucket=tokens.shape[0])
        out = self._aot_call("score", tokens.shape[0], self.params, tokens)
        if out is not None:
            return out
        return self._traced(self._score, self.params, tokens,
                            bucket=tokens.shape[0])

    def token_nlls_device(self, tokens: np.ndarray) -> jax.Array:
        """[n, S] → [n_padded, S] per-position NLLs on device."""
        tokens, _ = self._pad_batch(np.asarray(tokens))
        tokens = jax.device_put(tokens, self._batch_sharding)
        out = self._aot_call("token_nlls", tokens.shape[0],
                             self.params, tokens)
        if out is not None:
            return out
        return self._traced(self._token_nlls, self.params, tokens,
                            bucket=tokens.shape[0])

    def normscore_device(self, tokens: np.ndarray, mu, sigma) -> jax.Array:
        """Per-position-normalized scores (models.logbert.positional_z_max)."""
        tokens, _ = self._pad_batch(np.asarray(tokens))
        tokens = jax.device_put(tokens, self._batch_sharding)
        if self._qparams is not None:
            return self._traced(self._qnormscore, self._qparams, tokens,
                                mu, sigma, bucket=tokens.shape[0])
        out = self._aot_call("normscore", tokens.shape[0],
                             self.params, tokens, mu, sigma)
        if out is not None:
            return out
        return self._traced(self._normscore, self.params, tokens, mu, sigma,
                            bucket=tokens.shape[0])

    def train_step(self, rng: jax.Array, tokens: np.ndarray) -> float:
        # pad by wrapping real rows, NOT zeros: synthetic all-PAD rows would
        # enter the loss mean and train the model that empty sequences are
        # normal; duplicating real rows only slightly oversamples them
        tokens = np.asarray(tokens)
        n = len(tokens)
        dp = self.data_parallelism
        padded = ((n + dp - 1) // dp) * dp
        if padded != n:
            # modular repetition handles n < padded - n too (e.g. a 3-row
            # final batch on a data=8 mesh); a plain slice would come up
            # short and crash the sharded device_put
            tokens = tokens[np.arange(padded) % n]
        tokens = jax.device_put(narrow_tokens(tokens, self._vocab_size),
                                self._batch_sharding)
        self.params, self.opt_state, loss = self._traced(
            self._train, self.params, self.opt_state, rng, tokens,
            bucket=tokens.shape[0]
        )
        return float(loss)
