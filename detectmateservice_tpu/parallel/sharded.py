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

from typing import Optional, Sequence

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
    It is also the mesh placement of the detector's device executor
    (library/detectors/device_executor.py), beside that module's one-device
    placement: same attributes, same methods.
    """

    # a candidate tree cannot be trained or scored beside the live ones: the
    # train step donates the sharded trees in place
    forkable = False
    # placing the shards is part of the call (one ``dm.call`` span), not an
    # upload step of its own
    uploads_apart = False

    def __init__(
        self,
        scorer,
        mesh=None,
        rules: Optional[Sequence] = None,
        rng: Optional[jax.Array] = None,
    ):
        self.scorer = scorer
        self.mesh = mesh if mesh is not None else make_mesh()
        # where this placement runs, and its name in logs and labels
        self.devices = list(self.mesh.devices.flat)
        self.platform = self.devices[0].platform
        self.backend = "mesh"
        self.label = "mesh({})".format(
            ",".join(f"{k}={v}" for k, v in self.mesh.shape.items()))
        self.mesh_shape = dict(self.mesh.shape)
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
        self.params, self.opt_state = self.place_trees(params, opt_state)
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
        # the programs the executor's table is filled from
        self.jits = {"score": self._score, "normscore": self._normscore,
                     "token_nlls": self._token_nlls}

    @property
    def data_parallelism(self) -> int:
        return int(self.mesh.shape.get(AXIS_DATA, 1))

    def place_trees(self, params, opt_state):
        """Parameter and optimiser trees placed with the shardings the
        jitted programs were compiled against: a tree placed here serves
        from every compiled program, never through a recompile."""
        return (jax.device_put(params, self._param_sharding),
                jax.device_put(opt_state, self._opt_sharding))

    # -- weight-only int8 serving ----------------------------------------
    def _quant_sharding(self):
        """Shardings of ``quantize_tree(params)``: the int8 payloads shard
        exactly like their float leaves, the per-channel scales along the
        leaf's last-axis placement."""
        from ..models.quant import quant_shardings

        return quant_shardings(self.params, self._param_sharding, self.mesh)

    def place_quantized(self, qparams):
        return jax.device_put(qparams, self._quant_sharding())

    def jit_quantized(self, impl, n_extra: int):
        """Jit a scoring impl that takes a quantized tree, the batch and
        ``n_extra`` replicated arguments."""
        return jax.jit(impl, in_shardings=(
            self._quant_sharding(), self._batch_sharding,
            *(None,) * n_extra))

    def traced(self, fn, *args, bucket: Optional[int] = None):
        """Invoke a jitted fn (or a thunk that lowers one); on a seq mesh,
        tracing happens inside ring_context so the model's ``attention(impl="ring")`` resolves to
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

    def padded_rows(self, n: int) -> int:
        """``n`` rounded up to a multiple of the data-axis size."""
        dp = self.data_parallelism
        return ((n + dp - 1) // dp) * dp

    def place(self, tokens: np.ndarray) -> jax.Array:
        """Pad the batch to a multiple of the data-axis size (rows beyond
        the caller's are padding: the caller slices), narrow it to the wire
        dtype (see __init__) and shard it over the mesh."""
        tokens = np.asarray(tokens)
        padded = self.padded_rows(len(tokens))
        if padded != len(tokens):
            pad = np.zeros((padded - len(tokens),) + tokens.shape[1:],
                           tokens.dtype)
            tokens = np.concatenate([tokens, pad])
        return jax.device_put(narrow_tokens(tokens, self._vocab_size),
                              self._batch_sharding)

    def score(self, tokens: np.ndarray) -> np.ndarray:
        placed = self.place(tokens)
        return np.asarray(self.traced(self._score, self.params, placed,
                                      bucket=len(placed)))[:len(tokens)]

    def train_step(self, rng: jax.Array, tokens: np.ndarray) -> float:
        # pad by wrapping real rows, NOT zeros: synthetic all-PAD rows would
        # enter the loss mean and train the model that empty sequences are
        # normal; duplicating real rows only slightly oversamples them
        tokens = np.asarray(tokens)
        n = len(tokens)
        padded = self.padded_rows(n)
        if padded != n:
            # modular repetition handles n < padded - n too (e.g. a 3-row
            # final batch on a data=8 mesh); a plain slice would come up
            # short and crash the sharded device_put
            tokens = tokens[np.arange(padded) % n]
        tokens = jax.device_put(narrow_tokens(tokens, self._vocab_size),
                                self._batch_sharding)
        self.params, self.opt_state, loss = self.traced(
            self._train, self.params, self.opt_state, rng, tokens,
            bucket=tokens.shape[0]
        )
        return float(loss)
