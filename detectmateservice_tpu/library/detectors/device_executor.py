"""The device executor: place rows, call the program for ``(kind, bucket)``,
hand back device arrays — on the caller's thread.

One class, :class:`DeviceExecutor`, owns what ``JaxScorerDetector`` needs of
the device and is the only code that knows it:

* **placement** — one device (:class:`OneDevice`, below) or a mesh
  (``parallel.ShardedScorer``): two objects with the same attributes and
  methods. The detector holds the executor and never asks which it has.
* **the parameters that serve** — the float tree and optimiser state, and
  the int8 tree once the detector's parity gate admitted it.
* **one table of programs** keyed ``(kind, rows, quantized)`` with ``kind`` in
  :data:`KINDS`: the executable :meth:`DeviceExecutor.warm` compiled and
  kept where the bucket has one, else the jit. The programs are the scorer's
  own jitted ``_score`` / ``_normscore`` / ``_token_nlls`` (on a mesh the
  sharded scorer's own), called with the arguments they always got and
  wrapped in nothing: module names, HLO and the persistent compile cache's
  keys do not depend on this module.

This module imports jax; the detector imports it when it builds its scorer.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from ...engine import device_obs
from ...models.tokenizer import narrow_tokens
from ..common.core import LibraryError

KINDS = ("score", "normscore", "token_nlls")


def resolve_device(spec: Optional[str]):
    """``device: "<platform>:<id>"`` → that jax device; None = the first
    device of the resolved backend. A spec that names no device raises:
    a replica told to take chip 2 must not land on chip 0."""
    if not spec:
        return jax.devices()[0]
    platform, _, index = spec.partition(":")
    try:
        want = int(index or 0)
        for device in jax.devices(platform.lower()):
            if device.id == want:
                return device
    except (RuntimeError, ValueError) as exc:
        raise LibraryError(f"device {spec!r}: {exc}") from exc
    raise LibraryError(
        f"device {spec!r} names no device of this process "
        f"(expected '<platform>:<id>', e.g. 'tpu:0')")


def _batch_span(name: str, batch_kv: Optional[Dict[str, Any]]):
    """``device_obs.span`` for a served device batch; nothing for a call
    that is not one (warm-up, fit, parity: ``batch_kv`` None)."""
    if batch_kv is None:
        return device_obs.NULL_SPAN
    return device_obs.span(name, **batch_kv)


def _quantized_impls(scorer) -> Dict[str, Tuple[Callable, int]]:
    """The scoring impls over ``dequantize_tree`` — XLA fuses the
    int8→float dequant into the weight read, so the GEMMs stream 4× fewer
    weight bytes — each with the count of its arguments after the batch."""
    from ...models.quant import dequantize_tree

    compute_dtype = scorer.config.dtype

    def _qscore_impl(qparams, tokens):
        return scorer._score_impl(
            dequantize_tree(qparams, compute_dtype), tokens)

    def _qnormscore_impl(qparams, tokens, mu, sigma):
        return scorer._normscore_impl(
            dequantize_tree(qparams, compute_dtype), tokens, mu, sigma)

    return {"score": (_qscore_impl, 0), "normscore": (_qnormscore_impl, 2)}


class OneDevice:
    """Everything on one device: rows go up in the narrow wire format,
    committed to it, and the scorer's own jits run as they are. Same
    attributes and methods as the mesh placement,
    ``parallel.ShardedScorer``."""

    mesh_shape = None
    # a candidate tree can be trained and scored beside the live ones
    forkable = True
    # the upload is a step of its own (``dm.upload``) before the call
    uploads_apart = True

    def __init__(self, scorer, device, rng) -> None:
        self.scorer = scorer
        self.devices = [device]
        self.platform = self.backend = device.platform
        self.label = str(device)
        self.jits = {"score": scorer._score, "normscore": scorer._normscore,
                     "token_nlls": scorer._token_nlls}
        # pinned in device memory once (HBM residency; north-star item)
        self.params, self.opt_state = self.place_trees(*scorer.init(rng))

    def place_trees(self, params, opt_state):
        device = self.devices[0]
        return (jax.device_put(params, device),
                jax.device_put(opt_state, device))

    def place_quantized(self, qparams):
        return jax.device_put(qparams, self.devices[0])

    def jit_quantized(self, impl, n_extra: int):
        return jax.jit(impl)

    def traced(self, fn, *args, bucket: Optional[int] = None):
        return fn(*args)

    def padded_rows(self, n: int) -> int:
        return n

    def place(self, tokens: np.ndarray):
        """Upload a token batch in the narrow wire format (models.tokenizer
        narrow_tokens has the rule; the jitted impls cast back on device)."""
        return jax.device_put(
            narrow_tokens(tokens, self.scorer.config.vocab_size),
            self.devices[0])

    def train_step(self, rng, tokens: np.ndarray) -> float:
        # both trees are given up to the step and rebound at once: a step
        # holds one generation of parameters and moments, not two
        self.params, self.opt_state, loss = self.scorer.train_step(
            self.params, self.opt_state, rng, self.place(tokens),
            donate=True)
        return float(loss)


class DeviceExecutor:
    """See the module's docstring. Threads: the engine thread calls
    :meth:`run`; a boundary fit (its own thread, or the engine's) calls
    :meth:`train_step`; installs come from the fit's end, a restore or the
    rollout manager, which the detector serialises (it joins the fit before
    an install and swaps under its ``_fit_lock``). Each tree swaps by one
    reference assignment and a call reads one tree, once: a call in flight
    scores with whichever generation is current, never a mix of two."""

    def __init__(self, scorer, placement) -> None:
        self.scorer = scorer
        self._place = placement
        # (kind, quantized) -> jit; the quantized pair is built at the
        # first int8 install
        self._jits: Dict[Tuple[str, bool], Any] = {
            (kind, False): fn for kind, fn in placement.jits.items()}
        # (kind, placed rows, quantized) -> the executable warm() compiled.
        # jax's .lower().compile() does not seed the jit's own dispatch
        # cache: the executable must be kept and called
        self._kept: Dict[Tuple[str, int, bool], Any] = {}
        self._qparams = None

    @classmethod
    def open(cls, build_scorer: Callable[[str], Any], rng,
             mesh_shape: Optional[Dict[str, int]] = None,
             device: Optional[str] = None) -> "DeviceExecutor":
        """Resolve the placement first — kernel routing (compiled vs
        interpret-mode Pallas, flash vs einsum) follows the device the
        scorer runs on, never the global device list — then build the
        scorer for that platform (``build_scorer(platform)``) and place its
        freshly initialised parameters."""
        if mesh_shape:
            # multi-chip: batches shard over the mesh's data axis, params
            # per the model rules
            from ...parallel.mesh import make_mesh
            from ...parallel.sharded import ShardedScorer

            mesh = make_mesh(dict(mesh_shape))
            scorer = build_scorer(mesh.devices.flat[0].platform)
            return cls(scorer, ShardedScorer(scorer, mesh=mesh, rng=rng))
        dev = resolve_device(device)
        scorer = build_scorer(dev.platform)
        return cls(scorer, OneDevice(scorer, dev, rng))

    # -- placement facts --------------------------------------------------
    @property
    def platform(self) -> str:
        return self._place.platform

    @property
    def backend(self) -> str:
        """The ledger's and the metrics' backend label: the platform, or
        ``"mesh"``."""
        return self._place.backend

    @property
    def label(self) -> str:
        return self._place.label

    @property
    def devices(self) -> list:
        return self._place.devices

    @property
    def mesh_shape(self) -> Optional[Dict[str, int]]:
        return self._place.mesh_shape

    @property
    def forkable(self) -> bool:
        """Whether a candidate tree can be trained and scored beside the
        live ones (rollout fine-tuning, shadow scoring, the host twin's
        mirror)."""
        return self._place.forkable

    # -- the parameters that serve ----------------------------------------
    @property
    def params(self):
        return self._place.params

    @property
    def opt_state(self):
        return self._place.opt_state

    @property
    def qparams(self):
        """The int8 tree when it serves, else None."""
        return self._qparams

    def place_trees(self, params, opt_state):
        """A candidate's trees placed as the live ones are."""
        return self._place.place_trees(params, opt_state)

    def install(self, params, opt_state) -> None:
        """Swap in placed trees (a rollout candidate, a restore). The old
        generation's int8 tree must not outlive its float source: float
        serves until the detector has quantized and judged the new one."""
        self._qparams = None
        self._place.params, self._place.opt_state = params, opt_state

    def install_quantized(self, qparams) -> None:
        """Serve from an int8 tree (``models/quant.quantize_tree`` of the
        live params). The detector's parity gate decides whether it stays."""
        if ("score", True) not in self._jits:
            for kind, (impl, n_extra) in _quantized_impls(self.scorer).items():
                self._jits[(kind, True)] = self._place.jit_quantized(
                    impl, n_extra)
        self._qparams = self._place.place_quantized(qparams)

    def clear_quantized(self) -> None:
        """Back to the float tree (parity flip, or a fit about to move it)."""
        self._qparams = None

    def train_step(self, rng, tokens: np.ndarray) -> float:
        """One optimiser step of the boundary fit on the live trees."""
        return self._place.train_step(rng, tokens)

    def fork(self):
        """The live float tree and optimiser state for a candidate to start
        from; :meth:`fork_step` never touches them."""
        if not self.forkable:
            raise LibraryError(
                "continuous fine-tuning is not supported in mesh (sharded) "
                "mode; deploy externally-trained checkpoints instead")
        return self.params, self.opt_state

    def fork_step(self, params, opt_state, rng, tokens: np.ndarray):
        """One functional optimiser step on a candidate's trees →
        ``(params, opt_state, loss)``."""
        return self.scorer.train_step(params, opt_state, rng,
                                      self._place.place(tokens))

    # -- programs ---------------------------------------------------------
    def _tree(self, kind: str, params) -> Tuple[Any, bool]:
        """The tree a call of ``kind`` reads, and whether it is the int8
        one: an explicit candidate's, else the int8 tree when it serves
        (there is no quantized ``token_nlls``), else the live float tree."""
        if params is not None:
            return params, False
        qparams = self._qparams
        if qparams is not None and kind != "token_nlls":
            return qparams, True
        return self._place.params, False

    def kept_programs(self) -> list:
        """``(kind, placed rows, quantized)`` of every kept executable."""
        return sorted(self._kept)

    def warm(self, kind: str, bucket: int, *extra, params=None) -> None:
        """Compile the program that serves ``(kind, bucket)`` now — or, with
        ``params``, that will serve a candidate's float tree — through
        ``jit_fn.lower(*args).compile()``, and keep it; nothing to do where
        it is kept already. The caller holds the ledger context that says
        why (warm-up, a bucket's first use, an install). ``extra`` are the
        kind's arguments after the batch (``normscore``: mu, sigma)."""
        tree, quantized = self._tree(kind, params)
        place = self._place
        key = (kind, place.padded_rows(bucket), quantized)
        if key in self._kept:
            return
        seq_len = self.scorer.config.seq_len
        args = (tree, place.place(np.zeros((bucket, seq_len), np.int32)),
                *extra)
        jit_fn = self._jits[(kind, quantized)]
        self._kept[key] = place.traced(
            lambda: jit_fn.lower(*args).compile(), bucket=key[1])

    def run(self, kind: str, tokens: np.ndarray, *extra, params=None,
            batch_kv: Optional[Dict[str, Any]] = None):
        """Place ``[n, S]`` tokens and call the program for ``(kind, n)`` →
        ``(device array, aux)`` without forcing a readback; ``aux`` is what
        a ``score_aux`` scorer's call returned beside the scores, else
        None. A bucket that has a kept executable ALWAYS runs it: an
        argument it rejects (dtype, sharding, committed device) raises
        instead of quietly retracing. A bucket outside the table takes the
        jit, whose compile the ledger sees. ``params`` scores a candidate's
        placed float tree in place of the live one.

        ``batch_kv`` (a served device batch: ``_InflightSlot.span_kv``)
        marks the upload as ``dm.upload`` and the call, with the start of
        the asynchronous readback, as ``dm.call``; warm-up, fit and parity
        calls pass none and leave no span. On a mesh placing the shards is
        part of the call, so the whole of it is ``dm.call``."""
        place = self._place
        placed = None
        if place.uploads_apart:
            with _batch_span("dm.upload", batch_kv):
                placed = place.place(tokens)
        with _batch_span("dm.call", batch_kv):
            if placed is None:
                placed = place.place(tokens)
            tree, quantized = self._tree(kind, params)
            rows = len(placed)
            program = self._kept.get((kind, rows, quantized))
            if program is not None:
                out = program(tree, placed, *extra)
            else:
                out = place.traced(self._jits[(kind, quantized)], tree,
                                   placed, *extra, bucket=rows)
            aux = None
            if isinstance(out, (tuple, list)):
                out, aux = out
            if batch_kv is not None:
                try:
                    out.copy_to_host_async()
                    if aux is not None:
                        aux.copy_to_host_async()
                except AttributeError:
                    pass
        return out, aux
