"""JaxScorerDetector: TPU-batched neural anomaly scoring.

This is the component the BASELINE.json north star describes: the engine
micro-batches incoming messages and dispatches them to a jax.jit-compiled
anomaly scorer instead of the per-message callback; params live in device HBM
from ``setup_io`` on. The reference has no accelerator path at all (SURVEY.md
§0 "no training, no GPU/accelerator code") — this detector is the TPU-native
capability the rebuild adds, wrapped in the same CoreDetector contract
(train-then-detect, alert-or-None per message).

Phases:
1. **train** — the first ``data_use_training`` messages are tokenized and
   buffered (filtered from the output, like every detector's training phase),
2. **fit** — at the phase boundary the scorer trains for ``train_epochs``
   over the buffer on-device, then calibrates the alert threshold as
   ``mean + threshold_sigma * std`` of the training scores,
3. **detect** — batches are tokenized on CPU, padded to a power-of-two bucket
   (few compiled shapes → no recompile storms, SURVEY.md §7 hard part #2), and
   scored in one jit call; scores above threshold become DetectorSchema alerts.
"""
from __future__ import annotations

import math
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ...engine import device_obs
from ...schemas import DetectorSchema, ParserSchema, SchemaError
from ..common.core import LibraryError
from ..common.detector import BufferMode, CoreDetector, CoreDetectorConfig
from .scorer_families import FAMILIES


class JaxScorerDetectorConfig(CoreDetectorConfig):
    method_type: str = "jax_scorer"
    # "mlp" | "gru" | "logbert" | "moe_mla" | "moe_conv" | "moe_delta" |
    # "moe_ssm" | "moe_kda" (scorer_families.FAMILIES)
    model: str = "mlp"
    vocab_size: int = 32768
    seq_len: int = 32
    dim: int = 128
    depth: int = 2                    # logbert/gru layers
    heads: int = 4                    # logbert only
    # the five sparse-expert families only: the model's shape as the
    # published config.json keys under their published names (hidden_size,
    # kv_lora_rank, n_routed_experts ...: models/moe_mla.py MoEMLAArch;
    # layer_types, conv_L_cache, num_experts ...: models/moe_conv.py
    # MoEConvArch; full_attention_interval, linear_num_value_heads ...:
    # models/moe_delta.py MoEDeltaArch; hybrid_override_pattern,
    # mamba_num_heads, moe_latent_size ...: models/moe_ssm.py MoESSMArch;
    # layer_group_size, kda_lower_bound, n_group, topk_group ...:
    # models/moe_kda.py MoEKDAArch),
    # plus the chip's share of an expert-parallel group: router_experts
    # (the published count the router scores over; the published expert
    # count's key is then the count HELD here) and expert_offset (the
    # first one held); moe_ssm and moe_kda also take a tensor share:
    # tensor_parallel (chips that share each mixer; the keys that count
    # heads and groups are then this chip's part) and tensor_rank
    arch: Optional[Dict[str, Any]] = None
    score_topk: int = 0               # logbert/gru: 0=mean NLL, k>0=top-k mean
    # logbert/gru: candidate-vocab approximate scoring NLL. 0 = exact
    # full-vocab head; 0 < C < vocab_size estimates the logsumexp over a
    # fixed seeded C-subset (+ log(V/C) correction, target logit exact) —
    # ~V/C fewer head FLOPs.
    # Threshold units change with the approximation, so it is fit-frozen.
    score_vocab: int = 0
    # logbert attention path: "auto" (per traced call, from platform, shape
    # and mesh size: the flash kernel on a TPU for long sequences, the short
    # kernel on one TPU for whole sequences up to 128 tokens, einsum
    # otherwise — ops/attention.py attention_route; GET /admin/xla
    # buckets.attn_route) | "einsum" | "flash" | "short" | "blockwise" |
    # "ring" (sequence-parallel over the mesh_shape 'seq' axis)
    attn_impl: str = "auto"
    # scoring-head path: "einsum" = S-chunked einsum + logsumexp over
    # materialized logits; "pallas" = fused online-logsumexp kernel
    # (ops/scorehead.py) that keeps the logits in VMEM; "auto" = the
    # kernel for the exact head of gru/logbert on one TPU, einsum on the
    # CPU, on a multi-device mesh and for the candidate and mlp heads
    # (models/base.py head_route; GET /admin/xla buckets.head_route)
    head_impl: str = "auto"
    data_use_training: int = 256
    train_epochs: int = 3
    # small training buffers still get enough optimizer steps to converge
    min_train_steps: int = 100
    train_batch_size: int = 32
    threshold_sigma: float = 4.0
    score_threshold: Optional[float] = None  # explicit override wins
    # "none": score = sequence NLL. "position": score = max over positions of
    # (NLL - mu_pos)/sigma_pos with mu/sigma calibrated on training traffic —
    # noisy fields (pids, timestamps) self-suppress, low-entropy fields flag
    # unseen values sharply (models/logbert.py positional_z_max)
    score_norm: str = "none"
    # run the train→detect boundary fit in a background thread so the engine
    # loop keeps draining its input during training (batched path only)
    async_fit: bool = True
    max_batch: int = 1024
    # how many scored batches may be in flight before results are forced
    # back to the host; hides device→host readback latency behind the next
    # batch's CPU featurization (jax dispatch is async)
    pipeline_depth: int = 8
    # -- adaptive continuous batching (the coalescer) --------------------
    # > 0 enables deadline-aware micro-batch coalescing on the fitted
    # dispatch path: rows accumulate ACROSS process_batch/process_frames
    # calls toward the best-fitting warm compile bucket instead of
    # dispatching whatever one engine recv delivered, releasing when the
    # largest warm bucket fills to batch_target_occupancy ("full"), when
    # the oldest held row's wait approaches this budget ("deadline"), or
    # at engine idle/teardown ("flush"). The oldest-row wait is bounded by
    # batch_deadline_ms + one engine drain tick (the detector exports
    # drain_poll_ms = deadline/4 as the engine's short-poll hint). 0 = off:
    # every call dispatches what it got — the legacy behavior.
    batch_deadline_ms: float = 0.0
    # early-release threshold: dispatch as soon as the held rows fill this
    # fraction of the LARGEST active warm bucket — waiting longer cannot
    # raise occupancy (the next rows start a new batch), only latency
    batch_target_occupancy: float = 0.9
    # bucket retirement (coalescing only): every interval, active warm
    # device buckets that saw fewer than bucket_retire_min_dispatches
    # dispatches in the window are retired — their rows pad up to the next
    # warm bucket — shrinking the live compile set the XLA ledger tracks
    # (fewer shapes to keep warm across refits/param swaps). A retired
    # bucket that keeps winning best-fit anyway is resurrected via an
    # EXPECTED pre-warm compile before its first dispatch use, so
    # retirement can never page as an unexpected recompile. 0 = never
    # retire. The largest warm bucket is the pad-up backstop and is never
    # retired.
    bucket_retire_interval_s: float = 0.0
    bucket_retire_min_dispatches: int = 2
    # fused native featurization: serialized ParserSchema -> token matrix in
    # one GIL-free C call (wire-format walk + tokenize + crc32 hash), rows
    # sharded over a small pthread pool. On by default whenever the native
    # library loads; rows the kernel cannot featurize with byte-exact parity
    # (invalid UTF-8, >64 header entries, ASCII-lowering unicode) fall back
    # to the Python tokenizer per row. featurize_native_rows_total /
    # featurize_fallback_rows_total count the split. Off = always Python.
    native_featurize: bool = True
    # featurization pool width: 0 = auto (min(4, cores)); the pool is
    # process-wide (one pool in the C layer), so the widest configured
    # detector wins. See docs/configuration.md for sizing guidance.
    featurize_threads: int = 0
    # batches at or below this size score on a CPU-jitted twin of the model
    # (host-resident params) instead of the accelerator, skipping the two
    # host↔device transfers a lone message would pay. 0 disables the host
    # path; GET /admin/xla reports whether the twin is live and
    # detector_bucket_selected_total{path} which path scored what.
    host_score_max_batch: int = 128
    device: Optional[str] = None      # e.g. "tpu:0"; default = first device
    # multi-chip scale-out (BASELINE config #5): a mesh shape like
    # {"data": 8} shards batches over all chips (DP) and params per the
    # Megatron rules when "model" > 1 (TP) — parallel.ShardedScorer, the
    # device executor's mesh placement; XLA inserts the ICI collectives.
    # None = single device.
    mesh_shape: Optional[Dict[str, int]] = None
    # model compute dtype: "auto" = each family's default (bfloat16 — the
    # MXU-native format); "float32" is the right choice on CPU-only hosts,
    # where XLA:CPU emulates bf16 in software
    dtype: str = "auto"
    seed: int = 0


def _bucket(n: int, max_batch: int) -> int:
    """Round a ragged batch size up to a power of two (≤ max_batch)."""
    b = 1
    while b < n:
        b *= 2
    return min(b, max_batch)


class _InflightSlot:
    """One scored (or still-scoring) batch in the in-flight queue, which
    it joins in dispatch order: ``scores`` is the device array the scoring
    call handed back (host numpy from the host twin).

    Telemetry fields (engine/device_obs.py batch spans): ``t_enqueue`` is
    dispatch-call time (for a coalesced release, the OLDEST held row's
    arrival — so queue-wait telemetry includes the coalescer hold),
    ``t_release`` the dispatch call itself, ``t_start`` when the scoring
    call actually began, ``t_issued`` when the upload and
    the scoring call had been issued, ``trace_id`` the flight recorder's
    last completed trace at dispatch — the link from a device batch back to
    PR-1 traces — ``release`` why the coalescer let the batch go
    (full/deadline/flush; None uncoalesced), ``seq`` the batch's identifier
    (the ledger's, allotted at release: its ``dm.*`` annotations and its
    ring entry share it) and ``idle_start`` whether the release found
    nothing unfinished on the device (its release → call issued is then
    device idle time)."""

    __slots__ = ("scores", "aux", "raws", "real", "t_enqueue", "t_release",
                 "t_start", "t_issued", "bucket", "path", "trace_id",
                 "release", "tokens", "seq", "idle_start")

    def __init__(self, raws, real: int, bucket: int = 0,
                 path: str = "device", trace_id: Optional[str] = None,
                 release: Optional[str] = None,
                 tokens: Optional[np.ndarray] = None, seq: int = 0,
                 t_enqueue: Optional[float] = None,
                 t_release: Optional[float] = None):
        self.scores = None
        # what a score_aux scorer's call returned beside the scores (the
        # sparse-expert scorer's routing counts): read back with them
        self.aux = None
        self.raws = raws
        self.real = real
        # the REAL (unpadded) token rows, retained only while a rollout
        # sampler is attached: the drain path offers rows PAIRED with
        # their scores (dmdrift needs the live score distribution against
        # the rows that produced it). Memory bound: pipeline_depth slots x
        # bucket x seq_len x 4 bytes, None on untapped detectors.
        self.tokens = tokens
        now = time.monotonic()
        self.t_enqueue = now if t_enqueue is None else t_enqueue
        self.t_release = now if t_release is None else t_release
        self.t_start: Optional[float] = None
        self.t_issued: Optional[float] = None
        self.bucket = bucket
        self.path = path
        self.trace_id = trace_id
        self.release = release
        self.seq = seq
        self.idle_start = False

    def span_kv(self) -> Dict[str, Any]:
        """What every ``dm.*`` span of this batch carries."""
        return {"batch": self.seq, "bucket": self.bucket, "rows": self.real,
                "release": self.release or "none"}


class _ChainRaws:
    """Lazy concatenation of per-segment raw-message sequences (lists or
    native ``SpanRaws``): a coalesced release merges rows from several
    ``process_batch``/``process_frames`` calls into one dispatch without
    materializing a bytes object per row — only the ~1% anomalous rows are
    sliced out at alert-construction time (`_drain_one`)."""

    __slots__ = ("_segs", "_len")

    def __init__(self, segs):
        self._segs = segs
        self._len = sum(len(s) for s in segs)

    def __len__(self) -> int:
        return self._len

    def __getitem__(self, i):
        if isinstance(i, slice):
            # the dispatch/chunking path slices (contiguous, step 1): keep
            # the result lazy too
            start, stop, step = i.indices(self._len)
            if step != 1:
                return [self[j] for j in range(start, stop, step)]
            out, pos = [], 0
            for seg in self._segs:
                n = len(seg)
                lo, hi = max(start - pos, 0), min(stop - pos, n)
                if lo < hi:
                    out.append(seg[lo:hi])
                pos += n
                if pos >= stop:
                    break
            return _ChainRaws(out)
        if i < 0:
            i += self._len
        for seg in self._segs:
            if i < len(seg):
                return seg[i]
            i -= len(seg)
        raise IndexError("row index out of range")


class _BatchCoalescer:
    """Deadline-aware row accumulator between the engine and the device.

    Pure host-side bookkeeping, single-owner (only the engine thread
    touches it, like the rest of the dispatch path — no lock). Rows arrive
    as (tokens, raws) segments stamped with their arrival time and the
    ingress frame's tenant; ``take`` pops ``n`` rows, preserving each
    remainder segment's original arrival stamp (the deadline is per-ROW
    age, not per-call). With one tenant (the anonymous ``None`` default)
    release order is plain FIFO — byte-identical to the pre-tenant
    behavior. With several, releases are DEFICIT ROUND-ROBIN across the
    per-tenant queues (equal quanta), so a tenant holding thousands of
    rows cannot monopolize a device batch: every active tenant lands
    ~n/T rows per release while order stays FIFO within each tenant.
    The release POLICY — target occupancy, warm-bucket choice,
    retirement — lives in the detector, which owns the warm set and the
    XLA ledger."""

    __slots__ = ("deadline_s", "target_occupancy", "releases", "rows_in",
                 "max_wait_s", "wait_sum_s", "wait_n", "retired_total",
                 "row_hold_s", "rows_out", "_q", "_rr", "_deficit", "_total")

    def __init__(self, deadline_s: float, target_occupancy: float) -> None:
        from collections import deque

        self.deadline_s = deadline_s
        self.target_occupancy = target_occupancy
        self.releases = {"full": 0, "deadline": 0, "flush": 0}
        self.rows_in = 0
        self.max_wait_s = 0.0
        self.wait_sum_s = 0.0
        self.wait_n = 0
        self.retired_total = 0
        # row-weighted hold: sum over released rows of (release - the row's
        # arrival), and the rows it covers — their quotient is the MEAN
        # row's hold, where wait_sum_s / wait_n is the oldest row's
        self.row_hold_s = 0.0
        self.rows_out = 0
        # tenant -> deque of (t_arrival, tokens [k, S], raws); queues are
        # pruned when emptied so the table tracks ACTIVE tenants only
        self._q: Any = {}
        self._rr: Any = deque()      # round-robin rotation over _q keys
        self._deficit: Any = {}      # tenant -> carried DRR deficit (rows)
        self._total = 0

    def __len__(self) -> int:
        return self._total

    def add(self, tokens: np.ndarray, raws, now: float,
            tenant: Optional[str] = None) -> None:
        if not len(tokens):
            return
        q = self._q.get(tenant)
        if q is None:
            from collections import deque

            q = self._q[tenant] = deque()
            self._rr.append(tenant)
        q.append((now, tokens, raws))
        self._total += len(tokens)
        self.rows_in += len(tokens)

    def oldest_arrival(self) -> Optional[float]:
        """Arrival stamp of the oldest held row; None when nothing is held."""
        heads = [q[0][0] for q in self._q.values() if q]
        return min(heads) if heads else None

    def oldest_age(self, now: float) -> float:
        oldest = self.oldest_arrival()
        return 0.0 if oldest is None else max(0.0, now - oldest)

    def due_at(self) -> Optional[float]:
        """When :meth:`due` turns true for the rows held now."""
        oldest = self.oldest_arrival()
        return None if oldest is None else oldest + self.deadline_s * 0.75

    def due(self, now: float) -> bool:
        """True once the oldest row's wait APPROACHES the deadline: release
        one drain tick (deadline/4, the exported engine poll hint) early,
        so the wait lands at ~the budget instead of one tick past it."""
        if not self._total:
            return False
        return self.oldest_age(now) >= self.deadline_s * 0.75

    def held_by_tenant(self) -> Dict[str, int]:
        """Held-row depth per tenant (admin/bench visibility; the anonymous
        tenant reports as ``"default"``)."""
        return {(t if t is not None else "default"):
                sum(len(seg[1]) for seg in q)
                for t, q in self._q.items()}

    def take(self, n: int, now: Optional[float] = None):
        """Pop ``n`` rows → (tokens [n, S], raws, t_oldest). With ``now``
        (the release time) the popped rows' holds — rows × (now − their
        segment's arrival stamp) — are added to ``row_hold_s``/``rows_out``.

        The round starts at the tenant holding the globally-oldest row, so
        a deadline release always carries the row that tripped it; each
        visited tenant then serves up to quantum (+ carried deficit) rows
        before the rotation moves on. An emptied queue forfeits its
        carried deficit (classic DRR) and leaves the rotation."""
        quantum = max(1, n // max(1, len(self._rr)))
        oldest_key = min(self._q, key=lambda k: self._q[k][0][0])
        while self._rr[0] != oldest_key:
            self._rr.rotate(-1)
        parts, raw_segs, got = [], [], 0
        t_oldest = None
        while got < n:
            key = self._rr[0]
            q = self._q[key]
            deficit = self._deficit.get(key, 0) + quantum
            take_rows = min(deficit, n - got)
            served = 0
            while q and served < take_rows:
                t, tok, raws = q.popleft()
                if t_oldest is None or t < t_oldest:
                    t_oldest = t
                want = take_rows - served
                if now is not None:
                    self.row_hold_s += min(want, len(tok)) * max(0.0, now - t)
                if want < len(tok):
                    parts.append(tok[:want])
                    raw_segs.append(raws[:want])
                    # the remainder keeps ITS arrival stamp — splitting a
                    # call's rows across releases must not reset their
                    # deadline clock
                    q.appendleft((t, tok[want:], raws[want:]))
                    served += want
                else:
                    parts.append(tok)
                    raw_segs.append(raws)
                    served += len(tok)
            got += served
            if q:
                self._deficit[key] = deficit - served
                self._rr.rotate(-1)
            else:
                self._rr.popleft()
                self._deficit.pop(key, None)
                del self._q[key]
        self._total -= n
        if now is not None:
            self.rows_out += n
        tokens = parts[0] if len(parts) == 1 else np.concatenate(parts)
        raws = raw_segs[0] if len(raw_segs) == 1 else _ChainRaws(raw_segs)
        return tokens, raws, t_oldest

    def note_release(self, reason: str, wait_s: float) -> None:
        self.releases[reason] = self.releases.get(reason, 0) + 1
        self.max_wait_s = max(self.max_wait_s, wait_s)
        self.wait_sum_s += max(0.0, wait_s)
        self.wait_n += 1


class JaxScorerDetector(CoreDetector):
    config_class = JaxScorerDetectorConfig
    description = "JaxScorerDetector flags log lines the TPU scorer finds improbable."

    def __init__(self, name: Optional[str] = None, config: Any = None,
                 buffer_mode: BufferMode = BufferMode.MICRO_BATCH) -> None:
        super().__init__(name=name or "JaxScorerDetector", buffer_mode=buffer_mode,
                         config=config)
        self.config: JaxScorerDetectorConfig
        from ...models.tokenizer import HashTokenizer

        self._validate_static_config()
        self._tokenizer = HashTokenizer(
            vocab_size=self.config.vocab_size, seq_len=self.config.seq_len
        )
        self._scorer = None
        # device_executor.DeviceExecutor once the scorer is built: where the
        # parameters live, how rows reach them and which compiled program
        # runs (one device or a mesh: this class never asks which)
        self._exec = None
        self._rng = None
        self._threshold: Optional[float] = self.config.score_threshold
        # (mean, std) of the calibration scores, kept so a runtime
        # threshold_sigma reconfigure can recompute the threshold refit-free
        self._calib_stats: Optional[tuple] = None
        self._train_buffer: List[np.ndarray] = []
        self._fitted = False
        self._norm_mu: Optional[np.ndarray] = None     # [S] fp32, "position" norm
        self._norm_sigma: Optional[np.ndarray] = None  # [S] fp32
        import threading

        self._fit_thread = None                        # async boundary fit
        # guards the join-and-dispatch handoff in _finish_fit: the engine
        # loop and external callers (detect/save_checkpoint/flush_final) may
        # race it, and an unguarded handoff can double-dispatch the backlog
        self._fit_lock = threading.Lock()
        self._pending: List = []                       # (tokens_row, raw) backlog
        self._host_params = None                       # CPU twin for small batches
        self._host_score = None
        self._host_normscore = None
        self._cpu_device = None
        # why the host twin is (not) scoring: "off" | "unsupported" |
        # "pending" (built, params mirror at fit) | "ready" | "failed: ..."
        self._host_twin_state = "off"
        self._host_warm: set = set()                   # compiled host buckets
        self._host_warm_thread = None
        self._ready_supported: Optional[bool] = None   # jax.Array.is_ready seen?
        self._feat_counters = None  # (native_rows, fallback_rows) label pair
        # device observability (engine/device_obs.py): the process-wide XLA
        # compile ledger (set in _ensure_scorer) plus cached label children
        # for the per-dispatch batch telemetry — occupancy, bucket
        # selection, queue-wait vs device-time (one .labels() hash per
        # (path) / (bucket, path), never per batch)
        self._ledger = None
        self._batch_obs: Dict[str, tuple] = {}
        self._bucket_children: Dict[tuple, Any] = {}
        # adaptive continuous batching (batch_deadline_ms > 0): the
        # coalescer holds rows across calls; the warm/retired sets drive
        # its bucket choice (engine-thread-owned, like _inflight). Every
        # bucket enters _device_warm through an EXPECTED compile that the
        # executor keeps (setup_io warm-up or _warm_device_bucket), so
        # coalesced dispatch can never page as an unexpected recompile.
        self._coalescer: Optional[_BatchCoalescer] = None
        # tenant of the CURRENT ingress frame (engine note_tenant seam):
        # coalescer.add segments held rows by it so releases stay
        # weighted-fair across tenants (dmshed). Engine-thread-owned.
        self._ingress_tenant: Optional[str] = None
        self._device_warm: set = set()        # pre-warmed device buckets
        self._retired_buckets: set = set()    # retired from the active set
        self._retired_hits: Dict[int, int] = {}   # best-fit pressure window
        self._bucket_usage: Dict[int, int] = {}   # dispatches since sweep
        self._retire_last_sweep: Optional[float] = None
        self._coalesce_gauge = None
        self._release_children: Dict[str, Any] = {}
        # boundary counters (bound at scorer set-up, _bind_boundary_counters,
        # so each series reads 0 from boot): rows released by reason + the
        # row-seconds they were held, scored rows/batches per device, and
        # the host-known device idle time by cause (engine-thread-owned,
        # like _inflight; _dev_unfinished counts its device-path slots)
        self._rows_released_children: Dict[str, Any] = {}
        self._row_hold_child = None
        self._device_children: Optional[tuple] = None
        self._moe_children: Optional[tuple] = None
        self._idle_clock = None
        self._dev_unfinished = 0
        self._occ_stats = (0, 0.0)            # (dispatches, occupancy sum)
        # the native featurize module, loaded once: its absence is logged
        # here, at boot, and reported by GET /admin/xla — never discovered
        # from a slow tokenizer
        self._kern = None
        self._kern_error: Optional[str] = None
        try:
            from ...utils import matchkern

            self._kern = matchkern
        except ImportError as exc:
            self._kern_error = str(exc)
            import logging

            logging.getLogger(__name__).warning(
                "native featurize library unavailable (%s): every row takes "
                "the per-row Python tokenizer", exc)
        if self.config.featurize_threads > 0:
            kern = self._matchkern()
            if kern is not None:
                kern.set_featurize_threads(self.config.featurize_threads)
        # in-flight scored batches (_InflightSlot), oldest first
        from collections import deque

        self._inflight = deque()
        # self-diagnosis (engine/health.py): the hosting Service sets
        # health_monitor; drained_total is the progress counter behind the
        # device_inflight_stuck watchdog check
        self.health_monitor = None
        self._drained_total = 0
        # dmroll (rollout/): the Service-owned RolloutManager attaches a
        # traffic sampler here; the dispatch path offers every dispatched
        # token batch to it, and install_candidate is the
        # pre-warm-then-hot-swap seam promoted candidates cut over through
        self._rollout_sampler = None
        # dmdrift (obs/capacity.py): per-batch (rows, device-seconds)
        # callback feeding the capacity model; None costs one branch
        self._capacity_tap = None
        self._model_version = 0
        # weight-only int8 serving (dtype: int8w — models/quant.py): the
        # executor serves the quantized tree only after the
        # differential-parity gate passes (zero alert-decision flips on the
        # parity corpus), else the float tree keeps serving
        self._int8w = False
        self._parity_corpus = None
        self._int8_report: Optional[Dict[str, Any]] = None

    def _validate_static_config(self) -> None:
        """Reject bad enum-ish config at CONSTRUCTION (no jax import needed):
        ops/attention's router silently falls through to einsum for unknown
        strings, so a typo ('rign') would quietly run the wrong
        implementation while the operator believes sequence-parallel
        attention is active. Re-checked in _ensure_scorer for reconfigure."""
        cfg = self.config
        if cfg.score_norm not in ("none", "position"):
            raise LibraryError(
                f"unknown score_norm {cfg.score_norm!r}; expected 'none' or 'position'")
        if cfg.attn_impl not in ("auto", "einsum", "flash", "short",
                                 "blockwise", "ring"):
            raise LibraryError(
                f"unknown attn_impl {cfg.attn_impl!r}; expected 'auto', "
                "'einsum', 'flash', 'short', 'blockwise', or 'ring'")
        family = FAMILIES.get(cfg.model)
        if family is None:
            raise LibraryError(f"unknown scorer model {cfg.model!r}; "
                               f"expected one of {sorted(FAMILIES)}")
        if cfg.dtype not in ("auto", "bfloat16", "float32", "float16",
                             "int8w"):
            raise LibraryError(
                f"unknown dtype {cfg.dtype!r}; expected 'auto', 'bfloat16', "
                "'float32', 'float16', or 'int8w'")
        if cfg.head_impl not in ("auto", "einsum", "pallas"):
            raise LibraryError(
                f"unknown head_impl {cfg.head_impl!r}; expected 'auto', "
                "'einsum', or 'pallas'")
        if cfg.batch_deadline_ms < 0:
            raise LibraryError(
                f"batch_deadline_ms must be >= 0 (got {cfg.batch_deadline_ms})")
        if not 0.0 < cfg.batch_target_occupancy <= 1.0:
            raise LibraryError(
                "batch_target_occupancy must be in (0, 1] "
                f"(got {cfg.batch_target_occupancy})")
        if cfg.bucket_retire_interval_s < 0:
            raise LibraryError(
                "bucket_retire_interval_s must be >= 0 "
                f"(got {cfg.bucket_retire_interval_s})")
        refusal = family.refuses(cfg)
        if refusal:
            raise LibraryError(refusal)

    # -- lifecycle ------------------------------------------------------
    def setup_io(self) -> None:
        """Build the model, init params, pin them on the device, and
        AOT-compile (``lower(...).compile()``) the warm bucket set
        (reference hook role: core.py:209-211 'load models here').

        dmwarm (PR 17): the executor KEEPS the compiled executables and
        dispatches them directly — jax's AOT compile does not seed the jit's
        own cache, so warming-by-discarding would recompile on first
        dispatch. Warm-up wall time is split into the three phases
        ``scorer_warmup_seconds{phase=device_put|aot|cache_load}``, and the
        ``scorer_warmup_pending`` deep-health check registered here keeps
        the replica supervisor from promoting this process to ACTIVE while
        the warm set is still compiling."""
        import time as _time

        t0 = _time.monotonic()
        self._ensure_scorer()

        # boot→ACTIVE gate: register BEFORE the first compile so a deep
        # health probe racing the warm-up sees UNHEALTHY (the router treats
        # "degraded" as dispatchable — only unhealthy refuses traffic)
        monitor = getattr(self._ledger, "monitor", None)
        if monitor is not None:
            try:
                monitor.remove_check(device_obs.WarmupPendingCheck.name)
                monitor.add_check(device_obs.WarmupPendingCheck(
                    self._ledger, monitor))
            # dmlint: ignore[DM-R001] a bare-bones test monitor without the
            except Exception:  # noqa: BLE001 — check API must not fail boot
                pass
        # device_put phase: model build + param init + device placement all
        # happened inside _ensure_scorer
        t_warm = _time.monotonic()
        self._ledger.record_warmup_phase("device_put", t_warm - t0)
        cache_load0 = self._ledger.cache_load_seconds()

        # warm only the kernels this mode's detect path will run — every
        # extra warmed kernel costs a full XLA compile at startup (the
        # shared persistent compilation cache — compile_cache_dir —
        # amortizes restarts, not first boot)
        position = self.config.score_norm == "position" and self._norm_mu is None
        # the serving kernel: score when raw NLL serves, normscore (over
        # dummy statistics) when position normalization will
        kind, extra = "score", ()
        if position:
            kind, extra = "normscore", (
                np.zeros(self.config.seq_len, np.float32),
                np.ones(self.config.seq_len, np.float32))
        # small buckets are only ever scored on-device when the host path is
        # off; with it on, warming them would waste two accelerator compiles
        # (the host twin warms its own buckets at fit time)
        host_path = self._cpu_device is not None
        small = () if host_path else (1, 8)
        # compiles in here are the expected warm-up set; after
        # mark_warmup_complete a dispatch-path compile of a bucket in
        # _device_warm is an unexpected recompile (engine/device_obs.py —
        # the RecompileStorm signal: the cache for a shape we believed
        # compiled was invalidated). First touch of a bucket OUTSIDE the
        # warm set is planned growth and pre-warms expected instead
        # (_warm_device_bucket) on both the adaptive and legacy paths.
        with self._ledger.context(where="warmup", backend=self._exec.backend,
                                  expected=True):
            for b in (*small, self.config.train_batch_size, self.config.max_batch):
                bucket = _bucket(b, self.config.max_batch)
                self._device_warm.add(bucket)  # the coalescer's seed warm set
                with self._ledger.context(bucket=bucket):
                    self._exec.warm(kind, bucket, *extra)
            if position:
                # fit's calibration pass runs token_nlls at the train bucket
                bucket = _bucket(self.config.train_batch_size,
                                 self.config.max_batch)
                with self._ledger.context(bucket=bucket):
                    self._exec.warm("token_nlls", bucket)
        self._ledger.mark_warmup_complete()
        # the cache_load share of the warm-up is the persistent-cache
        # deserialization time jax reported; the rest of the wall is real
        # lowering + backend compile
        cache_load = max(0.0, self._ledger.cache_load_seconds() - cache_load0)
        wall = _time.monotonic() - t_warm
        self._ledger.record_warmup_phase("cache_load", cache_load)
        self._ledger.record_warmup_phase("aot", max(0.0, wall - cache_load))

    def warm_set_spec(self) -> Dict[str, Any]:
        """The AOT warm bucket set as a persistable spec. The rollout
        store writes it into the checkpoint manifest, so a promote on a
        RESTARTED process pre-warms what the original boot warmed — not
        whatever buckets the current process happens to have touched."""
        return {"buckets": sorted(int(b) for b in self._device_warm),
                "seq_len": int(self.config.seq_len),
                "dtype": str(self.config.dtype),
                "score_norm": str(self.config.score_norm)}

    def _ensure_scorer(self) -> None:
        if self._scorer is not None:
            return
        from ...utils.backend import apply_platform_pin

        apply_platform_pin()
        import jax

        from ...utils.profiling import enable_compilation_cache

        enable_compilation_cache()
        # XLA compile ledger: the jax.monitoring listener installs once per
        # process; this detector's jit call sites wrap themselves in ledger
        # contexts so every compile attributes to a (bucket, trigger) pair
        self._ledger = device_obs.get_ledger()
        device_obs.install_listener()
        # GET /admin/xla reports the live warm/retired bucket sets next to
        # the compile history they explain (bucket retirement shrinks the
        # compile set the ledger tracks — make that observable)
        self._ledger.set_bucket_state_provider(self._bucket_state)
        self._ledger.set_device_info_provider(self.device_info)
        cfg = self.config
        self._validate_static_config()
        import jax.numpy as jnp

        from .device_executor import DeviceExecutor

        self._int8w = cfg.dtype == "int8w"

        def build_scorer(platform: str):
            model_kw = {"platform": platform}
            if self._int8w:
                # weight-only int8 (models/quant.py): weights live as int8 +
                # per-channel scales and dequantize INSIDE the jitted impls;
                # activations use the platform's fast float — bf16 on
                # accelerators, f32 on CPU-sim (XLA:CPU runs bf16 GEMMs at
                # f32 speed, so the int8 win there is pure weight streaming)
                model_kw["dtype"] = (jnp.float32 if platform == "cpu"
                                     else jnp.bfloat16)
            elif cfg.dtype and cfg.dtype != "auto":
                model_kw["dtype"] = jnp.dtype(cfg.dtype).type
            try:
                return FAMILIES[cfg.model].build(cfg, model_kw)
            except ValueError as exc:   # a shape the family refuses, by name
                raise LibraryError(
                    f"scorer model {cfg.model!r}: {exc}") from exc

        self._rng = jax.random.PRNGKey(cfg.seed)
        # construction-time, before any other thread can exist
        self._exec = DeviceExecutor.open(build_scorer, self._rng,
                                         mesh_shape=cfg.mesh_shape,
                                         device=cfg.device)
        self._scorer = self._exec.scorer
        device_obs.export_hbm_gauges(self._obs_labels())
        self._bind_boundary_counters()
        if cfg.host_score_max_batch > 0:
            if self._exec.forkable:
                self._build_host_twin()
            else:
                self._host_twin_state = "unsupported"  # mesh owns the params

    def _build_host_twin(self) -> None:
        """Jit the CPU twin of the scorer (params mirror at fit). Why it is
        or is not live lands in ``_host_twin_state`` and the log — the twin
        is never silently absent."""
        import dataclasses
        import logging

        import jax

        cfg = self.config
        log = logging.getLogger(__name__)
        if not self._host_scoring_possible():
            self._host_twin_state = "unsupported"
            log.info("host twin unsupported for model=%r attn_impl=%r: every "
                     "batch scores on %s", cfg.model, cfg.attn_impl,
                     self._exec.label)
            return
        try:
            self._cpu_device = jax.devices("cpu")[0]
        except RuntimeError as exc:
            self._host_twin_state = f"failed: no CPU backend ({exc})"
            log.warning("host twin %s: every batch scores on %s",
                        self._host_twin_state, self._exec.label)
            return
        # the twin shares PARAMS with the device scorer but routes its
        # kernels for the CPU it runs on, and never through the pallas head:
        # in interpret mode per lone message that would be exactly the
        # latency path the twin exists to make fast
        host_scorer = self._scorer
        if self._exec.platform != "cpu" or cfg.head_impl == "pallas":
            host_scorer = type(self._scorer)(dataclasses.replace(
                self._scorer.config, platform="cpu", head_impl="einsum"))
        # the twin must share the candidate subset too: a restored
        # checkpoint may install persisted ids on self._scorer that differ
        # from this numpy's regenerated stream
        self._host_twin_scorer = host_scorer
        # placed by their arguments: _score_host commits params and tokens
        # to the CPU device, so these run there
        self._host_score = jax.jit(host_scorer._score_impl)
        self._host_normscore = jax.jit(host_scorer._normscore_impl)
        self._host_twin_state = "pending"

    def device_info(self) -> Dict[str, Any]:
        """Where this scorer runs and which helper paths are live — the
        ``device`` block of ``GET /admin/xla``, so a jax-free parent (the
        chip smoke) can assert it."""
        import jax

        from ...utils.backend import requested_platform
        from ...utils.profiling import persistent_cache_dir

        devices = self._exec.devices if self._exec is not None else []
        cfg = self.config
        return {
            "scorer": {
                "model": cfg.model, "vocab_size": cfg.vocab_size,
                "dim": cfg.dim, "depth": cfg.depth, "heads": cfg.heads,
                "seq_len": cfg.seq_len, "max_batch": cfg.max_batch,
                "arch": cfg.arch,
                "head": "candidate" if cfg.score_vocab else "exact",
                "dtype": (str(np.dtype(self._scorer.config.dtype))
                          if self._scorer is not None else cfg.dtype),
                "trained_rows": self._trained, "fitted": self._fitted,
            },
            "backend_requested": requested_platform(),
            "platform": devices[0].platform if devices else None,
            "device_kind": devices[0].device_kind if devices else None,
            "device_count": len(jax.devices()),
            "scorer_devices": [str(d) for d in devices],
            "mesh": (self._exec.mesh_shape
                     if self._exec is not None else None),
            "host_twin": {"state": self._host_twin_state,
                          "max_batch": cfg.host_score_max_batch,
                          "warm_buckets": sorted(self._host_warm)},
            "native_featurize": {"loaded": self._kern is not None,
                                 "enabled": cfg.native_featurize,
                                 "error": self._kern_error},
            "compile_cache_dir": persistent_cache_dir(),
        }

    def _host_scoring_possible(self) -> bool:
        """Whether the model can run on the host CPU twin at all
        (scorer_families.py has each family's reason); where it cannot,
        small batches ride the device path instead."""
        return FAMILIES[self.config.model].host_twin(self.config)

    def _sync_host_params(self) -> None:
        """Mirror the current params onto the host CPU backend (one transfer,
        after fit / checkpoint load) so small batches can score locally."""
        if self._cpu_device is None:
            return
        import jax
        import logging
        import threading

        log = logging.getLogger(__name__)
        # warm the lone-message bucket inline (it IS the sparse-traffic
        # latency path), then the remaining power-of-two buckets on a
        # background thread — until a bucket is warm its batches ride the
        # device path, so the engine loop never blocks on a host compile
        cap = self.config.host_score_max_batch
        try:
            # dmlint: ignore[DM-L001] ref-atomic mirror write
            self._host_params = jax.device_put(self._exec.params,
                                               self._cpu_device)
            with self._ledger.context(bucket=1, backend="cpu",
                                      where="host_warm", expected=True):
                jax.block_until_ready(self._score_host(
                    np.zeros((1, self.config.seq_len), np.int32)))
            self._host_warm.add(1)
        except Exception as exc:  # noqa: BLE001 — the device path still serves
            self._host_params = None
            self._host_twin_state = f"failed: {type(exc).__name__}: {exc}"
            log.exception("host twin failed to mirror/compile; every batch "
                          "scores on %s", self._exec.label)
            return
        self._host_twin_state = "ready"

        def _warm_rest():
            sizes, b = [], 2
            while b <= cap:
                sizes.append(b)
                b *= 2
            if cap not in sizes:  # non-power-of-two cap is its own bucket
                sizes.append(cap)
            for size in sizes:
                try:
                    # own thread → own context stack; these compiles are the
                    # planned host-bucket warm set, never recompile storms
                    with self._ledger.context(bucket=size, backend="cpu",
                                              where="host_warm",
                                              expected=True):
                        jax.block_until_ready(self._score_host(
                            np.zeros((size, self.config.seq_len), np.int32)))
                    self._host_warm.add(size)
                except Exception:  # noqa: BLE001 — unwarmed buckets ride the device path
                    log.exception("host twin bucket %d failed to compile; "
                                  "larger host buckets stay cold", size)
                    return

        # non-daemon on purpose: a daemon thread killed mid-XLA-compile at
        # interpreter exit aborts the process from C++ ("FATAL: exception
        # not rethrown"); the thread is short-lived (a handful of small CPU
        # compiles), so joining at exit is cheap and clean
        self._host_warm_thread = threading.Thread(
            target=_warm_rest, daemon=False, name="HostBucketWarm")
        self._host_warm_thread.start()

    def _serving(self) -> tuple:
        """The scoring kind that serves now, with its arguments after the
        batch: per-position normalization once calibrated (fit)."""
        if self._norm_mu is not None:
            return "normscore", (self._norm_mu, self._norm_sigma)
        return "score", ()

    def _score_dev(self, tokens: np.ndarray,
                   batch_kv: Optional[Dict[str, Any]] = None,
                   slot: Optional["_InflightSlot"] = None, params=None):
        """Dispatch scoring for [n, S] tokens through the device executor
        (``DeviceExecutor.run`` has the routing and the spans a served
        batch's ``batch_kv`` leaves); returns the device array without
        forcing readback. ``params`` scores a candidate's placed tree in
        place of the live one, under the live position-norm calibration, so
        live and candidate scores stay in one unit. Where the scorer's call
        returns counts beside the scores (``score_aux``) they go to
        ``slot.aux`` — read back with the scores at the drain — and nowhere
        when no slot is given."""
        kind, extra = self._serving()
        scores, aux = self._exec.run(kind, tokens, *extra, params=params,
                                     batch_kv=batch_kv)
        if slot is not None:
            slot.aux = aux
        return scores

    # -- weight-only int8 serving (dtype: int8w — models/quant.py) -------
    def _parity_scores(self, tokens: np.ndarray) -> np.ndarray:
        """Served-path scores for the parity corpus, chunked on the (warm)
        train bucket so the differential run never grows the compile set."""
        cfg = self.config
        bucket = _bucket(cfg.train_batch_size, cfg.max_batch)
        out = np.empty(len(tokens), np.float32)
        for start in range(0, len(tokens), bucket):
            chunk = tokens[start:start + bucket]
            real = len(chunk)
            if real < bucket:
                chunk = np.concatenate([chunk, np.zeros(
                    (bucket - real, tokens.shape[1]), np.int32)])
            with self._ledger.context(bucket=bucket):
                out[start:start + real] = np.asarray(
                    self._score_dev(chunk))[:real]
        return out

    def _activate_int8(self, where: str = "fit") -> Dict[str, Any]:
        """Quantize the live weights (per-channel int8 scales computed at
        INSTALL time) and cut the serving path over — gated on differential
        parity: the quantized path must flip ZERO alert decisions on the
        parity corpus vs the float path, or the float path stays live."""
        from ...models import quant

        report: Dict[str, Any] = {"activated": False, "where": where,
                                  "rows": 0, "flips": 0, "flip_ratio": 0.0}
        threshold = (float(self._threshold)
                     if self._threshold is not None else float("inf"))
        corpus = self._parity_corpus
        with self._ledger.context(where="int8_install",
                                  backend=self._exec.backend, expected=True):
            # install paths serialize: the fit thread is joined before an
            # install and the manager thread owns every promote
            qparams = quant.quantize_tree(self._exec.params)
            float_scores = None
            if corpus is not None and len(corpus):
                float_scores = self._parity_scores(
                    np.asarray(corpus, np.int32))
            # tentative install, then judge the q path on the same corpus
            self._exec.install_quantized(qparams)
            ok = True
            if float_scores is not None:
                q_scores = self._parity_scores(np.asarray(corpus, np.int32))
                flips = int(np.sum((float_scores > threshold)
                                   != (q_scores > threshold)))
                report.update(
                    rows=int(len(float_scores)), flips=flips,
                    flip_ratio=float(flips) / max(1, len(float_scores)))
                ok = flips == 0
            if not ok:
                # parity broke: the quantized tree never serves
                self._exec.clear_quantized()
            else:
                # parity held (or no corpus yet — a restored process before
                # its first fit): warm every warm bucket through the q path
                # so the dispatch path stays compile-free
                kind, extra = self._serving()
                for b in sorted(self._device_warm):
                    with self._ledger.context(bucket=b):
                        self._exec.warm(kind, b, *extra)
                report["activated"] = True
                report["gated"] = float_scores is not None
                report["bytes"] = quant.quant_stats(qparams)
        self._int8_report = report
        return report

    def _calibrate_position_norm(self, data: np.ndarray, bs: int) -> np.ndarray:
        """Masked per-position mean/std of training NLLs → mu/sigma [S].

        Returns the calibration split's z-max scores (computed host-side from
        the same NLLs — no second forward pass) for threshold calibration."""
        from ...models.tokenizer import PAD_ID

        # pad every chunk to the warmed compile bucket — a ragged tail shape
        # would force a fresh XLA compile right at the phase boundary
        bucket = _bucket(max(bs, self.config.train_batch_size),
                         self.config.max_batch)
        chunks = []
        for i in range(0, len(data), bucket):
            chunk = data[i:i + bucket]
            real = len(chunk)
            if real < bucket:
                chunk = np.concatenate(
                    [chunk, np.zeros((bucket - real,) + chunk.shape[1:], chunk.dtype)])
            chunks.append(np.asarray(
                self._exec.run("token_nlls", chunk)[0])[:real])
        nlls = np.concatenate(chunks)[: len(data)]
        mask = (data != PAD_ID).astype(np.float32)
        cnt = np.maximum(mask.sum(0), 1.0)
        mu = (nlls * mask).sum(0) / cnt
        var = ((nlls - mu) ** 2 * mask).sum(0) / cnt
        # sigma floor: a near-constant position stays sensitive to unseen
        # values without the z-score exploding on float jitter
        sigma = np.maximum(np.sqrt(var), 0.05)
        self._norm_mu = mu.astype(np.float32)
        self._norm_sigma = sigma.astype(np.float32)
        z = (nlls - mu) / sigma
        z = np.where(mask > 0, z, -np.inf)
        zmax = z.max(-1)
        # match positional_z_max: only all-PAD (-inf) rows become 0
        return np.where(np.isneginf(zmax), 0.0, zmax).astype(np.float32)

    # -- featurization (CPU side) ---------------------------------------
    def featurize(self, input_: ParserSchema) -> np.ndarray:
        return self._tokenizer.encode_parsed(
            input_.get("template") or "",
            list(input_["variables"]),
            dict(input_["logFormatVariables"]),
        )

    # -- training -------------------------------------------------------
    def train(self, input_: ParserSchema) -> None:
        """Single-message training path (engine_batch_size=1 parity mode):
        buffer the tokenized row so the phase-boundary ``fit`` has data —
        ``process_batch`` buffers directly and never calls this."""
        self._train_buffer.append(self.featurize(input_))

    def fit(self) -> Dict[str, float]:
        """Train on the buffered normal traffic, calibrate the threshold."""
        self._ensure_scorer()
        # the boundary fit legitimately compiles (train step, calibration
        # buckets) after warm-up — attributed here so it never counts as an
        # unexpected recompile
        with self._ledger.context(where="fit", backend=self._exec.backend,
                                  expected=True):
            return self._fit_impl()

    def _fit_impl(self) -> Dict[str, float]:
        import jax

        cfg = self.config
        if not self._train_buffer:
            self._fitted = True
            if self._threshold is None:
                self._threshold = float("inf")
            return {"loss": float("nan"), "threshold": self._threshold}
        data = np.stack(self._train_buffer)
        self._train_buffer = []
        if self._int8w:
            # training updates the FLOAT tree; the previous generation's
            # quantized tree must not serve (or calibrate) stale scores
            # mid-fit — _activate_int8 re-quantizes at the end
            self._exec.clear_quantized()
        bs = min(cfg.train_batch_size, len(data))
        loss = float("nan")
        rng = np.random.default_rng(cfg.seed)
        # "position" norm calibrates on a held-out split: statistics computed
        # on data the model memorized underestimate the NLL of *fresh* values
        # in high-entropy fields (pids, timestamps), which then all z-spike
        if cfg.score_norm == "position" and len(data) >= 64:
            n_cal = max(16, len(data) // 5)
            calib, train_data = data[-n_cal:], data[:-n_cal]
            bs = min(bs, len(train_data))  # keep the train loop non-empty
        else:
            calib, train_data = data, data
        steps_per_epoch = max(1, len(train_data) // bs)
        epochs = max(cfg.train_epochs,
                     -(-cfg.min_train_steps // steps_per_epoch))  # ceil division
        for _ in range(epochs):
            order = rng.permutation(len(train_data))
            for start in range(0, len(train_data) - bs + 1, bs):
                batch = train_data[order[start:start + bs]]
                self._rng, step_rng = jax.random.split(self._rng)
                # the boundary fit owns the live trees until _finish_fit
                # hands off (install_candidate joins the fit before swapping)
                loss = self._exec.train_step(step_rng, batch)
        if cfg.score_norm == "position":
            # calibrate BEFORE thresholding so the threshold is in z units;
            # the returned z-max scores reuse the same forward pass
            scores = self._calibrate_position_norm(calib, bs)
            self._calib_stats = (float(scores.mean()), float(scores.std()))
            if self._threshold is None:
                self._threshold = float(
                    scores.mean() + cfg.threshold_sigma * scores.std())
        else:
            bucket = _bucket(max(bs, cfg.train_batch_size), cfg.max_batch)
            parts = []
            for i in range(0, len(calib), bucket):
                chunk = calib[i:i + bucket]
                real = len(chunk)
                if real < bucket:  # stay on the warmed compile bucket
                    chunk = np.concatenate([chunk, np.zeros(
                        (bucket - real,) + chunk.shape[1:], chunk.dtype)])
                parts.append(np.asarray(self._score_dev(chunk))[:real])
            scores = np.concatenate(parts)[: len(calib)]
            self._calib_stats = (float(scores.mean()), float(scores.std()))
            if self._threshold is None:
                self._threshold = float(
                    scores.mean() + cfg.threshold_sigma * scores.std())
        if self._int8w:
            # the calibration split is the parity corpus: the scores the
            # threshold was calibrated on ARE the decisions int8 must keep
            self._parity_corpus = np.asarray(calib[:512], np.int32)
            self._activate_int8(where="fit")
        self._fitted = True
        self._sync_host_params()
        return {"loss": loss, "threshold": self._threshold}

    # -- scoring --------------------------------------------------------
    def score_tokens(self, tokens: np.ndarray) -> np.ndarray:
        """[N, S] → [N] fp32 scores, padded up to a compile bucket."""
        self._ensure_scorer()
        n = len(tokens)
        bucket = _bucket(n, self.config.max_batch)
        out = np.empty((n,), np.float32)
        for start in range(0, n, bucket):
            chunk = tokens[start:start + bucket]
            if len(chunk) < bucket:
                pad = np.zeros((bucket - len(chunk), tokens.shape[1]), np.int32)
                chunk = np.concatenate([chunk, pad])
            # single-message parity path: compiles attribute to "detect" and
            # stay expected — the storm detector watches the batched
            # dispatch path, not per-message scoring
            with self._ledger.context(bucket=bucket, where="detect",
                                      backend=self._exec.backend,
                                      expected=True):
                scores = np.asarray(self._score_dev(chunk))
            out[start:start + min(bucket, n - start)] = scores[: min(bucket, n - start)]
        return out

    # -- engine contract ------------------------------------------------
    def _featurize_pb_into(self, msg, out_row: np.ndarray) -> None:
        """Featurize a decoded pb2 ParserSchema into a zeroed token row.

        Hot-path twin of ``featurize`` that skips the wrapper layer (dict
        copies of map fields dominated the profile)."""
        parts = [msg.template]
        parts.extend(msg.variables)
        lfv = msg.logFormatVariables
        if lfv:
            parts.extend(f"{k}={lfv[k]}" for k in sorted(lfv))
        self._tokenizer.encode_into(" ".join(parts), out_row)

    def _matchkern(self):
        """The native featurize module, or None (knob off / not loadable —
        the load failure was logged at construction)."""
        return self._kern if self.config.native_featurize else None

    def _count_featurize_rows(self, native: int, fallback: int) -> None:
        """featurize_native_rows_total / featurize_fallback_rows_total —
        which path tokenized how many rows (label children cached: this
        runs once per micro-batch on the hot path)."""
        if not native and not fallback:
            return
        if self._feat_counters is None:
            from ...engine import metrics as m

            labels = dict(component_type=self.config.method_type,
                          component_id=self.name)
            self._feat_counters = (
                m.FEATURIZE_NATIVE_ROWS().labels(**labels),
                m.FEATURIZE_FALLBACK_ROWS().labels(**labels))
        if native:
            self._feat_counters[0].inc(native)
        if fallback:
            self._feat_counters[1].inc(fallback)

    def _featurize_raw_batch(self, batch: List[bytes]):
        """Serialized ParserSchema bytes → ([N, S] int32 tokens, [N] ok bool).

        Native kernel when built and ``native_featurize`` is on (protobuf
        wire parse + tokenize + hash in C, GIL-free and row-parallel);
        Python fallback otherwise — both produce identical rows (pinned by
        tests/test_native_kernels.py)."""
        kern = self._matchkern()
        if kern is not None:
            tokens, ok = kern.featurize_batch(
                batch, self.config.seq_len, self.config.vocab_size
            )
            if ok.all():
                self._count_featurize_rows(len(batch), 0)
            else:
                # the native kernel refuses rows it cannot featurize with
                # exact parity (e.g. >64 header-map entries); retry those in
                # Python so only genuinely corrupt messages stay failed
                flagged = np.flatnonzero(~ok)
                self._featurize_python_rows(batch, tokens, ok, flagged)
                self._count_featurize_rows(len(batch) - len(flagged),
                                           len(flagged))
            return tokens, ok
        tokens = np.zeros((len(batch), self.config.seq_len), np.int32)
        ok = np.zeros(len(batch), dtype=bool)
        self._featurize_python_rows(batch, tokens, ok, range(len(batch)))
        self._count_featurize_rows(0, len(batch))
        return tokens, ok

    def _featurize_python_rows(self, batch: List[bytes], tokens: np.ndarray,
                               ok: np.ndarray, indices) -> None:
        from ...schemas import schemas_pb2 as _pb

        for i in indices:
            msg = _pb.ParserSchema()
            try:
                msg.ParseFromString(batch[i])
            except Exception:
                continue
            tokens[i] = 0  # the native pass may have partially filled the row
            self._featurize_pb_into(msg, tokens[i])
            ok[i] = True

    def note_tenant(self, tenant: Optional[str]) -> None:
        """Engine seam (dmshed): the tenant the CURRENT ingress frame was
        attributed to — rows added to the coalescer until the next call are
        segmented under it, which is what makes releases weighted-fair.
        ``None`` clears the attribution (anonymous frame). Called on the
        engine thread, per frame, before the frame's messages arrive."""
        self._ingress_tenant = tenant

    def process_batch(self, batch: List[bytes]) -> List[Optional[bytes]]:
        """Batched hot path: one featurize kernel + one jit call per
        micro-batch, preserving the per-message in-order None-filtering
        contract. Raw bytes are decoded into schema objects only for the
        (rare) anomalous messages, at alert-construction time.

        The train→detect boundary fit runs in a background thread
        (``async_fit``): the engine loop keeps draining its input — messages
        that arrive mid-fit buffer in-process (ordered) instead of piling
        into socket buffers and dropping — and the pending backlog dispatches
        on the first call after the fit completes."""
        # dmlint: ignore[DM-L001] racy pre-check; _finish_fit re-checks under lock
        fit_thread = self._fit_thread  # local read: another thread may None it
        if fit_thread is not None and not fit_thread.is_alive():
            self._finish_fit()
        with device_obs.span("dm.featurize", rows=len(batch)):
            tokens, ok = self._featurize_raw_batch(batch)

        # split the batch across the train/detect phase boundary
        detect_idx: List[int] = []
        for i in range(len(batch)):
            if not ok[i]:
                continue
            if self._trained < self.config.data_use_training:
                self._train_buffer.append(tokens[i])
                self._trained += 1
                if self._trained == self.config.data_use_training:
                    self._start_fit()
            elif self._fit_thread is not None:
                # fit still running: keep order by buffering the message.
                # The append happens under _fit_lock so _finish_fit's
                # backlog handoff (stack + clear) can never interleave
                # with it and drop/mis-pair a message.
                with self._fit_lock:
                    if self._fit_thread is not None:
                        self._pending.append((tokens[i], batch[i]))
                        continue
                # fit finished and its backlog was already dispatched by
                # another thread between the check and the lock: this
                # message scores normally (order is preserved — backlog
                # dispatch happened first, detect_idx dispatches below)
                if not self._fitted:
                    self.fit()
                detect_idx.append(i)
            else:
                if not self._fitted:
                    self.fit()
                detect_idx.append(i)
        ready: List[Optional[bytes]] = []  # outputs from drained older batches
        if detect_idx:
            det_tokens = tokens[detect_idx]
            det_raws = [batch[i] for i in detect_idx]
            coalescer = self._get_coalescer()
            if coalescer is not None:
                # continuous batching: hold the rows toward a warm bucket;
                # _coalesce_pump below decides what (if anything) dispatches
                self._coalesce_add(det_tokens, det_raws,
                                   tenant=self._ingress_tenant)
            else:
                self._dispatch(det_tokens, det_raws)
        self._coalesce_pump()
        # event-driven drain: anything whose readback already landed goes out
        # NOW (bounded latency even under a steady stream that never lulls);
        # the depth gate stays as the backstop that also bounds memory
        while self._inflight and self._head_ready():
            ready.extend(self._drain_one())
        while len(self._inflight) > self.config.pipeline_depth:
            ready.extend(self._drain_one())
        # training/filtered messages of THIS batch produced no output; the
        # drained outputs (older batches) are already in order
        return ready

    def process_frames(self, frames: List[bytes]):
        """Fused wire-frame hot path (engine contract, opt-in): takes RAW
        wire frames — packed batch frames (engine/framing.py) or single
        messages — and returns ``(ready_outputs, n_messages, n_lines)``
        where ``n_lines`` follows the engine's newline line-count rule so
        read/written metrics stay in one unit.

        Frame expansion + featurization happen in ONE native call
        (dm_featurize_frames): no per-message bytes objects, list appends,
        or Python loop iterations exist on the steady-state path — the
        per-message Python cost is replaced by the C kernel's.
        Raw bytes are sliced lazily from the frame blob only for the ~1%
        anomalous messages at alert-construction time (SpanRaws).

        During the training phase or a running boundary fit the burst is
        materialized and delegated to ``process_batch`` (same semantics,
        per-message bookkeeping) — only the fitted steady state takes the
        vectorized path, which is exactly when throughput matters."""
        matchkern = self._matchkern()
        if matchkern is None:
            msgs: List[bytes] = []
            n_corrupt = 0
            for frame in frames:
                expanded = self._expand_frame_python(frame)
                if expanded is None:
                    n_corrupt += 1
                else:
                    msgs.extend(expanded)
            if n_corrupt:
                self.count_processing_errors(n_corrupt,
                                             "corrupt batch frame(s)")
            n_lines = sum(
                max(1, d.count(b"\n") + (0 if d.endswith(b"\n") else 1))
                for d in msgs)
            return self.process_batch(msgs), len(msgs), n_lines

        # dmlint: ignore[DM-L001] racy pre-check; _finish_fit re-checks under lock
        fit_thread = self._fit_thread  # local read: another thread may None it
        if fit_thread is not None and not fit_thread.is_alive():
            self._finish_fit()

        with device_obs.span("dm.featurize", frames=len(frames)):
            fb = matchkern.featurize_frames(frames, self.config.seq_len,
                                            self.config.vocab_size)
        if fb.n_corrupt_frames:
            self.count_processing_errors(fb.n_corrupt_frames,
                                         "corrupt batch frame(s)")
        n = len(fb)
        steady = (self._fitted and self._fit_thread is None
                  and self._trained >= self.config.data_use_training)
        if not steady:
            # phase boundary: per-message semantics via the classic path
            raws = [fb.raw(i) for i in range(n)]
            return self.process_batch(raws), n, fb.n_lines
        if fb.ok.all():
            self._count_featurize_rows(n, 0)
        else:
            # native kernel refused rows (e.g. >64 header-map entries):
            # retry them in Python for exact parity, like the batch path
            flagged = np.flatnonzero(~fb.ok)
            self._featurize_python_rows(
                matchkern.SpanRaws(fb.blob, fb.spans), fb.tokens, fb.ok,
                flagged)
            self._count_featurize_rows(n - len(flagged), len(flagged))
        ready: List[Optional[bytes]] = []
        if fb.ok.all():
            tokens, raws = fb.tokens, matchkern.SpanRaws(fb.blob, fb.spans)
            n_ok = n
        else:
            idx = np.flatnonzero(fb.ok)
            tokens = fb.tokens[idx]
            raws = matchkern.SpanRaws(fb.blob, fb.spans[idx])
            n_ok = len(idx)
        if n_ok:
            coalescer = self._get_coalescer()
            if coalescer is not None:
                # SpanRaws segments stay lazy inside the coalescer — no
                # per-message bytes objects until alert construction
                self._coalesce_add(tokens, raws,
                                   tenant=self._ingress_tenant)
            else:
                self._dispatch(tokens, raws)
        self._coalesce_pump()
        while self._inflight and self._head_ready():
            ready.extend(self._drain_one())
        while len(self._inflight) > self.config.pipeline_depth:
            ready.extend(self._drain_one())
        return ready, n, fb.n_lines

    @staticmethod
    def _expand_frame_python(frame: bytes) -> Optional[List[bytes]]:
        """Pure-Python frame expansion for the no-native fallback; None
        signals a corrupt batch frame (caller counts it — silent loss of a
        whole frame must be observable, matching the native branch)."""
        from ...engine.framing import FramingError, unpack_batch

        try:
            msgs = unpack_batch(frame)
        except FramingError:
            return None
        if msgs is None:
            return [frame] if frame else []
        return [m for m in msgs if m]

    def _head_ready(self) -> bool:
        """True when the oldest in-flight batch's scores are host-readable
        without blocking (host-path numpy results always are)."""
        slot = self._inflight[0]
        if isinstance(slot.scores, np.ndarray):
            return True
        is_ready = getattr(slot.scores, "is_ready", None)
        if callable(is_ready):
            self._ready_supported = True
            try:
                return bool(is_ready())
            except Exception:
                return False
        self._ready_supported = False
        return False  # cannot tell: leave it to the depth gate / flush

    def pending_count(self) -> int:
        """In-flight scored batches not yet drained, plus one while the
        coalescer holds rows (engine poll hint: while results are pending —
        or a held row's deadline is ticking — the engine shortens its recv
        timeout so a drain/release happens within one tick of readiness,
        not at the 100 ms lull)."""
        held = self._coalescer is not None and len(self._coalescer) > 0
        return len(self._inflight) + (1 if held else 0)

    @property
    def drain_poll_ms(self) -> Optional[int]:
        """Engine short-poll hint (engine.py): while the coalescer may hold
        rows, the engine must tick often enough to honor batch_deadline_ms.
        A quarter of the budget bounds the oldest-row overshoot to one tick
        (the coalescer also releases one tick EARLY — _BatchCoalescer.due),
        without hard-coding 5 ms polling onto second-scale budgets."""
        if self.config.batch_deadline_ms <= 0:
            return None
        return max(1, int(self.config.batch_deadline_ms / 4))

    def drained_total(self) -> int:
        """Monotonic count of drained in-flight batches — the progress
        counter the health watchdog pairs with ``pending_count`` to detect a
        stuck device queue (pending > 0 and this number frozen)."""
        return self._drained_total

    def drain_ready(self) -> List[Optional[bytes]]:
        """Engine short-poll tick: pop only batches whose readback already
        landed — never blocks the loop on an in-flight device batch. When the
        array type cannot report readiness at all, fall back to the blocking
        flush (otherwise nothing would ever drain on short ticks)."""
        out: List[Optional[bytes]] = []
        self._finish_fit(wait=False)
        self._coalesce_pump()  # deadline releases ride the short-poll tick
        while self._inflight and self._head_ready():
            out.extend(self._drain_one())
        if self._inflight and self._ready_supported is False:
            out.extend(self.flush())
        return out

    # -- async fit at the phase boundary --------------------------------
    def _start_fit(self) -> None:
        if not self.config.async_fit:
            self.fit()
            return
        import threading

        def _fit_safe():
            try:
                self.fit()
            except Exception:
                import logging

                logging.getLogger(__name__).exception("background fit failed")
                self._fitted = True  # fail open: detect with inf threshold
                if self._threshold is None:
                    self._threshold = float("inf")

        # publish AND start under the lock: _finish_fit's join-and-dispatch
        # handoff clears the handle under _fit_lock, so an unguarded write
        # here could lose that clear — and joining a published-but-unstarted
        # thread raises RuntimeError, so start() must happen before any
        # other thread can observe the handle (start is microseconds; the
        # fit itself runs on the new thread, not under the lock)
        with self._fit_lock:
            self._fit_thread = threading.Thread(target=_fit_safe, daemon=True,
                                                name="ScorerFit")
            self._fit_thread.start()

    def _finish_fit(self, wait: bool = False) -> None:
        """Join a finished (or, with ``wait``, still-running) fit thread and
        dispatch the ordered backlog that accumulated during the fit.

        Lock-guarded: the engine loop and external callers (detect /
        save_checkpoint / flush_final — mixed usage the class supports) may
        call this concurrently; without the lock both could observe a
        non-empty backlog and double-dispatch it."""
        # dmlint: ignore[DM-L001] racy pre-check; the read repeats under the lock
        pre = self._fit_thread  # local read: another thread may None it
        if pre is not None and pre.is_alive() and not wait:
            return  # cheap pre-check without the lock
        with self._fit_lock:
            thread = self._fit_thread
            if thread is None:
                return
            if thread.is_alive() and not wait:
                return
            # the fit thread never takes _fit_lock, so no deadlock here:
            # dmlint: ignore[DM-L002] _fit_lock IS the handoff serializer
            thread.join()
            self._fit_thread = None
            if self._pending:
                tokens = np.stack([t for t, _ in self._pending])
                raws = [r for _, r in self._pending]
                self._pending = []
                coalescer = self._get_coalescer()
                if coalescer is not None:
                    # the backlog's size is whatever the fit's duration made
                    # it — bucketing it through the coalescer (released by
                    # the caller's pump) keeps it on warm compile shapes
                    self._coalesce_add(tokens, raws)
                else:
                    self._dispatch(tokens, raws)

    def _route(self, n: int, coalesced: bool) -> tuple:
        """Where ``n`` rows score and in which compile bucket →
        ``(path, bucket)``.

        Small batches (≤ ``host_score_max_batch``) ride the CPU twin, in
        power-of-two host buckets that keep the padding compute proportional
        to the batch (padding everything to the cap costs ~60 ms for 128
        rows on a small CPU — measured, it broke the p50 target); those
        compile in a background warm thread, and a batch whose bucket is not
        warm yet rides the device path instead of stalling the engine loop
        on a synchronous XLA compile. A coalesced release buckets against
        the ACTIVE warm set (``_pick_device_bucket``) instead of the raw
        power-of-two rule, so it rides a pre-warmed compile shape."""
        cap = self.config.host_score_max_batch
        # dmlint: ignore[DM-L001] ref-atomic mirror swap (see _score_host)
        if 0 < n <= cap and self._host_params is not None:
            bucket = _bucket(n, cap)
            if bucket in self._host_warm:
                return "host", bucket
        if coalesced:
            bucket = self._pick_device_bucket(n)
            self._bucket_usage[bucket] = self._bucket_usage.get(bucket, 0) + 1
            return "device", bucket
        bucket = _bucket(n, self.config.max_batch)
        if bucket not in self._device_warm:
            # legacy (non-coalescer) path: a bucket outside the warm set —
            # traffic whose natural batch size the setup warm-up never saw,
            # e.g. a replica tier halving each scorer's burst — gets the
            # same EXPECTED on-demand pre-warm the adaptive path does,
            # instead of paging the first dispatch as an unexpected
            # recompile
            self._warm_device_bucket(bucket)
        return "device", bucket

    def _dispatch(self, tokens: np.ndarray, msgs: List[Any],
                  t_enqueue: Optional[float] = None,
                  release: Optional[str] = None,
                  route: Optional[tuple] = None,
                  seq: Optional[int] = None,
                  t_release: Optional[float] = None) -> None:
        """Asynchronously score [n, S] tokens, padded to a compile bucket:
        issued here, on the caller's thread, and drained later.

        Small batches score synchronously on the CPU twin instead
        (``_route``), skipping the upload and readback. The host result
        enters the same in-flight queue (as a ready numpy array) so ordering
        with accelerator batches is preserved.

        A coalesced release (``release`` set) backdates ``t_enqueue`` to the
        oldest held row's arrival — queue-wait telemetry then includes the
        coalescer hold — and brings the ``route`` and the batch identifier
        ``seq`` its ``dm.release`` span already carries (a further chunk, or
        an uncoalesced dispatch, is allotted its own) and ``t_release``, the
        pump's clock reading at which the release rule was seen met."""
        self._ensure_scorer()
        n = len(tokens)
        # retain real token rows on the slot only while a rollout sampler
        # is attached: the drain path offers rows PAIRED with their scores
        # (dmdrift reads the live score distribution off the reservoir)
        keep_tokens = self._rollout_sampler is not None
        path, bucket = route or self._route(n, release is not None)
        if path == "host":
            chunk = tokens
            if n < bucket:
                chunk = np.concatenate(
                    [tokens, np.zeros((bucket - n, tokens.shape[1]), np.int32)])
            slot = _InflightSlot(list(msgs), n, bucket=bucket,
                                 path="host",
                                 trace_id=self._current_trace_id(),
                                 release=release,
                                 tokens=tokens if keep_tokens else None,
                                 seq=seq or self._ledger.next_batch_seq(),
                                 t_enqueue=t_enqueue, t_release=t_release)
            slot.t_start = time.monotonic()
            # only warmed host buckets reach here, so a compile in this
            # context IS an unexpected recompile (a warm-set bug)
            with self._ledger.context(bucket=bucket, backend="cpu",
                                      where="host", expected=False):
                slot.scores = np.asarray(self._score_host(chunk))[:n]
            # synchronous path: scores are host-readable now — record
            # the span/occupancy here, not at drain
            self._observe_batch(slot, time.monotonic() - slot.t_start)
            self._inflight.append(slot)
            return
        for start in range(0, n, bucket):
            chunk = tokens[start:start + bucket]
            real = len(chunk)
            if real < bucket:
                chunk = np.concatenate(
                    [chunk, np.zeros((bucket - real, tokens.shape[1]), np.int32)]
                )
            slot = _InflightSlot(msgs[start:start + real], real,
                                 bucket=bucket, path="device",
                                 trace_id=self._current_trace_id(),
                                 release=release,
                                 tokens=(tokens[start:start + real]
                                         if keep_tokens else None),
                                 seq=seq or self._ledger.next_batch_seq(),
                                 t_enqueue=t_enqueue, t_release=t_release)
            seq = None                     # a further chunk takes its own
            # nothing unfinished on the device: the idle stretch since the
            # last batch was seen readable ends with this call being issued
            if self._dev_unfinished == 0:
                slot.idle_start = self._idle_clock.busy_from(
                    slot.t_release, self._release_at())
            self._dev_unfinished += 1
            self._inflight.append(slot)
            # a dispatch error propagates to the caller
            slot.scores = self._issue(slot, chunk)

    def _issue(self, slot: "_InflightSlot", chunk: np.ndarray):
        """Upload, scoring call and the start of the readback for one
        device batch, on the engine thread."""
        slot.t_start = time.monotonic()  # queue wait ends here
        try:
            with self._ledger.context(bucket=slot.bucket,
                                      backend=self._exec.backend,
                                      where="dispatch", expected=False):
                return self._score_dev(chunk, slot.span_kv(), slot)
        finally:
            slot.t_issued = time.monotonic()

    # -- adaptive continuous batching (the coalescer) --------------------
    def _get_coalescer(self) -> Optional["_BatchCoalescer"]:
        if self.config.batch_deadline_ms <= 0:
            return None
        if self._coalescer is None:
            self._coalescer = _BatchCoalescer(
                self.config.batch_deadline_ms / 1000.0,
                self.config.batch_target_occupancy)
        return self._coalescer

    def _coalesce_pump(self, force: bool = False) -> None:
        """Release due coalesced batches. Three reasons, in priority order:

        * ``full`` — the held rows fill the largest active warm bucket to
          ``batch_target_occupancy``; waiting longer cannot raise occupancy;
        * ``deadline`` — the oldest held row's wait approaches
          ``batch_deadline_ms`` (everything held goes, smaller buckets);
        * ``flush`` — the engine's idle/teardown drain (``force``), or the
          knob was turned off at runtime with rows still held.

        Single-owner like the rest of the dispatch path: only the engine
        thread pumps."""
        co = self._coalescer
        if co is None:
            return
        if not len(co):
            self._observe_coalesce_depth(0)
            return
        if self.config.batch_deadline_ms <= 0:
            force = True  # disabled at runtime with rows still held
        now = time.monotonic()
        self._idle_advance(now)
        largest = self._largest_active_bucket()
        target = self._release_target(largest)
        while len(co) >= target:
            self._release_coalesced(min(len(co), largest), "full", now)
        if force:
            while len(co):
                self._release_coalesced(min(len(co), largest), "flush", now)
        elif co.due(now):
            while len(co):
                self._release_coalesced(min(len(co), largest), "deadline",
                                        now)
        self._maybe_retire_buckets(now)
        self._observe_coalesce_depth(len(co))

    def _release_coalesced(self, n: int, reason: str, now: float) -> None:
        """One coalesced release: the batch's identifier and route are fixed
        first, so that ``dm.release`` — take, concatenate, pad, hand-off —
        carries what every later span of the batch carries."""
        self._ensure_scorer()
        co = self._coalescer
        seq = self._ledger.next_batch_seq()
        route = self._route(n, coalesced=True)
        with device_obs.span("dm.release", batch=seq, bucket=route[1],
                             rows=n, release=reason):
            self._idle_advance(now)      # before what is held changes
            held_s = co.row_hold_s
            tokens, raws, t_oldest = co.take(n, now)
            co.note_release(reason, now - t_oldest)
            self._count_release(reason)
            self._row_hold_child.inc(co.row_hold_s - held_s)
            self._rows_released_children[reason].inc(n)
            self._dispatch(tokens, raws, t_enqueue=t_oldest, release=reason,
                           route=route, seq=seq, t_release=now)

    def _coalesce_add(self, tokens: np.ndarray, raws,
                      tenant: Optional[str] = None) -> None:
        now = time.monotonic()
        self._idle_advance(now)          # before what is held changes
        self._coalescer.add(tokens, raws, now, tenant=tenant)

    def _release_at(self) -> Optional[float]:
        """When the rows the coalescer holds met, or will meet, its release
        rule (``DeviceIdleClock.advance``): None with nothing held, ``-inf``
        once the target is reached or coalescing was switched off with rows
        held, else the oldest row's due time."""
        co = self._coalescer
        if co is None or not len(co):
            return None
        if (len(co) >= self._release_target(self._largest_active_bucket())
                or self.config.batch_deadline_ms <= 0):
            return -math.inf
        return co.due_at()

    def _release_target(self, largest: int) -> int:
        """Held rows at which a ``full`` release goes."""
        return max(1, math.ceil(self._coalescer.target_occupancy * largest))

    def _idle_advance(self, now: float) -> None:
        clock = self._idle_clock
        if clock is not None and clock.idle:
            clock.advance(now, self._release_at())

    def _active_buckets(self) -> List[int]:
        """The warm set minus retirements, sorted ascending."""
        return sorted(self._device_warm - self._retired_buckets)

    def _largest_active_bucket(self) -> int:
        active = self._active_buckets()
        return active[-1] if active else _bucket(self.config.max_batch,
                                                 self.config.max_batch)

    def _pick_device_bucket(self, n: int) -> int:
        """Warm-set bucket choice for a coalesced release: the natural
        power-of-two bucket when active (pre-warming it — an expected
        compile — on first use), the next active bucket up when the natural
        one is retired (padding is cheaper than resurrecting a shape the
        usage window judged underused), resurrection once the retired
        bucket keeps winning best-fit anyway (persistent pressure means the
        traffic shape changed back)."""
        cap = self.config.max_batch
        natural = _bucket(n, cap)
        if natural in self._device_warm and natural not in self._retired_buckets:
            return natural
        if natural in self._retired_buckets:
            hits = self._retired_hits.get(natural, 0) + 1
            self._retired_hits[natural] = hits
            if hits <= max(1, self.config.bucket_retire_min_dispatches):
                # pad up: the largest bucket is never retired, so an active
                # bucket >= natural always exists
                for b in self._active_buckets():
                    if b >= natural:
                        return b
            self._retired_buckets.discard(natural)
        self._warm_device_bucket(natural)
        return natural

    def _warm_device_bucket(self, bucket: int) -> None:
        """Compile a device bucket BEFORE the dispatch path uses it — an
        EXPECTED compile (where="bucket_warm"): neither adaptive warm-set
        growth nor post-retirement resurrection may page as a recompile
        storm. The compile stalls this one release (like any planned warm),
        and every later dispatch on the bucket runs the kept executable."""
        self._ensure_scorer()
        kind, extra = self._serving()
        with self._ledger.context(bucket=bucket, backend=self._exec.backend,
                                  where="bucket_warm", expected=True):
            self._exec.warm(kind, bucket, *extra)
        self._device_warm.add(bucket)

    def _maybe_retire_buckets(self, now: float) -> None:
        interval = self.config.bucket_retire_interval_s
        if interval <= 0 or self._coalescer is None:
            return
        if self._retire_last_sweep is None:
            self._retire_last_sweep = now
            return
        if now - self._retire_last_sweep >= interval:
            self._retire_sweep(now)

    def _retire_sweep(self, now: float) -> None:
        """One retirement pass over the usage window: active buckets that
        saw fewer than ``bucket_retire_min_dispatches`` dispatches since
        the last sweep leave the active set (their future rows pad up),
        shrinking the compile set the XLA ledger tracks. The largest bucket
        is the pad-up backstop and always stays."""
        floor = max(1, self.config.bucket_retire_min_dispatches)
        active = self._active_buckets()
        largest = active[-1] if active else 0
        retired = [b for b in active
                   if b != largest and self._bucket_usage.get(b, 0) < floor]
        for b in retired:
            self._retired_buckets.add(b)
        if retired:
            self._coalescer.retired_total += len(retired)
            import logging

            logging.getLogger(__name__).info(
                "batch coalescer retired underused bucket(s) %s "
                "(< %d dispatches in %.1fs); active warm set now %s",
                retired, floor, self.config.bucket_retire_interval_s,
                self._active_buckets())
        self._bucket_usage.clear()
        self._retired_hits.clear()
        self._retire_last_sweep = now

    def _bucket_state(self) -> Dict[str, Any]:
        """The ledger's bucket-state provider (GET /admin/xla)."""
        def routes(record: str) -> Dict[str, str]:
            return {str(rows): route for rows, route in sorted(
                dict(getattr(self._scorer, record, {})).items())}

        return {
            "coalescing": self.config.batch_deadline_ms > 0,
            "warm": self._active_buckets(),
            "retired": sorted(self._retired_buckets),
            # which head (models/base.py head_route), which attention
            # (ops/attention.py attention_route), which short convolution
            # (ops/shortconv.py conv_route), which form of the delta rule
            # (ops/deltarule.py delta_route: the kernel, the chunked
            # jax.numpy form or the scan) and which expert path (the
            # sparse-expert scorers') each traced device executable took,
            # by its rows; decided at trace time, empty where the scorer has
            # no such part. The host twin's calls are not in it: it is
            # pinned to einsum
            "head_route": routes("head_routes"),
            "attn_route": routes("attn_routes"),
            "conv_route": routes("conv_routes"),
            "delta_route": routes("delta_routes"),
            "expert_route": routes("expert_routes"),
        }

    def batching_stats(self) -> Dict[str, Any]:
        """Scheduler counters for the bench / smoke harnesses: releases by
        reason, achieved occupancy, held depth, release waits, and the
        warm/retired bucket sets (also on ``GET /admin/xla`` via the
        ledger's bucket state)."""
        co = self._coalescer
        occ_n, occ_sum = self._occ_stats
        return {
            "enabled": self.config.batch_deadline_ms > 0,
            "held_rows": 0 if co is None else len(co),
            "rows_coalesced": 0 if co is None else co.rows_in,
            "releases": dict(co.releases) if co is not None else {},
            "max_wait_s": 0.0 if co is None else round(co.max_wait_s, 6),
            "mean_wait_s": (round(co.wait_sum_s / co.wait_n, 6)
                            if co is not None and co.wait_n else 0.0),
            "mean_row_hold_s": (round(co.row_hold_s / co.rows_out, 6)
                                if co is not None and co.rows_out else 0.0),
            "buckets_retired_total": 0 if co is None else co.retired_total,
            "held_by_tenant": {} if co is None else co.held_by_tenant(),
            "dispatches": occ_n,
            "occupancy_sum": round(occ_sum, 4),
            "occupancy_mean": round(occ_sum / occ_n, 4) if occ_n else None,
            "warm_buckets": self._active_buckets(),
            "retired_buckets": sorted(self._retired_buckets),
        }

    def _observe_coalesce_depth(self, depth: int) -> None:
        if self._coalesce_gauge is None:
            from ...engine import metrics as m

            self._coalesce_gauge = m.COALESCE_DEPTH().labels(
                **self._obs_labels())
        self._coalesce_gauge.set(depth)

    def _count_release(self, reason: str) -> None:
        child = self._release_children.get(reason)
        if child is None:
            from ...engine import metrics as m

            child = m.DEADLINE_RELEASES().labels(reason=reason,
                                                 **self._obs_labels())
            self._release_children[reason] = child
        child.inc()

    def _score_host(self, tokens: np.ndarray):
        """Score a small batch on the CPU backend with the mirrored params."""
        import jax

        tokens = jax.device_put(tokens, self._cpu_device)
        if self._norm_mu is not None:
            # dmlint: ignore[DM-L001] ref-atomic mirror swap; engine
            # thread reads whichever params generation is current
            return self._host_normscore(self._host_params, tokens,
                                        self._norm_mu, self._norm_sigma)
        # dmlint: ignore[DM-L001] ref-atomic mirror swap (see above)
        return self._host_score(self._host_params, tokens)

    def _drain_one(self) -> List[Optional[bytes]]:
        slot = self._inflight.popleft()
        self._drained_total += 1
        on_device = slot.path != "host"
        kv = slot.span_kv()
        raws, real = slot.raws, slot.real
        if on_device:
            with device_obs.span("dm.readback", **kv):
                scores = np.asarray(slot.scores)[:real]
                aux = None if slot.aux is None else np.asarray(slot.aux)
            self._device_batch_done(slot, time.monotonic())
            if aux is not None and self._moe_children is not None:
                for child, count in zip(self._moe_children, aux):
                    child.inc(int(count))
        else:
            scores = np.asarray(slot.scores)[:real]
        if self._rollout_sampler is not None and slot.tokens is not None:
            # drain-time tap (dmdrift): rows enter the reservoir PAIRED
            # with the scores this batch produced — the drift monitor's
            # live distribution is exactly what the dispatch path scored
            self._rollout_sampler.offer_rows(slot.tokens[:real], scores)
        entry = None
        if on_device:
            # np.asarray above forced the readback: scoring-call start →
            # now is the batch's device compute + readback time (the host
            # path recorded its synchronous span at dispatch)
            start = slot.t_start if slot.t_start is not None else slot.t_enqueue
            entry = self._observe_batch(slot, time.monotonic() - start)
        threshold = self._threshold if self._threshold is not None else float("inf")
        out: List[Optional[bytes]] = []
        with device_obs.span("dm.alert_build", **kv):
            hits = np.flatnonzero(scores > threshold)
            if hits.size:
                from ...schemas import schemas_pb2 as _pb

                for i in hits:  # touch only the anomalous rows (~1%)
                    msg = _pb.ParserSchema()
                    msg.ParseFromString(raws[i])
                    out.append(self._make_alert_pb(msg, float(scores[i])))
        if entry is not None:
            # the engine sends what this call returns: the ring entry's
            # last stamp
            self._ledger.note_sent(entry, time.monotonic() - slot.t_release)
        return out

    def _device_batch_done(self, slot: "_InflightSlot", now: float) -> None:
        """A device batch was seen readable at ``now``: close
        its share of the idle account, and start the idle clock when
        nothing else is unfinished on the device."""
        if slot.path == "host":
            return
        self._dev_unfinished = max(0, self._dev_unfinished - 1)
        clock = self._idle_clock
        if clock is None:
            return
        if slot.idle_start and slot.t_issued is not None:
            clock.issued(slot.t_issued - slot.t_release)
        if self._dev_unfinished == 0:
            clock.idle_from(now)

    def flush(self) -> List[Optional[bytes]]:
        """Idle-time drain (engine calls on every input lull): NON-blocking —
        a 100 ms lull does not mean the input stays idle, so waiting out a
        running boundary fit here would stall the engine loop and drop
        messages at the socket HWM (the failure async_fit exists to prevent).
        A finished fit's backlog is dispatched; a running fit is left alone.
        Coalesced rows release unconditionally (reason "flush"): an idle
        lull or teardown must never strand held rows."""
        self._finish_fit(wait=False)
        self._coalesce_pump(force=True)
        out: List[Optional[bytes]] = []
        while self._inflight:
            out.extend(self._drain_one())
        return out

    def flush_final(self) -> List[Optional[bytes]]:
        """Stop-time drain: waits for a running boundary fit so its pending
        backlog is scored and emitted before sockets close (and for the host
        bucket warmer, so post-restore usage sees a deterministic state)."""
        self._finish_fit(wait=True)
        warm = self._host_warm_thread
        if warm is not None and warm.is_alive():
            warm.join()
        return self.flush()

    def _make_alert_pb(self, msg, score: float) -> bytes:
        """Alert construction straight on the generated pb2 classes — at a
        1% anomaly rate over 250k+ lines/s this runs thousands of times per
        second, and the dict-style wrapper layers (field-descriptor lookups,
        map copies) measurably cap drain throughput. Field semantics match
        CoreDetector.make_output exactly — pinned field-by-field by
        test_batch_alert_full_field_parity_with_make_output."""
        from ...schemas import SCHEMA_VERSION, schemas_pb2 as _pb

        now = int(time.time())
        out = _pb.DetectorSchema()
        setattr(out, "__version__", SCHEMA_VERSION)
        out.detectorID = self.name
        out.detectorType = self.config.method_type
        out.alertID = str(next(self._alert_ids))
        out.detectionTimestamp = now
        out.receivedTimestamp = now
        if msg.logID:
            out.logIDs.append(msg.logID)
        ts = now
        lfv = msg.logFormatVariables
        for key in ("Time", "time", "timestamp"):
            value = lfv.get(key) if lfv else None
            if value:
                try:
                    ts = int(float(value))
                except ValueError:
                    pass
                break
        else:
            if msg.receivedTimestamp:
                ts = int(msg.receivedTimestamp)
        out.extractedTimestamps.append(ts)
        out.description = self.description
        out.score = score
        out.alertsObtain[f"{self.name} - score"] = (
            f"anomaly score {score:.4f} > {self._threshold:.4f}")
        return out.SerializeToString()

    def detect(self, input_: ParserSchema, output_: DetectorSchema) -> bool:
        """Single-message path (parity mode / tests): batch of one."""
        self._finish_fit(wait=True)  # mixed usage: boundary fit may be running
        if not self._fitted:
            self.fit()
        score = float(self.score_tokens(self.featurize(input_)[None])[0])
        if score > self._threshold:
            output_["score"] = score
            output_["alertsObtain"].update(
                {f"{self.name} - score": f"anomaly score {score:.4f} > {self._threshold:.4f}"}
            )
            return True
        return False

    # -- device observability (engine/device_obs.py) ---------------------
    def _obs_labels(self) -> Dict[str, str]:
        return dict(component_type=self.config.method_type,
                    component_id=self.name)

    def _bind_boundary_counters(self) -> None:
        """Scorer set-up, once the device is resolved: arm the ``dm.*``
        spans and create every child of the counters that tick where rows
        cross a boundary, so that each series is exported as 0 from boot (a
        reader that finds a series absent drops its metric)."""
        from ...engine import metrics as m

        labels = self._obs_labels()
        device_obs.arm_spans(labels)
        self._row_hold_child = m.ROW_HOLD_SECONDS().labels(**labels)
        for reason in ("full", "deadline", "flush"):
            self._rows_released_children[reason] = m.ROWS_RELEASED().labels(
                reason=reason, **labels)
        self._device_children = (
            m.DEVICE_LINES().labels(device=self._exec.label, **labels),
            m.DEVICE_BATCHES().labels(device=self._exec.label, **labels))
        self._moe_children = (
            m.MOE_ASSIGNMENTS().labels(**labels),
            m.MOE_HELD_ASSIGNMENTS().labels(**labels),
            m.MOE_BUSIEST_ASSIGNMENTS().labels(**labels))
        self._idle_clock = device_obs.DeviceIdleClock({
            cause: m.DEVICE_IDLE_SECONDS().labels(cause=cause, **labels)
            for cause in device_obs.DeviceIdleClock.CAUSES})
        # a POST /admin/profile capture reads the account at its two marks
        from ...utils.profiling import PROFILER

        PROFILER.set_idle_reader(self._idle_reading)

    def _idle_reading(self, now: float) -> Dict[str, float]:
        """The idle account as it stands at ``now``, a stretch still open
        counted up to it. Called on a capture's thread, not the engine's:
        it changes nothing, and the caller makes it again where it fell
        into an update of what the coalescer holds."""
        return self._idle_clock.reading(now, self._release_at())

    def _current_trace_id(self) -> Optional[str]:
        """Flight recorder's last completed trace id (the PR-1 link a
        device-batch span carries), or None off a traced pipeline."""
        monitor = self.health_monitor
        recorder = (getattr(monitor, "trace_recorder", None)
                    if monitor is not None else None)
        return (getattr(recorder, "last_trace_id", None)
                if recorder is not None else None)

    def _observe_batch(self, slot: "_InflightSlot",
                       device_s: float) -> Optional[Dict[str, Any]]:
        """Per-dispatch batch telemetry, recorded when a batch's scores
        become host-readable: occupancy (real/bucket — 1 minus padding
        waste), bucket selection, the queue-wait vs device-time split,
        attributed to the host or device path, and — device path only — the
        rows and the batch the chip scored
        (``detector_device_lines_total`` / ``_batches_total``); plus a span
        in the compile ledger carrying the dispatch-time trace id and the
        batch's stamps. Returns the ledger's ring entry."""
        from ...engine import metrics as m

        bucket, path = slot.bucket, slot.path
        if bucket <= 0:
            return None
        now = time.monotonic()
        if path == "device" and self._device_children is not None:
            lines_c, batches_c = self._device_children
            lines_c.inc(slot.real)
            batches_c.inc()
        t_start = slot.t_start if slot.t_start is not None else slot.t_enqueue
        queue_wait_s = max(0.0, t_start - slot.t_enqueue)
        children = self._batch_obs.get(path)
        if children is None:
            labels = dict(self._obs_labels(), path=path)
            children = (m.BATCH_OCCUPANCY().labels(**labels),
                        m.BATCH_QUEUE_WAIT().labels(**labels),
                        m.BATCH_DEVICE_SECONDS().labels(**labels))
            self._batch_obs[path] = children
        occ_h, wait_h, dev_h = children
        occ_h.observe(slot.real / bucket)
        # dmtel: link the queue-wait sample to the trace that was in flight
        # at dispatch time so a scrape with ?format=openmetrics carries an
        # exemplar pointing straight at an assembled trace in the collector.
        if slot.trace_id:
            wait_h.observe(queue_wait_s, {"trace_id": slot.trace_id})
        else:
            wait_h.observe(queue_wait_s)
        dev_h.observe(max(0.0, device_s))
        # running (dispatches, occupancy-sum) pair: the bench/smoke
        # harnesses read deltas of it per load phase (batching_stats)
        occ_n, occ_sum = self._occ_stats
        self._occ_stats = (occ_n + 1, occ_sum + slot.real / bucket)
        bucket_child = self._bucket_children.get((bucket, path))
        if bucket_child is None:
            bucket_child = m.BUCKET_SELECTED().labels(
                bucket=str(bucket), path=path, **self._obs_labels())
            self._bucket_children[(bucket, path)] = bucket_child
        bucket_child.inc()
        entry = None
        if self._ledger is not None:
            entry = self._ledger.record_span(
                bucket, slot.real, path, queue_wait_s, max(0.0, device_s),
                slot.trace_id, release=slot.release, seq=slot.seq or None,
                stamps={"oldest_arrival": slot.t_enqueue,
                        "release": slot.t_release, "pickup": slot.t_start,
                        "call_issued": slot.t_issued, "readable": now})
        tap = self._capacity_tap
        if tap is not None:
            # dmdrift capacity arithmetic: real rows + the device-time this
            # batch cost, from the one site every scored batch reports to
            tap(slot.real, max(0.0, device_s))
        return entry

    # -- model rollout (rollout/manager.py seams) ------------------------
    def set_rollout_sampler(self, sampler) -> None:
        """Attach the dispatch-path traffic tap (rollout/sampler.py). One
        ``offer_rows`` call per DRAINED micro-batch — rows enter paired
        with the scores they produced (dmdrift) — and the sampler bounds
        its own memory and does its own thinning."""
        self._rollout_sampler = sampler

    def set_capacity_tap(self, tap) -> None:
        """Attach the dmdrift capacity tap (obs/capacity.py): called as
        ``tap(n_rows, device_seconds)`` per observed batch, any dispatch
        path. None detaches."""
        self._capacity_tap = tap

    def model_version(self) -> int:
        """The installed checkpoint version (0 = the boot-time fit)."""
        # dmlint: ignore[DM-L001] int read; swap publishes ref-atomically
        return self._model_version

    def live_threshold(self) -> float:
        return float(self._threshold) if self._threshold is not None \
            else float("inf")

    def rollout_ready(self) -> bool:
        """Whether the continuous fine-tune/shadow cycle can run: a fitted
        scorer whose executor can fork its live params. Mesh (sharded) mode
        serves hot-swaps of externally-built checkpoints (install_candidate
        / load_params_checkpoint) but not in-process fine-tuning — the train
        path donates the sharded trees in place."""
        # dmlint: ignore[DM-L001] racy pre-check; install paths re-sync
        return (self._fitted and self._fit_thread is None
                and self._exec is not None and self._exec.forkable)

    def rollout_fine_tune(self, rows: np.ndarray, epochs: int = 1,
                          seed: int = 0):
        """Fine-tune a CANDIDATE param tree off the live params on sampled
        rows; the live tree is never touched (train_step is functional).
        Every jit call rides the train-bucket shape the boundary fit
        compiled, and anything new attributes to an expected
        ``rollout_fit`` ledger context — the dispatch path keeps its
        zero-unexpected-recompile contract while training runs on the
        manager thread."""
        self._ensure_scorer()
        import jax

        cfg = self.config
        rows = np.asarray(rows, np.int32)
        if not len(rows):
            raise LibraryError("no sampled rows to fine-tune on")
        bs = min(cfg.train_batch_size, len(rows))
        # a concurrent swap just means the candidate forks from the
        # pre-swap generation; the shadow gate judges it against whatever
        # is live at promote time
        params, opt_state = self._exec.fork()
        rng = jax.random.PRNGKey(cfg.seed + 1 + seed)
        order_rng = np.random.default_rng(cfg.seed + seed)
        loss, steps = float("nan"), 0
        with self._ledger.context(where="rollout_fit",
                                  backend=self._exec.backend, expected=True):
            for _ in range(max(1, epochs)):
                order = order_rng.permutation(len(rows))
                for start in range(0, len(rows) - bs + 1, bs):
                    batch = rows[order[start:start + bs]]
                    rng, step_rng = jax.random.split(rng)
                    params, opt_state, loss_arr = self._exec.fork_step(
                        params, opt_state, step_rng, batch)
                    loss = float(loss_arr)
                    steps += 1
        return params, opt_state, {"steps": steps, "loss": loss,
                                   "batch_size": bs}

    def rollout_scores(self, params, tokens: np.ndarray) -> np.ndarray:
        """Shadow-scoring path: [n, S] tokens → [n] fp32 scores under the
        given params (None = live). Chunks ride the train-bucket compile
        shape (guaranteed warm since the boundary fit) under an expected
        ``shadow`` ledger context."""
        self._ensure_scorer()
        if params is not None and not self._exec.forkable:
            raise LibraryError(
                "shadow scoring with explicit params is not supported in "
                "mesh (sharded) mode")
        tokens = np.asarray(tokens, np.int32)
        n = len(tokens)
        if n == 0:
            return np.zeros(0, np.float32)
        bucket = _bucket(self.config.train_batch_size, self.config.max_batch)
        out = np.empty(n, np.float32)
        if params is not None:
            params, _ = self._exec.place_trees(params, None)
        with self._ledger.context(bucket=bucket, where="shadow",
                                  backend=self._exec.backend, expected=True):
            for start in range(0, n, bucket):
                chunk = tokens[start:start + bucket]
                real = len(chunk)
                if real < bucket:
                    chunk = np.concatenate([chunk, np.zeros(
                        (bucket - real, tokens.shape[1]), np.int32)])
                scores = np.asarray(self._score_dev(chunk, params=params))
                out[start:start + real] = scores[:real]
        return out

    def _resolve_warm_set(self, warm_set) -> List[int]:
        """Buckets to pre-warm at install: the live warm set UNIONED with a
        persisted warm-set spec (rollout manifest — see warm_set_spec), so
        a promote on a restarted process warms what the recording boot
        warmed. A spec for a different sequence length is stale config and
        is ignored."""
        cfg = self.config
        warmed = set(self._device_warm)
        if warm_set:
            try:
                if int(warm_set.get("seq_len", cfg.seq_len)) == cfg.seq_len:
                    warmed.update(
                        b for b in (int(x) for x in warm_set.get("buckets", ()))
                        if 0 < b <= cfg.max_batch)
            except (TypeError, ValueError, AttributeError):
                pass  # malformed spec: warm the live set only
        return sorted(warmed)

    def install_candidate(self, params, opt_state, version: int = 0,
                          warm_set=None) -> Dict[str, Any]:
        """Zero-downtime hot-swap: pre-warm the candidate against EVERY
        warm device bucket (plus the persisted ``warm_set`` spec from the
        rollout manifest) under an expected ``model_swap`` ledger context
        *before* cutover, then swap the dispatch path's param refs under
        the ``_fit_lock`` handoff. The coalescer keeps draining while the
        warm runs on the caller's (manager) thread; because the candidate's
        avals match the live tree every warm call runs the executable the
        bucket already keeps (a candidate it rejects raises here, before the
        swap), and a compile for a bucket only the spec names is attributed
        expected here rather than paging as a recompile storm. The host
        CPU twin's mirror is computed pre-swap too, so small batches never
        score a stale model. Under ``dtype: int8w`` the candidate is
        re-quantized after the swap and the parity gate re-judged — a
        candidate that flips decisions under quantization serves float."""
        self._ensure_scorer()
        import jax

        # land a running boundary fit first: its completion would overwrite
        # the freshly-installed params with the pre-swap training result
        self._finish_fit(wait=True)
        cfg = self.config
        warmed = self._resolve_warm_set(warm_set)
        kind, extra = self._serving()
        with self._ledger.context(where="model_swap",
                                  backend=self._exec.backend, expected=True):
            dev_params, dev_opt = self._exec.place_trees(params, opt_state)
            for b in warmed:
                tokens = np.zeros((b, cfg.seq_len), np.int32)
                self._device_warm.add(b)
                with self._ledger.context(bucket=b):
                    self._exec.warm(kind, b, *extra, params=dev_params)
                    jax.block_until_ready(
                        self._score_dev(tokens, params=dev_params))
            # the mirror itself is recomputed from the candidate and
            # swapped under the lock; a mirror that cannot be made takes the
            # twin out of service rather than leave it scoring the old model
            # dmlint: ignore[DM-L001] presence probe
            host_live = self._host_params is not None
            host_params = None
            if host_live:
                try:
                    host_params = jax.device_put(params, self._cpu_device)
                except Exception as exc:  # noqa: BLE001 — the device path still serves
                    self._host_twin_state = (
                        f"failed: {type(exc).__name__}: {exc}")
                    import logging

                    logging.getLogger(__name__).exception(
                        "host twin could not mirror the candidate; every "
                        "batch scores on %s", self._exec.label)
            with self._fit_lock:
                # float serves until the candidate is requantized below
                self._exec.install(dev_params, dev_opt)
                if host_live:
                    self._host_params = host_params
                self._model_version = int(version)
        result = {"swapped": True, "version": int(version),
                  "prewarmed_buckets": warmed,
                  "backend": self._exec.backend}
        if self._int8w:
            result["int8"] = self._activate_int8(where="install")
        return result

    def save_params_checkpoint(self, directory: str, params,
                               opt_state) -> None:
        """Persist an EXPLICIT param tree (a rollout candidate) with this
        detector's state metadata — the versioned-store twin of
        ``save_checkpoint``, which persists the live tree."""
        from ...utils.checkpoint import MODEL_TREE_VERSIONS, save_scorer_state

        save_scorer_state(directory, params, opt_state, self.state_dict(),
                          tree_version=MODEL_TREE_VERSIONS.get(
                              self.config.model, 1))

    def load_params_checkpoint(self, directory: str):
        """Load a stored version's trees against the live templates WITHOUT
        installing them (promote-by-version / rollback load through here,
        then ``install_candidate``)."""
        from ...utils.checkpoint import (COMPATIBLE_TREE_VERSIONS,
                                         load_scorer_state)

        self._ensure_scorer()
        accepted = COMPATIBLE_TREE_VERSIONS.get(self.config.model, {1})
        # any live generation's tree structure restores identically, each
        # leaf with the live one's placement
        return load_scorer_state(directory, self._exec.params,
                                 self._exec.opt_state,
                                 accepted_tree_versions=accepted)

    # -- runtime reconfigure (POST /admin/reconfigure end-to-end) --------
    def validate_reconfigure(self, new_config) -> None:
        """Veto changes that would require rebuilding the compiled model or
        re-calibrating in different units — those need a restart/refit, and
        silently accepting them would mis-calibrate detection."""
        super().validate_reconfigure(new_config)
        frozen = ("model", "vocab_size", "seq_len", "dim", "depth", "heads",
                  "score_topk", "score_vocab", "score_norm", "mesh_shape",
                  "attn_impl", "dtype", "head_impl")
        for field in frozen:
            if getattr(new_config, field) != getattr(self.config, field):
                raise LibraryError(
                    f"{field!r} cannot change at runtime (old="
                    f"{getattr(self.config, field)!r} new="
                    f"{getattr(new_config, field)!r}); restart the service")

    def apply_config(self) -> None:
        """React to a live config swap: threshold semantics re-derive
        immediately (explicit score_threshold wins; a new threshold_sigma
        recomputes from the stored calibration stats; pre-fit, a withdrawn
        override clears so the upcoming fit calibrates instead of keeping
        the stale value forever)."""
        super().apply_config()
        if self.config.featurize_threads > 0:
            kern = self._matchkern()
            if kern is not None:
                kern.set_featurize_threads(self.config.featurize_threads)
        # batching knobs apply live: an existing coalescer re-reads the
        # budget/target (held rows keep their original arrival stamps); a
        # deadline turned off drains on the next pump (reason "flush")
        if self._coalescer is not None and self.config.batch_deadline_ms > 0:
            self._coalescer.deadline_s = self.config.batch_deadline_ms / 1000.0
            self._coalescer.target_occupancy = self.config.batch_target_occupancy
        if self.config.score_threshold is not None:
            self._threshold = float(self.config.score_threshold)
        elif self._calib_stats is not None:
            mean, std = self._calib_stats
            self._threshold = float(mean + self.config.threshold_sigma * std)
        elif not self._fitted:
            self._threshold = None  # the upcoming fit calibrates fresh
        else:
            # fitted but no stored calibration (e.g. a pre-calib-stats
            # checkpoint): nothing to recompute from — keep the live value
            # and say so rather than silently honoring half the request
            import logging

            logging.getLogger(__name__).warning(
                "reconfigure: no stored calibration stats; threshold stays %r",
                self._threshold)

    # -- state checkpointing (orbax; closes SURVEY §5.4) -----------------
    def state_dict(self) -> Dict[str, Any]:
        state = {
            "trained": self._trained,
            "threshold": self._threshold,
            "fitted": self._fitted,
            "calib_stats": (None if self._calib_stats is None
                            else list(self._calib_stats)),
            "norm_mu": None if self._norm_mu is None else self._norm_mu.tolist(),
            "norm_sigma": (None if self._norm_sigma is None
                           else self._norm_sigma.tolist()),
        }
        # candidate-vocab subset: numpy's Generator bit-stream is not
        # guaranteed stable across numpy versions, so "same seed" does not
        # guarantee the same subset after a restore under a different numpy —
        # which would silently shift the score_vocab approximation out from
        # under the fit-frozen threshold. Persist the ids and reuse them.
        cand = getattr(self._scorer, "_cand_cache", None)
        if cand is not None:
            state["cand_key"] = list(cand[0])
            state["cand_ids"] = cand[1].tolist()
        return state

    def save_checkpoint(self, directory: str) -> None:
        from ...utils.checkpoint import MODEL_TREE_VERSIONS, save_scorer_state

        # a boundary fit mutates params/threshold concurrently — land it
        # first so the checkpoint is a consistent post-fit snapshot
        self._finish_fit(wait=True)

        version = MODEL_TREE_VERSIONS.get(self.config.model, 1)
        # _finish_fit(wait=True) above ended the only racing writer
        save_scorer_state(directory, self._exec.params, self._exec.opt_state,
                          self.state_dict(), tree_version=version)

    def load_checkpoint(self, directory: str) -> None:
        from ...utils.checkpoint import (COMPATIBLE_TREE_VERSIONS,
                                         load_scorer_state)

        self._ensure_scorer()
        accepted = COMPATIBLE_TREE_VERSIONS.get(self.config.model, {1})
        # restore against the live trees so each leaf comes back with its
        # placement (one device, or its mesh sharding) intact
        params, opt_state, meta = load_scorer_state(
            directory, self._exec.params, self._exec.opt_state,
            accepted_tree_versions=accepted,
        )
        self._exec.install(params, opt_state)
        self._trained = int(meta.get("trained", 0))
        self._fitted = bool(meta.get("fitted", False))
        cand_key, cand_ids = meta.get("cand_key"), meta.get("cand_ids")
        if cand_key is not None and cand_ids is not None:
            # reuse the checkpointed subset verbatim — regenerating from the
            # seed under a different numpy could shift the approximation and
            # decalibrate the restored threshold
            cache = (tuple(cand_key), np.asarray(cand_ids, np.int32))
            self._scorer._cand_cache = cache
            twin = getattr(self, "_host_twin_scorer", None)
            if twin is not None and twin is not self._scorer:
                twin._cand_cache = cache
        stats = meta.get("calib_stats")
        self._calib_stats = None if stats is None else (float(stats[0]),
                                                        float(stats[1]))
        mu, sigma = meta.get("norm_mu"), meta.get("norm_sigma")
        # norm-mode mismatch: the checkpointed threshold is in the units the
        # checkpoint was calibrated under (z-scores with norm stats, raw NLL
        # without); applying it across a mode change silently mis-calibrates
        # detection, so it is discarded (fail open) unless config overrides
        norm_mismatch = (mu is not None) != (self.config.score_norm == "position")
        if self.config.score_norm == "position":
            self._norm_mu = None if mu is None else np.asarray(mu, np.float32)
            self._norm_sigma = (None if sigma is None
                                else np.asarray(sigma, np.float32))
        else:
            # a config that turned normalization off outranks checkpointed
            # calibration — otherwise scores and threshold disagree on units
            self._norm_mu = self._norm_sigma = None
        if self.config.score_threshold is not None:
            # explicit config override outranks the checkpointed calibration
            self._threshold = self.config.score_threshold
        else:
            thr = meta.get("threshold")
            if thr is not None and norm_mismatch:
                import logging

                logging.getLogger(__name__).warning(
                    "checkpoint norm calibration (%s) does not match config "
                    "score_norm=%r: discarding the checkpointed threshold "
                    "(alerts disabled until reconfigured or refitted)",
                    "present" if mu is not None else "absent",
                    self.config.score_norm)
                self._threshold = float("inf")
            elif thr is not None:
                self._threshold = float(thr)
            elif self._fitted:
                self._threshold = float("inf")
            else:
                # unfitted checkpoint: drop any stale in-memory calibration so
                # the next fit() recalibrates for the restored run
                self._threshold = None
        if self._int8w and self._fitted:
            # re-quantize from the restored float tree (the checkpoint
            # stores float weights — int8 is a serving-time representation).
            # Without a parity corpus in this process the activation is
            # ungated and the report records gated=False.
            self._activate_int8(where="restore")
        self._sync_host_params()
