"""ctypes bindings for the native hot-path kernels (native/matchkern/dmkern.c).

Role of the reference's ``detectmateperformance`` pybind11 package
(reference: uv.lock:278,301-310); this image has no pybind11, so the binding
layer is ctypes over a plain C shared library. The library is not shipped:
it is built from native/matchkern/dmkern.c on first import (or by
native/build.sh) and rebuilt whenever it reports another feature version.
Without a C compiler the import raises ImportError; importers then take the
pure-Python paths and say so.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
from pathlib import Path
from typing import List, Sequence, Tuple

import numpy as np

_PKG_DIR = Path(__file__).resolve().parent.parent
_LIB_PATH = _PKG_DIR / "_native" / "libdmkern.so"
_SRC_PATH = _PKG_DIR.parent / "native" / "matchkern" / "dmkern.c"

# Feature version this binding layer expects the library to report
# (dm_feature_version). The library is built from native/, never shipped:
# the loader and native/build.sh both stamp THIS number into the .so, and a
# library that is missing or reports another number is rebuilt — the one
# staleness rule (file times mean nothing on a fresh checkout). Bump it
# whenever a kernel's ABI or semantics change (and the default in
# native/matchkern/dmkern.c with it, for bare `cc` builds).
DM_FEATURE_VERSION = 7


def _rebuild() -> None:
    """Compile to a temp file and atomically replace, so concurrent importers
    never dlopen a half-written library."""
    import tempfile

    _LIB_PATH.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=str(_LIB_PATH.parent))
    os.close(fd)
    try:
        subprocess.run(["cc", "-O3", "-shared", "-fPIC", "-pthread",
                        f"-DDM_FEATURE_VERSION={DM_FEATURE_VERSION}",
                        "-o", tmp, str(_SRC_PATH)],
                       check=True, capture_output=True, timeout=120)
        os.chmod(tmp, 0o755)  # mkstemp creates 0600; other users must dlopen
        os.replace(tmp, str(_LIB_PATH))
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _lib_feature_version(lib: ctypes.CDLL) -> int:
    """Version the loaded library reports; 0 for pre-versioning builds."""
    try:
        fn = lib.dm_feature_version
    except AttributeError:
        return 0
    fn.restype = ctypes.c_int
    return int(fn())


def _close(lib: ctypes.CDLL) -> None:
    """Drop a mapping: dlopen returns the object it already holds for a
    path, so a stale library must be closed before its rebuilt successor
    at the same path can be mapped."""
    import _ctypes

    _ctypes.dlclose(lib._handle)


def _load() -> ctypes.CDLL:
    lib = ctypes.CDLL(str(_LIB_PATH)) if _LIB_PATH.exists() else None
    if lib is None or _lib_feature_version(lib) != DM_FEATURE_VERSION:
        # missing or stale: build from source
        if lib is not None:
            _close(lib)
        try:
            _rebuild()
        except (subprocess.SubprocessError, OSError) as exc:
            detail = getattr(exc, "stderr", b"") or b""
            raise ImportError(
                f"cannot build native kernel library from {_SRC_PATH}: {exc} "
                f"{detail.decode('utf-8', 'replace')[-500:]}".rstrip())
        lib = ctypes.CDLL(str(_LIB_PATH))
        if _lib_feature_version(lib) != DM_FEATURE_VERSION:
            raise ImportError(
                f"stale native kernel library {_LIB_PATH}: reports feature "
                f"version {_lib_feature_version(lib)} after a rebuild, "
                f"bindings expect {DM_FEATURE_VERSION}")
    lib.dm_featurize_batch.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_int, ctypes.c_int32,
    ]
    lib.dm_featurize_set_threads.argtypes = [ctypes.c_int]
    lib.dm_featurize_set_threads.restype = ctypes.c_int
    lib.dm_featurize_get_threads.restype = ctypes.c_int
    lib.dm_encode_batch.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int, ctypes.c_int32,
    ]
    lib.dm_match_templates.argtypes = [
        ctypes.c_char_p, ctypes.c_int,
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
    ]
    lib.dm_match_templates.restype = ctypes.c_int
    lib.dm_match_extract.argtypes = [
        ctypes.c_char_p, ctypes.c_int,
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.dm_match_extract.restype = ctypes.c_int
    lib.dm_match_extract_batch.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
    ]
    lib.dm_count_frame_msgs.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.dm_count_frame_msgs.restype = ctypes.c_int64
    lib.dm_featurize_frames.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int32,
    ]
    lib.dm_featurize_frames.restype = ctypes.c_int64
    # dm_parse_batch landed in round 5: an older committed .so may lack it
    # (a host without a compiler keeps using the rest of the kernels)
    if hasattr(lib, "dm_parse_batch"):
        lib.dm_parse_batch.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.c_int,
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int, ctypes.c_int,
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int,
            ctypes.c_int64, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int8),
        ]
        lib.dm_parse_batch.restype = ctypes.c_int64
    if hasattr(lib, "dm_parse_frames"):
        lib.dm_parse_frames.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int,
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int, ctypes.c_int,
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int,
            ctypes.c_int64, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int8),
        ]
        lib.dm_parse_frames.restype = ctypes.c_int64
    if hasattr(lib, "dm_parse_logs_batch"):
        lib.dm_parse_logs_batch.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int8),
        ]
        lib.dm_parse_logs_frames.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int8),
        ]
        lib.dm_parse_logs_frames.restype = ctypes.c_int64
        lib.dm_emit_parser_rows.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_int32),
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_char_p, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int,
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.dm_emit_parser_rows.restype = ctypes.c_int64
    if hasattr(lib, "dm_shm_acquire"):
        lib.dm_shm_init.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.dm_shm_acquire.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.dm_shm_acquire.restype = ctypes.c_int
        lib.dm_shm_publish.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                       ctypes.c_int]
        lib.dm_shm_publish.restype = ctypes.c_uint32
        lib.dm_shm_release.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                       ctypes.c_uint32]
        lib.dm_shm_release.restype = ctypes.c_int
        lib.dm_shm_abandon.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.dm_shm_state.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.dm_shm_state.restype = ctypes.c_int
        lib.dm_shm_gen.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.dm_shm_gen.restype = ctypes.c_uint32
    if hasattr(lib, "dm_nvd_scan"):
        lib.dm_nvd_build.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64,
        ]
        lib.dm_nvd_build.restype = ctypes.c_int
        lib.dm_nvd_scan.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int8),
        ]
    return lib


_lib = _load()


def set_featurize_threads(n: int) -> int:
    """Set the featurization pool width; returns the effective width.

    0 (or negative) = auto: min(4, online cores), the conservative default —
    featurization shares the host with jax dispatch/readback and (on CPU
    fallback hosts) XLA itself, so grabbing every core hurts more than it
    helps. The pool is PROCESS-WIDE (the C side keeps one pool); the widest
    setter wins. Threads spawn lazily on the first large batch and sleep on
    a condvar between jobs."""
    return int(_lib.dm_featurize_set_threads(int(n)))


def featurize_threads() -> int:
    """Current featurization pool width (resolving auto to its value)."""
    return int(_lib.dm_featurize_get_threads())


def lib_feature_version() -> int:
    """Feature version the loaded library reports (== DM_FEATURE_VERSION,
    enforced at import)."""
    return _lib_feature_version(_lib)


# env override for ops tuning without touching component config; auto default
set_featurize_threads(int(os.environ.get("DM_FEATURIZE_THREADS", "0") or 0))

_I64P = ctypes.POINTER(ctypes.c_int64)
_I32P = ctypes.POINTER(ctypes.c_int32)
_U8P = ctypes.POINTER(ctypes.c_uint8)

# 1-element placeholders handed to the parse kernels when no template
# matcher is configured (n_templates == 0: the C side never dereferences)
_ZERO_I64 = np.zeros(1, dtype=np.int64)
_ZERO_I32 = np.zeros(1, dtype=np.int32)
_ZERO_U8 = np.zeros(1, dtype=np.uint8)


def _pack(chunks: Sequence[bytes]) -> Tuple[bytes, np.ndarray]:
    offsets = np.zeros(len(chunks) + 1, dtype=np.int64)
    np.cumsum([len(c) for c in chunks], out=offsets[1:])
    return b"".join(chunks), offsets


def featurize_batch(msgs: Sequence[bytes], seq_len: int,
                    vocab_size: int) -> Tuple[np.ndarray, np.ndarray]:
    """Serialized ParserSchema bytes → ([N, seq_len] int32 tokens, [N] ok)."""
    blob, offsets = _pack(msgs)
    out = np.zeros((len(msgs), seq_len), dtype=np.int32)
    ok = np.zeros(len(msgs), dtype=np.uint8)
    _lib.dm_featurize_batch(
        blob, offsets.ctypes.data_as(_I64P), len(msgs),
        out.ctypes.data_as(_I32P), ok.ctypes.data_as(_U8P),
        seq_len, vocab_size,
    )
    return out, ok.astype(bool)


class FrameBatch:
    """Result of ``featurize_frames``: token rows plus lazy raw access.

    ``raws[i]`` slices the original frame blob only when asked — on the hot
    path only the ~1% anomalous messages (alert construction) and mid-fit
    backlog entries ever materialize their bytes.
    """

    __slots__ = ("tokens", "ok", "blob", "spans", "n_corrupt_frames", "n_lines")

    def __init__(self, tokens: np.ndarray, ok: np.ndarray, blob: bytes,
                 spans: np.ndarray, n_corrupt_frames: int, n_lines: int):
        self.tokens = tokens
        self.ok = ok
        self.blob = blob
        self.spans = spans                      # [n, 2] int64 [start, end)
        self.n_corrupt_frames = n_corrupt_frames
        self.n_lines = n_lines                  # engine newline-rule total

    def __len__(self) -> int:
        return len(self.ok)

    def raw(self, i: int) -> bytes:
        s, e = self.spans[i]
        return self.blob[s:e]


class SpanRaws:
    """List-of-bytes stand-in over (blob, spans): supports the indexing the
    scorer's dispatch/drain path uses without materializing N bytes objects."""

    __slots__ = ("blob", "spans")

    def __init__(self, blob: bytes, spans: np.ndarray):
        self.blob = blob
        self.spans = spans

    def __len__(self) -> int:
        return len(self.spans)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return SpanRaws(self.blob, self.spans[i])
        s, e = self.spans[i]
        return self.blob[s:e]


def featurize_frames(frames: Sequence[bytes], seq_len: int,
                     vocab_size: int) -> FrameBatch:
    """Wire frames (packed batch frames and/or single messages) → token
    rows, ok flags, and lazy raw-byte spans — one C crossing for the whole
    burst, no per-message Python objects."""
    blob, offsets = _pack(frames)
    n_frames = len(frames)
    counts = np.zeros(n_frames, dtype=np.int32)
    corrupt = np.zeros(n_frames, dtype=np.uint8)
    lines = np.zeros(1, dtype=np.int64)
    # the count pass filters packed empty messages (engine parity), so row
    # allocations are sized by real payloads only — a sender cannot buy a
    # token row for one wire byte
    total = int(_lib.dm_count_frame_msgs(
        blob, offsets.ctypes.data_as(_I64P), n_frames,
        counts.ctypes.data_as(_I32P), corrupt.ctypes.data_as(_U8P),
        lines.ctypes.data_as(_I64P)))
    tokens = np.zeros((total, seq_len), dtype=np.int32)
    ok = np.zeros(total, dtype=np.uint8)
    spans = np.zeros((total, 2), dtype=np.int64)
    if total:
        _lib.dm_featurize_frames(
            blob, offsets.ctypes.data_as(_I64P), n_frames,
            counts.ctypes.data_as(_I32P), corrupt.ctypes.data_as(_U8P),
            tokens.ctypes.data_as(_I32P), ok.ctypes.data_as(_U8P),
            spans.ctypes.data_as(_I64P), seq_len, vocab_size)
    return FrameBatch(tokens, ok.astype(bool), blob, spans,
                      int(corrupt.sum()), int(lines[0]))


def encode_batch(texts: Sequence[str], seq_len: int, vocab_size: int) -> np.ndarray:
    """Raw text lines → [N, seq_len] int32 token rows."""
    blob, offsets = _pack([t.encode("utf-8") for t in texts])
    out = np.zeros((len(texts), seq_len), dtype=np.int32)
    _lib.dm_encode_batch(
        blob, offsets.ctypes.data_as(_I64P), len(texts),
        out.ctypes.data_as(_I32P), seq_len, vocab_size,
    )
    return out


class TemplateMatcher:
    """Native first-match template scan; Python regex extracts the wildcard
    captures only for the one template the scan selected."""

    def __init__(self, templates: List[str]):
        import re

        self._templates = templates
        segments: List[bytes] = []
        counts = np.zeros(len(templates), dtype=np.int32)
        starts = np.zeros(len(templates), dtype=np.uint8)
        ends = np.zeros(len(templates), dtype=np.uint8)
        self._extract_res = []
        for i, template in enumerate(templates):
            parts = template.split("<*>")
            segments.extend(p.encode("utf-8") for p in parts)
            counts[i] = len(parts)
            starts[i] = 1 if template.startswith("<*>") else 0
            ends[i] = 1 if template.endswith("<*>") else 0
            escaped = [re.escape(p) for p in parts]
            if len(escaped) > 1:
                pattern = ("^" + "(.*?)".join(escaped[:-1]) + "(.*)" + escaped[-1] + "$")
            else:
                pattern = "^" + escaped[0] + "$"
            self._extract_res.append(re.compile(pattern))
        self._seg_blob, self._seg_offsets = _pack(segments)
        self._counts = counts
        self._starts = starts
        self._ends = ends
        # pointer conversions cost ~6 µs/call through ctypes; cache them
        # (the arrays are never reallocated) — measured 25% of the parser's
        # per-line budget before caching
        self._seg_offsets_p = self._seg_offsets.ctypes.data_as(_I64P)
        self._counts_p = counts.ctypes.data_as(_I32P)
        self._starts_p = starts.ctypes.data_as(_U8P)
        self._ends_p = ends.ctypes.data_as(_U8P)
        self._max_caps = max(1, int(counts.max()) if len(counts) else 1)
        # one reusable capture buffer per matcher: the engine loop is the
        # only caller on the hot path (per-thread reuse is safe there); the
        # buffer is reallocated per call ONLY if a caller races, via the
        # ctypes-level copy in np.ctypeslib — keep it simple: allocate in
        # match() when contention is possible is not worth 200 ns, reuse.
        self._caps = np.empty(2 * self._max_caps, dtype=np.int32)
        self._caps_p = self._caps.ctypes.data_as(_I32P)
        self._ncaps = np.zeros(1, dtype=np.int32)
        self._ncaps_p = self._ncaps.ctypes.data_as(_I32P)

    def match(self, line: str) -> Tuple[int, List[str]]:
        """Return (0-based template index, wildcard captures) or (-1, []).

        Captures come from the C scan's byte spans (dm_match_extract) —
        slicing instead of lazy-group regex matching, which was the parser
        hot path's ceiling (~45k lines/s on 8-wildcard templates). Falls
        back to the regex extractor on capture-buffer overflow or when a
        span splits a multi-byte character (possible only when a template
        literal's bytes occur mid-character)."""
        raw = line.encode("utf-8")
        idx = _lib.dm_match_extract(
            raw, len(raw),
            self._seg_blob, self._seg_offsets_p,
            self._counts_p, self._starts_p, self._ends_p,
            len(self._templates),
            self._caps_p, self._max_caps, self._ncaps_p,
        )
        if idx == -1:
            return -1, []
        if idx >= 0:
            n = int(self._ncaps[0])
            caps = self._caps
            try:
                return idx, [raw[caps[2 * k]:caps[2 * k + 1]].decode("utf-8")
                             for k in range(n)]
            except UnicodeDecodeError:
                pass  # span split a multibyte char: regex fallback below
            found = self._extract_res[idx].match(line)
            if found is None:
                return -1, []
            return idx, [g for g in found.groups() if g is not None]
        # idx == -2: more captures than the buffer (cannot happen with the
        # per-template max sizing, but the C contract allows it) — rematch
        idx2 = _lib.dm_match_templates(
            raw, len(raw), self._seg_blob, self._seg_offsets_p,
            self._counts_p, self._starts_p, self._ends_p,
            len(self._templates))
        if idx2 < 0:
            return -1, []
        found = self._extract_res[idx2].match(line)
        if found is None:
            return -1, []
        return idx2, [g for g in found.groups() if g is not None]

    def match_batch(self, lines: List[str]) -> List[Tuple[int, List[str]]]:
        """Batch variant of ``match``: ONE ctypes crossing for the whole
        micro-batch (the per-call overhead was ~20 µs/line, larger than the
        scan itself). Returns one (idx, captures) pair per line."""
        n = len(lines)
        if n == 0:
            return []
        raws = [line.encode("utf-8") for line in lines]
        blob, offsets = _pack(raws)
        idx_out = np.empty(n, dtype=np.int32)
        ncaps = np.empty(n, dtype=np.int32)
        caps = np.empty((n, 2 * self._max_caps), dtype=np.int32)
        _lib.dm_match_extract_batch(
            blob, offsets.ctypes.data_as(_I64P), n,
            self._seg_blob, self._seg_offsets_p,
            self._counts_p, self._starts_p, self._ends_p,
            len(self._templates),
            idx_out.ctypes.data_as(_I32P), caps.ctypes.data_as(_I32P),
            ncaps.ctypes.data_as(_I32P), self._max_caps,
        )
        # plain-list views: numpy scalar indexing costs ~200 ns/access and
        # the assembly loop below does ~18 accesses per line
        idx_list = idx_out.tolist()
        ncaps_list = ncaps.tolist()
        caps_list = caps.tolist()
        results: List[Tuple[int, List[str]]] = []
        for i in range(n):
            idx = idx_list[i]
            if idx == -1:
                results.append((-1, []))
                continue
            if idx >= 0:
                raw = raws[i]
                row = caps_list[i]
                try:
                    results.append((idx, [
                        raw[row[2 * k]:row[2 * k + 1]].decode("utf-8")
                        for k in range(ncaps_list[i])]))
                    continue
                except UnicodeDecodeError:
                    pass  # span split a multibyte char: regex fallback
            results.append(self.match(lines[i]))  # slow-path fallback
        return results


def has_parse_kernel() -> bool:
    """True when the loaded library carries the round-5 fused parser path."""
    return hasattr(_lib, "dm_parse_batch")


class ParseKernel:
    """Fused MatcherParser batch path: LogSchema payloads → serialized
    ParserSchema bytes, one C crossing per micro-batch (dm_parse_batch).

    Rows the kernel cannot process with EXACT Python-path parity come back
    with status -1 and the caller re-runs them in Python — same containment
    pattern as ``featurize_frames``'s ok-mask. ``status`` semantics:
    1 = emitted, 0 = filtered (None), -1 = Python fallback.

    All config-derived arrays are marshalled once at construction (the
    ctypes pointer conversions cost ~6 µs/call otherwise — same lesson as
    TemplateMatcher); ``parse_batch`` only packs the payload blob.
    """

    def __init__(self, lits: List[str], names: List[str], norm_flags: int,
                 accept_raw: bool, matcher, raw_templates: List[str],
                 method_type: str, parser_id: str, version: str):
        # lits/names come from the CALLER's log_format split (the parser owns
        # the capture-token grammar, template_matcher._TOKEN_RE) — one
        # definition of the grammar, one split, both paths agree by
        # construction. Empty lits = no log_format configured.
        self._n_lits = len(lits)
        self._lit_blob, self._lit_offsets = _pack([s.encode() for s in lits])
        self._name_blob, self._name_offsets = _pack([s.encode() for s in names])
        self._lit_offsets_p = self._lit_offsets.ctypes.data_as(_I64P)
        self._name_offsets_p = self._name_offsets.ctypes.data_as(_I64P)
        # dict(zip(names, groups)) is last-wins for duplicate capture names
        self._content_cap = -1
        for i, nm in enumerate(names):
            if nm == "Content":
                self._content_cap = i
        self._norm_flags = norm_flags
        self._accept_raw = 1 if accept_raw else 0
        self._matcher = matcher                    # TemplateMatcher or None
        self._tmpl_blob, self._tmpl_offsets = _pack(
            [t.encode() for t in raw_templates])
        self._tmpl_offsets_p = self._tmpl_offsets.ctypes.data_as(_I64P)
        self._n_templates = len(raw_templates)
        self._consts = (version.encode(), method_type.encode(),
                        parser_id.encode())
        self._names_total = int(self._name_offsets[-1])
        self._tmpl_max = max((len(t.encode()) for t in raw_templates),
                             default=0)
        # an older committed library can carry dm_parse_batch without the
        # frames variant; callers must check before routing frames here
        self.supports_frames = hasattr(_lib, "dm_parse_frames")

    def _seg_args(self):
        """The 7-tuple of template-matcher arrays (or the empty stub)."""
        m = self._matcher
        if m is not None:
            return (m._seg_blob, m._seg_offsets_p, m._counts_p,
                    m._starts_p, m._ends_p, len(m._templates), m._max_caps)
        return (b"", _ZERO_I64.ctypes.data_as(_I64P),
                _ZERO_I32.ctypes.data_as(_I32P),
                _ZERO_U8.ctypes.data_as(_U8P),
                _ZERO_U8.ctypes.data_as(_U8P), 0, 1)

    def _run_with_capacity(self, blob_len: int, n_rows: int, invoke):
        """Allocate the output buffer from the shared worst-case estimate
        and retry the C call with a grown buffer while it reports
        insufficient capacity. ``invoke(out_array, cap) -> used``; -1 means
        the output buffer was too small (grow and retry), -2 means the C
        side failed a malloc (real OOM — growing OUR buffer would only dig
        the hole deeper, so it raises immediately). ONE home for the
        estimate and the retry policy — the batch and frames entry points
        must never diverge on them."""
        cap = int(blob_len * 2 + n_rows * (256 + self._tmpl_max
                                           + self._names_total) + 1024)
        for _ in range(4):
            out = np.empty(cap, dtype=np.uint8)
            used = invoke(out, cap)
            if used >= 0:
                return out[:used].tobytes()
            if used == -2:
                raise MemoryError("parse kernel allocation failed (OOM)")
            if used != -1:
                raise RuntimeError(
                    f"parse kernel returned unknown error code {used}")
            cap *= 4
        raise MemoryError("parse kernel output buffer kept overflowing")

    def parse_batch(self, payloads: Sequence[bytes]):
        """→ (status int8 array, out blob bytes, offsets int64 array)."""
        import os
        import time

        n = len(payloads)
        blob, offsets = _pack(payloads)
        status = np.full(n, -1, dtype=np.int8)
        out_offsets = np.zeros(n + 1, dtype=np.int64)
        rand_hex = os.urandom(16 * n).hex().encode() if n else b""
        now = int(time.time())
        seg = self._seg_args()
        version, method_type, parser_id = self._consts

        def invoke(out, cap):
            return int(_lib.dm_parse_batch(
                blob, offsets.ctypes.data_as(_I64P), n, self._accept_raw,
                self._lit_blob, self._lit_offsets_p, self._n_lits,
                self._name_blob, self._name_offsets_p,
                self._content_cap, self._norm_flags,
                seg[0], seg[1], seg[2], seg[3], seg[4], seg[5],
                self._tmpl_blob, self._tmpl_offsets_p, seg[6],
                version, len(version), method_type, len(method_type),
                parser_id, len(parser_id),
                now, rand_hex,
                out.ctypes.data_as(_U8P), cap,
                out_offsets.ctypes.data_as(_I64P),
                status.ctypes.data_as(ctypes.POINTER(ctypes.c_int8))))

        out_blob = self._run_with_capacity(len(blob), n, invoke)
        return status, out_blob, out_offsets

    def parse_frames(self, frames: Sequence[bytes]) -> "ParsedFrames":
        """Wire frames (packed batch frames and/or single messages) →
        serialized ParserSchema bytes per contained message, one C crossing
        for the whole burst (count pass + dm_parse_frames) — the parser
        service's analog of the detector's featurize_frames."""
        import os
        import time

        blob, offsets = _pack(frames)
        n_frames = len(frames)
        counts = np.zeros(n_frames, dtype=np.int32)
        corrupt = np.zeros(n_frames, dtype=np.uint8)
        lines = np.zeros(1, dtype=np.int64)
        total = int(_lib.dm_count_frame_msgs(
            blob, offsets.ctypes.data_as(_I64P), n_frames,
            counts.ctypes.data_as(_I32P), corrupt.ctypes.data_as(_U8P),
            lines.ctypes.data_as(_I64P)))
        status = np.full(total, -1, dtype=np.int8)
        out_offsets = np.zeros(total + 1, dtype=np.int64)
        spans = np.zeros((total, 2), dtype=np.int64)
        if total == 0:
            return ParsedFrames(status, b"", out_offsets, blob, spans,
                                int(corrupt.sum()), int(lines[0]))
        rand_hex = os.urandom(16 * total).hex().encode()
        now = int(time.time())
        seg = self._seg_args()
        version, method_type, parser_id = self._consts

        def invoke(out, cap):
            return int(_lib.dm_parse_frames(
                blob, offsets.ctypes.data_as(_I64P), n_frames,
                counts.ctypes.data_as(_I32P), corrupt.ctypes.data_as(_U8P),
                self._accept_raw,
                self._lit_blob, self._lit_offsets_p, self._n_lits,
                self._name_blob, self._name_offsets_p,
                self._content_cap, self._norm_flags,
                seg[0], seg[1], seg[2], seg[3], seg[4], seg[5],
                self._tmpl_blob, self._tmpl_offsets_p, seg[6],
                version, len(version), method_type, len(method_type),
                parser_id, len(parser_id),
                now, rand_hex,
                out.ctypes.data_as(_U8P), cap,
                spans.ctypes.data_as(_I64P),
                out_offsets.ctypes.data_as(_I64P),
                status.ctypes.data_as(ctypes.POINTER(ctypes.c_int8))))

        out_blob = self._run_with_capacity(len(blob), total, invoke)
        return ParsedFrames(status, out_blob, out_offsets, blob, spans,
                            int(corrupt.sum()), int(lines[0]))


class ParsedFrames:
    """Result of ``ParseKernel.parse_frames``: per-message outputs plus lazy
    raw access for the fallback/error paths (same shape as FrameBatch)."""

    __slots__ = ("status", "out_blob", "ends", "frames_blob", "spans",
                 "n_corrupt_frames", "n_lines")

    def __init__(self, status, out_blob, ends, frames_blob, spans,
                 n_corrupt_frames, n_lines):
        self.status = status              # [m] int8: 1 ok / 0 filtered / -1
        self.out_blob = out_blob          # packed ParserSchema bytes
        self.ends = ends                  # [m+1] prefix ends into out_blob
        self.frames_blob = frames_blob
        self.spans = spans                # [m, 2] raw-byte spans per message
        self.n_corrupt_frames = n_corrupt_frames
        self.n_lines = n_lines

    def __len__(self) -> int:
        return len(self.status)

    def raw(self, i: int) -> bytes:
        s, e = self.spans[i]
        return self.frames_blob[s:e]


def has_logs_kernel() -> bool:
    """True when the loaded library carries the native LogSchema decode and
    ParserSchema emit entry points (the zero-copy host-path round)."""
    return hasattr(_lib, "dm_parse_logs_batch")


class LogsView:
    """Lazy (log, logID) field views over a decoded ingest blob.

    SpanRaws-style: nothing is sliced until a field is actually read, so the
    batched parser path materializes exactly the strings it needs and never
    a pb2 object. ``status`` semantics (dm_parse_logs_*): 1 = envelope,
    2 = raw line, 0 = JSON record (Python's json path), -1 = Python decode
    fallback (strict parse failure)."""

    __slots__ = ("blob", "spans", "fspans", "status", "n_corrupt_frames",
                 "n_lines")

    def __init__(self, blob: bytes, spans, fspans, status,
                 n_corrupt_frames: int = 0, n_lines: int = 0):
        self.blob = blob
        self.spans = spans            # [n, 2] payload byte spans
        self.fspans = fspans          # [n, 4] log/logID field spans
        self.status = status          # [n] int8
        self.n_corrupt_frames = n_corrupt_frames
        self.n_lines = n_lines

    def __len__(self) -> int:
        return len(self.status)

    def raw(self, i: int) -> bytes:
        s, e = self.spans[i]
        return self.blob[s:e]

    def raws(self) -> "SpanRaws":
        return SpanRaws(self.blob, self.spans)

    def log(self, i: int) -> str:
        """The row's ``log`` field. Envelope spans were UTF-8-validated in
        C; raw-line spans decode with errors="replace", exactly like
        ``decode_ingest_payload``'s bare-line shape."""
        row = self.fspans[i]
        s, e = row[0], row[1]
        if self.status[i] == 2:
            return self.blob[s:e].decode("utf-8", errors="replace")
        return self.blob[s:e].decode("utf-8")

    def log_id(self, i: int) -> str:
        row = self.fspans[i]
        return self.blob[row[2]:row[3]].decode("utf-8")


def parse_logs_batch(payloads: Sequence[bytes], accept_raw: bool) -> LogsView:
    """Payload list → lazy (log, logID) field views, one C crossing."""
    blob, offsets = _pack(payloads)
    n = len(payloads)
    fspans = np.zeros((n, 4), dtype=np.int64)
    status = np.full(n, -1, dtype=np.int8)
    if n:
        _lib.dm_parse_logs_batch(
            blob, offsets.ctypes.data_as(_I64P), n, 1 if accept_raw else 0,
            fspans.ctypes.data_as(_I64P),
            status.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)))
    spans = np.stack([offsets[:-1], offsets[1:]], axis=1)
    return LogsView(blob, spans, fspans, status)


def parse_logs_frames(frames: Sequence[bytes], accept_raw: bool) -> LogsView:
    """Wire frames → lazy per-message (log, logID) field views: frame
    expansion and LogSchema decode in one C pass, no per-message Python
    objects until a field is read."""
    blob, offsets = _pack(frames)
    n_frames = len(frames)
    counts = np.zeros(n_frames, dtype=np.int32)
    corrupt = np.zeros(n_frames, dtype=np.uint8)
    lines = np.zeros(1, dtype=np.int64)
    total = int(_lib.dm_count_frame_msgs(
        blob, offsets.ctypes.data_as(_I64P), n_frames,
        counts.ctypes.data_as(_I32P), corrupt.ctypes.data_as(_U8P),
        lines.ctypes.data_as(_I64P)))
    spans = np.zeros((total, 2), dtype=np.int64)
    fspans = np.zeros((total, 4), dtype=np.int64)
    status = np.full(total, -1, dtype=np.int8)
    if total:
        _lib.dm_parse_logs_frames(
            blob, offsets.ctypes.data_as(_I64P), n_frames,
            counts.ctypes.data_as(_I32P), corrupt.ctypes.data_as(_U8P),
            1 if accept_raw else 0,
            spans.ctypes.data_as(_I64P), fspans.ctypes.data_as(_I64P),
            status.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)))
    return LogsView(blob, spans, fspans, status,
                    int(corrupt.sum()), int(lines[0]))


class ParserEmitter:
    """Native ParserSchema serializer over a REUSABLE output arena.

    One C crossing serializes a whole batch of rows byte-identically to pb2
    ``SerializeToString`` (same emitters as ``parse_one_row``, whose output
    parity the differential fuzzer pins). The arena persists across calls —
    no per-batch allocation, no whole-blob copy; callers slice the rows they
    forward straight out of it."""

    def __init__(self, version: str, method_type: str, parser_id: str):
        self._consts = (version.encode(), method_type.encode(),
                        parser_id.encode())
        self._arena = np.empty(1 << 16, dtype=np.uint8)

    def emit(self, event_ids, templates, variables, log_ids, kv_items,
             now: int, rand_hex: bytes):
        """Serialize ``n`` rows; returns ``(arena, offsets)`` — row i is
        ``arena[offsets[i]:offsets[i+1]]``.

        ``variables`` is a list of per-row lists of bytes; ``kv_items`` a
        list of per-row lists of (key bytes, value bytes) pairs, already
        deduplicated in dict insertion order; ``rand_hex`` carries 32 hex
        chars per row (the parsedLogID pool)."""
        n = len(event_ids)
        eid = np.asarray(event_ids, dtype=np.int32)
        tmpl_blob, tmpl_offs = _pack(templates)
        var_flat = [v for row in variables for v in row]
        var_counts = np.asarray([len(row) for row in variables],
                                dtype=np.int32)
        var_blob, var_offs = _pack(var_flat)
        id_blob, id_offs = _pack(log_ids)
        key_flat = [k for row in kv_items for k, _ in row]
        val_flat = [v for row in kv_items for _, v in row]
        kv_counts = np.asarray([len(row) for row in kv_items],
                               dtype=np.int32)
        key_blob, key_offs = _pack(key_flat)
        val_blob, val_offs = _pack(val_flat)
        ts = np.full(n, int(now), dtype=np.int64)
        version, method_type, parser_id = self._consts
        out_offsets = np.zeros(n + 1, dtype=np.int64)
        while True:
            used = int(_lib.dm_emit_parser_rows(
                n, eid.ctypes.data_as(_I32P),
                tmpl_blob, tmpl_offs.ctypes.data_as(_I64P),
                var_blob, var_offs.ctypes.data_as(_I64P),
                var_counts.ctypes.data_as(_I32P),
                id_blob, id_offs.ctypes.data_as(_I64P),
                key_blob, key_offs.ctypes.data_as(_I64P),
                val_blob, val_offs.ctypes.data_as(_I64P),
                kv_counts.ctypes.data_as(_I32P),
                version, len(version), method_type, len(method_type),
                parser_id, len(parser_id),
                rand_hex,
                ts.ctypes.data_as(_I64P), ts.ctypes.data_as(_I64P),
                self._arena.ctypes.data_as(_U8P), len(self._arena),
                out_offsets.ctypes.data_as(_I64P)))
            if used >= 0:
                return self._arena, out_offsets
            # arena too small: grow geometrically and keep it (reusable)
            need = (len(tmpl_blob) + len(var_blob) + len(id_blob)
                    + len(key_blob) + len(val_blob) + 256 * n + 1024)
            self._arena = np.empty(max(len(self._arena) * 2, need),
                                   dtype=np.uint8)


# -- shm slot refcounts (dm_shm_*) -------------------------------------------
# Thin pass-throughs over the C11-atomic slot protocol (see dmkern.c): the
# zero-copy framing's sender/receiver sides both operate on a mapped header
# region through these, never through plain Python writes. `addr` is the
# base address of the header region (e.g. np.frombuffer(mmap).ctypes.data).

SHM_SLOT_STRIDE = 16


def has_shm_kernel() -> bool:
    return hasattr(_lib, "dm_shm_acquire")


def shm_header_bytes(n_slots: int) -> int:
    return n_slots * SHM_SLOT_STRIDE


def shm_init(addr: int, n_slots: int) -> None:
    _lib.dm_shm_init(addr, n_slots)


def shm_acquire(addr: int, n_slots: int) -> int:
    """Claim a FREE slot for writing; -1 when none (copy-downgrade)."""
    return int(_lib.dm_shm_acquire(addr, n_slots))


def shm_publish(addr: int, slot: int, refs: int) -> int:
    """Publish an acquired slot with `refs` readers; returns the gen."""
    return int(_lib.dm_shm_publish(addr, slot, refs))


def shm_release(addr: int, slot: int, gen: int) -> int:
    """Drop one reference; returns remaining refs, -1 for a stale ref."""
    return int(_lib.dm_shm_release(addr, slot, gen))


def shm_abandon(addr: int, slot: int) -> None:
    _lib.dm_shm_abandon(addr, slot)


def shm_state(addr: int, slot: int) -> int:
    return int(_lib.dm_shm_state(addr, slot))


def shm_gen(addr: int, slot: int) -> int:
    return int(_lib.dm_shm_gen(addr, slot))


def has_nvd_kernel() -> bool:
    return hasattr(_lib, "dm_nvd_scan")


NVD_EVENT_NONE = -(2 ** 63)  # C sentinel for "no EventID" (INT64_MIN)


class NvdScanKernel:
    """NewValueDetector steady-state scan: an EXACT (byte-equality)
    open-addressing table of (watch-key id, seen value) probed natively
    per batch. Verdict 0 = proven no-alert; -1 = run the row in Python.
    A STALE table (Python inserted values since the build, e.g. alert_once)
    only over-flags rows to Python — it can never suppress an alert — so
    rebuilds are a perf decision, not a correctness one.

    ``plans`` is {event_id_or_None: [(key_id, is_header, pos_or_name)]};
    ``seen_items`` is [(key_id, value_str)].
    """

    def __init__(self, plans, seen_items):
        events = []
        offs = [0]
        key_ids: List[int] = []
        headers: List[int] = []
        poss: List[int] = []
        names: List[bytes] = []
        for event_id, plan in plans.items():
            events.append(NVD_EVENT_NONE if event_id is None else int(event_id))
            for key_id, is_header, pos in plan:
                key_ids.append(key_id)
                headers.append(1 if is_header else 0)
                poss.append(-1 if is_header else int(pos))
                names.append(str(pos).encode() if is_header else b"")
            offs.append(len(key_ids))
        self._events = np.asarray(events, dtype=np.int64)
        self._offs = np.asarray(offs, dtype=np.int32)
        self._key_ids = np.asarray(key_ids, dtype=np.int32)
        self._headers = np.asarray(headers, dtype=np.uint8)
        self._poss = np.asarray(poss, dtype=np.int32)
        self._name_blob, self._name_offs = _pack(names)
        self._n_events = len(events)

        vals = [v.encode() for _, v in seen_items]
        self._arena, val_offs = _pack(vals)
        item_keys = np.asarray([k for k, _ in seen_items], dtype=np.int32)
        cap = 1
        while cap < 2 * max(1, len(vals)):
            cap *= 2
        self._t_key = np.zeros(cap, dtype=np.int32)
        self._t_hash = np.zeros(cap, dtype=np.uint32)
        self._t_off = np.zeros(cap, dtype=np.int64)
        self._t_len = np.full(cap, -1, dtype=np.int32)
        self._capacity = cap
        if vals:
            rc = _lib.dm_nvd_build(
                item_keys.ctypes.data_as(_I32P), self._arena,
                val_offs.ctypes.data_as(_I64P), len(vals),
                self._t_key.ctypes.data_as(_I32P),
                self._t_hash.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                self._t_off.ctypes.data_as(_I64P),
                self._t_len.ctypes.data_as(_I32P), cap)
            if rc != 0:
                raise RuntimeError("nvd table build overflow")
        # cache pointer conversions (same lesson as TemplateMatcher)
        self._p = (self._events.ctypes.data_as(_I64P),
                   self._offs.ctypes.data_as(_I32P),
                   self._key_ids.ctypes.data_as(_I32P),
                   self._headers.ctypes.data_as(_U8P),
                   self._poss.ctypes.data_as(_I32P),
                   self._name_offs.ctypes.data_as(_I64P),
                   self._t_key.ctypes.data_as(_I32P),
                   self._t_hash.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
                   self._t_off.ctypes.data_as(_I64P),
                   self._t_len.ctypes.data_as(_I32P))

    def scan(self, payloads: Sequence[bytes]) -> np.ndarray:
        n = len(payloads)
        blob, offsets = _pack(payloads)
        verdict = np.full(n, -1, dtype=np.int8)
        p = self._p
        _lib.dm_nvd_scan(
            blob, offsets.ctypes.data_as(_I64P), n,
            p[0], p[1], self._n_events,
            p[2], p[3], p[4], self._name_blob, p[5],
            p[6], p[7], p[8], p[9], self._capacity, self._arena,
            verdict.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)))
        return verdict
