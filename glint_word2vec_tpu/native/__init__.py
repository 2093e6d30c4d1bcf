"""Native host-ops library: build-on-first-use C++ kernels via ctypes.

See host_ops.cpp for what lives here and why. The library is compiled once
into ``_host_ops.so`` next to the source (g++ -O3) and loaded with ctypes;
every entry point has a pure-Python fallback, so the package works without a
compiler (``GLINT_W2V_NO_NATIVE=1`` forces the fallbacks, used in tests to
cover both paths).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "host_ops.cpp")
_SO = os.path.join(_HERE, "_host_ops.so")
_STAMP = _SO + ".sha256"  # what the .so was built from, and where
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _build_stamp() -> str:
    """Hash of the source AND of this machine's CPU: the build uses
    ``-march=native``, so a library that arrived with a copy of the
    working tree from another host must be rebuilt, not loaded — it can
    SIGILL."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(platform.machine().encode())
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features", "model name")):
                    h.update(line.encode())
                elif not line.strip():
                    break  # first processor's block is enough
    except OSError:
        h.update(platform.processor().encode())
    return h.hexdigest()


def _is_fresh() -> bool:
    """A .so is usable only if its stamp matches :func:`_build_stamp`; a
    missing stamp forces a rebuild."""
    if not os.path.exists(_SO) or not os.path.exists(_STAMP):
        return False
    try:
        with open(_STAMP) as f:
            return f.read().strip() == _build_stamp()
    except OSError:
        return False


def _build() -> bool:
    # Link to a private name, then rename: concurrent first users (xdist
    # workers on a fresh checkout) never load a half-written library.
    tmp = f"{_SO}.tmp{os.getpid()}"
    cmd = [
        "g++", "-O3", "-march=native", "-shared", "-fPIC", "-std=c++17",
        "-pthread", _SRC, "-o", tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        # graftlint: ignore[atomic-persist] best-effort build stamp: a torn stamp only fails the hash check and forces one rebuild
        with open(_STAMP, "w") as f:
            f.write(_build_stamp())
        return True
    except Exception as e:  # compiler missing, read-only fs, ...
        logger.warning("native host_ops build failed (%s); using Python fallbacks", e)
        return False


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library, or None."""
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        if os.environ.get("GLINT_W2V_NO_NATIVE"):
            _load_failed = True
            return None
        if not _is_fresh():
            if not _build():
                _load_failed = True
                return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            logger.warning("native host_ops load failed (%s)", e)
            _load_failed = True
            return None
        lib.alias_build.restype = ctypes.c_int
        lib.alias_build.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32),
        ]
        lib.window_batch_epoch.restype = ctypes.c_int64
        lib.window_batch_epoch.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.POINTER(ctypes.c_float), ctypes.c_int32,
            ctypes.c_uint64, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
        ]
        lib.corpus_open.restype = ctypes.c_void_p
        lib.corpus_open.argtypes = [ctypes.c_char_p, ctypes.c_int32]
        lib.corpus_vocab_size.restype = ctypes.c_int64
        lib.corpus_vocab_size.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.corpus_vocab_chars.restype = ctypes.c_int64
        lib.corpus_vocab_chars.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.corpus_vocab_fill.restype = ctypes.c_int
        lib.corpus_vocab_fill.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.corpus_encode.restype = ctypes.c_int64
        lib.corpus_encode.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.corpus_encode_fill.restype = ctypes.c_int
        lib.corpus_encode_fill.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.corpus_free.restype = None
        lib.corpus_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def _resolve_threads(threads: Optional[int]) -> int:
    """Thread count for the native parallel passes: an explicit argument
    wins; otherwise GLINT_NATIVE_THREADS (non-numeric/empty tolerated);
    0 = one per hardware core (resolved in C++)."""
    if threads is not None:
        return int(threads)
    try:
        return int(os.environ.get("GLINT_NATIVE_THREADS", "0"))
    except ValueError:
        return 0


def alias_build_native(weights: np.ndarray) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Native alias-table construction; None if the library is unavailable.
    Raises ValueError for invalid weights (mirroring the Python builder)."""
    lib = get_lib()
    if lib is None:
        return None
    w = np.ascontiguousarray(weights, dtype=np.float64)
    n = w.size
    prob = np.empty(n, dtype=np.float32)
    alias = np.empty(n, dtype=np.int32)
    rc = lib.alias_build(
        _ptr(w, ctypes.c_double), n, _ptr(prob, ctypes.c_float),
        _ptr(alias, ctypes.c_int32),
    )
    if rc == 1:
        raise ValueError("weights must be a nonempty 1-D array")
    if rc == 2:
        raise ValueError("weights must be finite and nonnegative")
    if rc == 3:
        raise ValueError("weights must sum to > 0")
    return prob, alias


def window_batch_epoch_native(
    ids: np.ndarray,
    offsets: np.ndarray,
    keep_prob: np.ndarray,
    window: int,
    seed: int,
    threads: Optional[int] = None,
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray, int]]:
    """Run a full subsample+window epoch pass natively, parallel across
    sentence chunks. The output is byte-identical for every thread count
    (deterministic per-sentence PRNG seeds + a two-phase count/fill).

    ``threads``: worker count; None reads GLINT_NATIVE_THREADS, else 0 =
    one per hardware core. Returns (centers, contexts, mask, words_done)
    with exactly the kept rows, or None if the library is unavailable.
    """
    lib = get_lib()
    if lib is None:
        return None
    threads = _resolve_threads(threads)
    C = max(1, 2 * int(window) - 3)
    ids_c = np.ascontiguousarray(ids, dtype=np.int32)
    off_c = np.ascontiguousarray(offsets, dtype=np.int64)
    kp_c = np.ascontiguousarray(keep_prob, dtype=np.float32)
    cap = int(ids_c.size)
    centers = np.empty(cap, dtype=np.int32)
    contexts = np.empty((cap, C), dtype=np.int32)
    mask = np.empty((cap, C), dtype=np.float32)
    words_done = ctypes.c_int64(0)
    rows = lib.window_batch_epoch(
        _ptr(ids_c, ctypes.c_int32), _ptr(off_c, ctypes.c_int64),
        off_c.size - 1, _ptr(kp_c, ctypes.c_float), int(window),
        ctypes.c_uint64(seed & (2**64 - 1)), _ptr(centers, ctypes.c_int32),
        _ptr(contexts, ctypes.c_int32), _ptr(mask, ctypes.c_float),
        cap, ctypes.byref(words_done), int(threads),
    )
    if rows < 0:  # capacity == total ids, so this cannot happen
        raise RuntimeError("window_batch_epoch capacity overflow")
    return (
        centers[:rows], contexts[:rows], mask[:rows], int(words_done.value)
    )


def corpus_scan_native(
    path: str,
    min_count: int,
    max_sentence_length: int,
    lowercase: bool = False,
    threads: Optional[int] = None,
) -> Optional[Tuple[list, np.ndarray, np.ndarray, np.ndarray]]:
    """Native fit_file ingestion: both corpus passes (vocab count + flat
    encode) in C++, one file handle each. The counting pass runs
    thread-parallel over mmap'd chunks for large files, with output
    identical to the sequential pass for every thread count; ``threads``
    None reads GLINT_NATIVE_THREADS (0 = one per hardware core).

    Returns ``(words, counts int64[n], ids int32[total], offsets
    int64[n_sentences+1])`` — the inputs ``Vocabulary`` + the flat corpus
    representation are built from — or None when the caller should use the
    Python path instead: native library unavailable, file unreadable,
    invalid UTF-8 anywhere in the corpus (Python's errors='replace' decode
    merges tokens differing only in invalid bytes — byte-level counting
    cannot reproduce that), or ``lowercase`` requested (``str.lower`` is
    Unicode-aware). An empty vocab returns empty arrays; the caller
    decides whether that is an error (``Vocabulary.from_sorted`` raises).

    Token/tie-break semantics match corpus/vocab.py exactly for valid
    UTF-8 corpora: full str.split() whitespace set, universal-newline
    sentence boundaries, count desc, first-seen order on ties, OOV
    dropped, per-line chunking at ``max_sentence_length`` (equality is
    unit-tested against the Python passes).
    """
    if lowercase:
        return None
    lib = get_lib()
    if lib is None:
        return None
    h = lib.corpus_open(os.fsencode(path), _resolve_threads(threads))
    if not h:
        return None
    try:
        n = int(lib.corpus_vocab_size(h, min_count))
        if n <= 0:
            return (
                [], np.zeros(0, np.int64), np.zeros(0, np.int32),
                np.zeros(1, np.int64),
            )
        nchars = int(lib.corpus_vocab_chars(h, min_count))
        chars = ctypes.create_string_buffer(max(nchars, 1))
        offs = np.empty(n + 1, dtype=np.int64)
        counts = np.empty(n, dtype=np.int64)
        lib.corpus_vocab_fill(
            h, min_count, chars, _ptr(offs, ctypes.c_int64),
            _ptr(counts, ctypes.c_int64),
        )
        raw = chars.raw[:nchars]
        bounds = offs.tolist()
        words = [
            raw[bounds[i]:bounds[i + 1]].decode("utf-8", errors="replace")
            for i in range(n)
        ]
        n_sent = ctypes.c_int64(0)
        n_ids = int(
            lib.corpus_encode(
                h, min_count, max_sentence_length, ctypes.byref(n_sent)
            )
        )
        if n_ids < 0:
            return None
        ids = np.empty(max(n_ids, 1), dtype=np.int32)[:n_ids]
        soffs = np.empty(int(n_sent.value) + 1, dtype=np.int64)
        lib.corpus_encode_fill(
            h, _ptr(ids, ctypes.c_int32), _ptr(soffs, ctypes.c_int64)
        )
        return words, counts, ids, soffs
    finally:
        lib.corpus_free(h)
