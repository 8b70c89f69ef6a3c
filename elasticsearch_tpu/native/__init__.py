"""Native host runtime: ctypes bindings for the C++ components.

Builds lazily with g++ on first use (a .so keyed by a hash of its
sources, see ``build_keyed``); everything degrades
gracefully to the pure-Python implementations when the toolchain or the
library is unavailable, so the framework never hard-depends on the native
layer (ref: the reference treats its native pieces — JNA, ml-cpp — as
optional accelerators/sidecars too).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

_HERE = os.path.dirname(__file__)
_SRC_DIR = os.path.join(_HERE, "src")

_lib = None
_lib_lock = threading.Lock()
_build_failed = False


def build_keyed(name: str, sources: List[str], flags: List[str]
                ) -> Optional[str]:
    """Path of ``lib<name>-<digest>.so`` built from ``sources`` (files
    under native/src; the first is compiled, the rest are headers it
    includes). The digest covers every source byte, so a library built
    from other sources (an untracked .so copied in with the checkout)
    is never loaded: a changed source means a new name and a rebuild.
    None when a source is missing or g++ fails."""
    paths = [os.path.join(_SRC_DIR, s) for s in sources]
    try:
        h = hashlib.sha256()
        for p in paths:
            with open(p, "rb") as fh:
                h.update(fh.read())
        so = os.path.join(_HERE, f"lib{name}-{h.hexdigest()[:16]}.so")
        if os.path.exists(so):
            return so
        # build beside the target and rename: concurrent builders (test
        # workers) never load a half-written library
        tmp = f"{so}.{os.getpid()}.tmp"
        subprocess.run(["g++", *flags, "-shared", "-fPIC", "-std=c++17",
                        paths[0], "-o", tmp],
                       check=True, capture_output=True, timeout=180)
        os.replace(tmp, so)
        return so
    except (OSError, subprocess.SubprocessError):
        return None


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        so = build_keyed("estpu_native",
                         ["estpu_native.cpp", "estpu_tokenize.h"], ["-O3"])
        if so is None:
            _build_failed = True
            return None
        lib = ctypes.CDLL(so)
        lib.tokenize_ascii.restype = ctypes.c_int
        lib.tokenize_ascii.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int), ctypes.c_int, ctypes.c_char_p]
        lib.murmur3_hash_utf16le.restype = ctypes.c_int32
        lib.murmur3_hash_utf16le.argtypes = [ctypes.c_char_p,
                                             ctypes.c_int]
        lib.varint_delta_encode.restype = ctypes.c_int
        lib.varint_delta_encode.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8)]
        lib.varint_delta_decode.restype = ctypes.c_int
        lib.varint_delta_decode.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int]
        lib.count_term_freqs.restype = ctypes.c_int
        lib.count_term_freqs.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int]
        lib.bm25_maxscore_topk.restype = ctypes.c_int
        lib.bm25_maxscore_topk.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_float), ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32)]
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None


def try_mlockall() -> Optional[int]:
    """Lock the process address space into RAM (ref: JNANatives.java
    tryMlockall under bootstrap.memory_lock). Returns 0 on success, an
    errno on failure, None when the native library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    lib.es_mlockall.restype = ctypes.c_int
    return int(lib.es_mlockall())


def install_system_call_filter() -> Optional[int]:
    """Install the seccomp BPF filter denying process-spawning syscalls
    with EACCES (ref: SystemCallFilter.java). Returns 0 when installed
    process-wide (seccomp(2)+TSYNC), 1 when only the calling thread is
    covered (prctl fallback), a negative errno on failure, None when
    the native library is unavailable. IRREVERSIBLE for the process —
    after this, no subprocess can ever be spawned."""
    lib = get_lib()
    if lib is None:
        return None
    lib.es_install_syscall_filter.restype = ctypes.c_int
    return int(lib.es_install_syscall_filter())


def tokenize_ascii(text: str, max_token_length: int = 255
                   ) -> Optional[List[Tuple[str, int, int]]]:
    """(term, start, end) triples via the native tokenizer; None if the
    native library is unavailable (callers fall back to Python)."""
    lib = get_lib()
    if lib is None:
        return None
    raw = text.encode("ascii")
    n = len(raw)
    max_tokens = n // 1 + 1
    offsets = (ctypes.c_int * (2 * max_tokens))()
    lowered = ctypes.create_string_buffer(n + 1)
    count = lib.tokenize_ascii(raw, n, max_token_length, offsets,
                               max_tokens, lowered)
    if count < 0:
        return None
    low = lowered.raw[:n].decode("ascii")
    return [(low[offsets[2 * i]: offsets[2 * i + 1]],
             offsets[2 * i], offsets[2 * i + 1]) for i in range(count)]


def varint_encode(values: np.ndarray) -> Optional[bytes]:
    """Delta+LEB128 encode a sorted int32 array."""
    lib = get_lib()
    if lib is None:
        return None
    values = np.ascontiguousarray(values, dtype=np.int32)
    out = np.empty(5 * len(values) + 1, np.uint8)
    n = lib.varint_delta_encode(
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), len(values),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return out[:n].tobytes()


def varint_decode(data: bytes, n: int) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None:
        return None
    buf = np.frombuffer(data, dtype=np.uint8)
    out = np.empty(n, np.int32)
    got = lib.varint_delta_decode(
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(buf),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), n)
    if got != n:
        raise ValueError(f"varint decode: expected {n} values, got {got}")
    return out


def count_term_freqs(term_ids: np.ndarray
                     ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    lib = get_lib()
    if lib is None:
        return None
    term_ids = np.ascontiguousarray(term_ids, dtype=np.int32)
    max_out = len(term_ids) + 1
    out_terms = np.empty(max_out, np.int32)
    out_tfs = np.empty(max_out, np.float32)
    n = lib.count_term_freqs(
        term_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), len(term_ids),
        out_terms.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        out_tfs.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), max_out)
    if n < 0:
        return None
    return out_terms[:n].copy(), out_tfs[:n].copy()


def maxscore_topk(docids: np.ndarray, sat: np.ndarray,
                  block_max: np.ndarray,
                  post_off: np.ndarray, post_len: np.ndarray,
                  blk_off: np.ndarray, blk_len: np.ndarray,
                  idfs: np.ndarray, k: int
                  ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Block-max MaxScore DAAT top-k (the C++ CPU baseline scorer; see
    estpu_native.cpp). Arrays reference the corpus block layout: per query
    term i, postings live at docids[post_off[i]:post_off[i]+post_len[i]]
    (ascending), ``sat`` holds tf/(tf+norm) per posting, ``block_max`` the
    per-128-block max sat. Returns (scores, docs) sorted (score desc,
    docid asc), or None without the native library."""
    lib = get_lib()
    if lib is None:
        return None
    docids = np.ascontiguousarray(docids, np.int32)
    sat = np.ascontiguousarray(sat, np.float32)
    block_max = np.ascontiguousarray(block_max, np.float32)
    post_off = np.ascontiguousarray(post_off, np.int64)
    post_len = np.ascontiguousarray(post_len, np.int64)
    blk_off = np.ascontiguousarray(blk_off, np.int64)
    blk_len = np.ascontiguousarray(blk_len, np.int64)
    idfs = np.ascontiguousarray(idfs, np.float32)
    n_terms = len(idfs)
    out_scores = np.empty(k, np.float32)
    out_docs = np.empty(k, np.int32)
    p = ctypes.POINTER
    n = lib.bm25_maxscore_topk(
        docids.ctypes.data_as(p(ctypes.c_int32)),
        sat.ctypes.data_as(p(ctypes.c_float)),
        block_max.ctypes.data_as(p(ctypes.c_float)),
        post_off.ctypes.data_as(p(ctypes.c_int64)),
        post_len.ctypes.data_as(p(ctypes.c_int64)),
        blk_off.ctypes.data_as(p(ctypes.c_int64)),
        blk_len.ctypes.data_as(p(ctypes.c_int64)),
        idfs.ctypes.data_as(p(ctypes.c_float)), n_terms, int(k),
        out_scores.ctypes.data_as(p(ctypes.c_float)),
        out_docs.ctypes.data_as(p(ctypes.c_int32)))
    if n < 0:
        return None
    return out_scores[:n].copy(), out_docs[:n].copy()


def murmur3_hash(key: str) -> Optional[int]:
    """Native routing hash (bit-exact with Murmur3HashFunction); None when
    the native library is unavailable (callers fall back to Python)."""
    lib = get_lib()
    if lib is None:
        return None
    data = key.encode("utf-16-le")
    return int(lib.murmur3_hash_utf16le(data, len(data)))
