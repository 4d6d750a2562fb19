"""Native host data plane: ctypes bindings for the C++ collate/scan kernels with
numpy fallbacks (identical semantics, property-tested against each other).

The library is built on first use (or via ``python -m trlx_tpu.native.build``); in
environments without a toolchain everything silently uses the numpy fallbacks.
"""

import ctypes
import glob
import hashlib
import os
import subprocess
import sys
from typing import List, Optional, Sequence, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC_PATH = os.path.join(_HERE, "data_plane.cpp")
_lib = None
_tried = False


def so_path() -> str:
    """The built library's path, keyed to a hash of its source: the file is
    git-ignored and copies of the tree carry it along, so a binary must never
    outlive the ``data_plane.cpp`` it was built from."""
    with open(_SRC_PATH, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"libdata_plane.{digest}.so")


def build(verbose: bool = False) -> Optional[str]:
    """Compile data_plane.cpp -> :func:`so_path`, dropping binaries of any
    other source. Returns the path or None."""
    path = so_path()
    tmp = f"{path}.{os.getpid()}.tmp"  # concurrent builders each rename a whole file
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC_PATH]
    try:
        res = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if res.returncode != 0:
            if verbose:
                print(res.stderr, file=sys.stderr)
            return None
        os.replace(tmp, path)
    except (OSError, subprocess.TimeoutExpired):
        return None
    for stale in glob.glob(os.path.join(_HERE, "libdata_plane*.so")):
        if stale != path:
            try:
                os.remove(stale)
            except OSError:
                pass
    return path


def get_lib():
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    path = so_path()
    if not os.path.exists(path):
        if build() is None:
            return None
    try:
        lib = ctypes.CDLL(path)
        lib.pad_collate_i32.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int32, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        lib.pad_collate_f32.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_float, ctypes.c_int,
            ctypes.c_void_p,
        ]
        lib.find_stop_positions.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p,
        ]
        _lib = lib
    except OSError:
        _lib = None
    return _lib


def _ragged_concat_i32(rows: Sequence[np.ndarray]):
    lengths = np.asarray([len(r) for r in rows], np.int64)
    offsets = np.zeros(len(rows), np.int64)
    np.cumsum(lengths[:-1], out=offsets[1:])
    flat = np.concatenate([np.asarray(r) for r in rows]) if rows else np.zeros(0)
    return flat, offsets, lengths


def pad_collate_i32(
    rows: Sequence[np.ndarray], target_len: int, pad_value: int, pad_left: bool
) -> Tuple[np.ndarray, np.ndarray]:
    """Pad ragged int32 rows to [B, target_len] + 0/1 mask. Native when available."""
    B = len(rows)
    lib = get_lib()
    if lib is not None:
        flat, offsets, lengths = _ragged_concat_i32([np.asarray(r, np.int32) for r in rows])
        flat = np.ascontiguousarray(flat, np.int32)
        out = np.empty((B, target_len), np.int32)
        mask = np.empty((B, target_len), np.int32)
        lib.pad_collate_i32(
            flat.ctypes.data, offsets.ctypes.data, lengths.ctypes.data,
            B, target_len, pad_value, int(pad_left), out.ctypes.data, mask.ctypes.data,
        )
        return out, mask
    # numpy fallback
    out = np.full((B, target_len), pad_value, np.int32)
    mask = np.zeros((B, target_len), np.int32)
    for i, r in enumerate(rows):
        r = np.asarray(r, np.int32)
        r = r[-target_len:] if pad_left else r[:target_len]
        if pad_left:
            out[i, target_len - len(r):] = r
            mask[i, target_len - len(r):] = 1
        else:
            out[i, : len(r)] = r
            mask[i, : len(r)] = 1
    return out, mask


def pad_collate_f32(
    rows: Sequence[np.ndarray], target_len: int, pad_value: float = 0.0, pad_left: bool = False
) -> np.ndarray:
    B = len(rows)
    lib = get_lib()
    if lib is not None:
        rows32 = [np.ascontiguousarray(r, np.float32) for r in rows]
        flat, offsets, lengths = _ragged_concat_i32(rows32)
        flat = np.ascontiguousarray(flat, np.float32)
        out = np.empty((B, target_len), np.float32)
        lib.pad_collate_f32(
            flat.ctypes.data, offsets.ctypes.data, lengths.ctypes.data,
            B, target_len, ctypes.c_float(pad_value), int(pad_left), out.ctypes.data,
        )
        return out
    out = np.full((B, target_len), pad_value, np.float32)
    for i, r in enumerate(rows):
        r = np.asarray(r, np.float32)
        r = r[-target_len:] if pad_left else r[:target_len]
        if pad_left:
            out[i, target_len - len(r):] = r
        else:
            out[i, : len(r)] = r
    return out


def find_stop_positions(seqs: np.ndarray, stop_token_seqs: Sequence[Sequence[int]]) -> np.ndarray:
    """First start index of any stop token-sequence per row; seq_len if none."""
    seqs = np.ascontiguousarray(seqs, np.int32)
    B, T = seqs.shape
    stops = [np.asarray(s, np.int32) for s in stop_token_seqs if len(s) > 0]
    if not stops:
        return np.full(B, T, np.int64)
    lib = get_lib()
    if lib is not None:
        flat, offsets, lengths = _ragged_concat_i32(stops)
        flat = np.ascontiguousarray(flat, np.int32)
        out = np.empty(B, np.int64)
        lib.find_stop_positions(
            seqs.ctypes.data, B, T, flat.ctypes.data, offsets.ctypes.data,
            lengths.ctypes.data, len(stops), out.ctypes.data,
        )
        return out
    out = np.full(B, T, np.int64)
    for i in range(B):
        row = seqs[i]
        for pat in stops:
            m = len(pat)
            for j in range(0, T - m + 1):
                if int(out[i]) <= j:
                    break
                if np.array_equal(row[j : j + m], pat):
                    out[i] = min(out[i], j)
                    break
    return out
