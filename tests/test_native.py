"""Native data plane: C++ kernels must agree exactly with the numpy fallbacks."""

import numpy as np
import pytest

import trlx_tpu.native as native


@pytest.fixture(scope="module")
def lib():
    lib = native.get_lib()
    if lib is None:
        pytest.skip("native toolchain unavailable")
    return lib


def _numpy_only(fn, *args, **kwargs):
    saved = native._lib, native._tried
    native._lib, native._tried = None, True
    try:
        return fn(*args, **kwargs)
    finally:
        native._lib, native._tried = saved


@pytest.mark.parametrize("pad_left", [True, False])
def test_pad_collate_i32_matches_numpy(lib, pad_left):
    rng = np.random.default_rng(0)
    rows = [rng.integers(1, 100, size=rng.integers(0, 12)).astype(np.int32) for _ in range(9)]
    out_c, mask_c = native.pad_collate_i32(rows, 10, pad_value=0, pad_left=pad_left)
    out_np, mask_np = _numpy_only(native.pad_collate_i32, rows, 10, pad_value=0, pad_left=pad_left)
    np.testing.assert_array_equal(out_c, out_np)
    np.testing.assert_array_equal(mask_c, mask_np)


def test_pad_collate_f32_matches_numpy(lib):
    rng = np.random.default_rng(1)
    rows = [rng.normal(size=rng.integers(0, 7)).astype(np.float32) for _ in range(5)]
    out_c = native.pad_collate_f32(rows, 6)
    out_np = _numpy_only(native.pad_collate_f32, rows, 6)
    np.testing.assert_array_equal(out_c, out_np)


def test_find_stop_positions_matches_numpy(lib):
    rng = np.random.default_rng(2)
    seqs = rng.integers(0, 5, size=(16, 20)).astype(np.int32)
    stops = [[1, 2], [3, 3, 3], [4]]
    got_c = native.find_stop_positions(seqs, stops)
    got_np = _numpy_only(native.find_stop_positions, seqs, stops)
    np.testing.assert_array_equal(got_c, got_np)
    # sanity: a row with a known stop
    seqs2 = np.array([[9, 9, 1, 2, 9, 9]], np.int32)
    assert native.find_stop_positions(seqs2, [[1, 2]])[0] == 2
    assert native.find_stop_positions(seqs2, [[7]])[0] == 6


def test_stale_library_is_rebuilt(lib, tmp_path, monkeypatch):
    """The built file is git-ignored and travels with copies of the tree, so
    it is keyed to a hash of its source: a binary of any other source is not
    loaded, and is dropped when the right one is built."""
    import os
    import shutil

    shutil.copy(native._SRC_PATH, tmp_path / "data_plane.cpp")
    monkeypatch.setattr(native, "_HERE", str(tmp_path))
    monkeypatch.setattr(native, "_SRC_PATH", str(tmp_path / "data_plane.cpp"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    stale = [tmp_path / "libdata_plane.so", tmp_path / "libdata_plane.0123456789abcdef.so"]
    for path in stale:
        path.write_bytes(b"not a shared object: loading this would fail")

    assert native.get_lib() is not None  # built from the source beside it
    built = native.so_path()
    assert os.path.exists(built) and not any(path.exists() for path in stale)

    # the source moves on: the old binary no longer answers to it
    with open(native._SRC_PATH, "a") as f:
        f.write("\n// edited\n")
    assert native.so_path() != built
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    assert native.get_lib() is not None
    assert os.path.exists(native.so_path()) and not os.path.exists(built)
