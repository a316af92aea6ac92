"""Native fastcsv kernel tests (reference: datavec CSVRecordReader tests;
the native path mirrors datavec's native-IO record reading)."""
import numpy as np
import pytest

from deeplearning4j_tpu.etl import CSVRecordReader
from deeplearning4j_tpu.native import native_available, read_csv_f32
from deeplearning4j_tpu.native import build as native_build


def _write(tmp_path, text, name="data.csv"):
    p = tmp_path / name
    p.write_text(text)
    return str(p)


def test_native_kernel_builds():
    """The environment ships g++; the kernel must actually build here."""
    assert native_available("fastcsv"), \
        native_build.build_error("fastcsv")


def test_native_parse_matches_python(tmp_path):
    rng = np.random.default_rng(0)
    want = rng.normal(size=(200, 7)).astype(np.float32)
    text = "\n".join(",".join(f"{v:.6g}" for v in row) for row in want)
    p = _write(tmp_path, text + "\n")
    got = read_csv_f32(p)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # and through the record reader's fast path
    got2 = CSVRecordReader(p).as_matrix()
    np.testing.assert_allclose(got2, want, rtol=1e-5)


def test_skip_lines_and_delimiter(tmp_path):
    p = _write(tmp_path, "h1;h2\n1;2\n3;4\n")
    got = read_csv_f32(p, delimiter=";", skip_num_lines=1)
    np.testing.assert_array_equal(got, [[1, 2], [3, 4]])


def test_ragged_and_nonnumeric_rejected(tmp_path):
    ragged = _write(tmp_path, "1,2\n3,4,5\n", "ragged.csv")
    with pytest.raises(ValueError, match="ragged|could not|cannot"):
        read_csv_f32(ragged)
    bad = _write(tmp_path, "1,2\n3,abc\n", "bad.csv")
    with pytest.raises(ValueError):
        read_csv_f32(bad)


def test_python_fallback_matches(tmp_path, monkeypatch):
    p = _write(tmp_path, "1.5,2.5\n3.5,4.5\n")
    native = read_csv_f32(p)
    import deeplearning4j_tpu.native.fastcsv as fc
    monkeypatch.setattr(fc, "load", lambda name: None)
    fallback = fc.read_csv_f32(p)
    np.testing.assert_array_equal(native, fallback)


def test_native_is_faster_on_large_file(tmp_path):
    """Sanity: the point of the kernel is throughput; it must not be
    slower than numpy's text loader on a non-trivial file."""
    if not native_available("fastcsv"):
        pytest.skip("no toolchain")
    import time
    rng = np.random.default_rng(1)
    m = rng.normal(size=(20000, 20)).astype(np.float32)
    text = "\n".join(",".join(f"{v:.6g}" for v in row) for row in m)
    p = _write(tmp_path, text + "\n", "big.csv")
    t0 = time.perf_counter()
    a = read_csv_f32(p)
    t_native = time.perf_counter() - t0
    t0 = time.perf_counter()
    b = np.loadtxt(p, delimiter=",", dtype=np.float32, ndmin=2)
    t_numpy = time.perf_counter() - t0
    np.testing.assert_allclose(a, b, rtol=1e-5)
    assert t_native < t_numpy * 1.5, (t_native, t_numpy)


def test_empty_trailing_cell_rejected_not_stolen(tmp_path):
    """Regression: an empty trailing cell must raise, not pull its value
    across the newline from the next record."""
    p = _write(tmp_path, "1,\n2,3\n", "trail.csv")
    with pytest.raises(ValueError):
        read_csv_f32(p)


def test_tab_delimiter_native(tmp_path):
    """Regression: tab is a legal delimiter; the padding skip must not
    consume it."""
    p = _write(tmp_path, "1\t2\n3\t4\n", "tabs.csv")
    got = read_csv_f32(p, delimiter="\t")
    np.testing.assert_array_equal(got, [[1, 2], [3, 4]])
    got2 = CSVRecordReader(p, delimiter="\t").as_matrix()
    np.testing.assert_array_equal(got2, [[1, 2], [3, 4]])


def test_library_is_keyed_by_its_source_hash(tmp_path, monkeypatch):
    """A checkout resets file times and _build/ is untracked, so a
    library left by another revision (here: garbage under the old,
    unkeyed name) must never be the one that loads."""
    import hashlib
    import os
    os.makedirs(native_build._BUILD, exist_ok=True)
    leftover = os.path.join(native_build._BUILD, "libfastcsv.so")
    with open(leftover, "wb") as f:
        f.write(b"not a shared library")
    os.utime(leftover, (2 ** 31, 2 ** 31))      # "newer" than the source
    monkeypatch.setattr(native_build, "_loaded", {})
    lib = native_build.load("fastcsv")
    assert lib is not None, native_build.build_error("fastcsv")
    with open(os.path.join(native_build._DIR, "fastcsv.cpp"), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    assert os.path.basename(lib._name) == f"libfastcsv-{digest}.so"
    os.remove(leftover)
