"""Native host data plane: fused add + CSV ingest vs NumPy reference."""

import numpy as np
import pytest

from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.utils import native


def test_native_builds_and_loads():
    assert native.available(), ("libdknative.so failed to build/load — "
                                "g++ is a baked-in tool, so this should "
                                "never fail here")


def test_native_load_always_goes_through_make(monkeypatch):
    """The library is not committed, so whatever file sits in native/ may
    be stale: every load runs ``make -C native`` (a no-op when the
    library is newer than dknative.cpp) before it opens the file."""
    import subprocess
    calls = []
    real_run = subprocess.run

    def run(cmd, **kw):
        calls.append(cmd)
        return real_run(cmd, **kw)

    monkeypatch.setattr(native.subprocess, "run", run)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    assert native.available()
    assert calls == [["make", "-C", native._NATIVE_DIR]]


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("n", [7, 1 << 10, (1 << 20) + 3])
def test_fused_add_matches_numpy(dtype, n):
    rng = np.random.default_rng(0)
    a = rng.normal(size=n).astype(dtype)
    b = rng.normal(size=n).astype(dtype)
    out = native.fused_add(a, b, 0.25)
    np.testing.assert_allclose(out, a + 0.25 * b, rtol=1e-6)
    assert out is not a  # replace semantics


def test_axpy_inplace():
    a = np.ones(100000, np.float32)
    b = np.full(100000, 2.0, np.float32)
    native.axpy_inplace(a, b, 0.5)
    np.testing.assert_allclose(a, 2.0)


def test_parse_csv_roundtrip(tmp_path):
    rng = np.random.default_rng(1)
    data = rng.integers(0, 255, size=(512, 11)).astype(np.float32)
    p = tmp_path / "data.csv"
    with open(p, "w") as f:
        for row in data:
            f.write(",".join(f"{v:.1f}" for v in row) + "\n")
    flat = native.parse_csv(str(p))
    np.testing.assert_allclose(flat.reshape(512, 11), data, rtol=1e-6)


def test_parse_csv_skips_headers_handles_tabs(tmp_path):
    """Non-numeric tokens (header rows) are skipped by count AND parse
    passes symmetrically; tabs/CRLF are separators (review regression)."""
    p = tmp_path / "h.csv"
    p.write_text("label,f1,f2\r\n1,2.5,3\n4\t5\t6\n")
    vals = native.parse_csv(str(p))
    np.testing.assert_allclose(vals, [1.0, 2.5, 3.0, 4.0, 5.0, 6.0])


def test_dataset_from_csv(tmp_path):
    rng = np.random.default_rng(2)
    labels = rng.integers(0, 10, size=256)
    feats = rng.random((256, 20)).astype(np.float32)
    p = tmp_path / "mnistish.csv"
    with open(p, "w") as f:
        for l, row in zip(labels, feats):
            f.write(str(l) + "," + ",".join(f"{v:.6f}" for v in row) + "\n")
    ds = Dataset.from_csv(str(p), num_features=20)
    assert ds["features"].shape == (256, 20)
    np.testing.assert_array_equal(ds["label"], labels)
    # CSV wrote 6 decimals; parse is exact to the printed precision
    np.testing.assert_allclose(ds["features"], feats, atol=1e-6)


def test_ps_commit_math_unchanged_with_native():
    """The native fused path must not change PS update-rule results."""
    from distkeras_tpu.ps import ADAGParameterServer
    center = {"params": [{"w": np.arange(4096, dtype=np.float32)}],
              "state": [{}]}
    delta = {"params": [{"w": np.full(4096, 2.0, np.float32)}], "state": [{}]}
    ps = ADAGParameterServer(center, num_workers=4)
    ps.handle_commit(delta, {})
    np.testing.assert_allclose(
        ps.get_model()["params"][0]["w"],
        np.arange(4096, dtype=np.float32) + 0.5)
