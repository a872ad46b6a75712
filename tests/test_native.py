"""Native (C++) host-runtime batch planner: contract + determinism.

The native planner shares the numpy planner's contract (every epoch
block is a permutation of the worker's index row; wraparound padding
with 0-weight tail) but uses its own RNG stream — so tests check the
CONTRACT, not byte equality with numpy.
"""

import numpy as np
import pytest

from dopt.data.pipeline import make_batch_plan
from dopt.native import fill_batch_plan_native, native_available

pytestmark = pytest.mark.skipif(
    not native_available(), reason="no C++ toolchain / native build failed"
)


def _index_matrix(w=4, l=37, base=100):
    rng = np.random.default_rng(0)
    return np.stack([rng.permutation(l) + base * (i + 1) for i in range(w)]).astype(np.int32)


def test_native_plan_contract():
    im = _index_matrix()
    idx, weight = fill_batch_plan_native(im, batch_size=8, local_ep=3,
                                         seed=7, round_idx=2)
    w, l = im.shape
    steps_per_epoch = -(-l // 8)
    assert idx.shape == (w, 3 * steps_per_epoch, 8)
    assert weight.shape == idx.shape
    for wi in range(w):
        for ep in range(3):
            block = idx[wi, ep * steps_per_epoch:(ep + 1) * steps_per_epoch]
            flat = block.reshape(-1)
            # Real (weight-1) entries are exactly a permutation of the row.
            wflat = weight[wi, ep * steps_per_epoch:(ep + 1) * steps_per_epoch].reshape(-1)
            real = flat[wflat == 1.0]
            np.testing.assert_array_equal(np.sort(real), np.sort(im[wi]))
            # Padding wraps around from the head of the permutation.
            pad = flat[wflat == 0.0]
            np.testing.assert_array_equal(pad, flat[:len(pad)])


def test_native_plan_deterministic_and_round_varying():
    im = _index_matrix()
    a = fill_batch_plan_native(im, batch_size=8, local_ep=2, seed=7, round_idx=0)
    b = fill_batch_plan_native(im, batch_size=8, local_ep=2, seed=7, round_idx=0)
    c = fill_batch_plan_native(im, batch_size=8, local_ep=2, seed=7, round_idx=1)
    d = fill_batch_plan_native(im, batch_size=8, local_ep=2, seed=8, round_idx=0)
    np.testing.assert_array_equal(a[0], b[0])
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[0], d[0])
    # Different epochs within one call shuffle differently.
    steps = a[0].shape[1] // 2
    assert not np.array_equal(a[0][:, :steps], a[0][:, steps:])


def test_native_plan_drop_last():
    im = _index_matrix(l=40)
    idx, weight = fill_batch_plan_native(im, batch_size=16, local_ep=1,
                                         seed=1, round_idx=0, drop_last=True)
    assert idx.shape == (4, 2, 16)  # 40 // 16 = 2 steps, 8 samples dropped
    assert (weight == 1.0).all()


def test_make_batch_plan_native_impl_dispatch():
    im = _index_matrix()
    plan = make_batch_plan(im, batch_size=8, local_ep=2, seed=3, round_idx=5,
                           impl="native")
    ref = fill_batch_plan_native(im, batch_size=8, local_ep=2, seed=3,
                                 round_idx=5)
    np.testing.assert_array_equal(plan.idx, ref[0])
    np.testing.assert_array_equal(plan.weight, ref[1])
    # numpy impl still the default and differs in stream, same contract
    py = make_batch_plan(im, batch_size=8, local_ep=2, seed=3, round_idx=5)
    assert py.idx.shape == plan.idx.shape
    assert not np.array_equal(py.idx, plan.idx)


def test_native_plan_worker_subset_matches_full_plan_rows():
    if not native_available():
        pytest.skip("native library unavailable")
    mat = np.arange(8 * 100, dtype=np.int64).reshape(8, 100)
    full = make_batch_plan(mat, batch_size=32, local_ep=2, seed=7,
                           round_idx=3, impl="native")
    sel = np.array([0, 3, 7])
    sub = make_batch_plan(mat, batch_size=32, local_ep=2, seed=7,
                          round_idx=3, impl="native", workers=sel)
    assert sub.idx.shape == (3, 8, 32)
    np.testing.assert_array_equal(sub.idx, full.idx[sel])
    np.testing.assert_array_equal(sub.weight, full.weight[sel])


def test_requested_native_planner_fails_with_the_compilers_stderr(
        tmp_path, monkeypatch):
    """``plan_impl="native"`` never degrades to the numpy planner (which
    draws a different batch order): an unbuildable library raises, and
    the error carries what g++ said."""
    import dopt.native as native

    bad = tmp_path / "plan.cpp"
    bad.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_SRC", str(bad))
    monkeypatch.setattr(native, "_LIB", str(tmp_path / "libdopt_bad.so"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_error", None)
    with pytest.raises(native.NativeUnavailable, match="error"):
        make_batch_plan(_index_matrix(), batch_size=8, impl="native")
    assert not native.native_available()
    assert list(tmp_path.glob("libdopt_bad.so*")) == []   # no litter
