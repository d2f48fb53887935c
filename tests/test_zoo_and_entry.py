"""Model zoo shapes/serde + driver entry points."""

import numpy as np
import jax
import pytest

from distkeras_tpu.models import zoo
from distkeras_tpu.models.model import Model


@pytest.mark.parametrize("name,xshape,oshape", [
    ("mlp_mnist", (2, 784), (2, 10)),
    ("convnet_mnist", (2, 28, 28, 1), (2, 10)),
    ("convnet_cifar10", (2, 32, 32, 3), (2, 10)),
    ("resnet20", (2, 32, 32, 3), (2, 10)),
    ("lstm_imdb", (2, 200), (2, 1)),
    ("transformer_classifier", (2, 200), (2, 2)),
])
def test_zoo_forward_shapes(name, xshape, oshape):
    model = zoo.ZOO[name]()
    v = model.init(0)
    int_input = name in ("lstm_imdb", "transformer_classifier")
    x = np.zeros(xshape, np.int32 if int_input else np.float32)
    y, _ = model.apply(v, x)
    assert y.shape == oshape
    # config serde roundtrip preserves output
    m2 = Model.from_config(model.config())
    y2, _ = m2.apply(v, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-6)


def test_resnet50_builds():
    """Shape-check only at reduced size (full 224² compile is a benchmark
    concern, not a unit-test one)."""
    model = zoo.resnet50(num_classes=10, input_size=64)
    v = model.init(0)
    x = np.zeros((1, 64, 64, 3), np.float32)
    y, _ = model.apply(v, x)
    assert y.shape == (1, 10)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(v["params"]))
    assert 20e6 < n_params < 30e6  # ~25.6M with a 10-class head


def test_resnet20_param_count():
    v = zoo.resnet20().init(0)
    n = sum(p.size for p in jax.tree_util.tree_leaves(v["params"]))
    assert 0.25e6 < n < 0.30e6  # canonical ~0.27M


def test_entry_points():
    import __graft_entry__ as ge
    fn, (variables, x) = ge.entry()
    y = jax.jit(fn)(variables, x)
    assert y.shape == (8, 10)


def test_dryrun_multichip_8():
    import __graft_entry__ as ge
    ge.dryrun_multichip(8)


def test_synthetic_datasets_learnable_shapes():
    from distkeras_tpu.data import datasets
    tr, te, meta = datasets.load_mnist(n_train=256)
    assert tr["features"].shape == (256, 784) and meta["num_classes"] == 10
    tr, te, meta = datasets.load_cifar10(n_train=128)
    assert tr["features"].shape == (128, 32, 32, 3)
    tr, te, meta = datasets.load_imdb(n_train=64, seq_len=50)
    assert tr["features"].shape == (64, 50) and tr["features"].dtype == np.int32
    tr, te, meta = datasets.load_imagenet_subset(n_train=8, image_size=32)
    assert tr["features"].shape == (8, 32, 32, 3)


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing in code
    (JAX reads the variable itself) and reports that directory."""
    from distkeras_tpu.utils.compile_cache import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    monkeypatch.setattr(jax.config, "update", lambda *a: pytest.fail(
        f"set {a} in code although the cache was placed from outside"))
    assert enable_compile_cache() == "/placed/from/outside"


def test_compile_cache_default_is_one_fixed_path(monkeypatch):
    """Unset, the cache goes to <checkout>/.jax_cache — the path is part
    of the cache key, so it is the same on every call and every run."""
    import os

    from distkeras_tpu.utils.compile_cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    set_to = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: set_to.append((name, value)))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert enable_compile_cache() == enable_compile_cache() \
        == os.path.join(root, ".jax_cache")
    assert set_to == [("jax_compilation_cache_dir",
                       os.path.join(root, ".jax_cache"))] * 2
