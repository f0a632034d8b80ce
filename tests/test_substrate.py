"""Substrate-layer tests: optimizer, checkpointing, data pipeline, F-LR,
crypto, sharding rules, end-to-end small training."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import crypto
from repro.core.fedlinear import FederatedLinear, split_columns
from repro.data import make_classification, make_regression
from repro.data.metrics import accuracy, f1_binary, rmse, ztest_two_sample
from repro.train import optim


def test_adamw_converges_quadratic():
    w = {"a": jnp.array([3.0, -2.0]), "b": jnp.array(5.0)}
    opt = optim.adamw_init(w)

    def loss(p):
        return (p["a"] ** 2).sum() + p["b"] ** 2

    for _ in range(300):
        g = jax.grad(loss)(w)
        w, opt = optim.adamw_update(w, g, opt, lr=5e-2, weight_decay=0.0)
    assert float(loss(w)) < 1e-3


def test_cosine_lr_schedule():
    lrs = [float(optim.cosine_lr(jnp.array(s), peak=1.0, warmup=10, total=100))
           for s in range(100)]
    assert lrs[0] < lrs[9] <= 1.0           # warmup rises
    assert lrs[99] < 0.2                    # decays toward floor
    assert max(lrs) <= 1.0 + 1e-6


def test_checkpoint_roundtrip(tmp_path):
    from repro.ckpt import latest_step, restore_checkpoint, save_checkpoint
    tree = {"w": jnp.arange(12.0).reshape(3, 4),
            "nested": {"b": jnp.ones((2,), jnp.bfloat16)},
            "lst": [jnp.array(3), jnp.array([1, 2])]}
    save_checkpoint(tmp_path, 7, tree)
    assert latest_step(tmp_path) == 7
    back = restore_checkpoint(tmp_path, 7, jax.tree.map(lambda x: x, tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_fedlinear_classification_parity():
    """F-LR with M parties == single-party logistic regression (exact: the
    psum of block dots IS the full dot)."""
    x, y = make_classification(600, 20, 2, seed=4)
    f1 = FederatedLinear().fit([x[:500]], y[:500])
    f3 = FederatedLinear().fit(split_columns(x[:500], 3), y[:500])
    p1 = f1.predict([x[500:]])
    p3 = f3.predict(split_columns(x[500:], 3))
    assert np.mean(p1 == p3) > 0.99
    assert accuracy(y[500:], p3) > 0.7


def test_fedlinear_regression():
    x, y = make_regression(600, 15, nonlinear=False, noise=0.1, seed=5)
    fl = FederatedLinear(task="regression", lr=0.3, steps=600).fit(
        split_columns(x[:500], 2), y[:500])
    pred = fl.predict(split_columns(x[500:], 2))
    assert rmse(y[500:], pred) < 0.5 * np.std(y[500:])


def test_crypto_id_alignment():
    a = crypto.hash_ids(np.array([10, 11, 12, 13]))
    b = crypto.hash_ids(np.array([12, 13, 14]))
    ia, ib = crypto.align_ids(a, b)
    assert len(ia) == 2
    assert set(zip(ia.tolist(), ib.tolist())) == {(2, 0), (3, 1)}


def test_crypto_label_roundtrip():
    y = np.array([0, 1, 2, 1, 0])
    y_enc, dec = crypto.encode_labels(y, 3, seed=1)
    assert not np.array_equal(y, y_enc) or True  # permutation may be identity
    np.testing.assert_array_equal(dec(y_enc), y)
    yr = np.random.default_rng(0).normal(size=10)
    yr_m, dec_r = crypto.mask_regression_targets(yr, seed=2)
    np.testing.assert_allclose(dec_r(yr_m), yr, atol=1e-9)


def test_pairwise_masks_cancel():
    m = crypto.pairwise_cancelling_masks(5, (3, 2), seed=3)
    np.testing.assert_allclose(m.sum(0), 0.0, atol=1e-5)


def test_ztest_sanity():
    rng = np.random.default_rng(0)
    a = rng.normal(0, 1, 200)
    _, p_same = ztest_two_sample(a, a + rng.normal(0, 0.01, 200))
    _, p_diff = ztest_two_sample(a, a + 1.0)
    assert p_same > 0.05 and p_diff < 0.01


def test_f1_binary():
    assert f1_binary([1, 1, 0, 0], [1, 0, 0, 0]) == pytest.approx(2 / 3)


def test_training_reduces_ce_end_to_end():
    """examples/train_transformer.py contract at tiny scale."""
    from repro.configs import registry
    from repro.launch.train import train_loop
    cfg = registry.get("internlm2-1.8b").with_(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        d_ff=128, vocab=128, dtype="float32", remat="none")
    _, losses = train_loop(cfg, steps=30, batch=4, seq=32, lr=3e-3,
                           log_every=29)
    assert losses[-1] < losses[0]


def test_sharding_rules_divisibility():
    """Every param spec must divide the mesh axes it names (on shapes from
    all 10 archs) — the invariant the dry-run relies on."""
    from repro.configs import registry as reg
    from repro.models import sharding, transformer
    # AbstractMesh: full production topology without needing 256 devices
    mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
    for arch in reg.ARCH_IDS:
        cfg = reg.get(arch)
        shapes = jax.eval_shape(
            lambda k, c=cfg: transformer.init_params(k, c), jax.random.key(0))
        specs = sharding.param_specs(shapes, mesh)

        def check(path, leaf, spec):
            for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 9):
                if ax is None:
                    continue
                size = (np.prod([mesh.shape[a] for a in ax])
                        if isinstance(ax, tuple) else mesh.shape[ax])
                assert dim % size == 0, (arch, path, leaf.shape, spec)

        jax.tree_util.tree_map_with_path(
            lambda p, l, s: check(p, l, s), shapes, specs)
