"""Federation session API tests.

The load-bearing claims:
  * session-built forests are BIT-IDENTICAL to the direct
    FederatedForest.fit path (both tasks) — the session adds an owner, not
    a different code path;
  * forest / boosting / F-LR all conform to the shared Estimator protocol
    and train/predict through one session surface;
  * the session owns the histogram backend (hist_impl) — the per-estimator
    override is deprecated;
  * the LeafTable plan behind fed.predict / fed.serve is invalidated and
    rebuilt when a model's ``trees_`` changes (fit_resumable continuations);
  * the sharded substrate lowers the same session programs on a
    (trees, parties) mesh (dry-run, subprocess-isolated device count).
"""
import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import (BoostParams, FederatedForest, ForestParams,
                        LinearParams)
from repro.data import make_classification, make_regression
from repro.data.metrics import accuracy
from repro.federation import Estimator, Federation, SimulatedSubstrate


@pytest.fixture(scope="module")
def cls_data():
    x, y = make_classification(700, 18, 3, seed=0)
    return x[:500], y[:500], x[500:], y[500:]


@pytest.fixture(scope="module")
def reg_data():
    x, y = make_regression(500, 12, seed=1)
    return x[:380], y[:380], x[380:], y[380:]


def _trees_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ------------------------------------------------------- fit parity (exact)
@pytest.mark.parametrize("task", ["classification", "regression"])
def test_session_fit_bit_identical_to_direct(cls_data, reg_data, task):
    """Federation.fit == FederatedForest.fit, down to the last bit."""
    xtr, ytr, xte, _ = cls_data if task == "classification" else reg_data
    p = ForestParams(task=task, n_classes=3, n_estimators=4, max_depth=6,
                     n_bins=16, seed=7)
    fed = Federation(parties=3, n_bins=p.n_bins)
    part = fed.ingest(xtr, ytr)
    session_model = fed.fit(p)
    direct = FederatedForest(p).fit(part, ytr)
    _trees_equal(session_model.trees_, direct.trees_)
    np.testing.assert_array_equal(fed.predict(session_model, xte),
                                  direct.predict(xte))
    # the compact session predict is also bit-identical to the dense kernel
    np.testing.assert_array_equal(fed.predict(session_model, xte),
                                  session_model.predict(xte))


def test_session_substrate_resolved_once(cls_data):
    fed = Federation(parties=2)
    assert isinstance(fed.substrate, SimulatedSubstrate)
    m1 = fed.fit(ForestParams(n_estimators=2, max_depth=3, n_bins=32,
                              n_classes=3),
                 fed.ingest(cls_data[0], cls_data[1]), cls_data[1])
    assert m1.substrate is fed.substrate


def test_session_requires_ingest_or_explicit_data():
    fed = Federation(parties=2)
    with pytest.raises(ValueError, match="ingest"):
        fed.fit(ForestParams(n_estimators=1))


def test_session_rejects_bin_count_mismatch(cls_data):
    """A spec binned differently from the ingested partition would train on
    truncated histograms — must be a loud error, not a silent wrong model."""
    fed = Federation(parties=2, n_bins=32)
    fed.ingest(cls_data[0], cls_data[1])
    with pytest.raises(ValueError, match="n_bins"):
        fed.fit(ForestParams(n_estimators=1, n_bins=16, n_classes=3))


def test_serve_with_knobs_is_not_cached(cls_data):
    """serve() must honor per-call server knobs — different knobs never get
    the cached knob-free server back."""
    xtr, ytr = cls_data[0], cls_data[1]
    p = ForestParams(n_estimators=2, max_depth=4, n_bins=8, n_classes=3)
    fed = Federation(parties=2, n_bins=8)
    fed.ingest(xtr, ytr)
    model = fed.fit(p)
    s1 = fed.serve(model, buckets=(32,))
    s2 = fed.serve(model, buckets=(32,), vote_impl="argmax")
    assert s2 is not s1 and s2.vote_impl == "argmax"
    assert fed.serve(model, buckets=(32,)) is s1   # knob-free path cached


# -------------------------------------------------- estimator conformance
def test_estimator_protocol_conformance(cls_data, reg_data):
    """One session surface drives all three model families."""
    xtr, ytr, xte, yte = cls_data
    fed = Federation(parties=3)
    fed.ingest(xtr, ytr)

    forest = fed.fit(ForestParams(n_estimators=5, max_depth=5, n_bins=32,
                                  n_classes=3))
    linear = fed.fit(LinearParams(steps=200))
    models = [forest, linear]

    rxtr, rytr, rxte, ryte = reg_data
    fed_r = Federation(parties=2, n_bins=16)
    fed_r.ingest(rxtr, rytr)
    boost = fed_r.fit(BoostParams(n_rounds=5, max_depth=3, n_bins=16))
    models.append(boost)

    for model in models:
        assert isinstance(model, Estimator), type(model)

    for model in (forest, linear):
        preds = fed.predict(model, xte)
        assert preds.shape == (len(xte),)
    assert accuracy(yte, fed.predict(forest, xte)) > 0.5
    assert fed_r.predict(boost, rxte).shape == (len(rxte),)


def test_fedlinear_partition_and_legacy_blocks_agree(cls_data):
    """The partition path (session) and the legacy block-list path train
    the identical F-LR model when the column split matches."""
    from repro.core.fedlinear import FederatedLinear
    xtr, ytr, xte, _ = cls_data
    fed = Federation(parties=2)
    part = fed.ingest(xtr, ytr)
    m_sess = fed.fit(LinearParams(steps=150))
    m_legacy = FederatedLinear(steps=150).fit(part.split_raw(xtr), ytr)
    np.testing.assert_array_equal(fed.predict(m_sess, xte),
                                  m_legacy.predict(part.split_raw(xte)))


# ------------------------------------------------------ hist_impl ownership
def test_forest_hist_impl_field_deprecated():
    with pytest.warns(DeprecationWarning, match="hist_impl"):
        FederatedForest(ForestParams(n_estimators=1), hist_impl="scatter")


def test_session_hist_impl_is_source_of_truth(cls_data):
    """Session-level hist_impl overrides the spec's — and produces the same
    forest (backends are exact-equivalent)."""
    xtr, ytr, xte, _ = cls_data
    p = ForestParams(n_estimators=2, max_depth=4, n_bins=8, n_classes=3,
                     hist_impl="auto")
    fed = Federation(parties=2, hist_impl="scatter", n_bins=8)
    part = fed.ingest(xtr, ytr)
    model = fed.fit(p)
    assert model.params.hist_impl == "scatter"
    # boosting specs get the session backend too
    boost = Federation(parties=2, hist_impl="scatter", n_bins=8)
    boost.ingest(xtr, (ytr == 1).astype(np.float64))
    bm = boost.fit(BoostParams(task="binary", n_rounds=2, max_depth=3,
                               n_bins=8))
    assert bm.params.hist_impl == "scatter"
    # same trees as the default backend (exactness across backends)
    ref = FederatedForest(p).fit(part, ytr)
    _trees_equal(model.trees_, ref.trees_)


# ----------------------------------------------------- LeafTable freshness
def test_predict_plan_refreshes_when_trees_change(cls_data, tmp_path):
    """fit_resumable extends the forest in place; the session's cached
    LeafTable must be rebuilt, not silently reused."""
    xtr, ytr, xte, _ = cls_data
    p4 = ForestParams(n_estimators=4, max_depth=6, n_bins=16, n_classes=3,
                      seed=3)
    fed = Federation(parties=3, n_bins=16)
    fed.ingest(xtr, ytr)
    d = str(tmp_path / "resume")
    model = fed.fit_resumable(p4, d)
    first = fed.predict(model, xte)
    plan_before = fed._plans[id(model)][1]

    # continuation: same seed-derived randomness, more trees -> trees_ swaps
    p6 = dataclasses.replace(p4, n_estimators=6)
    model.params = p6
    model.fit_resumable(fed._partition, ytr, d)
    assert int(model.trees_.is_leaf.shape[1]) == 6

    second = fed.predict(model, xte)
    plan_after = fed._plans[id(model)][1]
    assert plan_after is not plan_before
    direct = model.predict(xte)
    np.testing.assert_array_equal(second, direct)
    # the 4-tree prefix is the identical forest, so most votes agree but the
    # result must come from the 6-tree forest, not a stale 4-tree plan
    assert second.shape == first.shape


def test_serve_refreshes_server_when_trees_change(cls_data, tmp_path):
    """fed.serve returns the cached compiled server while trees_ is
    unchanged, and refreshes it in place when the model was updated."""
    xtr, ytr, xte, _ = cls_data
    p = ForestParams(n_estimators=3, max_depth=6, n_bins=16, n_classes=3,
                     seed=5)
    fed = Federation(parties=2, n_bins=16)
    fed.ingest(xtr, ytr)
    model = fed.fit(p)
    server = fed.serve(model, buckets=(32, 64))
    server.warmup()
    assert server.compile_count == 2
    assert fed.serve(model, buckets=(32, 64)) is server   # cache hit
    assert server.compile_count == 2                      # no recompiles
    np.testing.assert_array_equal(server.serve(xte), model.predict(xte))

    # refit -> trees_ is a new stack; same handle, same buckets
    model.params = dataclasses.replace(p, n_estimators=5)
    model.fit(fed._partition, ytr)
    server2 = fed.serve(model, buckets=(32, 64))
    assert server2 is server                              # refreshed in place
    assert int(server.trees.is_leaf.shape[1]) == 5
    np.testing.assert_array_equal(server.serve(xte), model.predict(xte))
    assert server.compile_count > 2                       # old execs dropped


# ---------------------------------------------------- serving, all families
def test_serve_boosting_model(reg_data):
    """fed.serve stands up the bucketed async engine for boosting — same
    compile-once contract, outputs match the estimator's predict."""
    rxtr, rytr, rxte, _ = reg_data
    fed = Federation(parties=2, n_bins=16)
    fed.ingest(rxtr, rytr)
    model = fed.fit(BoostParams(n_rounds=4, max_depth=3, n_bins=16))
    server = fed.serve(model, buckets=(32, 64), max_inflight=3)
    assert fed.serve(model, buckets=(32, 64), max_inflight=3) is server
    server.warmup()
    assert server.compile_count == 2
    out = server.serve(rxte)
    # one fused float32 program vs the per-round float64 host accumulation:
    # same ensemble, summation order differs
    np.testing.assert_allclose(out, model.predict(rxte), rtol=1e-4,
                               atol=1e-4)
    assert server.compile_count == 2                      # no recompiles
    # zero-row dtype matches, through the same engine path
    assert server.serve(rxte[:0]).dtype == out.dtype


def test_serve_boosting_binary(cls_data):
    xtr, ytr, xte, _ = cls_data
    fed = Federation(parties=2, n_bins=16)
    fed.ingest(xtr, (ytr == 1).astype(np.float64))
    model = fed.fit(BoostParams(task="binary", n_rounds=3, max_depth=3,
                                n_bins=16))
    server = fed.serve(model, buckets=(64,))
    np.testing.assert_array_equal(server.serve(xte),
                                  model.predict(xte).astype(np.int32))


def test_serve_linear_model(cls_data):
    """fed.serve works for F-LR: raw rows split/standardized per party and
    served through the same bucketed engine."""
    from repro.serving import LinearServer, RequestQueue
    xtr, ytr, xte, _ = cls_data
    fed = Federation(parties=3)
    part = fed.ingest(xtr, ytr)
    model = fed.fit(LinearParams(steps=150))
    server = fed.serve(model, buckets=(32, 128))
    assert isinstance(server, LinearServer)
    server.warmup()
    assert server.compile_count == 2
    want = model.predict(part.split_raw(xte))
    np.testing.assert_array_equal(server.serve(xte), want)
    assert server.compile_count == 2
    # queue traffic over the linear engine too
    q = RequestQueue(server)
    rid = q.submit(xte[:40])
    np.testing.assert_array_equal(q.drain()[rid], want[:40])


def test_serve_autotune_refreshes_buckets(cls_data):
    """serve(autotune_buckets=True) derives the bucket set from traffic and
    refreshes the cached server in place, keeping compile-once per epoch."""
    xtr, ytr, xte, _ = cls_data
    p = ForestParams(n_estimators=2, max_depth=4, n_bins=8, n_classes=3,
                     seed=9)
    fed = Federation(parties=2, n_bins=8)
    fed.ingest(xtr, ytr)
    model = fed.fit(p)
    counts = list(np.random.default_rng(0).integers(1, 120, size=50))
    server = fed.serve(model, autotune_buckets=True, traffic=counts)
    server.warmup()
    assert server.buckets[-1] >= max(counts)
    assert server.compile_count == len(server.buckets)
    for n in (3, 40, 100):
        np.testing.assert_array_equal(server.serve(xte[:n]),
                                      model.predict(xte[:n]))
    assert server.compile_count == len(server.buckets)    # epoch stability
    # next epoch reuses the same cached server (wave_stats-driven retune)
    assert fed.serve(model, autotune_buckets=True) is server


# ------------------------------------------------------------- checkpoints
def test_session_save_load_roundtrip(cls_data, tmp_path):
    """fed.save -> fed.load rehydrates a servable model, reconstructing the
    label decode from (n_classes, seed)."""
    xtr, ytr, xte, _ = cls_data
    p = ForestParams(n_estimators=3, max_depth=5, n_bins=16, n_classes=3,
                     seed=11)
    fed = Federation(parties=3, n_bins=16)
    fed.ingest(xtr, ytr)
    model = fed.fit(p)
    fed.save(model, str(tmp_path))
    restored = fed.load(str(tmp_path), p)
    _trees_equal(model.trees_, restored.trees_)
    np.testing.assert_array_equal(restored.predict(xte), model.predict(xte))
    np.testing.assert_array_equal(fed.predict(restored, xte),
                                  fed.predict(model, xte))


def test_save_load_model_family_tag(reg_data, tmp_path):
    """A saved boosting stack must never silently reload as a forest: save
    tags the family, load dispatches on it and rejects mismatches."""
    rxtr, rytr, rxte, _ = reg_data
    fed = Federation(parties=2, n_bins=16)
    fed.ingest(rxtr, rytr)
    model = fed.fit(BoostParams(n_rounds=3, max_depth=3, n_bins=16))
    d = str(tmp_path / "boost")
    fed.save(model, d)

    with pytest.raises(ValueError, match="boosting"):
        fed.load(d, ForestParams(task="regression", n_estimators=3,
                                 n_bins=16))
    with pytest.raises(ValueError, match="task"):
        fed.load(d, BoostParams(task="binary", n_rounds=3, max_depth=3,
                                n_bins=16))

    restored = fed.load(d, BoostParams(n_rounds=3, max_depth=3, n_bins=16))
    assert restored.base_ == model.base_
    assert len(restored.trees_) == len(model.trees_)
    np.testing.assert_allclose(restored.predict(rxte), model.predict(rxte),
                               rtol=1e-6)
    # and the restored handle serves through the same engine
    server = fed.serve(restored, buckets=(64,))
    np.testing.assert_allclose(server.serve(rxte), model.predict(rxte),
                               rtol=1e-4, atol=1e-4)

    # the reverse mismatch: a forest checkpoint refuses BoostParams
    fmodel = fed.fit(ForestParams(task="regression", n_estimators=2,
                                  max_depth=3, n_bins=16))
    d2 = str(tmp_path / "forest")
    fed.save(fmodel, d2)
    with pytest.raises(ValueError, match="forest"):
        fed.load(d2, BoostParams(n_rounds=2, n_bins=16))


def test_load_untagged_legacy_checkpoint(reg_data, tmp_path):
    """fit_resumable chunks (bare PartyTree snapshots, no meta) still load
    as forests — the pre-tag format stays readable."""
    from repro import ckpt
    rxtr, rytr, rxte, _ = reg_data
    fed = Federation(parties=2, n_bins=16)
    fed.ingest(rxtr, rytr)
    p = ForestParams(task="regression", n_estimators=2, max_depth=3,
                     n_bins=16)
    model = fed.fit(p)
    ckpt.save_checkpoint(str(tmp_path), 2, model.trees_)   # no meta
    restored = fed.load(str(tmp_path), p)
    np.testing.assert_array_equal(restored.predict(rxte),
                                  model.predict(rxte))


# ------------------------------------------------------- sharded substrate
_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro import compat
from repro.core.types import ForestParams
from repro.federation import Federation

mesh = compat.make_mesh((2, 4), ("trees", "parties"))
fed = Federation(parties=4, substrate="sharded", mesh=mesh,
                 hist_impl="scatter")
p = ForestParams(n_classes=2, n_estimators=2, max_depth=5, n_bins=8)
m, n, fp, t = 4, 4096, 8, 4
fit_args = (jax.ShapeDtypeStruct((m, n, fp), jnp.uint8),
            jax.ShapeDtypeStruct((m, fp), jnp.int32),
            jax.ShapeDtypeStruct((t, m * fp), jnp.bool_),
            jax.ShapeDtypeStruct((t, n), jnp.float32),
            jax.ShapeDtypeStruct((n, p.n_stat_channels), jnp.float32))
fit = fed.fit_program(p)
c = jax.jit(fit).lower(*fit_args).compile()
assert c.memory_analysis().temp_size_in_bytes > 0
trees_shape = jax.eval_shape(fit, *fit_args)
pred = fed.predict_program(p, compact=True, mask_dtype=jnp.uint8)
xb_test = jax.ShapeDtypeStruct((m, 512, fp), jnp.uint8)
leaf_idx = jax.ShapeDtypeStruct((t, 2 ** p.max_depth), jnp.int32)
jax.jit(pred).lower(trees_shape, xb_test, leaf_idx).compile()

# boosting builds one tree per round: its T=1 per-round args must NOT shard
# over a multi-shard "trees" axis (tree_sharded=False) — executes eagerly
from repro.core import BoostParams
from repro.data import make_regression
bmesh = compat.make_mesh((2, 1), ("trees", "parties"))
bfed = Federation(parties=1, substrate="sharded", mesh=bmesh)
x, y = make_regression(200, 6, seed=0)
bfed.ingest(x, y, n_bins=8)
bm = bfed.fit(BoostParams(n_rounds=2, max_depth=2, n_bins=8))
assert bfed.predict(bm, x[:32]).shape == (32,)
print("SHARDED_OK")
"""


@pytest.mark.slow
def test_sharded_substrate_drydown_lowers():
    """The session's sharded substrate lowers fit + compact predict on a
    (trees, parties) mesh (subprocess so the forced device count never
    leaks into other tests)."""
    env = dict(os.environ, PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1500,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert res.returncode == 0, res.stderr[-3000:]
    assert "SHARDED_OK" in res.stdout


def test_sharded_substrate_validation():
    from repro.federation import resolve_substrate
    with pytest.raises(ValueError, match="mesh"):
        Federation(parties=2, substrate="sharded")
    with pytest.raises(ValueError, match="unknown substrate"):
        resolve_substrate("warp-drive")


def test_run_sharded_matches_run_simulated_single_party():
    """protocol.run_sharded on a 1-device parties mesh == run_simulated."""
    import jax.numpy as jnp
    from repro.core import protocol
    from repro.core.types import PARTY_AXIS
    from repro.launch import mesh as mesh_mod

    def fn(x_i, scale):
        return jax.lax.psum(x_i.sum(), PARTY_AXIS) * scale

    x = jnp.arange(8.0).reshape(1, 8)          # one party's block
    mesh = mesh_mod.make_host_mesh(1, axes=(PARTY_AXIS,), shape=(1,))
    sim = protocol.run_simulated(fn, (x,), (2.0,))
    shd = protocol.run_sharded(fn, (x,), (2.0,), mesh=mesh)
    np.testing.assert_array_equal(np.asarray(sim), np.asarray(shd))


def test_from_checkpoint_with_mesh_derives_party_count(tmp_path):
    """ForestServer.from_checkpoint(mesh=...) must take M from the
    checkpointed stack, not the session default (regression test)."""
    from repro.launch import mesh as mesh_mod
    from repro.serving import ForestServer
    x, y = make_classification(300, 10, 2, seed=31)
    p = ForestParams(n_estimators=2, max_depth=4, n_bins=8, seed=32)
    fed = Federation(parties=1, n_bins=8)
    part = fed.ingest(x[:250], y[:250])
    model = fed.fit(p)
    fed.save(model, str(tmp_path))
    mesh = mesh_mod.make_host_mesh(1, axes=("trees", "parties"),
                                   shape=(1, 1))
    server = ForestServer.from_checkpoint(str(tmp_path), p, mesh=mesh,
                                          partition=part, buckets=(32,))
    np.testing.assert_array_equal(server.serve(x[250:]),
                                  model.predict(x[250:]))


def test_load_respects_fit_time_privacy_flags(cls_data, tmp_path):
    """A forest fitted with encrypt_labels=False must load with the same
    flag (the checkpoint stores no privacy metadata — documented contract);
    the reconstructed decode is only applied to encrypted fits."""
    xtr, ytr, xte, _ = cls_data
    p = ForestParams(n_estimators=2, max_depth=4, n_bins=8, n_classes=3,
                     seed=13)
    fed = Federation(parties=2, n_bins=8)
    fed.ingest(xtr, ytr)
    model = fed.fit(p, encrypt_labels=False)
    fed.save(model, str(tmp_path))
    restored = fed.load(str(tmp_path), p, encrypt_labels=False)
    np.testing.assert_array_equal(restored.predict(xte), model.predict(xte))
