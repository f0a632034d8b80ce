"""Each cell's check passes on the program and fails on its faults and on
its control, run through the harness at a small size on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import tiny


def test_fit_cell_is_correct_when_sound():
    r = tiny.run(tiny.cell("fit.tmkt"))
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "fit_job_s"}


def test_sharded_fit_cell_is_correct_when_sound():
    r = tiny.run(tiny.cell(tiny.SHARDED))
    assert r["correct"], r["checks"]


def test_serve_cell_is_correct_when_sound():
    r = tiny.run(tiny.cell("serve.tmkt.poisson"), seconds=1.0)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] == 40
    assert set(r["metrics"]) == {"setup_s", "serve_p50_ms",
                                 "serve_rows_per_s"}


# ------------------------------------------------------------------ faults
def _unfitted(trees):
    """PartyTree arrays as the fit initialises them."""
    fill = {"is_leaf": False, "leaf_stats": 0.0, "has_split": False,
            "split_floc": -1, "split_bin": -1, "owner": -1, "split_gid": -1}
    return type(trees)(**{k: jnp.full_like(v, fill[k])
                          for k, v in trees._asdict().items()})


def _moved_root_bin(trees):
    owner = int(np.asarray(trees.owner)[0, 0, 0])
    b = trees.split_bin
    return trees._replace(split_bin=b.at[owner, 0, 0].set(
        (b[owner, 0, 0] + 15) % 31))


def _patch_fit(monkeypatch, change):
    from repro.core.forest import FederatedForest
    fit = FederatedForest.fit

    def patched(self, partition, y):
        out = fit(self, partition, y)
        out.trees_ = change(out.trees_)
        return out
    monkeypatch.setattr(FederatedForest, "fit", patched)


def _half_batch(monkeypatch):
    """Histograms over every other row, doubled: the mean over the rest."""
    from repro.kernels import ops
    scatter = ops.BACKENDS["scatter"]

    def half(xb, seg, stats, n_level, n_bins):
        keep = jnp.arange(seg.shape[0]) % 2 == 0
        return scatter(xb, jnp.where(keep, seg, -1), 2.0 * stats, n_level,
                       n_bins)
    monkeypatch.setitem(ops.BACKENDS, "scatter", half)


def _no_exchange(monkeypatch):
    """Each party keeps its own split bests and its own partition bits."""
    from repro.core import tree

    class LocalLax:
        def __getattr__(self, name):
            return getattr(jax.lax, name)

        @staticmethod
        def all_gather(x, axis_name):
            n = jax.lax.axis_size(axis_name)
            return jnp.broadcast_to(x[None], (n,) + x.shape)

        @staticmethod
        def psum(x, axis_name):
            return x
    monkeypatch.setattr(tree, "lax", LocalLax())


FIT_FAULTS = {
    "state_unchanged": lambda mp: _patch_fit(mp, _unfitted),
    "half_batch": _half_batch,
    "no_exchange": _no_exchange,
    "answer_altered": lambda mp: _patch_fit(mp, _moved_root_bin),
}


@pytest.mark.parametrize("cell", ["fit.tmkt", tiny.SHARDED])
@pytest.mark.parametrize("fault", sorted(FIT_FAULTS))
def test_fit_fault_is_not_correct(monkeypatch, cell, fault):
    FIT_FAULTS[fault](monkeypatch)
    r = tiny.run(tiny.cell(cell))
    assert not r["correct"], r["checks"]


def _flip_first_answer(monkeypatch):
    from repro.serving import RequestQueue
    drain = RequestQueue.drain

    def patched(self):
        out = drain(self)
        for rid in sorted(out)[:1]:
            out[rid] = out[rid].copy()
            out[rid][0] = 1 - out[rid][0]
        return out
    monkeypatch.setattr(RequestQueue, "drain", patched)


def _half_wave(monkeypatch):
    """Every wave answers its first half of rows and zeros the rest."""
    from repro.serving.engine import ModelServer
    collect = ModelServer.collect

    def patched(self, wave):
        out = np.array(collect(self, wave))
        out[len(out) // 2:] = 0
        return out
    monkeypatch.setattr(ModelServer, "collect", patched)


@pytest.mark.parametrize("fault", [_flip_first_answer, _half_wave])
def test_serve_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    r = tiny.run(tiny.cell("serve.tmkt.poisson"), seconds=1.0)
    assert not r["correct"], r["checks"]


# ----------------------------------------------------------------- control
@pytest.mark.parametrize("name", ["fit.tmkt", tiny.SHARDED])
def test_fit_control_fails_the_limits(name):
    c = tiny.cell(name)
    c.clock = harness.CompileClock()
    d = harness.driver_for(c)
    d.setup()
    d.window(0.1)
    d.release()
    limits = c.params["limits"]
    got = d.control()
    assert any(v > limits[k] for k, v in got.items()), got


def test_serve_control_fails_the_limit():
    """bf16 binning moves ~2% of bins by one; with enough trees, levels and
    rows some of those moves cross a split and flip a close vote."""
    c = tiny.cell("serve.tmkt.poisson")
    c.config = dict(c.config, forest=dict(c.config["forest"],
                                          n_estimators=11, max_depth=6))
    c.traffic = dict(c.traffic, rate_per_s=100.0)
    c.params = dict(c.params, check_rows=100_000)
    c.clock = harness.CompileClock()
    d = harness.driver_for(c)
    d.setup()
    d.window(1.0)
    d.release()
    assert d.control()["served_mismatch"] > c.params["limits"][
        "served_mismatch"]
