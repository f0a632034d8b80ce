"""Chip smoke: Federated Forest's main path, end to end, on a TPU.

Drives the public lifecycle — ``Federation.ingest -> fit -> serve ->
RequestQueue`` — at the paper's published data scale with
``ForestParams(n_estimators=20, max_depth=8, n_bins=32)``:

  * classification: the target-marketing scenario, 156,198 rows x 95
    features, binary, split between a bank (11 features) and an e-commerce
    company (84 features);
  * regression: Year Prediction's shape, 515,345 rows x 90 features, over
    4 parties.

Data is synthetic (``repro.data``, seeded by ``--seed``).  Each task checks,
and prints as a boolean:

  * federated trees == a 1-party (centralized) fit, bit for bit;
  * one real-size histogram on the chip == ``np.add.at`` on the host (exact
    counts; regression sums within f32 tolerance);
  * served answers == ``fed.predict`` == a NumPy walk of the fitted trees;
  * every request resolved, no dead letters, no degraded waves.

Usage:
  python chip_smoke.py               one chip; exits non-zero off a TPU
  python chip_smoke.py --four-chips  only the sharded path: the
                                     classification job on a (trees=1,
                                     parties=4) mesh over 4 chips vs the
                                     simulated substrate on one of them
  python chip_smoke.py --rehearse    1/100 scale on any host (Pallas
                                     interpreter off a TPU); prints no
                                     device result

On success the last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import compat  # noqa: E402
from repro.core import ForestParams, PartyBlock, impurity  # noqa: E402
from repro.data import make_classification, make_regression  # noqa: E402
from repro.federation import Federation  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serving import RequestQueue, ServeConfig  # noqa: E402

CLS_ROWS, CLS_FEATURES, CLS_SPLIT = 156_198, 95, (11, 84)
REG_ROWS, REG_FEATURES, REG_PARTIES = 515_345, 90, 4
TEST_ROWS = 8192
BUCKETS = (32, 256, 2048)
HIST_LEVEL = 128          # node slots of the deepest histogram a fit issues
_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace",
}
_clock = {"compile": 0.0, "trace": 0.0, "cache_hits": 0}
CHECKS: dict[str, bool] = {}


def _on_duration(event: str, secs: float, **_kw) -> None:
    kind = _COMPILE_EVENTS.get(event)
    if kind:
        _clock[kind] += secs


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _clock["cache_hits"] += 1


def timed(label: str, fn):
    """Run ``fn``, block on its result, and print its wall seconds beside
    the XLA compile seconds (persistent-cache reads included), the jaxpr
    trace + lowering seconds (nested traces count once per level) and the
    persistent-cache hits inside it."""
    before = dict(_clock)
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    wall = time.perf_counter() - t0
    comp = _clock["compile"] - before["compile"]
    trace = _clock["trace"] - before["trace"]
    hits = _clock["cache_hits"] - before["cache_hits"]
    print(f"time {label}: wall_s={wall:.3f} compile_s={comp:.3f} "
          f"trace_lower_s={trace:.3f} cache_hits={hits}", flush=True)
    return out


def check(name: str, ok) -> None:
    CHECKS[name] = bool(ok)
    print(f"check {name}: {bool(ok)}", flush=True)


# ------------------------------------------------------------ host references
def global_view(model) -> dict:
    """The fitted forest as the master sees it: shared structure plus each
    split's global (feature id, bin), read from the owning party's row."""
    t = jax.tree.map(np.asarray, model.trees_)
    feat_gid = model.partition_.feat_gid
    owner = t.owner[0]
    o = np.clip(owner, 0, None)
    ti, ni = np.indices(owner.shape)
    floc = np.clip(t.split_floc[o, ti, ni], 0, None)
    split = owner >= 0
    return {"is_leaf": t.is_leaf[0], "leaf_stats": t.leaf_stats[0],
            "split_gid": t.split_gid[0],
            "split_feature": np.where(split, feat_gid[o, floc], -1),
            "split_bin": np.where(split, t.split_bin[o, ti, ni], -1),
            "parties_agree": all(
                np.array_equal(t.is_leaf[i], t.is_leaf[0])
                and np.array_equal(t.leaf_stats[i], t.leaf_stats[0])
                for i in range(t.is_leaf.shape[0]))}


def same_trees(a: dict, b: dict) -> bool:
    return a["parties_agree"] and b["parties_agree"] and all(
        np.array_equal(a[k], b[k]) for k in a if k != "parties_agree")


def host_walk(model, x_test: np.ndarray) -> np.ndarray:
    """Route every test row through the fitted PartyTree arrays with NumPy:
    the owner of each split compares its binned value, leaves vote."""
    p = model.params
    t = jax.tree.map(np.asarray, model.trees_)
    xb = model.partition_.bin_test(x_test)                    # (M, n, Fp)
    rows = np.arange(xb.shape[1])
    per_tree = []
    for k in range(t.is_leaf.shape[1]):
        node = np.zeros(len(rows), np.int64)
        for _ in range(p.max_depth):
            owner = t.owner[0, k, node]
            o = np.clip(owner, 0, None)
            floc = np.clip(t.split_floc[o, k, node], 0, None)
            right = xb[o, rows, floc] > t.split_bin[o, k, node]
            node = np.where(owner >= 0, 2 * node + 1 + right, node)
        assert t.is_leaf[0, k, node].all(), "walk ended off a leaf"
        per_tree.append(t.leaf_stats[0, k, node])             # (n, C)
    stats = np.stack(per_tree)                                # (T, n, C)
    if p.task == "classification":
        labels = stats.argmax(-1)
        votes = (labels[..., None] == np.arange(p.n_classes)).sum(0)
        return model._decode(votes.argmax(-1))
    vals = stats[..., 1] / np.maximum(stats[..., 0], np.float32(1e-12))
    return model._decode(vals.astype(np.float32).mean(0))


def histogram_check(task: str, part, y_stats: np.ndarray, hist_impl: str,
                    seed: int) -> None:
    """One real-size histogram on the device vs np.add.at on the host, over
    the widest party's real columns."""
    widths = (part.feat_gid >= 0).sum(1)
    i = int(widths.argmax())
    xb = part.xb[i][:, :widths[i]]
    n, f = xb.shape
    rng = np.random.default_rng(seed)
    seg = rng.integers(-1, HIST_LEVEL, n).astype(np.int32)
    w = rng.integers(0, 4, n).astype(np.float32)              # bootstrap-like
    stats = (y_stats * w[:, None]).astype(np.float32)
    got = np.asarray(timed(f"{task}.histogram", lambda: ops.histogram(
        xb, seg, stats, HIST_LEVEL, part.n_bins, impl=hist_impl)))
    keep = seg >= 0
    idx = (seg[keep][:, None], np.arange(f)[None, :], xb[keep])
    want = np.zeros((HIST_LEVEL, f, part.n_bins, stats.shape[1]))
    np.add.at(want, idx, stats[keep][:, None, :].astype(np.float64))
    if task == "classification":
        ok = np.array_equal(got, want)
    else:
        scale = np.zeros_like(want)
        np.add.at(scale, idx, np.abs(stats[keep][:, None, :]).astype(np.float64))
        ok = bool(np.all(np.abs(got - want) <= 1e-5 * scale + 1e-6))
    print(f"histogram {task}: party {i} N={n} F={f} L={HIST_LEVEL} "
          f"B={part.n_bins} C={stats.shape[1]}")
    check(f"{task}.chip_histogram_equals_host_numpy", ok)


def serve_check(task: str, fed: Federation, model, x_test: np.ndarray,
                seed: int, rounds: int = 3) -> None:
    predicted = timed(f"{task}.predict", lambda: fed.predict(model, x_test))
    walked = host_walk(model, x_test)
    if task == "classification":
        check(f"{task}.predict_equals_host_walk",
              np.array_equal(predicted, walked))
    else:   # float mean over trees: summation order may differ by an ulp
        check(f"{task}.predict_equals_host_walk",
              np.allclose(predicted, walked, rtol=1e-5, atol=1e-5))
    server = fed.serve(model, ServeConfig(buckets=BUCKETS))
    timed(f"{task}.serve_warmup", server.warmup)
    queue = RequestQueue(server)
    rng = np.random.default_rng(seed)
    served_ok, submitted, resolved = True, 0, 0
    for rnd in range(rounds):
        sizes = rng.integers(1, BUCKETS[-1] + 1, 8)
        if rnd == 0:
            sizes[:2] = (1, BUCKETS[-1])
        spans = {}
        for s in sizes:
            lo = int(rng.integers(0, len(x_test) - s + 1))
            spans[queue.submit(x_test[lo:lo + s])] = (lo, lo + int(s))
        submitted += len(spans)
        results = timed(f"{task}.serve_round{rnd}[rows={int(sizes.sum())}]",
                        queue.drain)
        resolved += sum(rid in results for rid in spans)
        for rid, (lo, hi) in spans.items():
            got = results.get(rid)
            if got is None or not np.array_equal(got, predicted[lo:hi]):
                served_ok = False
                if got is not None:
                    diff = np.abs(got.astype(np.float64) - predicted[lo:hi])
                    print(f"mismatch request {rid}: {int((diff > 0).sum())}/"
                          f"{hi - lo} rows differ, max |diff| {diff.max()!r}")
    degraded = sum(bool(w.get("degraded")) for w in server.wave_stats)
    dead = submitted - resolved + queue.pending_requests()
    print(f"serve {task}: requests={submitted} resolved={resolved} "
          f"dead_letters={dead} degraded_waves={degraded} "
          f"compiles={server.compile_count} buckets={server.buckets}")
    check(f"{task}.served_equals_predict", served_ok)
    check(f"{task}.all_requests_resolved", dead == 0 and degraded == 0
          and server.compile_count == len(server.buckets))


# --------------------------------------------------------------------- tasks
def run_task(task: str, x, y, x_test, groups, params: ForestParams,
             hist_impl: str | None, seed: int) -> None:
    print(f"== {task}: {x.shape[0]} rows x {x.shape[1]} features, "
          f"parties {[len(g) for g in groups]}, {params.n_estimators} trees "
          f"depth {params.max_depth} bins {params.n_bins}", flush=True)
    ids = np.arange(len(x))
    blocks = [PartyBlock(name=f"party{i}", x=x[:, g], ids=ids,
                         y=y if i == 0 else None, feature_ids=g)
              for i, g in enumerate(groups)]
    fed = Federation(parties=len(groups), n_bins=params.n_bins,
                     hist_impl=hist_impl)
    part = timed(f"{task}.ingest", lambda: fed.ingest(blocks))
    model = timed(f"{task}.fit", lambda: fed.fit(params))
    # the same fit again: each fit re-traces its program, but the executables
    # now come from the persistent cache, so this is the steady fit time
    model = timed(f"{task}.fit_again", lambda: fed.fit(params))

    central = Federation(parties=1, n_bins=params.n_bins, hist_impl=hist_impl)
    central.ingest(x, y)
    cmodel = timed(f"{task}.fit_centralized", lambda: central.fit(params))
    check(f"{task}.federated_equals_centralized",
          same_trees(global_view(model), global_view(cmodel)))

    y_stats = np.asarray(impurity.stat_channels(
        jax.numpy.asarray(y), params.task, params.n_classes))
    histogram_check(task, part, y_stats, hist_impl or "auto", seed)
    serve_check(task, fed, model, x_test, seed + 1)


def four_chip_path(x, y, x_test, params: ForestParams,
                   hist_impl: str | None) -> None:
    """The classification job on the sharded substrate over 4 chips, against
    the simulated substrate on one of them."""
    devices = jax.devices()[:4]
    if len(devices) < 4:
        raise SystemExit(f"--four-chips needs 4 devices, found {len(devices)}")
    mesh = compat.make_mesh((1, 4), ("trees", "parties"), devices=devices)
    print(f"== four chips: mesh {dict(mesh.shape)} over "
          f"{[d.id for d in devices]}", flush=True)
    sharded = Federation(parties=4, substrate="sharded", mesh=mesh,
                         n_bins=params.n_bins, hist_impl=hist_impl)
    sharded.ingest(x, y)
    m_sh = timed("sharded.fit", lambda: sharded.fit(params))
    for shard in m_sh.trees_.is_leaf.addressable_shards:
        print(f"party {shard.index[0].start} trees on device {shard.device.id}")
    placed = {s.index[0].start: s.device.id
              for s in m_sh.trees_.is_leaf.addressable_shards}
    check("sharded.one_device_per_party",
          sorted(placed) == [0, 1, 2, 3] and len(set(placed.values())) == 4)

    simulated = Federation(parties=4, n_bins=params.n_bins,
                           hist_impl=hist_impl)
    simulated.ingest(x, y)
    with jax.default_device(devices[0]):
        m_sim = timed("simulated.fit", lambda: simulated.fit(params))
        want = timed("simulated.predict",
                     lambda: simulated.predict(m_sim, x_test))
    check("sharded_trees_equal_simulated", all(
        np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
        zip(jax.tree.leaves(m_sh.trees_), jax.tree.leaves(m_sim.trees_))))
    got = timed("sharded.predict", lambda: sharded.predict(m_sh, x_test))
    check("sharded_predict_equals_simulated", np.array_equal(got, want))
    server = sharded.serve(m_sh, ServeConfig(buckets=BUCKETS))
    timed("sharded.serve_warmup", server.warmup)
    queue = RequestQueue(server)
    spans = {queue.submit(x_test[lo:hi]): (lo, hi)
             for lo, hi in ((0, 1), (1, 300), (300, 2348))}
    results = timed("sharded.serve_round", queue.drain)
    check("sharded_served_equals_simulated", all(
        np.array_equal(results[rid], want[lo:hi])
        for rid, (lo, hi) in spans.items()))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 4-chip path and its "
                         "single-chip comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="1/100 scale on any host; prints no device result")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    backend = ops.resolve_backend("auto")
    print(f"jax {jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind} device_count={device['count']} "
          f"hist_backend(auto)={backend} compile_cache={cache_dir}",
          flush=True)
    if not args.rehearse:
        if dev.platform != "tpu":
            print(f"chip_smoke: needs a TPU, JAX found platform "
                  f"{dev.platform!r}", file=sys.stderr)
            return 2
        check("hist_backend_is_pallas", backend == "pallas")
    hist_impl = None
    if args.rehearse and dev.platform != "tpu":
        hist_impl = "pallas_interpret"
    scale = 100 if args.rehearse else 1
    params = ForestParams(n_estimators=20, max_depth=8, n_bins=32,
                          seed=args.seed)

    x, y = make_classification(CLS_ROWS // scale + TEST_ROWS, CLS_FEATURES,
                               2, n_informative=24, seed=args.seed)
    x, y, x_test = x[:-TEST_ROWS], y[:-TEST_ROWS], x[-TEST_ROWS:]
    if args.four_chips:
        four_chip_path(x, y, x_test, params, hist_impl)
    else:
        run_task("classification", x, y, x_test,
                 [np.arange(CLS_SPLIT[0]),
                  np.arange(CLS_SPLIT[0], CLS_FEATURES)], params,
                 hist_impl, args.seed)
        x, y = make_regression(REG_ROWS // scale + TEST_ROWS, REG_FEATURES,
                               seed=args.seed)
        x, y, x_test = x[:-TEST_ROWS], y[:-TEST_ROWS], x[-TEST_ROWS:]
        run_task("regression", x, y, x_test,
                 np.array_split(np.arange(REG_FEATURES), REG_PARTIES),
                 ForestParams(task="regression", n_estimators=20,
                              max_depth=8, n_bins=32, seed=args.seed),
                 hist_impl, args.seed)

    failed = [k for k, v in CHECKS.items() if not v]
    if failed:
        print(f"chip_smoke: FAILED {failed}", file=sys.stderr)
        return 1
    if args.rehearse:
        print(f"rehearsal passed on {device}")
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
