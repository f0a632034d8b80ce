"""Party-first data plane: PartyBlock ingestion, M-party hashed-ID
alignment, party-local binning, and party-block serving.

The load-bearing claims:
  * M-party ``crypto.align_ids`` puts every party on one canonical common
    ordering — invariant to per-party row shuffles and to party order —
    and fails loudly on duplicate IDs / empty intersections;
  * ingesting shuffled, partially-overlapping PartyBlocks (superset rows
    per party) yields a partition — and a fitted forest, and served
    outputs — BIT-IDENTICAL to the centrally pre-aligned build, on both
    tasks and both substrates (party-local binning is per-feature, hence
    lossless by construction);
  * the raw-matrix compat path is a thin adapter over PartyBlocks and
    preserves its pre-aligned row order exactly;
  * serving re-aligns out-of-order / superset per-party request blocks
    before dispatch (ForestServer.serve_parties, RequestQueue.submit_parties).
"""
import hashlib
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import (FederatedForest, ForestParams, PartyBlock, crypto,
                        partition_from_blocks)
from repro.core.partyblock import CSVSource, align_party_blocks
from repro.data import make_classification, make_party_views, make_regression
from repro.federation import Federation


def _trees_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _parts_equal(a, b):
    np.testing.assert_array_equal(a.xb, b.xb)
    np.testing.assert_array_equal(a.feat_gid, b.feat_gid)
    np.testing.assert_array_equal(a.boundaries, b.boundaries)
    assert a.n_features == b.n_features
    for ra, rb in zip(a.raw_parts, b.raw_parts):
        np.testing.assert_array_equal(ra, rb)


# --------------------------------------------------- M-party alignment core
def test_align_ids_multiparty_canonical_order():
    """Positions index one shared ordering (sorted common hashed IDs),
    whatever each party's row order or the party order is."""
    rng = np.random.default_rng(0)
    ids = np.array([f"u{i}" for i in range(40)])
    views = [rng.permutation(ids) for _ in range(3)]
    hashed = [crypto.hash_ids(v) for v in views]
    pos = crypto.align_ids(*hashed)
    assert len(pos) == 3
    ref = views[0][pos[0]]
    for v, p in zip(views, pos):
        np.testing.assert_array_equal(v[p], ref)
    # canonical: sorted by hashed value
    np.testing.assert_array_equal(crypto.hash_ids(ref),
                                  np.sort(crypto.hash_ids(ids)))
    # party order permutation -> same canonical ordering
    pos_rev = crypto.align_ids(*hashed[::-1])
    np.testing.assert_array_equal(views[2][pos_rev[0]], ref)


def test_align_ids_two_party_compat():
    """The historical 2-party unpack still works (quickstart.py shape)."""
    a = crypto.hash_ids(np.arange(10))
    b = crypto.hash_ids(np.arange(5, 15))
    ia, ib = crypto.align_ids(a, b)
    np.testing.assert_array_equal(a[ia], b[ib])
    assert len(ia) == 5


def test_align_ids_errors():
    a = crypto.hash_ids(["x", "y", "z"])
    with pytest.raises(ValueError, match="duplicate"):
        crypto.align_ids(np.concatenate([a, a[:1]]), a)
    with pytest.raises(ValueError, match="intersection"):
        crypto.align_ids(a, crypto.hash_ids(["p", "q"]))
    with pytest.raises(ValueError, match="at least one"):
        crypto.align_ids()


def _hex_reference_alignment(views, salt):
    """Positions from argsort of hex digest strings: the alignment as it
    was specified before digests were kept as bytes."""
    hexes = [np.array([hashlib.sha256(f"{salt}:{i}".encode()).hexdigest()
                       for i in v]) for v in views]
    common = np.sort(hexes[0])
    for h in hexes[1:]:
        common = np.intersect1d(common, h, assume_unique=True)
    return [np.argsort(h)[np.searchsorted(h, common, sorter=np.argsort(h))]
            for h in hexes]


@pytest.mark.parametrize("n_parties", [3, 4])
def test_align_ids_equals_hexdigest_reference(n_parties):
    """Shuffled superset views align exactly as a sort of hex digests
    does: same positions, same canonical order."""
    rng = np.random.default_rng(n_parties)
    ids = np.array([f"c{i:07d}" for i in range(3000)])
    views = [rng.permutation(np.concatenate([
        ids, [f"p{k}-{j}" for j in range(50 + 20 * k)]]))
        for k in range(n_parties)]
    pos = crypto.align_ids(*[crypto.hash_ids(v, "job") for v in views])
    ref = _hex_reference_alignment(views, "job")
    for p, r in zip(pos, ref):
        assert p.dtype == np.int64
        np.testing.assert_array_equal(p, r)


@pytest.mark.parametrize("ids", [["u1", "u2", "ü3"], np.arange(5),
                                 np.array(["a", "bb"]), []])
def test_hash_ids_returns_raw_sha256_digests(ids):
    got = crypto.hash_ids(ids, salt="s")
    want = np.array([hashlib.sha256(f"s:{i}".encode()).digest()
                     for i in ids], dtype="S32")
    assert got.dtype == crypto.DIGEST and got.shape == (len(ids),)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(crypto.hash_ids(ids, "s", memo=True), want)


def _digests(*parts: bytes) -> np.ndarray:
    assert all(len(p) == 32 for p in parts)
    return np.array(parts, dtype="S32")


_P, _Q = b"\x5a" * 8, b"\xa5" * 8            # shared 8-byte prefixes
_TIE_LO, _TIE_HI = _P + b"\x01" * 24, _P + b"\x02" * 24
_FALSE_A, _FALSE_B = _Q + b"\x03" * 24, _Q + b"\x04" * 24


def test_align_ids_resolves_planted_prefix_ties():
    """Digests that share their first 8 bytes, within a party and across
    parties, align on the full digests: the tied pair in full-digest order,
    the false cross-party match left out."""
    shared = [bytes([k]) * 32 for k in (9, 200, 77)]
    a = _digests(_TIE_HI, shared[0], _FALSE_A, _TIE_LO, shared[1], shared[2])
    b = _digests(shared[2], _TIE_LO, shared[1], _FALSE_B, shared[0], _TIE_HI)
    pa, pb = crypto.align_ids(a, b)
    want = sorted(shared + [_TIE_LO, _TIE_HI])
    np.testing.assert_array_equal(a[pa], _digests(*want))
    np.testing.assert_array_equal(b[pb], _digests(*want))
    # a false cross-party match alone (no tie within a party)
    pa, pb = crypto.align_ids(_digests(_FALSE_A, shared[0]),
                              _digests(shared[0], _FALSE_B))
    assert pa.tolist() == [1] and pb.tolist() == [0]
    with pytest.raises(ValueError, match="intersection"):
        crypto.align_ids(_digests(_FALSE_A), _digests(_FALSE_B))


def test_duplicate_raw_ids_raise_naming_the_party():
    """A duplicate raw ID is a duplicate digest; the error names the party,
    on the hashed path (shuffled blocks) and the identity fast path."""
    ids = np.array([f"u{i}" for i in range(6)])
    dup = np.concatenate([ids[:5], ids[:1]])
    x = np.zeros((6, 2))
    for other_ids in (ids[::-1], dup):          # hashed path, fast path
        blocks = [PartyBlock("bank", x, ids=dup),
                  PartyBlock("shop", x, ids=other_ids)]
        with pytest.raises(ValueError,
                           match="party 'bank' has duplicate sample IDs"):
            align_party_blocks(blocks)
    with pytest.raises(ValueError, match="party 'shop' has duplicate"):
        crypto.align_ids(crypto.hash_ids(ids), crypto.hash_ids(dup),
                         names=["bank", "shop"])


def test_ingest_errors_are_loud():
    """Satellite: empty intersection / in-party duplicates surface as clear
    ValueErrors from Federation.ingest, not shape errors deep in the stack."""
    fed = Federation(parties=2)
    a = PartyBlock("a", np.zeros((3, 2)), ids=["1", "2", "3"], y=[0, 1, 0])
    with pytest.raises(ValueError, match="intersection"):
        fed.ingest([a, PartyBlock("b", np.zeros((2, 2)), ids=["8", "9"])])
    with pytest.raises(ValueError, match="duplicate"):
        fed.ingest([a, PartyBlock("b", np.zeros((3, 2)),
                                  ids=["1", "1", "3"])])
    with pytest.raises(ValueError, match="labels ride"):
        fed.ingest([a, PartyBlock("b", np.zeros((3, 2)),
                                  ids=["1", "2", "3"])], y=np.zeros(3))
    with pytest.raises(ValueError, match="declares 2"):
        fed.ingest([a])
    with pytest.raises(ValueError, match="more than one party"):
        fed.ingest([a, PartyBlock("b", np.zeros((3, 2)), ids=["1", "2", "3"],
                                  y=[1, 0, 1])])
    with pytest.raises(ValueError, match="unique"):
        fed.ingest([a, PartyBlock("a", np.zeros((3, 2)),
                                  ids=["1", "2", "3"])])
    # raw-matrix-only knobs must not be silently dropped on the block path
    ok = PartyBlock("b", np.zeros((3, 2)), ids=["1", "2", "3"])
    with pytest.raises(ValueError, match="raw-matrix"):
        fed.ingest([a, ok], contiguous=False)
    with pytest.raises(ValueError, match="raw-matrix"):
        fed.ingest([a, ok], seed=7)


def test_block_validation():
    with pytest.raises(ValueError, match="sample IDs for"):
        PartyBlock("p", np.zeros((3, 2)), ids=["1", "2"])
    with pytest.raises(ValueError, match="labels for"):
        PartyBlock("p", np.zeros((3, 2)), ids=["1", "2", "3"], y=[1])
    with pytest.raises(ValueError, match="feature_ids must be set"):
        partition_from_blocks(
            [PartyBlock("a", np.zeros((2, 1)), ids=["1", "2"],
                        feature_ids=[0]),
             PartyBlock("b", np.zeros((2, 1)), ids=["1", "2"])], 4)
    with pytest.raises(ValueError, match="partition 0..F-1"):
        partition_from_blocks(
            [PartyBlock("a", np.zeros((2, 1)), ids=["1", "2"],
                        feature_ids=[0]),
             PartyBlock("b", np.zeros((2, 1)), ids=["1", "2"],
                        feature_ids=[2])], 4)


# ------------------------------------------- losslessness under real ingest
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("contiguous", [True, False])
def test_partition_from_blocks_bit_identical_to_dense(seed, contiguous):
    """Property-style: shuffled rows, permuted party order, disjoint extra
    samples per party — the aligned partition equals the dense pre-aligned
    build bit for bit (party-local binning included, validate=True)."""
    x, y = make_classification(260, 11, 2, seed=seed)
    blocks, xa, ya = make_party_views(x, y, 3, overlap=0.7,
                                      contiguous=contiguous, seed=seed)
    order = np.random.default_rng(seed).permutation(3)
    part, yb, ids = partition_from_blocks([blocks[i] for i in order], 8,
                                          validate=True)
    dense = Federation(parties=3, n_bins=8, seed=seed).ingest(
        xa, ya, contiguous=contiguous)
    _parts_equal(part, dense)
    np.testing.assert_array_equal(yb, ya)
    assert len(ids) == len(xa)
    np.testing.assert_array_equal(part.dense_raw(), xa)


@pytest.mark.parametrize("task", ["classification", "regression"])
def test_party_first_fit_and_serve_bit_identical(task):
    """Acceptance: fit from realistic PartyBlocks == fit from the central
    pre-aligned matrix — bit-identical forest, identical predictions and
    served outputs — for both tasks (simulated substrate; the sharded
    substrate is covered subprocess-side below)."""
    if task == "classification":
        x, y = make_classification(300, 10, 3, seed=4)
        p = ForestParams(task=task, n_classes=3, n_estimators=4, max_depth=5,
                         n_bins=16, seed=11)
    else:
        x, y = make_regression(300, 10, seed=4)
        p = ForestParams(task=task, n_estimators=4, max_depth=5, n_bins=16,
                         seed=11)
    blocks, xa, ya = make_party_views(x, y, 3, overlap=0.75, seed=4)

    fed = Federation(parties=3, n_bins=16)
    part = fed.ingest(blocks, validate=True)
    assert part.n_samples == len(xa)
    np.testing.assert_array_equal(fed.labels_, ya)
    model = fed.fit(p)

    fed_c = Federation(parties=3, n_bins=16)
    fed_c.ingest(xa, ya)
    central = fed_c.fit(p)

    _trees_equal(model.trees_, central.trees_)
    xt = xa[:64]
    np.testing.assert_array_equal(fed.predict(model, xt),
                                  fed_c.predict(central, xt))
    # serving: identical outputs through the bucketed engine
    server = fed.serve(model, buckets=(32,))
    np.testing.assert_array_equal(server.serve(xt), central.predict(xt))


def test_ingest_invariant_to_party_order_and_shuffle():
    """Permuting the block list and re-shuffling each party's rows cannot
    change the session's partition, labels, or fitted forest."""
    x, y = make_classification(240, 9, 2, seed=6)
    blocks, _, _ = make_party_views(x, y, 3, overlap=0.8, seed=6)
    rng = np.random.default_rng(0)
    reshuffled = []
    for b in blocks[::-1]:
        perm = rng.permutation(b.n_samples)
        reshuffled.append(PartyBlock(
            name=b.name, x=b.x[perm], ids=b.ids[perm],
            y=None if b.y is None else b.y[perm],
            feature_ids=b.feature_ids))
    p = ForestParams(n_estimators=2, max_depth=4, n_bins=8, seed=3)
    fed1, fed2 = (Federation(parties=3, n_bins=8) for _ in range(2))
    part1, part2 = fed1.ingest(blocks), fed2.ingest(reshuffled)
    _parts_equal(part1, part2)
    np.testing.assert_array_equal(fed1.labels_, fed2.labels_)
    np.testing.assert_array_equal(fed1.aligned_ids_, fed2.aligned_ids_)
    _trees_equal(fed1.fit(p).trees_, fed2.fit(p).trees_)


# -------------------------------------------------------- DataSource / CSV
def test_csv_roundtrip_and_source(tmp_path):
    x, y = make_classification(60, 6, 2, seed=8)
    blocks, xa, ya = make_party_views(x, y, 2, overlap=0.9, seed=8)
    sources = []
    for b in blocks:
        sources.append(CSVSource(b.to_csv(str(tmp_path / f"{b.name}.csv")),
                                 name=b.name))
    loaded = sources[0].load()
    assert loaded.name == blocks[0].name
    np.testing.assert_array_equal(loaded.ids, blocks[0].ids)
    np.testing.assert_array_equal(loaded.x, blocks[0].x)
    np.testing.assert_array_equal(loaded.y, blocks[0].y)
    assert loaded.y.dtype == np.int64          # integral labels -> int
    # global feature ids survive the round trip (gf<N> headers)
    np.testing.assert_array_equal(loaded.feature_ids, blocks[0].feature_ids)

    # full ingest through the DataSource hook == the dense build
    fed = Federation(parties=2, n_bins=8)
    part = fed.ingest(sources, validate=True)
    dense = Federation(parties=2, n_bins=8).ingest(xa, ya)
    np.testing.assert_array_equal(part.xb, dense.xb)
    np.testing.assert_array_equal(fed.labels_, ya)


def test_csv_roundtrip_preserves_encoding_under_name_reorder(tmp_path):
    """Party names whose sorted order differs from the original party order
    must not scramble the global column encoding through a CSV round trip —
    feature_ids ride along in the headers."""
    x, y = make_classification(80, 6, 2, seed=21)
    blocks, xa, ya = make_party_views(x, y, 2, overlap=0.9, seed=21)
    renamed = [PartyBlock(name=n, x=b.x, ids=b.ids, y=b.y,
                          feature_ids=b.feature_ids)
               for n, b in zip(("zulu", "alpha"), blocks)]
    sources = [CSVSource(b.to_csv(str(tmp_path / f"{b.name}.csv")),
                         name=b.name) for b in renamed]
    fed_direct = Federation(parties=2, n_bins=8)
    direct = fed_direct.ingest(renamed)
    fed_csv = Federation(parties=2, n_bins=8)
    via_csv = fed_csv.ingest(sources, validate=True)
    _parts_equal(direct, via_csv)            # the round trip is the identity
    assert via_csv.party_names == ("alpha", "zulu")   # canonical name sort
    # and the model is still the dense pre-aligned one: the party AXIS order
    # differs (sorted by the new names) but the global column encoding — and
    # hence every split and prediction — is preserved bit-for-bit
    p = ForestParams(n_estimators=2, max_depth=4, n_bins=8, seed=2)
    fed_dense = Federation(parties=2, n_bins=8)
    fed_dense.ingest(xa, ya)
    np.testing.assert_array_equal(
        fed_csv.predict(fed_csv.fit(p), xa),
        fed_dense.predict(fed_dense.fit(p), xa))


def test_ingest_empty_blocks_raise_loudly():
    """Zero-row blocks must hit the empty-intersection error, not an
    IndexError deep in binning (the identity fast path included)."""
    empty = [PartyBlock("a", np.empty((0, 2)), ids=np.empty(0, dtype="<U4")),
             PartyBlock("b", np.empty((0, 3)), ids=np.empty(0, dtype="<U4"))]
    with pytest.raises(ValueError, match="intersection"):
        Federation(parties=2).ingest(empty)


def test_csv_regression_labels_keep_float_dtype(tmp_path):
    """Whole-number regression targets round trip as float64: only
    lexically-integer label columns ("3", not "3.0") become class ids."""
    b = PartyBlock("reg", np.arange(8.0).reshape(4, 2),
                   ids=["a", "b", "c", "d"], y=[10.0, 20.0, 30.0, 40.0])
    loaded = PartyBlock.from_csv(b.to_csv(str(tmp_path / "reg.csv")))
    assert loaded.y.dtype == np.float64
    np.testing.assert_array_equal(loaded.y, b.y)


def test_parse_party_csv_specs():
    from repro.launch.train import parse_party_csvs
    s = parse_party_csvs(["bank=/data/run=3/bank.csv", "/tmp/bare.csv",
                          "/data/run=3/ecom.csv"], "id", "label")
    assert (s[0].name, s[0].path) == ("bank", "/data/run=3/bank.csv")
    assert (s[1].name, s[1].path) == (None, "/tmp/bare.csv")
    assert (s[2].name, s[2].path) == (None, "/data/run=3/ecom.csv")


def test_csv_missing_id_column(tmp_path):
    f = tmp_path / "bad.csv"
    f.write_text("a,b\n1.0,2.0\n")
    with pytest.raises(ValueError, match="no 'id' column"):
        PartyBlock.from_csv(str(f))


def test_csv_nan_and_missing_values_raise_loudly(tmp_path):
    """Satellite: a NaN or empty feature cell fails the parse naming the
    column and the data row — binning would otherwise silently bucket NaNs
    and corrupt every split on that feature."""
    f = tmp_path / "nan.csv"
    f.write_text("id,age,income\nu1,33,50000\nu2,41,NaN\nu3,29,61000\n")
    with pytest.raises(ValueError, match=r"'income'.*data row 1"):
        PartyBlock.from_csv(str(f))
    f.write_text("id,age,income\nu1,33,50000\nu2,41,1.0\nu3,,61000\n")
    with pytest.raises(ValueError, match=r"'age'.*data row 2"):
        PartyBlock.from_csv(str(f))
    # the chunked reader shares the parse helpers: same contract, and the
    # row index stays global even when the bad row is deep in a later chunk
    from repro.streaming import ChunkedCSVSource
    f.write_text("id,a\n" + "".join(f"u{i},{i}.5\n" for i in range(7))
                 + "u7,nan\n")
    with pytest.raises(ValueError, match=r"'a'.*data row 7"):
        for _ in ChunkedCSVSource(str(f)).iter_chunks(3):
            pass


# ------------------------------------------------------ party-block serving
def test_serve_parties_realigns_out_of_order_and_superset():
    """ForestServer.serve_parties: request blocks keyed by hashed IDs with
    shuffled rows and party-local extras serve exactly the model's
    predictions on the aligned common rows."""
    x, y = make_classification(260, 9, 2, seed=10)
    blocks, xa, ya = make_party_views(x, y, 3, overlap=0.85, seed=10)
    fed = Federation(parties=3, n_bins=16)
    part = fed.ingest(blocks)
    model = fed.fit(ForestParams(n_estimators=3, max_depth=4, n_bins=16,
                                 seed=1))
    server = fed.serve(model, buckets=(64,))

    xt, _ = make_classification(40, 9, 2, seed=77)
    qids = np.array([f"q{i}" for i in range(len(xt))])
    rng = np.random.default_rng(3)
    req = []
    for i, name in enumerate(part.party_names):
        gid = part.feat_gid[i][part.feat_gid[i] >= 0]
        rows = rng.permutation(len(xt))
        extra = rng.normal(size=(4, len(gid)))
        req.append(PartyBlock(
            name=name, x=np.concatenate([xt[rows][:, gid], extra]),
            ids=np.concatenate([qids[rows],
                                [f"{name}-only{j}" for j in range(4)]])))
    ids, preds = server.serve_parties(req[::-1])    # any party order
    order = np.argsort(crypto.hash_ids(qids))
    np.testing.assert_array_equal(ids, qids[order])
    np.testing.assert_array_equal(preds, model.predict(xt[order]))

    # queue path: same alignment, results keyed by request id
    from repro.serving import RequestQueue
    q = RequestQueue(server)
    rid, q_ids = q.submit_parties(req)
    np.testing.assert_array_equal(q_ids, ids)
    np.testing.assert_array_equal(q.drain()[rid], preds)


def test_linear_serve_parties_roundtrip():
    """LinearServer.serve_parties: the F-LR engine accepts raw per-party
    request blocks through the same re-alignment path as the tree engines —
    aligned rows stay raw and are standardized with the fit-time moments."""
    from repro.core import LinearParams
    from repro.serving import LinearServer, ServeConfig
    x, y = make_classification(240, 8, 2, seed=11)
    blocks, xa, ya = make_party_views(x, y, 3, overlap=0.85, seed=11)
    fed = Federation(parties=3, n_bins=8)
    part = fed.ingest(blocks)
    model = fed.fit(LinearParams(steps=150))
    server = fed.serve(model, ServeConfig(buckets=(64,)))
    assert isinstance(server, LinearServer)

    xt, _ = make_classification(30, 8, 2, seed=78)
    qids = np.array([f"q{i}" for i in range(len(xt))])
    rng = np.random.default_rng(4)
    req = []
    for i, name in enumerate(part.party_names):
        gid = part.feat_gid[i][part.feat_gid[i] >= 0]
        rows = rng.permutation(len(xt))
        extra = rng.normal(size=(3, len(gid)))
        req.append(PartyBlock(
            name=name, x=np.concatenate([xt[rows][:, gid], extra]),
            ids=np.concatenate([qids[rows],
                                [f"{name}-only{j}" for j in range(3)]])))
    ids, preds = server.serve_parties(req[::-1])    # any party order
    order = np.argsort(crypto.hash_ids(qids))
    np.testing.assert_array_equal(ids, qids[order])
    np.testing.assert_array_equal(preds, model.predict(xt[order]))


def test_hash_ids_cache_bit_identity():
    """The serving-path hash memo is invisible: cold and warm lookups
    produce identical digests, repeated IDs hit the memo, and ingest
    (which hashes each ID once per salt) leaves it untouched."""
    crypto._HASH_CACHE.clear()
    ids = np.array([f"u{i}" for i in range(50)])
    cold = crypto.hash_ids(ids, memo=True)
    assert cold.dtype == crypto.DIGEST
    assert len(crypto._HASH_CACHE) >= 50
    warm = crypto.hash_ids(np.concatenate([ids, ids]), memo=True)
    np.testing.assert_array_equal(warm[:50], cold)
    np.testing.assert_array_equal(warm[50:], cold)
    np.testing.assert_array_equal(crypto.hash_ids(ids), cold)
    # a different salt is a different preimage, never a stale cache hit
    assert not np.array_equal(crypto.hash_ids(ids, salt="other", memo=True),
                              cold)
    crypto._HASH_CACHE.clear()
    x, y = make_classification(60, 4, seed=3)
    blocks, _, _ = make_party_views(x, y, 2, overlap=0.8, seed=3)
    part = Federation(parties=2, n_bins=8).ingest(blocks, salt="job")
    assert crypto._HASH_CACHE == {}
    part.bin_party_blocks(blocks, salt="job")     # a serving request
    assert len(crypto._HASH_CACHE) == np.unique(
        np.concatenate([b.ids for b in blocks])).size


def test_serve_parties_validates_block_names():
    x, y = make_classification(200, 8, 2, seed=12)
    blocks, _, _ = make_party_views(x, y, 2, overlap=0.9, seed=12)
    fed = Federation(parties=2, n_bins=8)
    part = fed.ingest(blocks)
    model = fed.fit(ForestParams(n_estimators=2, max_depth=3, n_bins=8))
    server = fed.serve(model, buckets=(32,))
    bad = PartyBlock("nobody", np.zeros((2, 4)), ids=["1", "2"])
    with pytest.raises(ValueError, match="cover exactly"):
        server.serve_parties([blocks[0], bad])
    with pytest.raises(ValueError, match="features"):
        server.serve_parties([
            PartyBlock(b.name, np.zeros((2, b.n_features + 1)),
                       ids=["1", "2"]) for b in blocks])


# ------------------------------------------------- raw-matrix compat adapter
def test_raw_matrix_adapter_preserves_row_order():
    """The compat path is PartyBlocks underneath, but pre-aligned implicit
    IDs take the identity alignment: rows stay exactly as given."""
    x, y = make_classification(150, 7, 2, seed=14)
    fed = Federation(parties=2, n_bins=8)
    part = fed.ingest(x, y)
    np.testing.assert_array_equal(fed.aligned_ids_, np.arange(len(x)))
    np.testing.assert_array_equal(fed.labels_, y)
    np.testing.assert_array_equal(part.dense_raw(), x)
    assert part.party_names == ("party000", "party001")


# ------------------------------------------------------- sharded substrate
_SHARDED_BLOCKS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=6"
import numpy as np
import jax
from repro import compat
from repro.core import ForestParams, PartyBlock, crypto
from repro.data import make_classification, make_party_views
from repro.federation import Federation

x, y = make_classification(240, 9, 2, seed=5)
blocks, xa, ya = make_party_views(x, y, 3, overlap=0.8, seed=5)
p = ForestParams(n_estimators=2, max_depth=4, n_bins=8, seed=3)

mesh = compat.make_mesh((2, 3), ("trees", "parties"))
fed = Federation(parties=3, substrate="sharded", mesh=mesh, n_bins=8,
                 hist_impl="scatter")
part = fed.ingest(blocks, validate=True)
model = fed.fit(p)

fed_c = Federation(parties=3, substrate="sharded", mesh=mesh, n_bins=8,
                   hist_impl="scatter")
fed_c.ingest(xa, ya)
central = fed_c.fit(p)

for la, lb in zip(jax.tree_util.tree_leaves(model.trees_),
                  jax.tree_util.tree_leaves(central.trees_)):
    assert np.array_equal(np.asarray(la), np.asarray(lb)), "trees diverge"

xt = xa[:32]
assert np.array_equal(fed.predict(model, xt), fed_c.predict(central, xt))

# party-block serving on the sharded substrate, out-of-order + superset
server = fed.serve(model, buckets=(32,))
qids = np.array([f"q{i}" for i in range(len(xt))])
rng = np.random.default_rng(0)
req = []
for i, name in enumerate(part.party_names):
    gid = part.feat_gid[i][part.feat_gid[i] >= 0]
    rows = rng.permutation(len(xt))
    extra = rng.normal(size=(3, len(gid)))
    req.append(PartyBlock(
        name=name, x=np.concatenate([xt[rows][:, gid], extra]),
        ids=np.concatenate([qids[rows], [f"{name}-{j}" for j in range(3)]])))
ids, preds = server.serve_parties(req)
order = np.argsort(crypto.hash_ids(qids))
assert np.array_equal(ids, qids[order])
assert np.array_equal(preds, central.predict(xt[order]))
print("PARTY_SHARDED_OK")
"""


def test_party_ingest_sharded_substrate_bit_identical():
    """Acceptance, sharded half: the same PartyBlock ingest feeds the
    shard_map substrate and stays bit-identical to the dense pre-aligned
    build — fit, predict, and party-block serving (subprocess so the forced
    device count never leaks into other tests)."""
    env = dict(os.environ, PYTHONPATH="src")
    res = subprocess.run([sys.executable, "-c", _SHARDED_BLOCKS_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=1500,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert res.returncode == 0, res.stderr[-3000:]
    assert "PARTY_SHARDED_OK" in res.stdout
