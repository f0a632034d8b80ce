"""Privacy layer (paper §4.3) — simulation-grade, same trust model as the paper.

The paper's own mechanisms are deliberately lightweight (MD5-hashed IDs,
"encrypted" labels shared to every client, RSA/AES on the wire).  We mirror
that model honestly rather than pretend at MPC:

  * sample IDs: salted SHA-256 (MD5 is broken; same role, stronger hash) —
    alignment happens on hashed IDs only;
  * labels: class-id permutation "encoding" for classification (training is
    invariant to it), affine masking for regression targets (variance-based
    split gains are invariant to affine maps of y);
  * feature names: random integer encoding (the master only ever sees encoded
    ids — our ``feat_gid``);
  * gains in transit: additive masks that cancel under the all-reduce, so the
    aggregate argmax input is exact while any single message is masked.

None of this is semantically-secure MPC — neither is the paper's. The point
is that the *information flow* matches §4.3: raw features never leave a
party; the master sees only encoded ids and masked statistics.
"""
from __future__ import annotations

import hashlib

import numpy as np

from repro.observability import trace as tracing

DEFAULT_SALT = "repro-ff"

#: A hashed sample ID: the raw 32-byte sha256 digest.  Digests sort in the
#: order of their lowercase hex spelling, so either gives the same alignment.
DIGEST = np.dtype("S32")

# preimage "salt:id" -> digest, for party-block serving requests
# (``submit_parties`` -> ``bin_party_blocks``), which hash their sample IDs to
# align them; production traffic revisits the same ID universe wave after
# wave.  Raw-row requests hash nothing, and ingest hashes each ID once per
# salt, so neither uses it.  Bounded: when full it is cleared wholesale (IDs
# re-hash on the next request) rather than growing one entry per distinct ID
# forever.
_HASH_CACHE: dict[str, bytes] = {}
_HASH_CACHE_MAX = 1 << 20


def hash_ids(ids, salt: str = DEFAULT_SALT, *,
             memo: bool = False) -> np.ndarray:
    """Irreversible sample-ID encryption for alignment (paper: MD5).

    Returns the sha256 digest of ``f"{salt}:{id}"`` for each ID as one
    ``S32`` array (:data:`DIGEST`): raw fixed-width digests, not hex.
    ``memo=True`` memoizes per preimage, for a caller that hashes the same
    IDs request after request; bit-identical either way (the memo stores the
    digest itself)."""
    if (isinstance(ids, np.ndarray) and ids.ndim == 1
            and ids.dtype.kind in "biuOSU"):
        ids = ids.tolist()  # faster to iterate; formats as NumPy scalars do
    sha256 = hashlib.sha256
    if memo:
        cache = _HASH_CACHE
        if len(cache) > _HASH_CACHE_MAX:
            cache.clear()
        digests = []
        for i in ids:
            key = f"{salt}:{i}"
            d = cache.get(key)
            if d is None:
                d = cache[key] = sha256(key.encode()).digest()
            digests.append(d)
    else:
        digests = [sha256(f"{salt}:{i}".encode()).digest() for i in ids]
    return np.frombuffer(bytearray().join(digests), dtype=DIGEST)


def align_ids(*hashed_parties: np.ndarray,
              names=None) -> tuple[np.ndarray, ...]:
    """Private-set-intersection stand-in, generalized to M parties.

    Iterated hashed-ID intersection (paper §4.3: alignment sees hashed IDs
    only).  Returns one int64 position array per party; gathering party i's
    rows at ``positions[i]`` puts every party on the same **canonical common
    ordering** — the lexicographic sort of the common hashed IDs — which is
    invariant to each party's row order and to the order the parties are
    listed in.

    Hashed IDs are :func:`hash_ids`' ``S32`` digests: the parties are sorted
    and intersected on the big-endian ``uint64`` of each digest's first 8
    bytes, and the full digests decide wherever two of those keys are equal
    within a party or match across parties with different digests; any such
    case reruns the whole alignment on the full digests, so the result is
    exact.  Arrays of any other dtype align on their own values.  Runs in
    the span ``ingest.align``, whose ``prefix_ties`` counts the equal-key
    runs and false cross-party matches that went to the full digests.

    Raises ValueError on duplicate hashed IDs within a party (alignment
    would be ambiguous), naming the party by ``names`` (default: its
    index), and on an empty intersection (no shared samples).
    """
    if not hashed_parties:
        raise ValueError("align_ids needs at least one party's hashed IDs")
    hs = [np.ascontiguousarray(h).reshape(-1) for h in hashed_parties]
    names = list(range(len(hs)) if names is None else names)
    with tracing.TRACER.span("ingest.align", parties=len(hs)) as span:
        positions, ties = None, 0
        if all(h.dtype == DIGEST for h in hs):
            positions, runs = _align_unique(
                [h.view(">u8").reshape(-1, 4)[:, 0] for h in hs])
            ties = sum(runs)
            if positions is not None:
                ties = _false_matches(hs, positions)
                positions = None if ties else positions
        if positions is None:
            positions, runs = _align_unique(hs)
        span.set(prefix_ties=ties)
    for name, r in zip(names, runs):
        if r:
            raise ValueError(
                f"party {name!r} has duplicate sample IDs: alignment "
                f"would be ambiguous — deduplicate before ingest")
    if positions[0].size == 0:
        raise ValueError(
            f"empty hashed-ID intersection across parties {names}: no "
            f"shared samples to align (same ID space and salt on every "
            f"party?)")
    return tuple(p.astype(np.int64, copy=False) for p in positions)


def _align_unique(hs):
    """Sort each party's values once.  Returns ``(positions, runs)``: the
    positions of the values common to all parties, in sorted order, and
    per party the count of runs of repeated values; positions is None
    where any value repeats."""
    orders, sorted_, runs = [], [], []
    for h in hs:
        order = np.argsort(h)   # unstable is exact: a repeat stops below
        s = h[order]
        eq = s[1:] == s[:-1]
        runs.append(int(eq[:1].sum() + np.count_nonzero(eq[1:] & ~eq[:-1])))
        orders.append(order)
        sorted_.append(s)
    if any(runs):
        return None, runs
    common = sorted_[0]
    for s in sorted_[1:]:
        common = np.intersect1d(common, s, assume_unique=True)
    return [o[np.searchsorted(s, common)]
            for o, s in zip(orders, sorted_)], runs


def _false_matches(hs, positions) -> int:
    """Rows whose digests differ from party 0's, though their 64-bit
    prefixes matched."""
    rows = [np.take(h.view(np.uint64).reshape(-1, 4), p, axis=0)
            for h, p in zip(hs, positions)]
    return sum(int(np.count_nonzero((r != rows[0]).any(axis=1)))
               for r in rows[1:] if not np.array_equal(r, rows[0]))


def align_hashed(hashes, names, *, identity_fast_path: bool = True):
    """Align M parties' already-hashed ID arrays with the loud-error contract.

    The shared back half of every ingest path (distributed workers,
    streaming sources): takes the pre-aligned identity fast path when all
    arrays are equal (preserving the caller's row order bit-for-bit), and
    otherwise runs :func:`align_ids` onto the canonical sorted-hash common
    ordering, which rejects duplicates and an empty intersection with the
    party names attached.  The identity fast path checks no uniqueness: the
    callers validate their parties' raw IDs before hashing.

    Callers that decide the fast path on *raw* IDs themselves (the local
    streaming plane, mirroring align_party_blocks exactly) pass
    ``identity_fast_path=False`` so equal hashes of unequal raw IDs cannot
    skip the canonical reordering.

    Returns ``(positions, common_hashed)``: one int64 position array per
    party and the common hashed IDs in the aligned order.
    """
    hs = [np.asarray(h).reshape(-1) for h in hashes]
    first = hs[0]
    if identity_fast_path and all(h.shape == first.shape
                                  and np.array_equal(h, first)
                                  for h in hs[1:]):
        if first.size == 0:     # the fast path must keep the loud-error
            raise ValueError(   # contract, not fall through to binning
                f"empty hashed-ID intersection across parties "
                f"{list(names)}: no shared samples to align")
        pos = np.arange(len(first), dtype=np.int64)
        return [pos.copy() for _ in hs], first.copy()
    positions = list(align_ids(*hs, names=names))
    return positions, hs[0][positions[0]]


def encode_labels(y: np.ndarray, n_classes: int, seed: int = 0):
    """Permute class ids: clients train on encoded labels (classification is
    invariant); only the label owner can decode. Returns (y_enc, decode)."""
    perm = np.random.default_rng(seed).permutation(n_classes)
    return perm[y.astype(np.int64)], label_decoder(n_classes, seed)


def label_decoder(n_classes: int, seed: int = 0):
    """Reconstruct encode_labels' decode from (n_classes, seed) alone — the
    label owner can decode a checkpoint-restored forest without the original
    training labels in memory (Federation.load relies on this)."""
    inv = np.argsort(np.random.default_rng(seed).permutation(n_classes))
    return lambda y_enc: inv[np.asarray(y_enc, dtype=np.int64)]


def mask_regression_targets(y: np.ndarray, seed: int = 0):
    """Affine mask a*y + b (a>0): SSE split gains scale by a^2, so the argmax
    split — hence the tree — is unchanged; leaf values decode affinely."""
    a, b = _regression_mask(seed)
    return a * y + b, regression_unmasker(seed)


def _regression_mask(seed: int) -> tuple[float, float]:
    rng = np.random.default_rng(seed)
    return float(rng.uniform(0.5, 2.0)), float(rng.normal())


def regression_unmasker(seed: int = 0):
    """Reconstruct mask_regression_targets' decode from the seed alone
    (Federation.load, same role as label_decoder for classification)."""
    a, b = _regression_mask(seed)
    return lambda p: (np.asarray(p) - b) / a


def encode_feature_names(names: list[str], seed: int = 0) -> dict[str, int]:
    """Random integer encoding of feature names (master sees only these)."""
    perm = np.random.default_rng(seed).permutation(len(names))
    return {n: int(e) for n, e in zip(names, perm)}


def pairwise_cancelling_masks(n_parties: int, shape, seed: int = 0) -> np.ndarray:
    """(M, *shape) float32 masks with sum_i mask_i == 0: adding mask_i to party
    i's message hides it point-to-point while psum recovers the exact sum."""
    rng = np.random.default_rng(seed)
    m = rng.normal(size=(n_parties, *shape)).astype(np.float32)
    m[-1] = -m[:-1].sum(0)
    return m
