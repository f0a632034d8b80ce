"""Party-per-process federation: the transport-backed DistributedSubstrate.

The third Substrate implementation.  Each party is its own OS process
(federation/party_worker.py) holding its own data; a coordinator in the
session process drives the protocol over framed msgpack sockets
(federation/transport.py):

  * **fit** — the per-level split exchange of core/tree.build_tree, run as
    real messages: every party computes its local best splits (the same
    jitted split-search kernels the in-process substrates run), the
    coordinator performs the paper's master reduce (core.tree.reduce_level)
    on the gathered bests, and one psum per level broadcasts the
    owner-computed partition bits.  Integer routing state advances in exact
    numpy arithmetic, so the built PartyTree is bit-identical to
    SimulatedSubstrate on the same seeds.
  * **predict/serve** — the one-round masked-leaf collective (Prop. 1): each
    worker emits its leaf-membership mask, a single psum intersects them,
    and every party votes locally.
  * **ingest** — the hashed-ID alignment handshake of align_party_blocks
    over the same channel: workers load their own blocks, ship salted
    SHA-256 hashes only, the coordinator intersects them, and parties bin
    locally.  Raw sample IDs and raw features never leave a party; only
    hashed IDs, binned values, and masked statistics cross the wire.

Fault tolerance rides on transport primitives: per-round-trip timeout
budgets (PartyTimeout), retry with jittered exponential backoff
(RetryPolicy), a per-party circuit breaker (CircuitOpenError after K
consecutive failures), health-check pings, and an injectable chaos hook
(drop/delay/kill one party's next run) that the fault tests use to prove
each behavior deterministically.  Serving degradation — answering from the
trees whose split paths avoid a dead party — is :func:`surviving_trees`
plus a predict program scoped to the live parties (serving/engine.py).

Collective semantics match the in-graph substrates exactly: gathers stack
party payloads in ascending party order (= ``lax.all_gather``); psums use
``np.add.reduce(stack, axis=0, dtype=payload.dtype)``, which preserves the
payload dtype like an XLA psum (a uint8 membership mask stays uint8).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import multiprocessing
import os
import socket
import time
from pathlib import Path
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.analysis import runtime as egress_runtime
from repro.core import crypto, impurity, tree
from repro.core.party import VerticalPartition, _pad_groups
from repro.core.partyblock import (CSVSource, DataSource, PartyBlock,
                                   feature_groups)
from repro.core.tree import PartyTree
from repro.core.types import PARTY_AXIS, ForestParams
from repro.federation import transport
from repro.observability import trace as tracing
from repro.federation.transport import (CircuitBreaker, PartyDead,
                                        PartyTimeout, PartyUnavailableError,
                                        ProtocolError, RetryPolicy)

transport.register_namedtuple(PartyTree)


class RunAborted(Exception):
    """Coordinator superseded this run (timeout elsewhere, retry incoming)."""


# ------------------------------------------------------------------ worker comm
class Comm:
    """Worker-side collective endpoint for one run.

    The distributed twin of the SPMD axis primitives: ``all_gather`` /
    ``psum`` send one ``coll`` message and block for the coordinator's
    combined ``coll_result``.  Messages from superseded runs are skipped;
    an ``abort`` for the current run raises :class:`RunAborted`."""

    def __init__(self, channel, run_id, party_index: int, n_parties: int):
        self.channel = channel
        self.run_id = run_id
        self.party_index = int(party_index)
        self.n_parties = int(n_parties)
        self._seq = 0

    def _round(self, kind: str, arrays) -> list:
        arrays = [np.asarray(a) for a in arrays]
        with tracing.TRACER.span(f"coll.{kind}", category="comm",
                                 seq=self._seq):
            return self._round_inner(kind, arrays)

    def _round_inner(self, kind: str, arrays) -> list:
        self.channel.send({"op": "coll", "run": self.run_id,
                           "seq": self._seq, "kind": kind, "data": arrays})
        while True:
            msg = self.channel.recv(None)
            op = msg.get("op")
            if op in ("shutdown",):
                raise RunAborted
            if op == "abort":
                if msg.get("run") == self.run_id:
                    raise RunAborted
                continue
            if msg.get("run") != self.run_id:
                continue                      # superseded-run stragglers
            if op != "coll_result" or msg.get("seq") != self._seq:
                raise ProtocolError(
                    f"expected coll_result seq {self._seq}, got "
                    f"{op} seq {msg.get('seq')}")
            self._seq += 1
            return msg["data"]

    def all_gather(self, *arrays):
        """Stacked (M, ...) payloads in party order, like lax.all_gather."""
        out = self._round("gather", arrays)
        return out[0] if len(arrays) == 1 else out

    def psum(self, *arrays):
        """Dtype-preserving sum over parties, like lax.psum."""
        out = self._round("psum", arrays)
        return out[0] if len(arrays) == 1 else out


# ------------------------------------------------------------- program registry
DIST_PROGRAMS: dict[str, Callable] = {}


def register_program(name: str):
    """Register a worker-side protocol body: body(comm, payload, *args)."""
    def deco(fn):
        DIST_PROGRAMS[name] = fn
        return fn
    return deco


# --------------------------------------------------------- forest fit protocol
@functools.partial(jax.jit, static_argnames=("off", "width", "cap", "params",
                                             "hist_impl", "search"))
def _level_search(xb_i32, node, wstats, fmask, feat_gid, *, off, width, cap,
                  params: ForestParams, hist_impl, search):
    """One level's party-local compute — the same kernels build_tree jits."""
    nil = node - off
    in_lvl = (nil >= 0) & (nil < width)
    seg = jnp.where(in_lvl, nil, -1)
    dump = jnp.where(seg >= 0, seg, width)
    c = wstats.shape[-1]
    nstats = jnp.zeros((width + 1, c), jnp.float32).at[dump].add(wstats)[:width]
    cnt = impurity.count_of(nstats, params.task)
    if not search:
        return nstats, cnt
    if params.frontier_cap and cap < width:
        g, gid, bin_, floc = tree._split_search_frontier(
            xb_i32, seg, wstats, fmask, feat_gid, width, cap, params,
            hist_impl)
    else:
        (g, gid, bin_, floc), _ = tree._split_search_dense(
            xb_i32, seg, wstats, fmask, feat_gid, width, params, hist_impl,
            None)
    return nstats, cnt, g, gid, bin_, floc


def _fit_tree(comm: Comm, xb_np, xb_dev, feat_gid_dev, fmask, wstats,
              params: ForestParams, hist_impl: str) -> PartyTree:
    """Level-synchronous build of one tree over the wire.

    Mirrors core/tree.build_tree stage for stage: jitted local split search,
    gather -> reduce_level -> psum as coordinator round trips, and the
    shared integer routing state advanced in exact numpy arithmetic."""
    n = xb_np.shape[0]
    c = wstats.shape[-1]
    nn = params.n_nodes
    me = comm.party_index
    wstats_dev = jnp.asarray(wstats)
    fmask_dev = jnp.asarray(fmask)

    node = np.zeros((n,), np.int32)
    is_leaf = np.zeros((nn,), bool)
    leaf_stats = np.zeros((nn, c), np.float32)
    has_split = np.zeros((nn,), bool)
    split_floc = np.full((nn,), -1, np.int32)
    split_bin = np.full((nn,), -1, np.int32)
    owner = np.full((nn,), -1, np.int32)
    split_gid = np.full((nn,), -1, np.int32)

    for d in range(params.max_depth + 1):
        level_span = tracing.TRACER.begin("fit.level", category="compute",
                                          level=d)
        off, width = params.level_slice(d)
        cap = min(width, n, params.frontier_cap or width)
        last = d == params.max_depth
        res = _level_search(xb_dev, jnp.asarray(node), wstats_dev, fmask_dev,
                            feat_gid_dev, off=off, width=width, cap=cap,
                            params=params, hist_impl=hist_impl,
                            search=not last)
        if last:                    # bottom level: everything alive is a leaf
            nstats, cnt = (np.asarray(r) for r in res)
            leaf_stats[off:off + width] = nstats
            is_leaf[off:off + width] = cnt > 0
            tracing.TRACER.finish(level_span)
            break
        nstats, cnt, g_loc, gid_loc, bin_loc, floc_loc = (
            np.asarray(r) for r in res)
        leaf_stats[off:off + width] = nstats

        # the paper's master: gather -> reduce -> notify, as round trips
        g_all, gid_all, bin_all = comm.all_gather(g_loc, gid_loc, bin_loc)
        do_split, owner_lv, gid_best, bin_best = (
            np.asarray(a) for a in tree.reduce_level(
                jnp.asarray(g_all), jnp.asarray(gid_all),
                jnp.asarray(bin_all), jnp.asarray(cnt), params))
        is_leaf[off:off + width] = (cnt > 0) & ~do_split
        mine = do_split & (owner_lv == me)
        has_split[off:off + width] = mine
        split_floc[off:off + width] = np.where(mine, floc_loc, -1)
        split_bin[off:off + width] = np.where(mine, bin_loc, -1)
        owner[off:off + width] = np.where(do_split, owner_lv, -1)
        split_gid[off:off + width] = np.where(do_split, gid_best, -1)

        # owner computes the partition; one psum broadcasts it
        nil = node - off
        in_lvl = (nil >= 0) & (nil < width)
        nil_c = np.clip(nil, 0, width - 1)
        floc_lv = np.where(mine, floc_loc, 0)
        bin_lv = np.where(mine, bin_loc, 0)
        mine_s = in_lvl & mine[nil_c]
        vals = np.take_along_axis(xb_np, floc_lv[nil_c][:, None], axis=1)[:, 0]
        go_r_loc = np.where(mine_s, (vals > bin_lv[nil_c]).astype(np.int32),
                            np.int32(0))
        go_r = comm.psum(go_r_loc)
        advance = in_lvl & do_split[nil_c]
        node = np.where(advance, 2 * node + 1 + go_r, node).astype(np.int32)
        tracing.TRACER.finish(level_span)

    return PartyTree(is_leaf, leaf_stats, has_split, split_floc, split_bin,
                     owner, split_gid)


@register_program("forest_fit")
def _forest_fit_body(comm: Comm, payload, xb, feat_gid, feat_sels, weights,
                     y_stats):
    """Per-party fit body: one _fit_tree per bagging round, fields stacked."""
    params = ForestParams(**payload["params"])
    if params.hist_subtraction:
        raise NotImplementedError(
            "hist_subtraction threads parent histograms through the level "
            "loop — in-process substrates only")
    hist_impl = payload.get("hist_impl") or params.hist_impl
    xb_np = np.asarray(xb).astype(np.int32)
    feat_gid = np.asarray(feat_gid, np.int32)
    feat_sels = np.asarray(feat_sels)
    weights = np.asarray(weights, np.float32)
    y_stats = np.asarray(y_stats, np.float32)
    xb_dev = jnp.asarray(xb_np)
    feat_gid_dev = jnp.asarray(feat_gid)

    trees_out = []
    for t in range(feat_sels.shape[0]):
        fmask = (feat_gid >= 0) & feat_sels[t][np.clip(feat_gid, 0, None)]
        wstats = y_stats * weights[t][:, None]
        trees_out.append(_fit_tree(comm, xb_np, xb_dev, feat_gid_dev, fmask,
                                   wstats, params, hist_impl))
    return jax.tree.map(lambda *xs: np.stack(xs), *trees_out)


# ----------------------------------------------------- forest predict protocol
@functools.partial(jax.jit, static_argnames=("params", "mask_dtype"))
def _membership_dense(trees, xbt, *, params: ForestParams, mask_dtype):
    from repro.core import prediction
    mem = lax.map(lambda tr: prediction.tree_leaf_membership(tr, xbt, params),
                  trees)
    return mem.astype(mask_dtype), prediction.masked_leaf_stats(trees)


@functools.partial(jax.jit, static_argnames=("params", "mask_dtype"))
def _membership_compact(trees, xbt, leaf_idx, *, params: ForestParams,
                        mask_dtype):
    from repro.core import prediction

    def one(args):
        tr, idx = args
        return prediction.tree_leaf_membership_compact(tr, xbt, params, idx)

    mem = lax.map(one, (trees, leaf_idx))
    return mem.astype(mask_dtype), prediction.gather_leaf_stats(trees,
                                                               leaf_idx)


@functools.partial(jax.jit, static_argnames=("params", "vote_impl",
                                             "n_active"))
def _vote_local(m, leaf, *, params: ForestParams, vote_impl, n_active):
    from repro.core import prediction
    inter = m == jnp.asarray(n_active, m.dtype)     # Prop. 1 intersection
    return prediction._combine_votes(inter, leaf, params, True, vote_impl)


@register_program("forest_predict")
def _forest_predict_body(comm: Comm, payload, trees, xbt, leaf_idx=None):
    """The one-round protocol: local membership, ONE psum, local vote."""
    params = ForestParams(**payload["params"])
    mask_dtype = payload.get("mask_dtype") or "int32"
    vote_impl = payload.get("vote_impl", "einsum")
    trees = jax.tree.map(jnp.asarray, trees)
    xbt = jnp.asarray(np.asarray(xbt))
    if payload.get("compact") and leaf_idx is not None:
        mem, leaf = _membership_compact(trees, xbt, jnp.asarray(leaf_idx),
                                        params=params, mask_dtype=mask_dtype)
    else:
        mem, leaf = _membership_dense(trees, xbt, params=params,
                                      mask_dtype=mask_dtype)
    m = comm.psum(np.asarray(mem))
    out = _vote_local(jnp.asarray(m), leaf, params=params,
                      vote_impl=vote_impl, n_active=comm.n_parties)
    return np.asarray(out)


# ------------------------------------------------------- linear / toy protocol
@jax.jit
def _linear_dot(x_i, w_i):
    return x_i @ w_i


@register_program("linear_predict")
def _linear_predict_body(comm: Comm, payload, x_i, w_i, b):
    """F-LR joint logit: z = psum_i(X_i w_i) + b, thresholded per task."""
    z_loc = np.asarray(_linear_dot(jnp.asarray(np.asarray(x_i, np.float32)),
                                   jnp.asarray(np.asarray(w_i, np.float32))))
    z = comm.psum(z_loc) + np.float32(np.asarray(b))
    if payload["task"] == "classification":
        return (z > 0).astype(np.int32)
    return np.asarray(z, np.float32)


@register_program("toy_affine")
def _toy_affine_body(comm: Comm, payload, x, scale):
    """Conformance-suite protocol: exercises both collectives in int32."""
    x = np.asarray(x)
    g = comm.all_gather(x)
    s = comm.psum((x * scale).astype(x.dtype))
    return (g.sum(0, dtype=x.dtype) + s
            + np.asarray(comm.party_index, x.dtype))


def toy_affine_fn(x, scale):
    """The in-graph twin of the toy protocol, for vmap/shard_map substrates —
    the conformance suite asserts bit-identity of the two on every
    registered substrate."""
    g = lax.all_gather(x, PARTY_AXIS)
    s = lax.psum((x * scale).astype(x.dtype), PARTY_AXIS)
    return (g.sum(0, dtype=x.dtype) + s
            + lax.axis_index(PARTY_AXIS).astype(x.dtype))


# ----------------------------------------------------------------- spec builders
def forest_fit_spec(params: ForestParams, hist_impl: str | None = None):
    return {"name": "forest_fit",
            "payload": {"params": dataclasses.asdict(params),
                        "hist_impl": hist_impl},
            "bound": ()}


def forest_predict_spec(params: ForestParams, *, compact=False,
                        mask_dtype=jnp.int32, vote_impl="einsum"):
    # bound argnums: trees (0, party arg) and leaf_idx (2, shared) are the
    # model-side operands the serving engine ships once per executable.
    return {"name": "forest_predict",
            "payload": {"params": dataclasses.asdict(params),
                        "compact": bool(compact),
                        "mask_dtype": np.dtype(mask_dtype).name,
                        "vote_impl": vote_impl},
            "bound": (0, 2)}


def linear_predict_spec(task: str):
    return {"name": "linear_predict", "payload": {"task": task},
            "bound": (1, 2)}


def toy_affine_spec():
    return {"name": "toy_affine", "payload": {}, "bound": ()}


# ---------------------------------------------------------- degraded serving
def surviving_trees(trees, dead_parties) -> np.ndarray:
    """Indices of trees whose split paths avoid every dead party's features.

    A tree where a dead party owns no splits descends both branches at that
    party's (nonexistent) nodes, so its membership mask over the surviving
    parties intersects to exactly the full-federation leaf assignment —
    predictions from these trees are exact, not approximate."""
    owner = np.asarray(trees.owner)
    if owner.ndim == 3:                       # (M, T, nn) party stack
        owner = owner[0]                      # owner is the shared master view
    dead = np.asarray(sorted(set(int(p) for p in dead_parties)))
    if dead.size == 0:
        return np.arange(owner.shape[0])
    hit = np.isin(owner, dead) & (owner >= 0)
    return np.flatnonzero(~hit.any(axis=1))


# ------------------------------------------------------------------ coordinator
def _worker_entry(host, port, index, src_root):
    import sys
    if src_root and src_root not in sys.path:
        sys.path.insert(0, src_root)
    from repro.federation.party_worker import worker_main
    worker_main(host, port, index)


class Coordinator:
    """Session-side driver: spawns one worker process per party, relays the
    collectives, and owns the fault-tolerance state (retry policy, breaker,
    dead-party set)."""

    def __init__(self, parties: int, *, host: str = "127.0.0.1",
                 round_timeout: float = 120.0, connect_timeout: float = 30.0,
                 retry: RetryPolicy | None = None, breaker_threshold: int = 3):
        self.n_parties = int(parties)
        self.round_timeout = float(round_timeout)
        self.connect_timeout = float(connect_timeout)
        self.retry = retry or RetryPolicy()
        self.breaker = CircuitBreaker(breaker_threshold)
        self._host = host
        self.channels: dict[int, transport.Channel] = {}
        self._procs: list = []
        self._dead: set[int] = set()
        # party -> the jax platform its worker reported in its hello
        self.worker_platforms: dict[int, str] = {}
        self._nonce = 0
        self._run_id = 0
        self._bind_id = 0
        self._started = False

    # ------------------------------------------------------------- lifecycle
    def start(self) -> None:
        if self._started:
            return
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.bind((self._host, 0))
        srv.listen(self.n_parties)
        host, port = srv.getsockname()
        src_root = str(Path(__file__).resolve().parents[2])
        ctx = multiprocessing.get_context("spawn")
        old_pp = os.environ.get("PYTHONPATH")
        # Workers run on the host CPU: on one host an accelerator belongs to
        # the coordinator process, and a worker that reached for it would
        # fail or hang on the device the coordinator already holds.
        child_env = {"PYTHONPATH": (src_root if not old_pp
                                    else src_root + os.pathsep + old_pp),
                     "JAX_PLATFORMS": "cpu"}
        saved = {k: os.environ.get(k) for k in child_env}
        os.environ.update(child_env)
        try:
            for i in range(self.n_parties):
                p = ctx.Process(target=_worker_entry,
                                args=(host, port, i, src_root), daemon=True)
                p.start()
                self._procs.append(p)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        srv.settimeout(self.connect_timeout)
        try:
            for _ in range(self.n_parties):
                sock, _addr = srv.accept()
                ch = transport.Channel(sock)
                hello = ch.recv(timeout=self.connect_timeout)
                if hello.get("op") != "hello":
                    raise ProtocolError(f"expected hello, got {hello}")
                idx = int(hello["party"])
                self.worker_platforms[idx] = hello.get("platform")
                ch.party = idx
                self.channels[idx] = ch
        except (socket.timeout, TimeoutError) as e:
            self.shutdown()
            raise PartyDead(
                f"not all {self.n_parties} party workers connected within "
                f"{self.connect_timeout:.0f}s") from e
        finally:
            srv.close()
        self._started = True

    def shutdown(self) -> None:
        for p, ch in list(self.channels.items()):
            if p not in self._dead:
                try:
                    ch.send({"op": "shutdown"})
                except transport.TransportError:
                    pass
            ch.close()
        self.channels.clear()
        for proc in self._procs:
            proc.join(timeout=5.0)
            if proc.is_alive():
                proc.terminate()
        self._procs.clear()
        self._started = False

    # ----------------------------------------------------------------- plumbing
    def next_run_id(self) -> int:
        self._run_id += 1
        return self._run_id

    def new_bind_id(self) -> int:
        self._bind_id += 1
        return self._bind_id

    def _mark_failure(self, p: int, e: Exception) -> None:
        if isinstance(e, PartyDead):
            self._dead.add(p)
            ch = self.channels.get(p)
            if ch is not None:
                ch.close()

    def _send(self, p: int, msg: dict) -> None:
        if p in self._dead:
            raise PartyDead(f"party {p}: process is gone", parties=(p,))
        try:
            self.channels[p].send(msg)
        except PartyUnavailableError as e:
            self._mark_failure(p, e)
            raise

    def _recv_run(self, p: int, rid) -> dict:
        ch = self.channels[p]
        deadline = time.monotonic() + self.round_timeout
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                raise PartyTimeout(
                    f"party {p}: no protocol message within the "
                    f"{self.round_timeout:.1f}s round budget", parties=(p,))
            try:
                msg = ch.recv(timeout=left)
            except PartyUnavailableError as e:
                self._mark_failure(p, e)
                raise
            if msg.get("run") == rid and msg.get("op") in ("coll", "result",
                                                           "error"):
                return msg
            # anything else is superseded-run traffic or a late ack: skip

    def _abort(self, rid, active) -> None:
        for p in active:
            if p in self._dead:
                continue
            try:
                self.channels[p].send({"op": "abort", "run": rid})
            except transport.TransportError:
                self._mark_failure(p, PartyDead(f"party {p}", parties=(p,)))

    # -------------------------------------------------------------- run loop
    def run_once(self, rid, msgs: dict[int, dict], active) -> dict[int, Any]:
        """Drive one protocol run to completion: relay every collective
        round, return per-party results.  Raises PartyTimeout/PartyDead with
        the failure attributed to a party (after aborting the others)."""
        try:
            for p in active:
                self._send(p, msgs[p])
            while True:
                with tracing.TRACER.span("round", category="comm",
                                         rid=rid) as rspan:
                    got = {p: self._recv_run(p, rid) for p in active}
                    ops = {m["op"] for m in got.values()}
                    if "error" in ops:
                        bad = next(p for p, m in got.items()
                                   if m["op"] == "error")
                        self._abort(rid, active)
                        m = got[bad]
                        raise RuntimeError(
                            f"party {bad} failed in {msgs[bad]['name']!r}: "
                            f"{m.get('message')}\n{m.get('traceback', '')}")
                    if ops == {"result"}:
                        rspan.set(kind="result")
                        return {p: m["data"] for p, m in got.items()}
                    if ops != {"coll"}:
                        self._abort(rid, active)
                        raise ProtocolError(
                            f"mixed protocol messages {ops}")
                    seqs = {m["seq"] for m in got.values()}
                    kinds = {m["kind"] for m in got.values()}
                    if len(seqs) != 1 or len(kinds) != 1:
                        self._abort(rid, active)
                        raise ProtocolError(
                            f"desynchronized collective (seq {seqs}, "
                            f"kind {kinds})")
                    kind, seq = kinds.pop(), seqs.pop()
                    rspan.set(kind=kind, seq=seq)
                    n_arr = len(got[active[0]]["data"])
                    combined = []
                    for j in range(n_arr):
                        stack = np.stack([np.asarray(got[p]["data"][j])
                                          for p in active])
                        combined.append(
                            stack if kind == "gather"
                            else np.add.reduce(stack, axis=0,
                                               dtype=stack.dtype))
                    reply = {"op": "coll_result", "run": rid, "seq": seq,
                             "data": combined}
                    for p in active:
                        self._send(p, reply)
        except PartyUnavailableError as e:
            # abort EVERY active party, including the one the failure is
            # attributed to: a slow-but-alive party must learn its run was
            # superseded, or it will block on a coll_result that never
            # comes and swallow the next run's message as stale traffic
            # (_abort already skips dead parties and eats transport errors)
            self._abort(rid, active)
            raise

    def run_retrying(self, build_msgs, active) -> dict[int, Any]:
        """run_once under the retry policy + circuit breaker.

        Transport failures (timeout/dead) are retried with jittered
        exponential backoff and charged to the breaker; protocol-body
        exceptions (RuntimeError from a worker traceback) are not — a bug
        does not become less buggy on retry."""
        active = list(active)
        last: PartyUnavailableError | None = None
        for attempt in range(self.retry.attempts):
            for p in active:
                self.breaker.allow(p)         # raises CircuitOpenError
            rid = self.next_run_id()
            msgs = build_msgs(rid)
            name = msgs[active[0]]["name"] if active else "?"
            try:
                with tracing.TRACER.span(f"run.{name}", category="host",
                                         rid=rid, attempt=attempt):
                    out = self.run_once(rid, msgs, active)
            except PartyUnavailableError as e:
                last = e
                for p in (e.parties or active):
                    self.breaker.record_failure(p)
                if attempt + 1 < self.retry.attempts:
                    self.retry.backoff(attempt)
                continue
            for p in active:
                self.breaker.record_success(p)
            return out
        raise last

    # ------------------------------------------------------ request/response
    def request(self, p: int, msg: dict, *, timeout: float | None = None) -> dict:
        """One out-of-band round trip (ping/chaos/bind/ingest ops), matched
        on an echoed nonce so stale run traffic cannot satisfy it."""
        if p in self._dead:
            raise PartyDead(f"party {p}: process is gone", parties=(p,))
        self._nonce += 1
        n = self._nonce
        ch = self.channels[p]
        try:
            ch.send(dict(msg, nonce=n))
            deadline = time.monotonic() + (timeout or self.round_timeout)
            while True:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise PartyTimeout(
                        f"party {p}: no reply to {msg.get('op')!r}",
                        parties=(p,))
                reply = ch.recv(timeout=left)
                if reply.get("nonce") != n:
                    continue
                if reply.get("op") == "error":
                    raise RuntimeError(
                        f"party {p}: {reply.get('message')}")
                return reply
        except PartyUnavailableError as e:
            self._mark_failure(p, e)
            raise

    def health(self, timeout: float = 2.0) -> dict[int, float | None]:
        """Ping every party; latency in seconds, None for the unreachable.
        Reads do not feed the circuit breaker — health is observation."""
        out: dict[int, float | None] = {}
        for p in range(self.n_parties):
            if p in self._dead or p not in self.channels:
                out[p] = None
                continue
            t0 = time.perf_counter()
            try:
                r = self.request(p, {"op": "ping"}, timeout=timeout)
                out[p] = (time.perf_counter() - t0
                          if r.get("op") == "pong" else None)
            except (PartyUnavailableError, RuntimeError):
                out[p] = None
        return out

    def chaos(self, party: int, mode: str, seconds: float = 0.0) -> None:
        """Arm a one-shot fault at a worker: its NEXT run message is dropped
        (``drop_run``), delayed (``delay_run``), or kills the process
        (``die``).  The fault-injection tests' entry point."""
        self.request(party, {"op": "chaos", "mode": mode, "seconds": seconds})

    def unavailable_parties(self) -> tuple[int, ...]:
        return tuple(sorted(self._dead | set(self.breaker.open_parties())))


# ------------------------------------------------------------------- ingest
def _source_spec(src) -> dict:
    if isinstance(src, CSVSource):
        return {"kind": "csv", **dataclasses.asdict(src)}
    if isinstance(src, PartyBlock):
        return {"kind": "block", "name": src.name, "x": src.x,
                "ids": src.ids, "y": src.y, "feature_ids": src.feature_ids,
                "feature_names": (list(src.feature_names)
                                  if src.feature_names else None)}
    if isinstance(src, DataSource):
        raise TypeError(
            f"cannot ship a {type(src).__name__} to a party worker — "
            f"distributed ingest takes CSVSource (loaded party-side) or a "
            f"materialized PartyBlock")
    raise TypeError(f"expected PartyBlock or CSVSource, got "
                    f"{type(src).__name__}")


def distributed_ingest(coord: Coordinator, sources, n_bins: int, *,
                       salt: str = crypto.DEFAULT_SALT,
                       validate: bool = False):
    """partition_from_blocks over the wire: load at the parties, align on
    hashed IDs only, bin party-locally, assemble the stacked partition.

    Mirrors the in-process path decision for decision (canonical sorted-name
    party order, pre-aligned fast path, sorted-hash common ordering,
    feature-id partition checks, exactly-one-label-holder), so the returned
    partition is bit-identical to central ingestion of the same blocks.
    ``common_ids`` holds the HASHED ids — raw IDs never reach the
    coordinator."""
    if validate:
        raise ValueError(
            "validate=True re-bins the assembled central matrix, which the "
            "distributed substrate never holds — validate on an in-process "
            "substrate instead")
    sources = list(sources)
    if len(sources) != coord.n_parties:
        raise ValueError(f"expected {coord.n_parties} party sources, got "
                         f"{len(sources)}")
    # Provisioning is the one sanctioned raw flow: each in-memory source is
    # shipped to ITS OWN party's worker process — the same trust domain, a
    # stand-in for the worker reading its silo's storage directly (CSV
    # sources ship as paths and are read worker-side).  The static
    # suppression below and the runtime allow_egress() are a deliberate
    # pair; see analysis/policy.py.
    with egress_runtime.allow_egress(
            "provisioning: a party's own block to its own worker"):
        metas = [coord.request(w, {"op": "load_block",  # egress: ok(provisioning — party's own raw block to its own worker process, same trust domain)
                                   "source": _source_spec(s)})
                 for w, s in enumerate(sources)]
    names = [m["name"] for m in metas]
    if len(set(names)) != len(names):
        raise ValueError(f"party names must be unique, got {names}")
    order = sorted(range(len(names)), key=lambda w: names[w])

    hashes = [np.asarray(coord.request(w, {"op": "hash_block_ids",
                                           "salt": salt})["hashes"])
              for w in order]
    # per-party uniqueness was validated worker-side (hash_block_ids names
    # the party); align_hashed owns the fast path + loud-error contract
    positions, common = crypto.align_hashed(
        hashes, [names[w] for w in order], check_unique=False)

    groups, n_features = feature_groups(
        [metas[w].get("feature_ids") for w in order],
        [int(metas[w]["n_features"]) for w in order])

    feat_gid = _pad_groups(groups)
    m, fp = feat_gid.shape
    xb = np.zeros((m, len(common), fp), dtype=np.uint8)
    boundaries = np.zeros((n_features, max(n_bins - 1, 0)), dtype=np.float64)
    y, holder = None, None
    for i, w in enumerate(order):
        r = coord.request(w, {"op": "bin_block", "positions": positions[i],
                              "n_bins": n_bins})
        xb_i = np.asarray(r["xb"])
        xb[i, :, : xb_i.shape[1]] = xb_i
        boundaries[groups[i]] = np.asarray(r["boundaries"])
        if r.get("y") is not None:
            if holder is not None:
                raise ValueError(
                    f"labels held by more than one party ({holder!r} and "
                    f"{names[w]!r}); exactly one party owns the labels")
            holder, y = names[w], np.asarray(r["y"])

    part = VerticalPartition(xb=xb, feat_gid=feat_gid,
                             n_features=n_features, boundaries=boundaries,
                             raw_parts=None,
                             party_names=tuple(names[w] for w in order))
    return part, y, common


# --------------------------------------------------------- streaming ingest
def _stream_source_spec(src) -> dict:
    """Wire spec for a chunked source — what ships to a party worker so the
    worker can stream the data *locally*.  CSVs ship as a path (the file
    lives with the party; its raw rows never cross the wire); in-memory
    blocks ship once as arrays (tests / small silos); products ship their
    schema + version around an inner source spec."""
    from repro import streaming
    if isinstance(src, streaming.DataProduct):
        s = src.schema
        return {"kind": "product", "name": src.name,
                "version": int(src.version),
                "schema": {"n_features": int(s.n_features),
                           "feature_ids": (list(s.feature_ids)
                                           if s.feature_ids is not None
                                           else None),
                           "feature_dtype": s.feature_dtype,
                           "id_kind": s.id_kind,
                           "has_labels": bool(s.has_labels)},
                "inner": _stream_source_spec(src.source)}
    if isinstance(src, streaming.ChunkedCSVSource):
        return {"kind": "csv_chunks", **dataclasses.asdict(src)}
    if isinstance(src, CSVSource):
        return {"kind": "csv_chunks", **dataclasses.asdict(src)}
    if isinstance(src, streaming.ArraySource):
        return dict(_source_spec(src.block), kind="block_chunks")
    if isinstance(src, PartyBlock):
        return dict(_source_spec(src), kind="block_chunks")
    if isinstance(src, DataSource):
        raise TypeError(
            f"cannot ship a {type(src).__name__} to a party worker — "
            f"distributed streaming takes chunked CSVs (streamed "
            f"party-side), blocks, or DataProducts over them")
    raise TypeError(f"expected a chunked source, PartyBlock or CSVSource, "
                    f"got {type(src).__name__}")


def stream_source_from_spec(spec: dict):
    """Worker-side inverse of :func:`_stream_source_spec`."""
    from repro import streaming
    kind = spec["kind"]
    if kind == "product":
        s = spec["schema"]
        return streaming.DataProduct(
            name=spec["name"], version=int(spec["version"]),
            source=stream_source_from_spec(spec["inner"]),
            schema=streaming.ProductSchema(
                n_features=int(s["n_features"]),
                feature_ids=(tuple(int(f) for f in s["feature_ids"])
                             if s["feature_ids"] is not None else None),
                feature_dtype=s["feature_dtype"], id_kind=s["id_kind"],
                has_labels=bool(s["has_labels"])))
    if kind == "csv_chunks":
        return streaming.ChunkedCSVSource(
            path=spec["path"], name=spec.get("name"),
            id_column=spec.get("id_column", "id"),
            label_column=spec.get("label_column", "label"),
            delimiter=spec.get("delimiter", ","))
    if kind == "block_chunks":
        names = spec.get("feature_names")
        return streaming.ArraySource(PartyBlock(
            name=spec["name"], x=spec["x"], ids=spec["ids"],
            y=spec.get("y"), feature_ids=spec.get("feature_ids"),
            feature_names=tuple(names) if names else None))
    raise transport.ProtocolError(f"unknown stream source kind {kind!r}")


def distributed_streaming_ingest(coord: Coordinator, sources, n_bins: int, *,
                                 chunk_rows: int, capacity: int,
                                 salt: str = crypto.DEFAULT_SALT,
                                 append: bool = False):
    """Streamed ingest over the wire: each party worker scans and bins its
    own chunks process-side (repro.streaming.PartyStream held at the
    worker); the coordinator sees hashed IDs, sketch-derived boundaries,
    binned values and the aligned labels — never raw features or raw IDs.

    ``append=True`` extends the streams the workers already hold (one new
    source per party, worker order matching the original ingest) and
    re-assembles over the union — the distributed twin of
    ``Federation.ingest_append``.  Returns ``(partition, y, common_hashed)``
    exactly like :func:`distributed_ingest`."""
    sources = list(sources)
    if len(sources) != coord.n_parties:
        raise ValueError(f"expected {coord.n_parties} party sources, got "
                         f"{len(sources)}")
    # provisioning: same sanctioned raw flow as distributed_ingest — each
    # party's own chunked source goes to its own worker (in-memory array
    # sources ship raw; CSV sources ship as paths, read worker-side)
    with egress_runtime.allow_egress(
            "provisioning: a party's own chunked source to its own worker"):
        metas = [coord.request(w, {"op": "stream_scan",  # egress: ok(provisioning — party's own raw chunk source to its own worker process, same trust domain)
                                   "source": _stream_source_spec(s),
                                   "chunk_rows": int(chunk_rows),
                                   "capacity": int(capacity), "salt": salt,
                                   "append": bool(append)})
                 for w, s in enumerate(sources)]
    names = [m["name"] for m in metas]
    if len(set(names)) != len(names):
        raise ValueError(f"party names must be unique, got {names}")
    order = sorted(range(len(names)), key=lambda w: names[w])

    # workers validated per-party ID uniqueness during the scan
    positions, common = crypto.align_hashed(
        [np.asarray(metas[w]["hashes"]) for w in order],
        [names[w] for w in order], check_unique=False)
    groups, n_features = feature_groups(
        [metas[w].get("feature_ids") for w in order],
        [int(metas[w]["n_features"]) for w in order])

    feat_gid = _pad_groups(groups)
    m, fp = feat_gid.shape
    xb = np.zeros((m, len(common), fp), dtype=np.uint8)
    boundaries = np.zeros((n_features, max(n_bins - 1, 0)), dtype=np.float64)
    y, holder = None, None
    for i, w in enumerate(order):
        r = coord.request(w, {"op": "stream_bin", "positions": positions[i],
                              "n_bins": int(n_bins)})
        xb_i = np.asarray(r["xb"])
        xb[i, :, : xb_i.shape[1]] = xb_i
        boundaries[groups[i]] = np.asarray(r["boundaries"])
        if r.get("y") is not None:
            if holder is not None:
                raise ValueError(
                    f"labels held by more than one party ({holder!r} and "
                    f"{names[w]!r}); exactly one party owns the labels")
            holder, y = names[w], np.asarray(r["y"])

    part = VerticalPartition(xb=xb, feat_gid=feat_gid,
                             n_features=n_features, boundaries=boundaries,
                             raw_parts=None,
                             party_names=tuple(names[w] for w in order))
    return part, y, common


# ------------------------------------------------------------------- substrate
class _DistCallable:
    """A distributed protocol program bound to a coordinator.

    Call convention matches the simulated substrate: the first ``n_party``
    args carry a leading (M, ...) party axis (sliced per party before the
    wire), the rest are shared; the output is the per-party result stack.
    ``bind`` ships chosen argnums to the workers once (the serving engine's
    AOT seam) — later calls send None at those positions."""

    def __init__(self, substrate: "DistributedSubstrate", spec: dict,
                 n_party: int, n_shared: int, active=None):
        self.substrate = substrate
        self.spec = dict(spec)
        self.n_party = int(n_party)
        self.n_shared = int(n_shared)
        self.active = (tuple(int(p) for p in active) if active is not None
                       else tuple(range(substrate.n_parties)))
        self._bind_id = None
        self._bound_set: set[int] = set()

    def _slot(self, a, p):
        return jax.tree.map(lambda x: np.asarray(x)[p], a)

    def bind(self, *args) -> "_DistCallable":
        coord = self.substrate.coordinator
        bid = coord.new_bind_id()
        bound = tuple(k for k in (self.spec.get("bound") or ())
                      if k < len(args) and args[k] is not None)
        for p in self.active:
            shipped = {}
            for k in bound:
                shipped[k] = (self._slot(args[k], p) if k < self.n_party
                              else jax.tree.map(np.asarray, args[k]))
            coord.request(p, {"op": "bind", "bind": bid, "args": shipped})
        new = _DistCallable(self.substrate, self.spec, self.n_party,
                            self.n_shared, self.active)
        new._bind_id = bid
        new._bound_set = set(bound)
        return new

    def __call__(self, *args):
        if len(args) > self.n_party + self.n_shared:
            raise TypeError(
                f"{self.spec['name']}: expected at most "
                f"{self.n_party + self.n_shared} args, got {len(args)}")
        coord = self.substrate.coordinator
        active = list(self.active)
        shared = [None if (i + self.n_party) in self._bound_set
                  else jax.tree.map(np.asarray, a)
                  for i, a in enumerate(args[self.n_party:])]

        def build(rid):
            msgs = {}
            for p in active:
                wire = []
                for k, a in enumerate(args):
                    if k in self._bound_set:
                        wire.append(None)
                    elif k < self.n_party:
                        wire.append(self._slot(a, p))
                    else:
                        wire.append(shared[k - self.n_party])
                msgs[p] = {"op": "run", "run": rid,
                           "name": self.spec["name"],
                           "payload": self.spec.get("payload") or {},
                           "args": wire, "bound": self._bind_id,
                           "party_index": p, "n_parties": len(active)}
            return msgs

        outs = coord.run_retrying(build, active)
        return jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                            *[outs[p] for p in active])


class DistributedSubstrate:
    """Party-per-process execution: one OS process per party, message-passing
    collectives, production fault tolerance.  Registered as "distributed" in
    the substrate registry; workers spawn lazily on first use."""

    name = "distributed"
    mesh = None
    tree_axis = None

    def __init__(self, parties: int, *, host: str = "127.0.0.1",
                 round_timeout: float = 120.0, connect_timeout: float = 30.0,
                 retry: RetryPolicy | None = None,
                 breaker_threshold: int = 3):
        if parties < 1:
            raise ValueError(f"need at least 1 party, got {parties}")
        self.n_parties = int(parties)
        self._opts = dict(host=host, round_timeout=round_timeout,
                          connect_timeout=connect_timeout, retry=retry,
                          breaker_threshold=breaker_threshold)
        self._coord: Coordinator | None = None

    @property
    def coordinator(self) -> Coordinator:
        if self._coord is None:
            self._coord = Coordinator(self.n_parties, **self._opts)
            self._coord.start()
        return self._coord

    # ----------------------------------------------------- Substrate protocol
    def program(self, fn, n_party: int, n_shared: int, *, shared_specs=None,
                out_specs=None, distributed: dict | None = None,
                parties=None):
        if distributed is None:
            raise NotImplementedError(
                f"{getattr(fn, '__name__', fn)!r} has no distributed "
                f"protocol body — only forest fit/predict, F-LR predict and "
                f"the conformance toy protocol run party-per-process")
        return _DistCallable(self, distributed, n_party, n_shared,
                             active=parties)

    jit = program

    def compile(self, program):
        return program                         # already an executable protocol

    def aot_compile(self, program, *args):
        return program.bind(*args)

    def context(self):
        return contextlib.nullcontext()

    def exchange(self, op: str, payload: dict | None = None, *,
                 party: int | None = None, timeout: float | None = None):
        """Out-of-band request to one party (or all): the transport seam the
        Substrate protocol grew for this implementation."""
        coord = self.coordinator
        msg = dict(payload or {}, op=op)
        if party is not None:
            return coord.request(party, msg, timeout=timeout)
        return {p: coord.request(p, msg, timeout=timeout)
                for p in range(self.n_parties)
                if p not in coord._dead}

    def shutdown(self) -> None:
        if self._coord is not None:
            self._coord.shutdown()
            self._coord = None

    # ------------------------------------------------------------ operations
    def ingest_blocks(self, sources, n_bins: int, *,
                      salt: str = crypto.DEFAULT_SALT,
                      validate: bool = False):
        return distributed_ingest(self.coordinator, sources, n_bins,
                                  salt=salt, validate=validate)

    def ingest_stream(self, sources, n_bins: int, *,
                      salt: str = crypto.DEFAULT_SALT, validate: bool = False,
                      chunk_rows: int, capacity: int, append: bool = False):
        if validate:
            raise ValueError(
                "validate=True re-bins the assembled central matrix, which "
                "the distributed substrate never holds — validate on an "
                "in-process substrate instead")
        return distributed_streaming_ingest(
            self.coordinator, sources, n_bins, chunk_rows=chunk_rows,
            capacity=capacity, salt=salt, append=append)

    def health(self, timeout: float = 2.0):
        return self.coordinator.health(timeout=timeout)

    def collect_telemetry(self) -> dict[int, dict]:
        """Pull each live party's buffered spans + metric snapshot into this
        process: worker spans join the session tracer (so one export covers
        the whole federation) and party metrics merge under a ``party<i>.``
        prefix.  Returns the raw per-party replies.  No-op (empty dict) if
        the coordinator was never started."""
        from repro.observability import registry as _registry
        if self._coord is None:
            return {}
        coord = self._coord
        out: dict[int, dict] = {}
        for p in range(self.n_parties):
            if p in coord._dead or p not in coord.channels:
                continue
            try:
                r = coord.request(p, {"op": "telemetry"})
            except (PartyUnavailableError, RuntimeError):
                continue
            for s in r.get("spans") or ():
                tracing.TRACER.adopt(s)
            _registry.REGISTRY.merge(r.get("metrics") or {},
                                     prefix=f"party{p}.")
            out[p] = {"spans": len(r.get("spans") or ()),
                      "metrics": len(r.get("metrics") or ())}
        return out

    def chaos(self, party: int, mode: str, seconds: float = 0.0):
        self.coordinator.chaos(party, mode, seconds)

    def unavailable_parties(self) -> tuple[int, ...]:
        if self._coord is None:
            return ()
        return self._coord.unavailable_parties()

    def __repr__(self) -> str:
        state = "up" if self._coord is not None else "cold"
        return f"DistributedSubstrate(parties={self.n_parties}, {state})"
