"""Party-per-process substrate: the correctness oracle and deterministic
fault injection.

Oracle — a real 3-party localhost deployment (one OS process per party,
message-passing collectives over sockets) is BIT-IDENTICAL to the vmap
simulation: fit + predict on both tasks, CSV party ingest, and serving
through Federation.serve with a ServeConfig.

Fault tolerance — every failure mode the coordinator claims to handle is
demonstrated deterministically via the workers' one-shot chaos hook:

  * ``drop_run``  — the round times out, the jittered-backoff retry
    recovers it exactly (and the injectable sleeper records the schedule);
  * ``delay_run`` — a PartyTimeout surfaces when the retry budget is 1,
    and the aborted worker rejoins the next round;
  * ``die``       — the dead party is detected, retries fast-fail, the
    circuit breaker opens, health() reports the party down;
  * degraded serving — after a kill, ForestServer answers from the trees
    whose split paths avoid the dead party's features, exactly.
"""
import time

import jax
import numpy as np
import pytest

from repro.core import ForestParams
from repro.core.partyblock import CSVSource
from repro.data import make_classification, make_party_views, make_regression
from repro.federation import Federation, distributed
from repro.federation.distributed import DistributedSubstrate, surviving_trees
from repro.federation.transport import (CircuitOpenError, PartyDead,
                                        PartyTimeout, PartyUnavailableError,
                                        RetryPolicy)
from repro.serving import ServeConfig

M = 3


def _trees_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@pytest.fixture(scope="module")
def dist_fed():
    """One 3-party deployment shared by the oracle tests (fault tests build
    their own — they kill workers)."""
    fed = Federation(parties=M, substrate="distributed", n_bins=8,
                     round_timeout=60.0,
                     retry=RetryPolicy(attempts=2, base=0.05, seed=0))
    yield fed
    fed.close()


# ------------------------------------------------------------------- oracle
@pytest.mark.parametrize("task", ["classification", "regression"])
def test_fit_predict_bit_identity(dist_fed, task):
    if task == "classification":
        x, y = make_classification(120, 6, 2, seed=0)
    else:
        x, y = make_regression(120, 6, seed=1)
    p = ForestParams(task=task, n_estimators=3, max_depth=3, n_bins=8,
                     seed=0)
    sim = Federation(parties=M, n_bins=8)
    sim.ingest(x, y)
    ref = sim.fit(p)
    dist_fed.ingest(x, y)
    model = dist_fed.fit(p)
    _trees_equal(ref.trees_, model.trees_)
    xt = x[:40]
    np.testing.assert_array_equal(np.asarray(dist_fed.predict(model, xt)),
                                  np.asarray(sim.predict(ref, xt)))


def test_workers_run_on_host_cpu(dist_fed):
    """Party workers are spawned with JAX_PLATFORMS=cpu: an accelerator on
    the host belongs to the coordinator, and each hello says so."""
    coord = dist_fed.substrate.coordinator
    assert coord.worker_platforms == {i: "cpu" for i in range(M)}


def test_distributed_csv_ingest_matches_in_process(dist_fed, tmp_path):
    """Per-party CSV extracts ingested through the party processes (raw
    features and IDs never leave the worker) build the same partition — and
    the same fitted forest — as the in-process block path."""
    x, y = make_classification(90, 6, 2, seed=2)
    blocks, _, _ = make_party_views(x, y, M, overlap=0.8, seed=2)
    sources = [CSVSource(b.to_csv(str(tmp_path / f"{b.name}.csv")),
                         name=b.name) for b in blocks]
    sim = Federation(parties=M, n_bins=8)
    part_sim = sim.ingest(sources, validate=True)
    # validate=True re-bins the central matrix the distributed substrate
    # never holds — refused loudly, not silently skipped
    with pytest.raises(ValueError, match="validate"):
        dist_fed.ingest(sources, validate=True)
    part = dist_fed.ingest(sources)
    np.testing.assert_array_equal(part.xb, part_sim.xb)
    np.testing.assert_array_equal(part.feat_gid, part_sim.feat_gid)
    np.testing.assert_array_equal(part.boundaries, part_sim.boundaries)
    # the coordinator only ever sees hashed IDs: aligned_ids_ carries the
    # same canonical ordering, one salted hash away from the raw IDs
    from repro.core import crypto
    np.testing.assert_array_equal(dist_fed.aligned_ids_,
                                  crypto.hash_ids(sim.aligned_ids_))
    p = ForestParams(n_estimators=2, max_depth=3, n_bins=8, seed=0)
    _trees_equal(sim.fit(p).trees_, dist_fed.fit(p).trees_)


# ---------------------------------------------------------- fault injection
def _toy(sub):
    """The cheap two-collective conformance protocol — runs in numpy at the
    workers, so fault tests pay no jit tax."""
    prog = sub.program(None, 1, 1,
                       distributed=distributed.toy_affine_spec())
    x = np.arange(sub.n_parties * 4, dtype=np.int32).reshape(
        sub.n_parties, 4)
    return prog, x, np.int32(3)


def test_retry_recovers_dropped_round():
    """A swallowed run message times out; the retry replays the round
    bit-identically, sleeping the deterministic jittered-backoff schedule."""
    policy = RetryPolicy(attempts=3, base=0.01, seed=7,
                         sleeper=lambda d: None)
    sub = DistributedSubstrate(2, round_timeout=2.0, retry=policy)
    try:
        prog, x, s = _toy(sub)
        want = np.asarray(prog(x, s))           # healthy round first
        sub.chaos(0, "drop_run")
        got = np.asarray(prog(x, s))
        np.testing.assert_array_equal(got, want)
        assert len(policy.slept) == 1           # one timeout, one backoff
        twin = RetryPolicy(attempts=3, base=0.01, seed=7)
        assert policy.slept[0] == twin.delay(0)  # schedule is reproducible
    finally:
        sub.shutdown()


def test_round_timeout_surfaces_then_worker_rejoins():
    """With a retry budget of 1, a delayed party surfaces PartyTimeout
    attributed to it; the abort unblocks the worker, which serves the next
    round normally."""
    sub = DistributedSubstrate(2, round_timeout=1.0,
                               retry=RetryPolicy(attempts=1))
    try:
        prog, x, s = _toy(sub)
        want = np.asarray(prog(x, s))
        sub.chaos(1, "delay_run", seconds=2.0)
        with pytest.raises(PartyTimeout) as err:
            prog(x, s)
        assert err.value.parties == (1,)
        time.sleep(2.0)                  # let the worker wake + drain abort
        np.testing.assert_array_equal(np.asarray(prog(x, s)), want)
    finally:
        sub.shutdown()


def test_killed_party_opens_circuit_breaker():
    """A hard process death fails the round on every retry, opens the
    party's circuit (later calls fail fast, no timeout burned), and shows
    up in health() and unavailable_parties()."""
    policy = RetryPolicy(attempts=3, base=0.01, seed=0,
                         sleeper=lambda d: None)
    sub = DistributedSubstrate(2, round_timeout=10.0, retry=policy,
                               breaker_threshold=3)
    try:
        prog, x, s = _toy(sub)
        prog(x, s)                              # healthy round first
        sub.chaos(1, "die")
        with pytest.raises(PartyDead):
            prog(x, s)                          # all 3 attempts fail
        assert len(policy.slept) == 2           # backoff between attempts
        assert 1 in sub.unavailable_parties()
        with pytest.raises(CircuitOpenError):
            prog(x, s)                          # breaker: fail fast
        h = sub.health(timeout=2.0)
        assert h[1] is None and h[0] is not None
    finally:
        sub.shutdown()


def test_degraded_serving_after_kill_is_exact():
    """Kill a party mid-traffic: with allow_degraded the server answers
    from the trees whose split paths avoid the dead party's features —
    bit-identical to a forest holding only those trees (their masks never
    consult the dead party, so the leaf intersection is unchanged)."""
    p = ForestParams(n_estimators=10, max_depth=3, n_bins=8,
                     max_features=0.34, seed=0)
    x, y = make_classification(160, 9, 2, seed=0)
    sim = Federation(parties=M, n_bins=8)
    sim.ingest(x, y)
    ref = sim.fit(p)
    fed = Federation(parties=M, substrate="distributed", n_bins=8,
                     retry=RetryPolicy(attempts=2, base=0.01, seed=0,
                                       sleeper=lambda d: None))
    try:
        fed.ingest(x, y)
        model = fed.fit(p)
        server = fed.serve(model, ServeConfig(buckets=(32,),
                                              allow_degraded=True))
        xt = x[:30]
        want = np.asarray(sim.predict(ref, xt))
        np.testing.assert_array_equal(server.serve(xt), want)
        assert not server.wave_stats[-1].get("degraded")

        # kill the party the most trees' split paths avoid
        survivors = {pi: surviving_trees(model.trees_, [pi]).size
                     for pi in range(M)}
        victim = max(survivors, key=survivors.get)
        assert survivors[victim] > 0, "fixture forest has no avoider trees"
        fed.substrate.chaos(victim, "die")
        got = server.serve(xt)
        stats = server.wave_stats[-1]
        assert stats.get("degraded")
        assert victim in stats["dead_parties"]
        assert stats["n_trees"] == survivors[victim]
        assert victim in fed.substrate.unavailable_parties()

        sel = surviving_trees(ref.trees_, [victim])
        deg = type(ref)(p)
        deg.trees_ = jax.tree.map(lambda a: np.asarray(a)[:, sel],
                                  ref.trees_)
        deg.partition_ = ref.partition_
        deg._decode = ref._decode
        np.testing.assert_array_equal(got, np.asarray(deg.predict(xt)))
    finally:
        fed.close()


def test_degraded_serving_refused_without_optin():
    """Without allow_degraded a dead party is a hard serving error — no
    silently approximate answers."""
    p = ForestParams(n_estimators=2, max_depth=3, n_bins=8, seed=0)
    x, y = make_classification(80, 6, 2, seed=1)
    fed = Federation(parties=M, substrate="distributed", n_bins=8,
                     retry=RetryPolicy(attempts=2, base=0.01, seed=0,
                                       sleeper=lambda d: None))
    try:
        fed.ingest(x, y)
        model = fed.fit(p)
        server = fed.serve(model, ServeConfig(buckets=(32,)))
        server.serve(x[:10])
        fed.substrate.chaos(0, "die")
        with pytest.raises(PartyUnavailableError):
            server.serve(x[:10])
    finally:
        fed.close()


# ----------------------------------------------------------- privacy egress
def _tcp_channel_pair():
    """A real loopback TCP Channel pair (Channel sets TCP_NODELAY, so a
    unix socketpair won't do)."""
    import socket

    from repro.federation.transport import Channel
    lst = socket.socket()
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    a = socket.create_connection(lst.getsockname(), timeout=5)
    b, _ = lst.accept()
    lst.close()
    return Channel(a, party=0), Channel(b, party=0)


def test_egress_guard_blocks_raw_send_and_names_the_key():
    """Deliberately ship a raw-tagged feature block / raw IDs through a
    real Channel: the wire refuses, the error names the payload key path
    and the taint label.  Sanitized protocol traffic on the same channel
    flows untouched."""
    from repro.analysis import runtime as egress_rt
    from repro.analysis.runtime import PrivacyViolationError
    from repro.core.partyblock import PartyBlock

    assert egress_rt.enabled(), "conftest must arm REPRO_EGRESS_GUARD"
    tx, rx = _tcp_channel_pair()
    try:
        block = PartyBlock(name="leaky", x=np.arange(10.0).reshape(5, 2),
                           ids=np.arange(5), y=np.zeros(5, np.int64))
        with pytest.raises(PrivacyViolationError) as ei:
            tx.send({"op": "leak", "payload": {"x": block.x}})
        assert ei.value.path == "msg['payload']['x']"
        assert "raw features" in str(ei.value)
        assert "'leaky'" in str(ei.value)
        with pytest.raises(PrivacyViolationError) as ei:
            tx.send({"op": "leak", "ids": block.ids})
        assert ei.value.path == "msg['ids']"
        assert "raw sample IDs" in str(ei.value)
        # a column view shares the raw buffer — still blocked
        with pytest.raises(PrivacyViolationError):
            tx.send({"op": "leak", "col": block.x[:, 0]})
        # the sanctioned protocol message is untouched and round-trips
        hashes = block.hashed_ids("salt0")
        tx.send({"op": "hashes", "hashes": hashes})
        got = rx.recv(timeout=10)
        np.testing.assert_array_equal(np.asarray(got["hashes"]), hashes)
    finally:
        tx.sock.close()
        rx.sock.close()


def test_guarded_traffic_is_bit_identical(dist_fed):
    """The egress guard is armed for the whole suite (conftest): this pins
    down explicitly that guarded distributed fit/predict/ingest produce
    bit-identical results to the in-process simulation — the guard only
    ever blocks, it never perturbs."""
    from repro.analysis import runtime as egress_rt

    assert egress_rt.enabled()
    x, y = make_classification(90, 6, 2, seed=7)
    p = ForestParams(n_estimators=2, max_depth=3, n_bins=8, seed=4)
    sim = Federation(parties=M, n_bins=8)
    sim.ingest(x, y)
    ref = sim.fit(p)
    dist_fed.ingest(x, y)
    model = dist_fed.fit(p)
    _trees_equal(ref.trees_, model.trees_)
    np.testing.assert_array_equal(
        np.asarray(dist_fed.predict(model, x[:25])),
        np.asarray(sim.predict(ref, x[:25])))
