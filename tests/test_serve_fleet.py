"""Fleet serving: routing policies, the shared specialization plane
(publish/subscribe, conflict resolution, crash tolerance), cross-replica
warm starts with zero recompiles, and fleet-level metric aggregation."""
import json
import os

import jax.numpy as jnp
import pytest

from repro.checkpoint import (PLANE_RECORD_VERSION, load_plane_record,
                              save_plane_record)
from repro.core import (Controller, ExhaustiveSweep, IridescentRuntime,
                        VariantCache)
from repro.core.runtime import encode_context_key
from repro.serve import Completion, Request, ServeMetrics
from repro.serve.fleet import (DeadlineSpill, JoinShortestQueue,
                               ReplicaRouter, RoundRobin, SpecPlane,
                               make_routing_policy)


class FakeReplica:
    def __init__(self, depth=0, accept=True):
        self._depth = depth
        self.accept = accept
        self.got = []

    def submit(self, request):
        self.got.append(request)
        return self.accept

    def depth(self):
        return self._depth


# -- routing policies ----------------------------------------------------------

def test_round_robin_cycles_fairly():
    reps = [FakeReplica() for _ in range(3)]
    router = ReplicaRouter(reps, policy="round-robin")
    for _ in range(6):
        assert router.submit(Request())
    assert [len(r.got) for r in reps] == [2, 2, 2]
    assert router.routed == [2, 2, 2] and router.refused == [0, 0, 0]


def test_jsq_picks_reported_min_depth():
    reps = [FakeReplica(depth=5), FakeReplica(depth=1), FakeReplica(depth=3)]
    router = ReplicaRouter(reps, policy="jsq")
    router.submit(Request())
    assert len(reps[1].got) == 1
    # ties break to the lowest index — deterministic under equal load
    reps[0]._depth = reps[2]._depth = 1
    router.submit(Request())
    assert len(reps[0].got) == 1


def test_spill_keeps_home_until_deadline_threatened():
    reps = [FakeReplica(depth=0), FakeReplica(depth=0)]
    router = ReplicaRouter(reps, policy="spill", est_wait_s=0.1, margin=0.5)
    router.submit(Request(deadline_s=10.0))       # home 0, not overloaded
    router.submit(Request(deadline_s=10.0))       # home 1
    assert [len(r.got) for r in reps] == [1, 1]
    # home 0 now backlogged enough to blow a tight deadline: spill to 1
    reps[0]._depth = 50
    router.submit(Request(deadline_s=1.0))
    assert len(reps[1].got) == 2
    assert router.policy.spills == 1
    assert router.stats()["spills"] == 1


def test_spill_deadline_less_uses_max_depth():
    reps = [FakeReplica(depth=40), FakeReplica(depth=0)]
    pol = DeadlineSpill(max_depth=32)
    router = ReplicaRouter(reps, policy=pol)
    router.submit(Request())                      # home 0 over max_depth
    assert len(reps[1].got) == 1 and pol.spills == 1


def test_router_counts_refusals_never_retries():
    reps = [FakeReplica(accept=False), FakeReplica()]
    router = ReplicaRouter(reps, policy="round-robin")
    assert router.submit(Request()) is False      # landed on the refuser
    assert router.submit(Request()) is True
    assert router.refused == [1, 0]
    assert len(reps[0].got) == 1                  # offered once, open-loop


def test_router_validation_and_policy_factory():
    with pytest.raises(ValueError):
        ReplicaRouter([])
    with pytest.raises(ValueError):
        make_routing_policy("power-of-two")
    assert isinstance(make_routing_policy("round-robin"), RoundRobin)
    assert isinstance(make_routing_policy("jsq"), JoinShortestQueue)


# -- plane records -------------------------------------------------------------

def _record(path, **kw):
    defaults = dict(handler="h", context=encode_context_key(4),
                    config={"fused": True}, goodput=1.0, epoch=1,
                    replica="1", t=0.0)
    defaults.update(kw)
    save_plane_record(str(path), **defaults)
    return str(path)


def test_plane_record_round_trip(tmp_path):
    p = _record(tmp_path / "r.json", goodput=2.5, epoch=3)
    with open(p) as f:
        assert json.load(f)["version"] == PLANE_RECORD_VERSION  # wire format
    rec = load_plane_record(p)
    assert rec["config"] == {"fused": True}
    assert (rec["goodput"], rec["epoch"], rec["replica"]) == (2.5, 3, "1")


@pytest.mark.parametrize("payload", [
    b"",                                          # truncated to nothing
    b'{"version": 1, "handler"',                  # torn mid-write
    b"\x00\xffnot json",                          # binary garbage
    json.dumps({"version": 999}).encode(),        # unknown version
    json.dumps([1, 2, 3]).encode(),               # not a record
    json.dumps({"version": 1, "handler": "h"}).encode(),  # fields missing
])
def test_plane_ignores_bad_records(tmp_path, payload):
    bad = tmp_path / "bad.json"
    bad.write_bytes(payload)
    assert load_plane_record(str(bad)) is None
    _record(tmp_path / "good.json")
    plane = SpecPlane(str(tmp_path), replica="me")
    winners = plane.resolve()                     # bad record never fatal
    assert list(winners) == [("h", encode_context_key(4))]


def test_plane_conflict_resolution_rank(tmp_path):
    plane = SpecPlane(str(tmp_path), replica="me")
    a = SpecPlane(str(tmp_path), replica="a")
    b = SpecPlane(str(tmp_path), replica="b")
    # freshest epoch wins regardless of goodput
    a.publish("h", 4, {"fused": True}, goodput=9.0, epoch=1)
    b.publish("h", 4, {"fused": False}, goodput=0.1, epoch=2)
    winner = plane.resolve()[("h", encode_context_key(4))]
    assert winner["replica"] == "b" and winner["config"] == {"fused": False}
    # equal epochs: goodput evidence breaks the tie
    a.publish("h", 8, {"fused": True}, goodput=5.0, epoch=7)
    b.publish("h", 8, {"fused": False}, goodput=3.0, epoch=7)
    assert plane.resolve()[("h", encode_context_key(8))]["replica"] == "a"
    # full tie: replica id keeps it deterministic fleet-wide
    a.publish("h", 16, {"fused": True}, goodput=1.0, epoch=1)
    b.publish("h", 16, {"fused": True}, goodput=1.0, epoch=1)
    assert plane.resolve()[("h", encode_context_key(16))]["replica"] == "b"


def test_plane_publish_after_poll_supersedes(tmp_path):
    # The Lamport property: a replica that has *seen* epoch N publishes at
    # N+1, so its update wins the next resolution everywhere.
    a = SpecPlane(str(tmp_path), replica="a")
    b = SpecPlane(str(tmp_path), replica="b")
    a.publish("h", 4, {"fused": True}, goodput=1.0)
    b.resolve()
    b.publish("h", 4, {"fused": False}, goodput=0.5)
    winner = a.resolve()[("h", encode_context_key(4))]
    assert winner["replica"] == "b" and winner["epoch"] == 2


class FakeHandler:
    def __init__(self, fail=False):
        self.fail = fail
        self.seeded = []

    def seed_spec_state(self, enc, cfg):
        if self.fail:
            raise ValueError("stale config")
        self.seeded.append((enc, dict(cfg)))


class FakeRuntime:
    def __init__(self, **handlers):
        self.handlers = handlers


def test_plane_poll_seeds_remote_winners_once(tmp_path):
    a = SpecPlane(str(tmp_path), replica="a")
    b = SpecPlane(str(tmp_path), replica="b")
    a.publish("h", 4, {"fused": True}, goodput=1.0)
    h = FakeHandler()
    rt = FakeRuntime(h=h)
    b.poll(rt)
    assert h.seeded == [(encode_context_key(4), {"fused": True})]
    b.poll(rt)                                    # idempotent: same winner
    assert len(h.seeded) == 1
    a.publish("h", 4, {"fused": False}, goodput=2.0)
    b.poll(rt)                                    # fresher record re-seeds
    assert h.seeded[-1] == (encode_context_key(4), {"fused": False})
    # a's own records never loop back onto a
    own = FakeHandler()
    a.poll(FakeRuntime(h=own))
    assert own.seeded == []


def test_plane_poll_tolerates_seed_failure_and_unknown_handler(tmp_path):
    a = SpecPlane(str(tmp_path), replica="a")
    a.publish("h", 4, {"fused": True}, goodput=1.0)
    a.publish("ghost", 4, {"fused": True}, goodput=1.0)
    bad = FakeHandler(fail=True)
    b = SpecPlane(str(tmp_path), replica="b")
    b.poll(FakeRuntime(h=bad))                    # raises inside: swallowed
    assert bad.seeded == []
    bad.fail = False
    b.poll(FakeRuntime(h=bad))                    # not marked applied: retried
    assert len(bad.seeded) == 1


def test_plane_publish_controller_skips_unchanged(tmp_path):
    class FakeCtl:
        def __init__(self, winners):
            self.winners = winners

        def settled_winners(self):
            return self.winners

    plane = SpecPlane(str(tmp_path), replica="a")
    ctl = FakeCtl({4: ({"fused": True}, 2.0)})
    assert plane.publish_controller("h", ctl) == 1
    assert plane.publish_controller("h", ctl) == 0    # unchanged: no churn
    ctl.winners = {4: ({"fused": False}, 3.0)}
    assert plane.publish_controller("h", ctl) == 1


# -- warm start round trip -----------------------------------------------------

def _fused_builder(spec):
    fused = spec.enum("fused", False, (False, True), guarded=False)

    def f(x, w):
        if fused:
            return x @ w
        h = w.shape[1] // 2
        return jnp.concatenate([x @ w[:, :h], x @ w[:, h:]], axis=-1)

    return f


def test_plane_round_trip_warm_start_zero_recompiles(tmp_path):
    """The acceptance chain: replica 1 explores, publishes its settled
    winner; replica 2 (sharing a *portable* variant cache) polls, is
    seeded, and activates the winner as a cache hit — zero XLA compiles,
    and its Controller admits the context directly settled."""
    cache_dir = str(tmp_path / "variants")
    plane_dir = str(tmp_path / "plane")
    ctx_fn = lambda a, k: int(a[0].shape[0])  # noqa: E731
    x, w = jnp.ones((4, 8)), jnp.ones((8, 8))

    rt1 = IridescentRuntime(async_compile=False,
                            variant_cache=VariantCache(cache_dir,
                                                       portable=True))
    h1 = rt1.register("step", _fused_builder, context_fn=ctx_fn)
    ctl1 = Controller(
        h1, lambda: ExhaustiveSweep([{"fused": True}, {"fused": False}]),
        metric=lambda view: 2.0 if view.active_config()["fused"] else 1.0,
        dwell=2, wait_compiles=True)
    for _ in range(30):
        h1(x, w)
        ctl1.step()
        if ctl1.settled():
            break
    assert ctl1.settled()
    winners = ctl1.settled_winners()
    assert winners[4][0] == {"fused": True}
    plane1 = SpecPlane(plane_dir, replica="1")
    assert plane1.publish_controller("step", ctl1) == 1
    assert rt1.compile_stats()["xla_compiles"] > 0    # replica 1 paid
    rt1.shutdown()

    rt2 = IridescentRuntime(async_compile=False,
                            variant_cache=VariantCache(cache_dir,
                                                       portable=True))
    h2 = rt2.register("step", _fused_builder, context_fn=ctx_fn)
    ctl2 = Controller(
        h2, lambda: ExhaustiveSweep([{"fused": True}, {"fused": False}]),
        metric=lambda view: 1.0, dwell=2, wait_compiles=True)
    SpecPlane(plane_dir, replica="2").poll(rt2)
    h2(x, w)
    ctl2.step()
    stats = rt2.compile_stats()
    assert stats["xla_compiles"] == 0                 # compile-free
    assert stats["cache_hits"] >= 1
    assert h2.active_config(context=4) == {"fused": True}
    assert ctl2.settled()                             # admitted in EXPLOIT
    rt2.shutdown()


# -- fleet metric aggregation --------------------------------------------------

def _completion(latency, tokens=4, within=True):
    return Completion(rid=0, prompt_tokens=2, tokens=tokens, arrival_t=0.0,
                      service_t=latency / 2, first_token_t=latency / 2,
                      finish_t=latency, within_slo=within)


def test_metrics_state_round_trip():
    m = ServeMetrics(slo_s=0.5)
    m.observe(_completion(0.1))
    m.observe(_completion(0.9, within=False))
    m.observe_shed(3)
    back = ServeMetrics.from_state(m.state())
    assert back.completed == 2 and back.shed == 3
    assert back.goodput_tokens == 4 and back.completed_tokens == 8
    assert back.slo_s == 0.5
    assert back.percentile(50) == m.percentile(50)
    # state() is JSON-portable: the worker ships it over a pipe
    wire = json.loads(json.dumps(m.state()))
    assert ServeMetrics.from_state(wire).completed == 2


def test_metrics_merge_counters_and_rank_percentiles():
    a, b = ServeMetrics(slo_s=0.5), ServeMetrics(slo_s=0.5)
    for lat in (0.1, 0.2, 0.3):
        a.observe(_completion(lat))
    for lat in (0.4, 0.5, 0.6):
        b.observe(_completion(lat, within=False))
    merged = ServeMetrics.merge(a, b)
    assert merged.completed == 6
    assert merged.goodput_tokens == 12 and merged.completed_tokens == 24
    assert merged.slo_met == 3 and merged.slo_missed == 3
    # nearest-rank over the *combined* samples, not averaged percentiles
    assert merged.percentile(50) == pytest.approx(0.3)
    assert merged.percentile(99) == pytest.approx(0.6)
    # instances and state() snapshots mix freely (the fleet front merges
    # wire snapshots from subprocess replicas)
    assert ServeMetrics.merge(a, b.state()).completed == 6
    # slo_s survives only under fleet-wide agreement
    c = ServeMetrics(slo_s=9.9)
    assert ServeMetrics.merge(a, c).slo_s is None
    assert ServeMetrics.merge(a, b).slo_s == 0.5


def test_metrics_merge_empty_and_single():
    assert ServeMetrics.merge().completed == 0
    m = ServeMetrics()
    m.observe(_completion(0.2))
    assert ServeMetrics.merge(m).completed == 1


def test_metrics_buffers_bounded_on_long_streams():
    """Regression (ISSUE 9 satellite): sample buffers are reservoirs — a
    long-lived server never grows them past ``window``, and percentiles
    stay nearest-rank over a uniform sample of the whole stream."""
    m = ServeMetrics(slo_s=10.0, window=64)
    for i in range(10_000):
        m.observe(_completion(0.001 * (i % 100 + 1)))
    assert len(m._latencies) == 64
    assert m._latencies.seen == 10_000
    assert m.completed == 10_000          # counters are exact, not sampled
    # the retained sample spans the stream's range, not just its head
    assert 0.0 < m.percentile(50) <= 0.1
    st = m.state()
    assert len(st["latencies"]) == 64 and st["latencies_seen"] == 10_000


def test_metrics_merge_stays_bounded():
    parts = []
    for r in range(8):
        m = ServeMetrics(slo_s=1.0, window=2048)
        for i in range(1000):
            m.observe(_completion(0.01))
        parts.append(m)
    merged = ServeMetrics.merge(*parts)
    assert merged.completed == 8000
    assert len(merged._latencies) <= merged.window
    assert merged._latencies.seen == 8000
    # merging merges never compounds the window either
    again = ServeMetrics.merge(merged, merged)
    assert len(again._latencies) <= again.window
    assert again._latencies.seen == 16_000


def test_metrics_from_state_accepts_pre_reservoir_wire_format():
    # older snapshots carry no *_seen fields: seen defaults to len(samples)
    wire = {"slo_s": 0.5, "latencies": [0.1, 0.2], "completed": 2}
    back = ServeMetrics.from_state(wire)
    assert back._latencies.seen == 2
    assert back.percentile(50) == pytest.approx(0.1)


# -- subprocess worker ---------------------------------------------------------

def test_subprocess_worker_round_trip(tmp_path):
    """One synthetic worker behind the stdio protocol: ready, serves a
    routed schedule, reports depth, exits with mergeable stats."""
    from repro.serve.fleet.worker import SubprocessReplica, worker_command

    rep = SubprocessReplica(
        worker_command("--replica-id", "w", "--d", "64", "--dwell", "2",
                       "--max-wall-s", "60"),
        name="w")
    try:
        assert rep.wait_ready(300.0)
        router = ReplicaRouter([rep], policy="round-robin")
        for _ in range(6):
            assert router.submit(Request(prompt_tokens=4, max_new_tokens=2))
    finally:
        rep.close()
        stats = rep.join(300.0)
    assert stats is not None and stats["replica"] == "w"
    merged = ServeMetrics.merge(stats["metrics"])
    assert merged.completed == 6
    assert stats["compile"]["xla_compiles"] > 0       # cold: no shared cache
    assert stats["settled"]                           # winners reported
