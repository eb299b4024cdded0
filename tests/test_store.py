"""Store tests: versioning, optimistic concurrency, List+Watch contract, binding.

Pins the semantics client-go's Reflector depends on (reference:
tools/cache/reflector.go:394 ListAndWatch; BindingREST storage.go:149)."""

import threading

import pytest

from kubernetes_tpu.store import (
    ADDED,
    DELETED,
    MODIFIED,
    AlreadyBoundError,
    APIStore,
    ConflictError,
    LockOrderViolation,
    NotFoundError,
)
from kubernetes_tpu.testing import MakeNode, MakePod, mutation_detector_guard


@pytest.fixture(autouse=True)
def _force_mutation_detector(monkeypatch):
    """ISSUE 5 satellite: every store op this module exercises (CRUD, watch
    replay, bind_many, status writes) runs under the force-enabled mutation
    detector and is re-checked at teardown — the runtime counterpart of
    schedlint MU001 on the store's own surface."""
    yield from mutation_detector_guard(monkeypatch)


def test_create_assigns_monotonic_rv():
    s = APIStore()
    p1 = s.create("pods", MakePod("a").obj())
    p2 = s.create("pods", MakePod("b").obj())
    assert 0 < p1.metadata.resource_version < p2.metadata.resource_version


def test_update_conflict_detection():
    s = APIStore()
    p = s.create("pods", MakePod("a").obj())
    stale = MakePod("a").obj()
    stale.metadata.resource_version = p.metadata.resource_version - 1  # stale rv
    with pytest.raises(ConflictError):
        s.update("pods", stale)
    p.spec.priority = 5
    updated = s.update("pods", p)
    assert updated.spec.priority == 5


def test_guaranteed_update_retries():
    s = APIStore()
    s.create("pods", MakePod("a").obj())

    def mutate(pod):
        pod.metadata.labels["x"] = "y"
        return pod

    out = s.guaranteed_update("pods", "default/a", mutate)
    assert out.metadata.labels["x"] == "y"


def test_list_watch_contract():
    """Every event after LIST's rv is seen exactly once, in order."""
    s = APIStore()
    s.create("pods", MakePod("a").obj())
    items, rv = s.list("pods")
    assert len(items) == 1

    w = s.watch("pods", since_rv=rv)
    s.create("pods", MakePod("b").obj())
    s.delete("pods", "default/a")

    ev1 = w.get(timeout=1)
    ev2 = w.get(timeout=1)
    assert ev1.type == ADDED and ev1.obj.metadata.name == "b"
    assert ev2.type == DELETED and ev2.obj.metadata.name == "a"
    assert ev1.resource_version < ev2.resource_version
    w.stop()


def test_watch_replay_from_history():
    s = APIStore()
    s.create("pods", MakePod("a").obj())
    s.create("pods", MakePod("b").obj())
    w = s.watch("pods", since_rv=0)
    evs = [w.get(timeout=1), w.get(timeout=1)]
    assert [e.obj.metadata.name for e in evs] == ["a", "b"]
    w.stop()


def test_watch_filters_kind():
    s = APIStore()
    w = s.watch("pods")
    s.create("nodes", MakeNode("n1").obj())
    s.create("pods", MakePod("a").obj())
    ev = w.get(timeout=1)
    assert ev.kind == "pods"
    w.stop()


def test_bind_transactional():
    s = APIStore()
    s.create("pods", MakePod("a").obj())
    s.bind("default", "a", "node-1")
    assert s.get("pods", "default/a").spec.node_name == "node-1"
    with pytest.raises(AlreadyBoundError):
        s.bind("default", "a", "node-2")


def test_store_copies_on_write():
    s = APIStore()
    pod = MakePod("a").obj()
    s.create("pods", pod)
    pod.spec.priority = 99  # caller mutation must not leak into the store
    assert s.get("pods", "default/a").spec.priority == 0


def test_not_found():
    s = APIStore()
    with pytest.raises(NotFoundError):
        s.get("pods", "default/missing")
    with pytest.raises(NotFoundError):
        s.delete("pods", "default/missing")


def test_concurrent_writers_unique_rvs():
    s = APIStore()
    errs = []

    def writer(i):
        try:
            for j in range(50):
                s.create("pods", MakePod(f"p-{i}-{j}").obj())
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(8)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert not errs
    items, rv = s.list("pods")
    assert len(items) == 400
    rvs = [o.metadata.resource_version for o in items]
    assert len(set(rvs)) == 400 and max(rvs) <= rv


def test_get_returns_copy():
    """Caller mutation of a fetched object must not corrupt the store."""
    s = APIStore()
    s.create("pods", MakePod("a").obj())
    p = s.get("pods", "default/a")
    p.spec.node_name = "sneaky"
    assert s.get("pods", "default/a").spec.node_name == ""
    s.bind("default", "a", "n1")  # must not see "sneaky"


def test_delete_event_carries_post_delete_rv():
    s = APIStore()
    s.create("pods", MakePod("a").obj())
    w = s.watch("pods", since_rv=s.resource_version())
    s.delete("pods", "default/a")
    ev = w.get(timeout=1)
    assert ev.type == DELETED
    assert ev.obj.metadata.resource_version == ev.resource_version
    w.stop()


def test_watch_too_old_rv_raises():
    from kubernetes_tpu.store import ResourceVersionTooOldError

    s = APIStore()
    s._history_limit = 8  # force trimming
    for i in range(20):
        s.create("pods", MakePod(f"p{i}").obj())
    with pytest.raises(ResourceVersionTooOldError):
        s.watch("pods", since_rv=1)


def test_watch_event_objects_are_copies():
    """Mutating an event object must not corrupt the store (the client-go
    mutation-detector failure mode that bit the scheduler's assume path)."""
    s = APIStore()
    w = s.watch("pods", since_rv=0)
    s.create("pods", MakePod("a").obj())
    ev = w.get(timeout=1)
    ev.obj.spec.node_name = "sneaky"
    assert s.get("pods", "default/a").spec.node_name == ""
    s.bind("default", "a", "n1")  # must succeed
    # repair: the module fixture re-checks every store at teardown, and this
    # test's POINT was that the deliberate mutation stayed private
    ev.obj.spec.node_name = ""
    w.stop()


def test_bounded_drain_leaves_remainder_buffered():
    """drain(max_n) must LEAVE excess events in the buffer — a capped
    consumer breaking out of a full drain() silently dropped the rest of a
    large backlog (the north-star 100k run lost 90% of its ADDED events)."""
    from kubernetes_tpu.testing import MakePod

    store = APIStore()
    w = store.watch("pods", maxsize=50_000)
    for i in range(30_000):
        store.create("pods", MakePod(f"p{i}").obj())
    first = w.drain(10_000)
    assert len(first) == 10_000
    assert first[0].obj.metadata.name == "p0"
    rest = w.drain()
    assert len(rest) == 20_000
    assert rest[0].obj.metadata.name == "p10000"
    assert not w.terminated


def test_ring_watch_survives_overflow_with_counted_drops():
    """Ring mode (ISSUE 12 satellite): a slow observability subscriber with
    ring=True drops its own OLDEST deliveries on overflow — counted as
    reason="ring_overflow" — and the subscription SURVIVES with the newest
    events buffered, instead of terminating into a relist. Writers are
    never blocked either way (put_nowait throughout)."""
    from kubernetes_tpu.testing import MakePod

    store = APIStore()
    w = store.watch("pods", maxsize=64, ring=True)
    for i in range(200):
        store.create("pods", MakePod(f"r{i}").obj())
    assert not w.terminated
    assert w.ring_dropped == 200 - 64
    evs = w.drain()
    assert len(evs) == 64
    # the ring kept the NEWEST window
    assert evs[-1].obj.metadata.name == "r199"
    assert evs[0].obj.metadata.name == "r136"
    # drops are observable: per-watch counter + store-level reason bucket
    tel = store.watch_telemetry()
    assert tel["dropped"].get("ring_overflow", 0) == 136
    row = next(s for s in tel["subscribers"] if s["id"] == w.id)
    assert row["ring"] is True and row["ring_dropped"] == 136
    # the stream keeps flowing after the lossy window
    store.create("pods", MakePod("after").obj())
    got = w.drain()
    assert len(got) == 1 and got[0].obj.metadata.name == "after"
    assert not w.terminated


def test_non_ring_watch_still_terminates_on_overflow():
    """The default contract is unchanged: a cache-building consumer that
    falls maxsize behind is evicted and must relist (terminate→relist is
    its correctness signal; a silent gap would corrupt its cache)."""
    from kubernetes_tpu.testing import MakePod

    store = APIStore()
    w = store.watch("pods", maxsize=16)
    for i in range(40):
        store.create("pods", MakePod(f"t{i}").obj())
    assert w.terminated
    assert store.watch_telemetry()["dropped"].get("overflow", 0) >= 1


def test_ring_watch_coalesced_batches_drop_as_units():
    """Coalesced mode + ring: each CoalescedEvent is one buffered item, so
    the ring drops whole batches (counted once per dropped delivery, the
    same unit the chaos drop site counts)."""
    from kubernetes_tpu.testing import MakePod

    store = APIStore()
    w = store.watch("pods", maxsize=2, coalesce=True, ring=True)
    for wave in range(4):
        store.create_many(
            "pods", [MakePod(f"c{wave}-{i}").obj() for i in range(10)],
            consume=True)
    assert not w.terminated
    assert w.ring_dropped == 2
    evs = w.drain()
    assert len(evs) == 2
    # newest two waves retained
    assert evs[-1].events[-1].obj.metadata.name == "c3-9"


# -- runtime lock-order assertion (ISSUE 5: dynamic companion of LK001) --------


def test_lock_order_inversion_raises_under_check():
    """Holding the pods shard and then taking the global RV lock is the
    docstring-forbidden order; the _OrderedRLock companion (enabled by the
    autouse STORE_LOCK_ORDER_CHECK fixture) must refuse it loudly instead
    of leaving a latent deadlock."""
    s = APIStore()
    with s._pods_lock:
        with pytest.raises(LockOrderViolation):
            s._lock.acquire()


def test_lock_order_mandated_and_reentrant_orders_pass():
    s = APIStore()
    # global -> shard (the mandated order), nested reentrantly
    with s._lock:
        with s._pods_lock:
            with s._lock:  # reentrant global under both: fine
                pass
    # the composite pair acquirer
    with s._pods_pair:
        pass
    # shard alone, released, THEN global+shard — bind_many's two-phase shape
    with s._pods_lock:
        pass
    with s._lock:
        with s._pods_lock:
            pass


def test_lock_order_check_covers_real_store_traffic():
    """The wrapped locks must be transparent to the store's actual write
    paths (create/bind_many/status/delete all run global->shard or
    shard-alone phases)."""
    s = APIStore()
    assert type(s._lock).__name__ == "_OrderedRLock"  # fixture is live
    for i in range(4):
        s.create("pods", MakePod(f"lk-{i}").obj())
    s.create("nodes", MakeNode("n1").obj())
    bound, errs = s.bind_many(
        [("default", f"lk-{i}", "n1") for i in range(3)])
    assert (bound, errs) == (3, [])
    s.update_pod_status("default", "lk-3",
                        lambda st: setattr(st, "phase", "Running"))
    s.delete("pods", "default/lk-3")


def test_lock_order_check_off_by_default(monkeypatch):
    monkeypatch.delenv("STORE_LOCK_ORDER_CHECK", raising=False)
    s = APIStore()
    assert type(s._lock).__name__ == "RLock"


def test_list_filters_and_copies_outside_the_lock():
    """A LIST takes its snapshot under the lock and filters and copies it
    after: a writer must get through while a 100k-pod LIST is copying (the
    held lock starved the API server's request thread on the chip smoke)."""
    store = APIStore()
    store.create_many("pods", [MakePod(f"p{i}").obj() for i in range(3)])
    wrote = []

    def writer():
        store.create("pods", MakePod("during").obj())
        wrote.append(True)

    def predicate(p):
        if not wrote:
            t = threading.Thread(target=writer)
            t.start()
            t.join(timeout=5)
        return True

    pods, rv = store.list("pods", predicate)
    assert wrote, "the writer was blocked while the LIST filtered"
    # the LIST is still the snapshot at its RV: the concurrent create is not in it
    assert {p.metadata.name for p in pods} == {"p0", "p1", "p2"}
    assert store.get("pods", "default/during").metadata.resource_version > rv


def test_keys_lists_without_copies():
    store = APIStore()
    store.create("nodes", MakeNode("n1").obj())
    store.create("pods", MakePod("p").obj())
    assert store.keys("nodes") == ["n1"]
    assert store.keys("pods") == ["default/p"]
    assert store.keys("services") == []
