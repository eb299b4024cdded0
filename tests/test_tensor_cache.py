"""TensorCache: generation-diff incremental tensorization parity.

reference: pkg/scheduler/backend/cache/cache.go:186 UpdateSnapshot — only
NodeInfos with a newer generation are re-copied; the TPU build mirrors that
diff into its numpy cluster tensors + PTS count columns. Property: after ANY
sequence of binds/unbinds/node churn, the incremental tensors equal a fresh
full rebuild.
"""

import numpy as np
import pytest

from kubernetes_tpu.scheduler import Cache, Framework
from kubernetes_tpu.scheduler.batch import BatchScheduler
from kubernetes_tpu.scheduler.plugins import default_plugins
from kubernetes_tpu.snapshot.tensorizer import (
    TensorCache,
    build_cluster_tensors,
    build_pod_batch,
)
from kubernetes_tpu.store import APIStore
from kubernetes_tpu.testing import MakeNode, MakePod
from kubernetes_tpu.utils import FakeClock

ZONE = "topology.kubernetes.io/zone"


def _pods(i0, n, spread=False):
    out = []
    for i in range(i0, i0 + n):
        mk = MakePod(f"p{i}").labels({"app": "w"}).req({"cpu": "200m", "memory": "256Mi"})
        if spread:
            mk = mk.topology_spread(2, ZONE, "DoNotSchedule", {"app": "w"})
        out.append(mk.obj())
    return out


def _assert_cluster_equal(got, want):
    np.testing.assert_array_equal(got.alloc, want.alloc)
    np.testing.assert_array_equal(got.used, want.used)
    np.testing.assert_array_equal(got.used_nz, want.used_nz)
    np.testing.assert_array_equal(got.pod_count, want.pod_count)
    np.testing.assert_array_equal(got.max_pods, want.max_pods)
    assert got.node_names == want.node_names


class TestTensorCache:
    def test_incremental_equals_full_rebuild_under_churn(self):
        cache = Cache(clock=FakeClock())
        for i in range(40):
            cache.add_node(MakeNode(f"n{i}").labels({ZONE: f"z{i % 4}"})
                           .capacity({"cpu": "8", "memory": "16Gi", "pods": "50"}).obj())
        tc = TensorCache()
        for step in range(6):
            # churn: bind a few spread pods to rotating nodes
            for j in range(5):
                p = MakePod(f"b{step}-{j}").labels({"app": "w"}).req(
                    {"cpu": "100m"}).obj()
                p.spec.node_name = f"n{(step * 5 + j) % 40}"
                cache.add_pod(p)
            snap = cache.update_snapshot()
            batch_pods = _pods(step * 10, 8, spread=True)

            cluster, changed = tc.cluster_tensors(snap)
            if step > 0:
                assert changed is not None, "expected the incremental path"
                assert 0 < len(changed) <= 5
            batch = build_pod_batch(batch_pods, snap, cluster,
                                    reuse=tc, changed_nodes=changed)

            fresh_cluster = build_cluster_tensors(snap)
            fresh_batch = build_pod_batch(batch_pods, snap, fresh_cluster)
            _assert_cluster_equal(cluster, fresh_cluster)
            np.testing.assert_array_equal(
                cluster.selcls_count, fresh_cluster.selcls_count)

    def test_label_change_falls_back_to_full_rebuild(self):
        cache = Cache(clock=FakeClock())
        for i in range(8):
            cache.add_node(MakeNode(f"n{i}").labels({ZONE: "z0"})
                           .capacity({"cpu": "4", "pods": "10"}).obj())
        tc = TensorCache()
        snap = cache.update_snapshot()
        tc.cluster_tensors(snap)
        # a real watch event delivers a NEW node object (store copies on read)
        n = MakeNode("n3").labels({ZONE: "z9"}).capacity(
            {"cpu": "4", "pods": "10"}).obj()
        cache.add_node(n)
        snap2 = cache.update_snapshot()
        cluster, changed = tc.cluster_tensors(snap2)
        assert changed is None  # structural: full rebuild
        fresh = build_cluster_tensors(snap2)
        _assert_cluster_equal(cluster, fresh)

    def test_node_add_remove_falls_back(self):
        cache = Cache(clock=FakeClock())
        for i in range(4):
            cache.add_node(MakeNode(f"n{i}").capacity(
                {"cpu": "4", "pods": "10"}).obj())
        tc = TensorCache()
        tc.cluster_tensors(cache.update_snapshot())
        cache.add_node(MakeNode("extra").capacity({"cpu": "4", "pods": "10"}).obj())
        cluster, changed = tc.cluster_tensors(cache.update_snapshot())
        assert changed is None
        assert len(cluster.node_names) == 5

    def test_batch_scheduler_end_to_end_with_cache(self):
        """BatchScheduler with the TensorCache schedules a churny PTS workload
        identically to expectations (all placed, skew respected)."""
        store = APIStore()
        for i in range(20):
            store.create("nodes", MakeNode(f"n{i}").labels({ZONE: f"z{i % 4}"})
                         .capacity({"cpu": "8", "memory": "16Gi", "pods": "50"}).obj())
        sched = BatchScheduler(store, Framework(default_plugins()),
                               batch_size=16, solver="exact")
        sched.sync()
        for r in range(3):
            for p in _pods(r * 16, 16, spread=True):
                store.create("pods", p)
            sched.run_until_idle()
        pods, _ = store.list("pods")
        bound = [p for p in pods if p.spec.node_name]
        assert len(bound) == 48
        # maxSkew=2 across 4 zones
        from collections import Counter

        zones = Counter(p.spec.node_name for p in bound)
        per_zone = Counter()
        for p in bound:
            per_zone[int(p.spec.node_name[1:]) % 4] += 1
        assert max(per_zone.values()) - min(per_zone.values()) <= 2

    def test_device_mirrors_track_host_after_churn(self):
        """The persistent HBM mirrors (diff -> device streaming) must equal a
        fresh upload of the host arrays after any churn sequence."""
        import jax.numpy as jnp

        cache = Cache(clock=FakeClock())
        for i in range(30):
            cache.add_node(MakeNode(f"n{i}").labels({ZONE: f"z{i % 3}"})
                           .capacity({"cpu": "8", "memory": "16Gi", "pods": "50"}).obj())
        tc = TensorCache()
        for step in range(5):
            for j in range(4):
                p = MakePod(f"d{step}-{j}").labels({"app": "w"}).req(
                    {"cpu": "250m"}).obj()
                p.spec.node_name = f"n{(step * 4 + j) % 30}"
                cache.add_pod(p)
            snap = cache.update_snapshot()
            cluster, changed = tc.cluster_tensors(snap)
            build_pod_batch(_pods(step * 8, 6, spread=True), snap, cluster,
                            reuse=tc, changed_nodes=changed)
            views = tc.device_views(cluster)
            for f in TensorCache.DEVICE_FIELDS:
                np.testing.assert_array_equal(
                    np.asarray(views[f]), getattr(cluster, f), err_msg=f)
            np.testing.assert_array_equal(
                np.asarray(views["selcls_count"]), cluster.selcls_count)

    def test_pod_axis_reuse_parity(self):
        """Re-solving the identical backlog (same pod objects) must produce
        PodBatchTensors equal to a fresh build — the pod-axis fast path skips
        the per-pod loops and must not drift."""
        cache = Cache(clock=FakeClock())
        for i in range(20):
            cache.add_node(MakeNode(f"n{i}").labels({ZONE: f"z{i % 4}"})
                           .capacity({"cpu": "8", "memory": "16Gi", "pods": "50"}).obj())
        tc = TensorCache()
        backlog = _pods(0, 12, spread=True) + _pods(100, 4)
        snap = cache.update_snapshot()
        cluster, changed = tc.cluster_tensors(snap)
        b1 = build_pod_batch(backlog, snap, cluster, reuse=tc, changed_nodes=changed)
        # churn a node, re-solve the SAME backlog
        p = MakePod("bound").labels({"app": "w"}).req({"cpu": "500m"}).obj()
        p.spec.node_name = "n7"
        cache.add_pod(p)
        snap2 = cache.update_snapshot()
        cluster2, changed2 = tc.cluster_tensors(snap2)
        b2 = build_pod_batch(backlog, snap2, cluster2, reuse=tc,
                             changed_nodes=changed2)
        fresh_cluster = build_cluster_tensors(snap2)
        fb = build_pod_batch(backlog, snap2, fresh_cluster)
        np.testing.assert_array_equal(b2.req, fb.req)
        np.testing.assert_array_equal(b2.req_nz, fb.req_nz)
        np.testing.assert_array_equal(b2.class_of_pod, fb.class_of_pod)
        np.testing.assert_array_equal(b2.balanced_active, fb.balanced_active)
        np.testing.assert_array_equal(b2.tables.filter_ok, fb.tables.filter_ok)
        np.testing.assert_array_equal(
            cluster2.selcls_count, fresh_cluster.selcls_count)
        assert b2.req.dtype == np.int32
        # the fast path actually engaged (shares the pod-axis arrays)
        assert b2.class_of_pod is b1.class_of_pod


def test_selector_counts_match_a_per_pod_walk():
    """Selector-class counts are computed once per (namespace, labels,
    terminating) signature; they must equal a plain walk of every bound pod
    against each class's selector: a spread selector counts live pods of its
    namespace only, an anti-affinity term counts its own group only."""
    host = "kubernetes.io/hostname"
    cache = Cache(clock=FakeClock())
    for i in range(12):
        cache.add_node(MakeNode(f"n{i}").labels({ZONE: f"z{i % 3}",
                                                 host: f"n{i}"})
                       .capacity({"cpu": "16", "pods": "110"}).obj())
    bound = []
    for i in range(60):
        mk = MakePod(f"b{i}").req({"cpu": "100m"})
        mk = mk.namespace("other" if i % 7 == 0 else "default")
        mk = mk.labels({"app": "spread", "grp": f"g{i % 5}"} if i % 3
                       else {"app": "web"})
        p = mk.obj()
        if i % 11 == 0:
            p.metadata.deletion_timestamp = 1.0
        p.spec.node_name = f"n{(i * 5) % 12}"
        cache.add_pod(p)
        bound.append(p)
    batch_pods = [MakePod(f"q{i}").labels({"app": "spread", "grp": f"g{i % 5}"})
                  .req({"cpu": "100m"})
                  .topology_spread(1, ZONE, "DoNotSchedule", {"app": "spread"})
                  .pod_anti_affinity(host, {"grp": f"g{i % 5}"}).obj()
                  for i in range(10)]
    snap = cache.update_snapshot()
    cluster = build_cluster_tensors(snap)
    batch = build_pod_batch(batch_pods, snap, cluster)
    names = cluster.node_names

    def walk(pred):
        col = np.zeros(len(names), dtype=np.int32)
        for p in bound:
            if pred(p):
                col[names.index(p.spec.node_name)] += 1
        return col

    spread = walk(lambda p: p.metadata.namespace == "default"
                  and p.metadata.deletion_timestamp is None
                  and p.metadata.labels.get("app") == "spread")
    assert batch.ct_sel.size
    for t in range(batch.ct_sel.size):
        np.testing.assert_array_equal(
            cluster.selcls_count[batch.ct_sel[t]], spread)
    rn_sel, rn_key = batch.ipa.rn_sel, batch.ipa.rn_key
    checked = set()
    for i, pod in enumerate(batch_pods):
        c = int(batch.class_of_pod[i])
        grp = pod.metadata.labels["grp"]
        for j in range(rn_key.shape[1]):
            if rn_key[c, j] < 0:
                continue
            want = walk(lambda p: p.metadata.namespace == "default"
                        and p.metadata.labels.get("grp") == grp)
            np.testing.assert_array_equal(
                cluster.selcls_count[rn_sel[c, j]], want)
            checked.add(grp)
    assert checked == {f"g{k}" for k in range(5)}


# -- dirty-row updates of the HBM mirrors ---------------------------------------

# more nodes than the 2,048-slot bucket: every count below scatters but the last
BIG = 2100
LAST = BIG - 1


class _Mirrored:
    """BIG nodes in a scheduler Cache, tensorized by a TensorCache whose HBM
    mirrors start from a full upload; step(rows) binds one pod to each
    row's node, then refreshes the host tensors (with a spread batch's
    selector-class counts when spread) and the mirrors."""

    def __init__(self, spread=False):
        self.cache = Cache(clock=FakeClock())
        for i in range(BIG):
            self.cache.add_node(
                MakeNode(f"n{i}").labels({ZONE: f"z{i % 4}"})
                .capacity({"cpu": "64", "memory": "256Gi", "pods": "500"})
                .obj())
        self.tc = TensorCache()
        self.batch_pods = _pods(0, 4, spread=True) if spread else None
        self.bound = 0
        self.views = self.step(())
        assert self.tc.upload["mode"] == "full"

    def step(self, rows):
        for i in rows:
            p = MakePod(f"c{self.bound}").labels({"app": "w"}).req(
                {"cpu": "100m"}).obj()
            p.spec.node_name = f"n{i}"
            self.cache.add_pod(p)
            self.bound += 1
        snap = self.cache.update_snapshot()
        self.cluster, changed = self.tc.cluster_tensors(snap)
        if self.batch_pods is not None:
            build_pod_batch(self.batch_pods, snap, self.cluster,
                            reuse=self.tc, changed_nodes=changed)
        self.views = self.tc.device_views(self.cluster)
        return self.views

    def assert_mirrors_equal_host(self):
        for f in TensorCache.DEVICE_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(self.views[f]), getattr(self.cluster, f),
                err_msg=f)
        if self.batch_pods is not None:
            np.testing.assert_array_equal(
                np.asarray(self.views["selcls_count"]),
                self.cluster.selcls_count)


class _Compiles:
    """Counts XLA backend compiles, by function name, while open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        from jax import monitoring

        self.by_fun = {}
        monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, _duration, fun_name="?", **_kw):
        if event == self.EVENT:
            self.by_fun[fun_name] = self.by_fun.get(fun_name, 0) + 1

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on)

    def total(self):
        return sum(self.by_fun.values())


@pytest.mark.parametrize("selcls", [False, True], ids=["rows", "selcls"])
@pytest.mark.parametrize("dirty,mode,bucket", [
    (1, "scatter", 64), (63, "scatter", 64), (64, "scatter", 64),
    (65, "scatter", 128), (1023, "scatter", 1024), (1024, "scatter", 1024),
    (1025, "scatter", 2048), (2049, "full", BIG),
])
def test_mirror_update_parity_across_bucket_edges(dirty, mode, bucket,
                                                  selcls):
    """Each dirty count pads to its bucket (rows padded with the node count,
    dropped on device) and the mirrors equal the host arrays after every
    step; the last row, never dirty here, keeps its value (a -1 pad would
    wrap onto it). A count whose bucket reaches the node count uploads the
    whole arrays. selcls: a spread batch's selector-class counts take the
    same update along their node axis."""
    m = _Mirrored(spread=selcls)
    before = {f: np.asarray(m.views[f])[LAST].copy()
              for f in TensorCache.DEVICE_FIELDS}
    sc_host = m.cluster.selcls_count
    if selcls:
        assert sc_host.size
        sc_before = np.asarray(m.views["selcls_count"]).copy()
    # two steps: a first and a repeated count, on different rows
    for offset in (0, LAST - dirty):
        m.step(range(offset, offset + dirty))
        assert m.tc.upload == {"mode": mode, "rows": dirty, "bucket": bucket}
        m.assert_mirrors_equal_host()
        for f in TensorCache.DEVICE_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(m.views[f])[LAST], before[f], err_msg=f)
    if selcls:
        # the incremental count path kept the host array, so the mirror took
        # the column update (or the full upload), and the counts moved
        assert m.cluster.selcls_count is sc_host
        sc_now = np.asarray(m.views["selcls_count"])
        assert (sc_now != sc_before).any()
        np.testing.assert_array_equal(sc_now[:, LAST], sc_before[:, LAST])


def test_mirror_updates_compile_once_per_bucket():
    """After one update in each bucket, 20 more with distinct dirty counts
    inside those buckets compile nothing; the eager scatter they replace
    compiles again for each new count."""
    m = _Mirrored(spread=True)
    for dirty in (1, 65, 129, 257, 513):  # buckets 64 .. 1024
        m.step(range(dirty))
    counts = [2, 3, 17, 40, 63, 66, 90, 127, 130, 200, 255, 258, 300, 400,
              511, 514, 600, 777, 900, 1023]
    assert len(set(counts)) == 20
    with _Compiles() as seen:
        for dirty in counts:
            m.step(range(dirty))
            assert m.tc.upload["mode"] == "scatter"
    m.assert_mirrors_equal_host()
    assert seen.total() == 0, seen.by_fun
    import jax.numpy as jnp

    host = m.cluster.alloc
    rows = np.arange(1237)
    with _Compiles() as eager:
        jnp.asarray(host).at[rows].set(host[rows]).block_until_ready()
    assert eager.total() > 0


def test_batch_record_carries_the_upload():
    """Each device batch's flight record says how its mirrors were updated:
    the first uploads everything, the next scatters the rows the first
    one's assumes dirtied, and once those placements are confirmed, a batch
    that places nothing leaves the next one nothing dirty."""
    store = APIStore()
    for i in range(80):
        store.create("nodes", MakeNode(f"n{i}").capacity(
            {"cpu": "8", "memory": "16Gi", "pods": "50"}).obj())
    sched = BatchScheduler(store, Framework(default_plugins()),
                           batch_size=64, solver="fast",
                           pipeline_binds=False)
    sched.sync()

    def batch(pods):
        store.create_many("pods", pods, consume=True)
        sched.pump_events()
        assert sched.schedule_batch(timeout=0.0) == len(pods)
        return sched.flightrec.last()["upload"]

    first = batch([MakePod(f"a{i}").req({"cpu": "100m"}).obj()
                   for i in range(8)])
    assert first == {"mode": "full", "rows": 80, "bucket": 80}
    second = batch([MakePod("huge-0").req({"cpu": "64"}).obj()])
    assert second["mode"] == "scatter"
    assert 1 <= second["rows"] <= 8 and second["bucket"] == 64
    # the bind confirmations dirty the same rows once more, at most
    later = [batch([MakePod(f"huge-{k}").req({"cpu": "64"}).obj()])
             for k in range(1, 4)]
    assert later[-1] == later[-2] == {"mode": "none", "rows": 0, "bucket": 0}
    assert all(u["mode"] != "full" and u["rows"] <= 8 for u in later)
