"""TensorCache: generation-diff incremental tensorization parity.

reference: pkg/scheduler/backend/cache/cache.go:186 UpdateSnapshot — only
NodeInfos with a newer generation are re-copied; the TPU build mirrors that
diff into its numpy cluster tensors + PTS count columns. Property: after ANY
sequence of binds/unbinds/node churn, the incremental tensors equal a fresh
full rebuild.
"""

import numpy as np

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
