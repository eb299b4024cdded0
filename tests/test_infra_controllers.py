"""GC, namespace, resourcequota, endpointslice, tainteviction, HPA controller
tests — mirrors pkg/controller/{garbagecollector,namespace,resourcequota,
endpointslice,tainteviction,podautoscaler} unit tests in compressed form."""

from kubernetes_tpu.api.networking import EndpointSlice, Service
from kubernetes_tpu.api.policy import HorizontalPodAutoscaler, ResourceQuota
from kubernetes_tpu.api.types import Namespace, ObjectMeta, Taint, new_uid
from kubernetes_tpu.api.workloads import ReplicaSet
from kubernetes_tpu.controllers import (
    EndpointSliceController,
    GarbageCollector,
    HorizontalPodAutoscalerController,
    NamespaceController,
    ReplicaSetController,
    ResourceQuotaController,
    TaintEvictionController,
)
from kubernetes_tpu.store import APIStore, NotFoundError
from kubernetes_tpu.testing import MakeNode, MakePod
from kubernetes_tpu.utils import FakeClock

import pytest


def set_phase(store, key, phase):
    def mutate(p):
        p.status.phase = phase
        return p

    store.guaranteed_update("pods", key, mutate)


class TestGarbageCollector:
    def test_orphaned_pod_collected(self):
        store = APIStore()
        rs = ReplicaSet.from_dict({"metadata": {"name": "rs"}, "spec": {
            "replicas": 1, "template": {"spec": {"containers": [{"name": "c"}]}}}})
        rs.metadata.uid = new_uid()
        store.create("replicasets", rs)
        pod = MakePod("owned").obj()
        pod.metadata.owner_references = [{"kind": "ReplicaSet", "name": "rs",
                                          "uid": rs.metadata.uid, "controller": True}]
        store.create("pods", pod)
        free = MakePod("free").obj()
        store.create("pods", free)
        gc = GarbageCollector(store, clock=FakeClock())
        assert gc.sweep() == 0  # owner alive: nothing collected
        store.delete("replicasets", "default/rs")
        assert gc.sweep() == 1
        with pytest.raises(NotFoundError):
            store.get("pods", "default/owned")
        assert store.get("pods", "default/free")  # ownerless object untouched

    def test_uid_mismatch_is_orphan(self):
        store = APIStore()
        rs = ReplicaSet.from_dict({"metadata": {"name": "rs"}, "spec": {}})
        rs.metadata.uid = new_uid()
        store.create("replicasets", rs)
        pod = MakePod("stale").obj()
        pod.metadata.owner_references = [{"kind": "ReplicaSet", "name": "rs",
                                          "uid": "old-uid", "controller": True}]
        store.create("pods", pod)
        gc = GarbageCollector(store, clock=FakeClock())
        assert gc.sweep() == 1  # recreated owner does not adopt


class TestNamespaceController:
    def test_terminating_namespace_drained(self):
        store = APIStore()
        store.create("namespaces", Namespace(metadata=ObjectMeta(name="team-a")))
        store.create("pods", MakePod("p1", namespace="team-a").obj())
        store.create("pods", MakePod("p2", namespace="team-a").obj())
        store.create("pods", MakePod("other", namespace="default").obj())
        ctl = NamespaceController(store, clock=FakeClock())
        ctl.sync_all()
        ctl.mark_terminating("team-a")
        ctl.process()
        ctl.process()  # second pass observes emptiness and removes the ns
        assert not store.list("pods", lambda p: p.metadata.namespace == "team-a")[0]
        with pytest.raises(NotFoundError):
            store.get("namespaces", "team-a")
        assert store.get("pods", "default/other")


class TestResourceQuota:
    def test_usage_recalculated(self):
        store = APIStore()
        quota = ResourceQuota.from_dict({
            "metadata": {"name": "q", "namespace": "default"},
            "spec": {"hard": {"requests.cpu": "4", "pods": "10",
                              "count/replicasets": "5"}},
        })
        store.create("resourcequotas", quota)
        store.create("pods", MakePod("a").req({"cpu": "500m"}).obj())
        store.create("pods", MakePod("b").req({"cpu": "250m"}).obj())
        rs = ReplicaSet.from_dict({"metadata": {"name": "rs"}, "spec": {}})
        store.create("replicasets", rs)
        ctl = ResourceQuotaController(store, clock=FakeClock())
        ctl.sync_all()
        ctl.process()
        q = store.get("resourcequotas", "default/q")
        assert q.used["requests.cpu"] == "750m"
        assert q.used["pods"] == "2"
        assert q.used["count/replicasets"] == "1"

    def test_pod_deletion_updates_usage(self):
        store = APIStore()
        store.create("resourcequotas", ResourceQuota.from_dict({
            "metadata": {"name": "q"}, "spec": {"hard": {"pods": "10"}}}))
        store.create("pods", MakePod("a").obj())
        ctl = ResourceQuotaController(store, clock=FakeClock())
        ctl.sync_all()
        ctl.process()
        store.delete("pods", "default/a")
        ctl.reconcile_once()
        assert store.get("resourcequotas", "default/q").used["pods"] == "0"


class TestEndpointSlice:
    def _setup(self):
        store = APIStore()
        svc = Service.from_dict({
            "metadata": {"name": "web"},
            "spec": {"selector": {"app": "web"},
                     "ports": [{"name": "http", "port": 80, "targetPort": 8080}]},
        })
        svc.metadata.uid = new_uid()
        store.create("services", svc)
        ctl = EndpointSliceController(store, clock=FakeClock())
        ctl.sync_all()
        return store, ctl

    def test_slice_tracks_ready_pods(self):
        store, ctl = self._setup()
        for i in range(3):
            pod = MakePod(f"w{i}").labels({"app": "web"}).node(f"n{i}").obj()
            store.create("pods", pod)
        set_phase(store, "default/w0", "Running")
        set_phase(store, "default/w1", "Running")
        ctl.reconcile_once()
        es = store.get("endpointslices", "default/web-0")
        assert len(es.endpoints) == 3
        ready = {e.target_ref: e.ready for e in es.endpoints}
        assert ready == {"default/w0": True, "default/w1": True, "default/w2": False}
        assert es.ports[0].port == 80
        assert all(e.addresses[0].startswith("10.") for e in es.endpoints)

    def test_non_matching_and_unscheduled_excluded(self):
        store, ctl = self._setup()
        store.create("pods", MakePod("other").labels({"app": "db"}).node("n1").obj())
        store.create("pods", MakePod("pending").labels({"app": "web"}).obj())
        ctl.reconcile_once()
        es = store.get("endpointslices", "default/web-0")
        assert es.endpoints == []

    def test_service_deletion_removes_slices(self):
        store, ctl = self._setup()
        ctl.reconcile_once()
        assert store.get("endpointslices", "default/web-0")
        store.delete("services", "default/web")
        ctl.reconcile_once()
        with pytest.raises(NotFoundError):
            store.get("endpointslices", "default/web-0")

    def test_slices_capped_and_chunked(self):
        store, ctl = self._setup()
        ctl.max_endpoints = 2
        for i in range(5):
            store.create("pods",
                         MakePod(f"w{i}").labels({"app": "web"}).node("n").obj())
        ctl.reconcile_once()
        slices, _ = store.list("endpointslices")
        assert sorted(s.metadata.name for s in slices) == ["web-0", "web-1", "web-2"]
        assert sum(len(s.endpoints) for s in slices) == 5

    def test_many_slices_scale_down_keeps_low_ordinals(self):
        """11 slices shrunk to 2: lexicographic ordering (web-10 < web-2) must
        not confuse the reconciler into deleting live slices."""
        store, ctl = self._setup()
        ctl.max_endpoints = 1
        for i in range(11):
            store.create("pods",
                         MakePod(f"w{i:02d}").labels({"app": "web"}).node("n").obj())
        ctl.reconcile_once()
        assert len(store.list("endpointslices")[0]) == 11
        for i in range(2, 11):
            store.delete("pods", f"default/w{i:02d}")
        ctl.reconcile_once()
        slices, _ = store.list("endpointslices")
        assert sorted(s.metadata.name for s in slices) == ["web-0", "web-1"]
        assert sum(len(s.endpoints) for s in slices) == 2


class TestDisruptionController:
    """Mirrors pkg/controller/disruption trySync: disruptionsAllowed =
    max(0, currentHealthy - desiredHealthy)."""

    def _setup(self):
        from kubernetes_tpu.controllers import DisruptionController

        store = APIStore()
        ctl = DisruptionController(store, clock=FakeClock())
        ctl.sync_all()
        return store, ctl

    def _pdb(self, store, name="pdb", min_available=None, max_unavailable=None,
             labels=None):
        from kubernetes_tpu.api.policy import PodDisruptionBudget
        from kubernetes_tpu.api.labels import Selector
        from kubernetes_tpu.api.types import ObjectMeta

        store.create("poddisruptionbudgets", PodDisruptionBudget(
            metadata=ObjectMeta(name=name, namespace="default"),
            selector=Selector.from_match_labels(labels or {"app": "web"}),
            min_available=min_available, max_unavailable=max_unavailable))

    def test_min_available_absolute(self):
        store, ctl = self._setup()
        for i in range(5):
            store.create("pods", MakePod(f"w{i}").labels({"app": "web"})
                         .node("n1").obj())
        self._pdb(store, min_available=3)
        ctl.reconcile_once()
        pdb = store.get("poddisruptionbudgets", "default/pdb")
        assert pdb.disruptions_allowed == 2

    def test_max_unavailable_percent(self):
        store, ctl = self._setup()
        for i in range(10):
            store.create("pods", MakePod(f"w{i}").labels({"app": "web"})
                         .node("n1").obj())
        self._pdb(store, max_unavailable="20%")
        ctl.reconcile_once()
        # desired = 10 - ceil(20% of 10) = 8 -> allowed 2
        assert store.get("poddisruptionbudgets", "default/pdb").disruptions_allowed == 2

    def test_unbound_pods_not_healthy(self):
        store, ctl = self._setup()
        for i in range(3):
            store.create("pods", MakePod(f"w{i}").labels({"app": "web"}).obj())
        self._pdb(store, min_available=1)
        ctl.reconcile_once()
        # 0 healthy (none bound): allowed stays 0
        assert store.get("poddisruptionbudgets", "default/pdb").disruptions_allowed == 0

    def test_pod_events_retrigger(self):
        store, ctl = self._setup()
        self._pdb(store, min_available=1)
        ctl.reconcile_once()
        assert store.get("poddisruptionbudgets", "default/pdb").disruptions_allowed == 0
        for i in range(2):
            store.create("pods", MakePod(f"w{i}").labels({"app": "web"})
                         .node("n1").obj())
        ctl.reconcile_once()
        assert store.get("poddisruptionbudgets", "default/pdb").disruptions_allowed == 1


class TestTaintEviction:
    def _setup(self):
        store = APIStore()
        clock = FakeClock(start=100.0)
        store.create("nodes", MakeNode("n1").obj())
        ctl = TaintEvictionController(store, clock=clock)
        ctl.sync_all()
        return store, clock, ctl

    def _taint_node(self, store, name="n1"):
        def mutate(n):
            n.spec.taints.append(Taint(key="node.kubernetes.io/unreachable",
                                       effect="NoExecute"))
            return n

        store.guaranteed_update("nodes", name, mutate)

    def test_untolerated_pod_evicted_immediately(self):
        store, clock, ctl = self._setup()
        store.create("pods", MakePod("p").node("n1").obj())
        self._taint_node(store)
        ctl.reconcile_once()
        with pytest.raises(NotFoundError):
            store.get("pods", "default/p")

    def test_toleration_seconds_delays_eviction(self):
        store, clock, ctl = self._setup()
        pod = MakePod("p").node("n1").toleration(
            "node.kubernetes.io/unreachable", operator="Exists",
            effect="NoExecute").obj()
        pod.spec.tolerations[0] = type(pod.spec.tolerations[0])(
            key="node.kubernetes.io/unreachable", operator="Exists",
            effect="NoExecute", toleration_seconds=30)
        store.create("pods", pod)
        self._taint_node(store)
        ctl.reconcile_once()
        assert store.get("pods", "default/p")  # still tolerated
        clock.step(31)
        ctl.tick()
        with pytest.raises(NotFoundError):
            store.get("pods", "default/p")

    def test_forever_toleration_never_evicts(self):
        store, clock, ctl = self._setup()
        pod = MakePod("p").node("n1").toleration(
            "node.kubernetes.io/unreachable", operator="Exists",
            effect="NoExecute").obj()
        store.create("pods", pod)
        self._taint_node(store)
        ctl.reconcile_once()
        clock.step(10_000)
        ctl.tick()
        assert store.get("pods", "default/p")

    def test_additional_taint_tightens_deadline(self):
        # a second NoExecute taint with smaller tolerationSeconds must replace
        # the stale longer deadline (tainteviction timed-worker semantics)
        store, clock, ctl = self._setup()
        pod = MakePod("p").node("n1").obj()
        T = Taint
        from kubernetes_tpu.api.types import Toleration

        pod.spec.tolerations = [
            Toleration(key="node.kubernetes.io/unreachable", operator="Exists",
                       effect="NoExecute", toleration_seconds=600),
            Toleration(key="node.kubernetes.io/memory-pressure", operator="Exists",
                       effect="NoExecute", toleration_seconds=5),
        ]
        store.create("pods", pod)
        self._taint_node(store)  # unreachable: 600s countdown
        ctl.reconcile_once()
        assert store.get("pods", "default/p")

        def add_second(n):
            n.spec.taints.append(T(key="node.kubernetes.io/memory-pressure",
                                   effect="NoExecute"))
            return n

        store.guaranteed_update("nodes", "n1", add_second)
        ctl.reconcile_once()
        clock.step(6)  # past the tightened 5s deadline, far before 600s
        ctl.tick()
        with pytest.raises(NotFoundError):
            store.get("pods", "default/p")

    def test_removing_tight_taint_restores_longer_deadline(self):
        # inverse of the tighten case: dropping the 5s taint while the 600s
        # taint remains must reschedule on the longer deadline
        store, clock, ctl = self._setup()
        pod = MakePod("p").node("n1").obj()
        from kubernetes_tpu.api.types import Toleration

        pod.spec.tolerations = [
            Toleration(key="node.kubernetes.io/unreachable", operator="Exists",
                       effect="NoExecute", toleration_seconds=600),
            Toleration(key="node.kubernetes.io/memory-pressure", operator="Exists",
                       effect="NoExecute", toleration_seconds=5),
        ]
        store.create("pods", pod)

        def add_both(n):
            n.spec.taints = [
                Taint(key="node.kubernetes.io/unreachable", effect="NoExecute"),
                Taint(key="node.kubernetes.io/memory-pressure", effect="NoExecute"),
            ]
            return n

        store.guaranteed_update("nodes", "n1", add_both)
        ctl.reconcile_once()

        def drop_tight(n):
            n.spec.taints = [t for t in n.spec.taints
                             if t.key == "node.kubernetes.io/unreachable"]
            return n

        store.guaranteed_update("nodes", "n1", drop_tight)
        ctl.reconcile_once()
        clock.step(10)  # past the stale 5s deadline
        ctl.tick()
        assert store.get("pods", "default/p")  # survives on the 600s countdown
        clock.step(600)
        ctl.tick()
        with pytest.raises(NotFoundError):
            store.get("pods", "default/p")

    def test_taint_removed_cancels_pending_eviction(self):
        store, clock, ctl = self._setup()
        pod = MakePod("p").node("n1").obj()
        pod.spec.tolerations = [type(pod.spec.tolerations[0] if pod.spec.tolerations
                                     else __import__("kubernetes_tpu.api.types",
                                                     fromlist=["Toleration"]).Toleration())(
            key="node.kubernetes.io/unreachable", operator="Exists",
            effect="NoExecute", toleration_seconds=60)]
        store.create("pods", pod)
        self._taint_node(store)
        ctl.reconcile_once()

        def clear(n):
            n.spec.taints = []
            return n

        store.guaranteed_update("nodes", "n1", clear)
        ctl.reconcile_once()
        clock.step(120)
        ctl.tick()
        assert store.get("pods", "default/p")

    def test_binds_on_untainted_nodes_are_not_synced(self):
        """A pod bound to a node with no NoExecute taint has nothing to count
        down: it gets no key (a pod read and a node read per bind), while a
        pod bound to an already tainted node is still examined."""
        store, clock, ctl = self._setup()
        store.create("nodes", MakeNode("n2").obj())
        store.create("pods", MakePod("calm").node("n1").obj())
        self._taint_node(store, "n2")
        store.create("pods", MakePod("doomed").node("n2").obj())
        ctl.pump()
        assert "pod|default/calm" not in ctl._dirty
        assert "pod|default/doomed" in ctl._dirty
        ctl.process()
        with pytest.raises(NotFoundError):
            store.get("pods", "default/doomed")
        assert store.get("pods", "default/calm")

    def test_relist_of_untainted_nodes_lists_no_pods(self):
        """A relist marks every node; an untainted node's sync must not LIST
        the cluster's pods (O(nodes x pods): at 10k nodes / 100k pods it held
        the interpreter for minutes and starved the scheduler)."""
        store, clock, ctl = self._setup()
        for i in range(50):
            store.create("nodes", MakeNode(f"m{i}").obj())
            store.create("pods", MakePod(f"p{i}").node(f"m{i}").obj())
        ctl.reconcile_once()
        listed = []
        orig = store.list

        def spy(kind, *a, **kw):
            listed.append(kind)
            return orig(kind, *a, **kw)

        store.list = spy
        ctl.sync_all()
        ctl.reconcile_once()
        assert "pods" not in listed


class TestHPA:
    def _setup(self, target=50, minr=1, maxr=10):
        store = APIStore()
        clock = FakeClock(start=1000.0)
        rs = ReplicaSet.from_dict({
            "metadata": {"name": "web"},
            "spec": {"replicas": 2,
                     "selector": {"matchLabels": {"app": "web"}},
                     "template": {"metadata": {"labels": {"app": "web"}},
                                  "spec": {"containers": [{"name": "c"}]}}},
        })
        rs.metadata.uid = new_uid()
        store.create("replicasets", rs)
        hpa = HorizontalPodAutoscaler.from_dict({
            "metadata": {"name": "web"},
            "spec": {"scaleTargetRef": {"kind": "ReplicaSet", "name": "web"},
                     "minReplicas": minr, "maxReplicas": maxr,
                     "targetCPUUtilizationPercentage": target},
        })
        store.create("horizontalpodautoscalers", hpa)
        ctl = HorizontalPodAutoscalerController(store, clock=clock,
                                                downscale_stabilization=300)
        ctl.sync_all()
        return store, clock, ctl

    def _add_pod(self, store, name, request="1", usage_milli=500):
        pod = (MakePod(name).labels({"app": "web"}).req({"cpu": request})
               .node("n1").phase("Running").obj())
        pod.metadata.annotations["metrics.k8s.io/cpu-usage"] = f"{usage_milli}m"
        store.create("pods", pod)

    def test_scale_up_on_high_utilization(self):
        store, clock, ctl = self._setup(target=50)
        self._add_pod(store, "w0", usage_milli=900)  # 90% of 1 cpu, target 50%
        self._add_pod(store, "w1", usage_milli=900)
        ctl.resync()
        rs = store.get("replicasets", "default/web")
        assert rs.spec.replicas == 4  # ceil(2 * 0.9/0.5)
        hpa = store.get("horizontalpodautoscalers", "default/web")
        assert hpa.desired_replicas == 4

    def test_within_tolerance_no_change(self):
        store, clock, ctl = self._setup(target=50)
        self._add_pod(store, "w0", usage_milli=520)
        self._add_pod(store, "w1", usage_milli=480)
        ctl.resync()
        assert store.get("replicasets", "default/web").spec.replicas == 2

    def test_scale_down_stabilization(self):
        store, clock, ctl = self._setup(target=50)
        self._add_pod(store, "w0", usage_milli=100)
        self._add_pod(store, "w1", usage_milli=100)

        def stamp(h):
            h.last_scale_time = clock.now()
            return h

        store.guaranteed_update("horizontalpodautoscalers", "default/web", stamp)
        ctl.resync()
        assert store.get("replicasets", "default/web").spec.replicas == 2  # held
        clock.step(301)
        ctl.resync()
        assert store.get("replicasets", "default/web").spec.replicas == 1

    def test_bounded_by_max(self):
        store, clock, ctl = self._setup(target=10, maxr=3)
        self._add_pod(store, "w0", usage_milli=1000)
        self._add_pod(store, "w1", usage_milli=1000)
        ctl.resync()
        assert store.get("replicasets", "default/web").spec.replicas == 3


class TestHPAEndToEnd:
    def test_hpa_drives_replicaset_controller(self):
        """HPA scales the ReplicaSet spec; the RS controller materializes pods."""
        store = APIStore()
        clock = FakeClock(start=0.0)
        rs = ReplicaSet.from_dict({
            "metadata": {"name": "web"},
            "spec": {"replicas": 1,
                     "selector": {"matchLabels": {"app": "web"}},
                     "template": {"metadata": {"labels": {"app": "web"}},
                                  "spec": {"containers": [{"name": "c"}]}}},
        })
        rs.metadata.uid = new_uid()
        store.create("replicasets", rs)
        store.create("horizontalpodautoscalers", HorizontalPodAutoscaler.from_dict({
            "metadata": {"name": "web"},
            "spec": {"scaleTargetRef": {"kind": "ReplicaSet", "name": "web"},
                     "maxReplicas": 5, "targetCPUUtilizationPercentage": 50},
        }))
        rs_ctl = ReplicaSetController(store, clock=clock)
        hpa_ctl = HorizontalPodAutoscalerController(store, clock=clock)
        rs_ctl.sync_all()
        hpa_ctl.sync_all()
        rs_ctl.process()
        pods, _ = store.list("pods")
        assert len(pods) == 1

        def hot(p):
            p.metadata.annotations["metrics.k8s.io/cpu-usage"] = "1000m"
            p.spec.containers[0].resources = {"requests": {"cpu": "1"}}
            p.status.phase = "Running"
            return p

        store.guaranteed_update("pods", pods[0].key, hot)
        hpa_ctl.resync()
        rs_ctl.reconcile_once()
        assert len(store.list("pods")[0]) == 2  # ceil(1 * 100%/50%) = 2
