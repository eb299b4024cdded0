"""Controller + hollow-node tests: the full control loop without machines
(SURVEY.md §4 kubemark tier — real logic, fake CRI)."""

import pytest

from kubernetes_tpu.agent import HollowCluster, HollowKubelet
from kubernetes_tpu.api.workloads import Deployment, ReplicaSet
from kubernetes_tpu.controllers import (
    DeploymentController,
    NodeLifecycleController,
    ReplicaSetController,
)
from kubernetes_tpu.scheduler import Framework, Scheduler
from kubernetes_tpu.scheduler.batch import BatchScheduler
from kubernetes_tpu.scheduler.plugins import default_plugins
from kubernetes_tpu.store import APIStore, NotFoundError
from kubernetes_tpu.testing import MakeNode
from kubernetes_tpu.utils import FakeClock


def make_rs(name="web", replicas=3, labels=None, cpu="100m"):
    labels = labels or {"app": name}
    return ReplicaSet.from_dict({
        "metadata": {"name": name},
        "spec": {
            "replicas": replicas,
            "selector": {"matchLabels": labels},
            "template": {
                "metadata": {"labels": labels},
                "spec": {"containers": [{"name": "c", "resources": {"requests": {"cpu": cpu}}}]},
            },
        },
    })


class TestReplicaSetController:
    def test_scale_up_and_down(self):
        store = APIStore()
        rsc = ReplicaSetController(store)
        rsc.sync_all()
        store.create("replicasets", make_rs(replicas=3))
        rsc.reconcile_once()
        pods, _ = store.list("pods")
        assert len(pods) == 3
        assert all(p.metadata.owner_references[0]["kind"] == "ReplicaSet" for p in pods)

        def scale(rs):
            rs.spec.replicas = 1
            return rs

        store.guaranteed_update("replicasets", "default/web", scale)
        rsc.run_until_stable()
        pods, _ = store.list("pods")
        assert len(pods) == 1

    def test_replaces_deleted_pod(self):
        store = APIStore()
        rsc = ReplicaSetController(store)
        rsc.sync_all()
        store.create("replicasets", make_rs(replicas=2))
        rsc.run_until_stable()
        pods, _ = store.list("pods")
        store.delete("pods", pods[0].key)
        rsc.run_until_stable()
        pods, _ = store.list("pods")
        assert len(pods) == 2

    def test_cascade_delete(self):
        store = APIStore()
        rsc = ReplicaSetController(store)
        rsc.sync_all()
        store.create("replicasets", make_rs(replicas=2))
        rsc.run_until_stable()
        store.delete("replicasets", "default/web")
        rsc.run_until_stable()
        pods, _ = store.list("pods")
        assert pods == []


class TestDeploymentController:
    def test_creates_rs_and_scales(self):
        store = APIStore()
        dc = DeploymentController(store)
        rsc = ReplicaSetController(store)
        dc.sync_all()
        rsc.sync_all()
        store.create("deployments", Deployment.from_dict({
            "metadata": {"name": "api"},
            "spec": {
                "replicas": 4,
                "selector": {"matchLabels": {"app": "api"}},
                "template": {"metadata": {"labels": {"app": "api"}},
                             "spec": {"containers": [{"name": "c"}]}},
            },
        }))
        for _ in range(5):
            dc.reconcile_once()
            rsc.reconcile_once()
        rses, _ = store.list("replicasets")
        assert len(rses) == 1 and rses[0].spec.replicas == 4
        pods, _ = store.list("pods")
        assert len(pods) == 4
        assert all("pod-template-hash" in p.metadata.labels for p in pods)

    def test_rolling_update_creates_new_rs(self):
        store = APIStore()
        dc = DeploymentController(store)
        rsc = ReplicaSetController(store)
        dc.sync_all()
        rsc.sync_all()
        dep = Deployment.from_dict({
            "metadata": {"name": "api"},
            "spec": {
                "replicas": 2,
                "selector": {"matchLabels": {"app": "api"}},
                "template": {"metadata": {"labels": {"app": "api"}},
                             "spec": {"containers": [{"name": "c", "image": "v1"}]}},
            },
        })
        store.create("deployments", dep)
        for _ in range(5):
            dc.reconcile_once()
            rsc.reconcile_once()

        def update(d):
            d.spec.template.spec.containers[0].image = "v2"
            return d

        store.guaranteed_update("deployments", "default/api", update)
        # pods never go Running (no kubelet) -> old RS can shrink only within
        # maxUnavailable; with the default maxUnavailable=0 old stays until new
        # pods run. Mark new pods Running by hand to let the rollout finish.
        for _ in range(10):
            dc.reconcile_once()
            rsc.reconcile_once()
            pods, _ = store.list("pods")
            for p in pods:
                if p.status.phase != "Running":
                    store.update_pod_status(p.metadata.namespace, p.metadata.name,
                                            lambda st: setattr(st, "phase", "Running"))
        rses, _ = store.list("replicasets")
        by_image = {rs.spec.template.spec.containers[0].image: rs.spec.replicas for rs in rses}
        assert by_image.get("v2") == 2
        assert by_image.get("v1", 0) == 0


class TestNodeLifecycle:
    def test_unhealthy_node_tainted_and_evicted(self):
        clock = FakeClock(start=100.0)
        store = APIStore()
        kubelet = HollowKubelet(store, "n0", clock=clock)
        kubelet.register()
        nlc = NodeLifecycleController(store, clock=clock, grace_period=40.0)
        nlc.sync_all()
        nlc.monitor()
        node = store.get("nodes", "n0")
        assert not any(t.key == "node.kubernetes.io/not-ready" for t in node.spec.taints)

        # bind a pod, then stop heartbeating past grace
        from kubernetes_tpu.testing import MakePod

        store.create("pods", MakePod("victim").req({"cpu": "1"}).obj())
        store.bind("default", "victim", "n0")
        clock.step(41)
        nlc.monitor()
        node = store.get("nodes", "n0")
        assert any(t.key == "node.kubernetes.io/not-ready" and t.effect == "NoExecute"
                   for t in node.spec.taints)
        conds = {c.type: c.status for c in node.status.conditions}
        assert conds["Ready"] == "False"
        with pytest.raises(NotFoundError):
            store.get("pods", "default/victim")

        # heartbeat resumes -> taint cleared
        kubelet.heartbeat()
        nlc.monitor()
        node = store.get("nodes", "n0")
        assert not any(t.key == "node.kubernetes.io/not-ready" for t in node.spec.taints)
        conds = {c.type: c.status for c in node.status.conditions}
        assert conds["Ready"] == "True"


class TestFullControlLoop:
    def test_deployment_to_running_pods_via_hollow_nodes(self):
        """The whole system: Deployment -> RS -> pods -> scheduler binds ->
        hollow kubelets run them -> status flows back to RS/Deployment."""
        store = APIStore()
        cluster = HollowCluster(store, n_nodes=4, zone_count=2)
        cluster.register_all()
        sched = BatchScheduler(store, Framework(default_plugins()), solver="auto")
        sched.sync()
        dc = DeploymentController(store)
        rsc = ReplicaSetController(store)
        dc.sync_all()
        rsc.sync_all()

        store.create("deployments", Deployment.from_dict({
            "metadata": {"name": "web"},
            "spec": {
                "replicas": 8,
                "selector": {"matchLabels": {"app": "web"}},
                "template": {"metadata": {"labels": {"app": "web"}},
                             "spec": {"containers": [{"name": "c", "resources": {
                                 "requests": {"cpu": "500m"}}}]}},
            },
        }))
        for _ in range(8):
            dc.reconcile_once()
            rsc.reconcile_once()
            sched.run_until_idle()
            cluster.pump_all()
        pods, _ = store.list("pods")
        assert len(pods) == 8
        assert all(p.spec.node_name for p in pods)
        assert all(p.status.phase == "Running" for p in pods)
        dep = store.get("deployments", "default/web")
        assert dep.status.ready_replicas == 8

    def test_node_failure_reschedules_pods(self):
        """Failure detection end to end: node dies -> taint+evict -> RS
        replaces -> scheduler binds replacements elsewhere."""
        clock = FakeClock(start=0.0)
        store = APIStore()
        kubelets = [HollowKubelet(store, f"n{i}", clock=clock) for i in range(3)]
        for k in kubelets:
            k.register()
        sched = Scheduler(store, Framework(default_plugins()))
        sched.sync()
        rsc = ReplicaSetController(store)
        rsc.sync_all()
        nlc = NodeLifecycleController(store, clock=clock, grace_period=40.0)
        nlc.sync_all()

        store.create("replicasets", make_rs(replicas=3))
        for _ in range(4):
            rsc.reconcile_once()
            sched.run_until_idle()
            for k in kubelets:
                k.pump()
        pods, _ = store.list("pods")
        assert all(p.spec.node_name for p in pods)

        # n0 dies: others keep heartbeating
        clock.step(41)
        for k in kubelets[1:]:
            k.heartbeat()
        nlc.monitor()
        for _ in range(6):
            rsc.reconcile_once()
            sched.run_until_idle()
            for k in kubelets[1:]:
                k.pump()
        pods, _ = store.list("pods")
        assert len(pods) == 3
        assert all(p.spec.node_name in ("n1", "n2") for p in pods)


def test_deployment_scale_down():
    """Scaling a deployment down must shrink the current ReplicaSet."""
    store = APIStore()
    dc, rsc = DeploymentController(store), ReplicaSetController(store)
    dc.sync_all()
    rsc.sync_all()
    store.create("deployments", Deployment.from_dict({
        "metadata": {"name": "web"},
        "spec": {"replicas": 6, "selector": {"matchLabels": {"app": "web"}},
                 "template": {"metadata": {"labels": {"app": "web"}},
                              "spec": {"containers": [{"name": "c"}]}}},
    }))
    for _ in range(5):
        dc.reconcile_once()
        rsc.reconcile_once()
    assert len(store.list("pods")[0]) == 6

    def scale(d):
        d.spec.replicas = 2
        return d

    store.guaranteed_update("deployments", "default/web", scale)
    for _ in range(5):
        dc.reconcile_once()
        rsc.reconcile_once()
    assert len(store.list("pods")[0]) == 2


def test_sync_all_relists_when_a_burst_outruns_the_watch_buffer():
    """A burst of more events than the watch buffer holds, landing between a
    controller's LIST and its WATCH, makes the resume too old. sync_all must
    list again and watch, not raise: raised in a controller's thread (or the
    leader's start callback) it ended the controller on the chip smoke's
    100k-pod burst."""
    from kubernetes_tpu.store import Watch
    from kubernetes_tpu.testing import MakePod

    store = APIStore()
    rsc = ReplicaSetController(store)
    orig = store.list_many
    calls = []

    def list_then_burst(kinds):
        out = orig(kinds)
        calls.append(out[1])
        if len(calls) == 1:
            store.create_many("pods", (MakePod(f"b-{i}").obj() for i in
                                       range(Watch.DEFAULT_MAXSIZE)),
                              consume=True)
        return out

    store.list_many = list_then_burst
    store.create("replicasets", make_rs(replicas=1))
    rsc.sync_all()
    assert len(calls) == 2 and calls[1] > calls[0]
    assert rsc._watch is not None and not rsc._watch.terminated
    assert rsc.relists == 1 and rsc.reconcile_stats()["relists"] == 1


def test_reconcile_pumps_between_bounded_drains():
    """reconcile_once syncs at most DRAIN_KEYS keys before it pumps the
    watch again: a drain of every dirty key at 10k nodes outlasted the watch
    buffer under lease renewals and evicted the watch."""
    from kubernetes_tpu.controllers.base import Controller
    from kubernetes_tpu.testing import MakePod

    synced = []

    class PodKeys(Controller):
        watch_kinds = ("pods",)

        def key_of_object(self, kind, obj):
            return obj.key

        def sync(self, key):
            synced.append(key)

    store = APIStore()
    ctl = PodKeys(store, telemetry=False)
    ctl.sync_all()
    n = 2 * Controller.DRAIN_KEYS + 5
    store.create_many("pods", (MakePod(f"b-{i}").obj() for i in range(n)),
                      consume=True)
    assert ctl.reconcile_once() == Controller.DRAIN_KEYS
    ctl.run_until_stable()
    assert len(synced) == n and not ctl._dirty


def test_burst_larger_than_the_watch_buffer_forces_no_relist():
    """Controllers watch coalesced: a create_many chunk is one buffered item,
    so a burst of several buffers' worth of pods between two pumps neither
    evicts the watch nor relists, and every pod still reaches the key
    function."""
    from kubernetes_tpu.controllers.base import Controller
    from kubernetes_tpu.store import Watch
    from kubernetes_tpu.testing import MakePod

    class PodKeys(Controller):
        watch_kinds = ("pods",)

        def key_of_object(self, kind, obj):
            return obj.key

        def sync(self, key):
            pass

    store = APIStore()
    ctl = PodKeys(store, telemetry=False)
    ctl.sync_all()
    n = 2 * Watch.DEFAULT_MAXSIZE + 7
    for lo in range(0, n, 5000):
        store.create_many("pods", (MakePod(f"b-{i}").obj()
                                   for i in range(lo, min(lo + 5000, n))),
                          consume=True)
    assert ctl.pump() == n
    assert not ctl._watch.terminated and ctl.relists == 0
    assert len(ctl._dirty) == n
