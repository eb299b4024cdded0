"""Bring up the deployment a cell runs against: the served control plane
(`kadm.init_control_plane`, with every controller it starts), the cluster's
nodes with a renewed Lease each and the labels the configuration gives them
(`reference.node_labels`), and pods built from the configuration's
templates.

A template states, under the Kubernetes field names, a pod's `requests`,
`labels`, `topologySpreadConstraints` and `affinity` (node affinity, pod
affinity and anti-affinity, required and preferred), so upstream's template
YAML copies into JSON as it stands. A field not in TEMPLATE_FIELDS is an
error when the configuration is loaded (`catalog.load_cell`), never
dropped.

Nothing here touches a device at import: the chip belongs to one process.
"""

from __future__ import annotations

import threading
import time

from benchmark.reference import node_labels

LEASE_RENEW_S, LEASE_SLICES = 10.0, 20  # kubelet's node-lease renew interval
PROBE_NAMESPACE = "probe"


def node_names(config: dict, cut: int) -> list:
    return [f"node-{i}" for i in range(max(1, config["nodes"]["count"] // cut))]


def make_nodes(config: dict, names: list) -> list:
    from kubernetes_tpu.testing import MakeNode

    cap = config["nodes"]["capacity"]
    labels = node_labels(config, names)
    return [MakeNode(n).labels(labels[n]).capacity(dict(cap)).obj()
            for n in names]


# what a template may state: a key maps to the fields it may hold (None: a
# value taken whole); a list's one entry is what each of its items may hold
_SELECTOR = {"matchLabels": None, "matchExpressions": [
    {"key": None, "operator": None, "values": None}]}
_NODE_TERM = {"matchExpressions": _SELECTOR["matchExpressions"],
              "matchFields": _SELECTOR["matchExpressions"]}
_POD_TERM = {"topologyKey": None, "labelSelector": _SELECTOR,
             "namespaces": None, "namespaceSelector": _SELECTOR,
             "matchLabelKeys": None}
_POD_AFFINITY = {
    "requiredDuringSchedulingIgnoredDuringExecution": [_POD_TERM],
    "preferredDuringSchedulingIgnoredDuringExecution": [
        {"weight": None, "podAffinityTerm": _POD_TERM}]}
TEMPLATE_FIELDS = {
    "requests": None,
    "labels": None,
    "topologySpreadConstraints": [{
        "maxSkew": None, "topologyKey": None, "whenUnsatisfiable": None,
        "labelSelector": _SELECTOR, "minDomains": None,
        "nodeAffinityPolicy": None, "nodeTaintsPolicy": None,
        "matchLabelKeys": None}],
    "affinity": {
        "nodeAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": {
                "nodeSelectorTerms": [_NODE_TERM]},
            "preferredDuringSchedulingIgnoredDuringExecution": [
                {"weight": None, "preference": _NODE_TERM}]},
        "podAffinity": _POD_AFFINITY,
        "podAntiAffinity": _POD_AFFINITY},
}


def check_template(value, fields=TEMPLATE_FIELDS, where: str = "template") -> None:
    """Raise ValueError naming the first field the pod factory does not
    know."""
    if fields is None:
        return
    if isinstance(fields, list):
        if not isinstance(value, list):
            raise ValueError(f"{where}: a list is expected")
        for i, v in enumerate(value):
            check_template(v, fields[0], f"{where}[{i}]")
        return
    if not isinstance(value, dict):
        raise ValueError(f"{where}: an object is expected")
    for k, v in value.items():
        if k not in fields:
            raise ValueError(f"{where}.{k}: a field the pod factory does not "
                             f"know (it knows {sorted(fields)})")
        check_template(v, fields[k], f"{where}.{k}")


class PodFactory:
    """Pods of one template, as many as asked, each a cheap structural clone
    of one built template (the store treats the shared deep members as
    read-only). Names and uids come from the caller, so a seed fixes them."""

    def __init__(self, template: dict, namespace: str = "default"):
        from kubernetes_tpu.api.types import Affinity, TopologySpreadConstraint
        from kubernetes_tpu.testing import MakePod

        mp = MakePod("template", namespace).labels(dict(template.get("labels") or {}))
        mp.req(dict(template["requests"]))
        pod = mp.obj()
        if "topologySpreadConstraints" in template:
            pod.spec.topology_spread_constraints = [
                TopologySpreadConstraint.from_dict(c)
                for c in template["topologySpreadConstraints"]]
        if "affinity" in template:
            pod.spec.affinity = Affinity.from_dict(template["affinity"])
        self._template = pod

    def make(self, names: list, uid_prefix: str) -> list:
        from kubernetes_tpu.store.store import pod_structural_clone

        out = []
        for name in names:
            p = pod_structural_clone(self._template)
            p.metadata.name = name
            p.metadata.uid = f"{uid_prefix}-{name}"
            out.append(p)
        return out


class Kubelets:
    """What each node's kubelet does for the control plane: hold a Lease in
    kube-node-lease and renew it every LEASE_RENEW_S. Without it the node
    lifecycle controller taints every node not-ready after its grace period
    and nothing schedules. The renewals spread over the interval (one of
    LEASE_SLICES slices per tick), as real kubelets' do, and ticks keep a
    fixed cadence: these kubelets share the interpreter with the control
    plane, and a tick slowed by it must not push the next one later."""

    def __init__(self, store, names):
        from kubernetes_tpu.api.types import ObjectMeta
        from kubernetes_tpu.api.workloads import Lease

        self.store = store
        self.leases = [Lease(
            metadata=ObjectMeta(name=n, namespace="kube-node-lease"),
            holder_identity=n) for n in names]
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-kubelets")

    def start(self) -> "Kubelets":
        from kubernetes_tpu.utils import Clock

        now = Clock().now()
        for lease in self.leases:
            lease.acquire_time = lease.renew_time = now
        self.store.create_many("leases", self.leases)
        self._thread.start()
        return self

    def _loop(self) -> None:
        from kubernetes_tpu.utils import Clock

        clock = Clock()
        tick = LEASE_RENEW_S / LEASE_SLICES
        due = clock.now() + tick
        k = 0
        while not self._stop.wait(max(0.0, due - clock.now())):
            due += tick
            for lease in self.leases[k::LEASE_SLICES]:  # update() stores a copy
                lease.renew_time = clock.now()
                self.store.update("leases", lease, check_rv=False)
            k = (k + 1) % LEASE_SLICES

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=60)


class Deployment:
    """The control plane and the cluster of one run. `up()` returns once the
    leader runs the scheduler and every controller, and every node is
    registered; `down()` stops all of it and waits."""

    def __init__(self, config: dict, cut: int):
        self.config = config
        self.names = node_names(config, cut)
        self.res = None
        self.kubelets = None
        self.sched = None
        self.store = None
        self.cp = None

    def up(self, timeout_s: float = 120.0) -> "Deployment":
        from kubernetes_tpu.api.types import Namespace, ObjectMeta
        from kubernetes_tpu.cli.kadm import init_control_plane

        self.res = init_control_plane(port=0)
        if not self.res.wait_ready(timeout=60):
            raise RuntimeError("control plane never took the lease")
        cp = self.cp = self.res.control_plane
        # the leader starts the scheduler and then each controller; load
        # waits for all of them, as it would after `kadm init` returns
        t0 = time.monotonic()
        while len(cp.controllers) < len(cp.controller_names):
            if time.monotonic() - t0 > timeout_s:
                raise RuntimeError("controllers never all started")
            time.sleep(0.01)
        self.sched, self.store = cp.scheduler, self.res.store
        self.store.create("namespaces",
                          Namespace(metadata=ObjectMeta(name=PROBE_NAMESPACE)))
        nodes = make_nodes(self.config, self.names)
        self.kubelets = Kubelets(self.store, self.names).start()
        self.store.create_many("nodes", nodes, consume=True)
        return self

    @property
    def url(self) -> str:
        return self.res.url

    def relists(self) -> int:
        """Relists summed over the running controllers (`Controller.relists`)."""
        return sum(getattr(c, "relists", 0) for c in list(self.cp.controllers))

    def down(self) -> None:
        if self.kubelets is not None:
            self.kubelets.stop()
            self.kubelets = None
        if self.res is not None:
            self.res.stop()
            self.res = None
