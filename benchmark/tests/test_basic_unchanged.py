"""basic-5k through the configuration-driven harness builds the same nodes,
pods and probe requests as the harness built before configurations could
state labels, constraints and checks, and the reference reads the same
counts and limits on the same logs. The earlier code is kept here, frozen,
as the oracle."""

import json
import os
import random

import pytest

from benchmark import deploy, probe
from benchmark.catalog import ROOT
from benchmark.reference import PodShape, check, limits, quantity

with open(os.path.join(ROOT, "benchmark", "configs", "basic-5k.json")) as f:
    BASIC = json.load(f)

OLD_CHECKS = ("missing", "unbound", "double_bind", "overcommit",
              "false_unschedulable", "readback", "fill_gap")
OLD_LIMITS = {c: 0 for c in OLD_CHECKS} | {"fill_gap": 12}


def _old_make_nodes(config, names):
    from kubernetes_tpu.testing import MakeNode

    cap = config["nodes"]["capacity"]
    return [MakeNode(n).labels({"kubernetes.io/hostname": n})
            .capacity(dict(cap)).obj() for n in names]


def _old_pods(template, names, uid_prefix):
    from kubernetes_tpu.store.store import pod_structural_clone
    from kubernetes_tpu.testing import MakePod

    mp = MakePod("template", "default").labels(dict(template.get("labels") or {}))
    mp.req(dict(template["requests"]))
    one = mp.obj()
    out = []
    for name in names:
        p = pod_structural_clone(one)
        p.metadata.name = name
        p.metadata.uid = f"{uid_prefix}-{name}"
        out.append(p)
    return out


def _old_pod_body(name, requests):
    return json.dumps({
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": name},
        "spec": {"containers": [{
            "name": "pause", "image": "registry.k8s.io/pause:3.10",
            "resources": {"requests": requests, "limits": requests}}]},
    }).encode()


def _old_check(log, config, node_names, shape_of, acked, readback):
    cap = config["nodes"]["capacity"]
    a_cpu, a_mem = quantity(cap["cpu"], milli=True), quantity(cap["memory"])
    a_pods = quantity(cap["pods"])
    least_allocated = config.get("scoring") == "least-allocated"
    used = {n: [0, 0, 0] for n in node_names}
    out = {c: 0 for c in OLD_CHECKS}
    node_of, seen, ever_bound, unsched, shapes, before = {}, set(), set(), set(), {}, {}
    group = None

    def close_delivery():
        if before and least_allocated:
            floor = min(u[2] for u in used.values())
            out["fill_gap"] = max(out["fill_gap"], max(before.values()) - floor)
        before.clear()

    def room(sh):
        return any(u[0] + sh.cpu <= a_cpu and u[1] + sh.mem <= a_mem
                   and u[2] + 1 <= a_pods for u in used.values())

    for op, key, node, g in log:
        if g != group:
            close_delivery()
            group = g
        if op == "A":
            seen.add(key)
            node_of[key] = None
            shapes[key] = shape_of(key)
        elif op == "B":
            sh = shapes.get(key) or shape_of(key)
            if key in ever_bound:
                out["double_bind"] += 1
                continue
            ever_bound.add(key)
            node_of[key] = node
            u = used.get(node)
            if u is None:
                out["overcommit"] += 1
                continue
            before.setdefault(node, u[2])
            u[0] += sh.cpu
            u[1] += sh.mem
            u[2] += 1
            if u[0] > a_cpu or u[1] > a_mem or u[2] > a_pods:
                out["overcommit"] += 1
        elif op == "X":
            out["double_bind"] += 1
        elif op == "U":
            if key not in unsched and node_of.get(key, 0) is None:
                unsched.add(key)
                if room(shapes.get(key) or shape_of(key)):
                    out["false_unschedulable"] += 1
        elif op == "D":
            n = node_of.pop(key, None)
            sh = shapes.pop(key, None)
            if n is not None and sh is not None and n in used:
                u = used[n]
                u[0] -= sh.cpu
                u[1] -= sh.mem
                u[2] -= 1
    close_delivery()
    out["missing"] = sum(1 for k in acked if k not in seen)
    out["unbound"] = sum(1 for k, n in node_of.items() if n is None)
    for key, got in readback.items():
        if got != node_of.get(key):
            out["readback"] += 1
    return out


def test_basic_nodes_equal_the_old_ones():
    names = deploy.node_names(BASIC, 64)
    new, old = deploy.make_nodes(BASIC, names), _old_make_nodes(BASIC, names)
    for n, o in zip(new, old):
        o.metadata.uid = n.metadata.uid  # MakeNode draws a fresh uid
        assert n == o
        assert list(n.metadata.labels) == list(o.metadata.labels)
    assert len(new) == len(old) == 78


@pytest.mark.parametrize("template", sorted(BASIC["templates"]))
def test_basic_pods_and_probe_requests_equal_the_old_ones(template):
    t = BASIC["templates"][template]
    names = [f"m-0000abcd-{i}" for i in range(5)]
    new = deploy.PodFactory(t).make(names, "0000abcd")
    old = _old_pods(t, names, "0000abcd")
    assert new == old
    assert probe.pod_body("p-1", t) == _old_pod_body("p-1", t["requests"])


# hand-made logs: every op, nodes the cluster has and one it does not, pods
# bound twice, moved, refused, deleted before and after binding, some never
# added; delivered in groups of 1-6 events
NODES = ["a", "b", "c", "d"]
SMALL = {"nodes": {"capacity": {"cpu": "1", "memory": "2Gi", "pods": "3"}},
         "templates": {"plain": {"requests": {"cpu": "300m", "memory": "500Mi"}}}}
LOGS = [dict(SMALL, scoring="least-allocated"), SMALL, BASIC]


def _log(seed):
    rnd = random.Random(seed)
    keys = [f"default/m-{i}" for i in range(24)]
    log, g = [], 0
    for _ in range(160):
        if rnd.random() < 0.3:
            g += 1
        op = rnd.choices("ABXUD", weights=(5, 6, 1, 2, 3))[0]
        node = rnd.choice(NODES + ["zz"]) if op in "BX" else None
        log.append((op, rnd.choice(keys), node, g))
    acked = set(rnd.sample(keys, 12)) | {"default/m-99"}
    readback = {k: rnd.choice(NODES + [None]) for k in rnd.sample(keys, 4)}
    return log, acked, readback


@pytest.mark.parametrize("seed", range(12))
@pytest.mark.parametrize("cfg", range(len(LOGS)))
def test_reference_counts_and_limits_equal_the_old_ones(seed, cfg):
    config = LOGS[cfg]
    shape = PodShape(next(iter(config["templates"].values())))
    log, acked, readback = _log(seed)
    new = check(log, config, NODES, lambda _k: shape, acked, readback)
    old = _old_check(log, config, NODES, lambda _k: shape, acked, readback)
    assert new == old and list(new) == list(old)
    assert limits(config) == OLD_LIMITS and list(limits(config)) == list(OLD_LIMITS)


def test_the_logs_reach_every_count():
    """The hand-made logs above are not all zeros: each count reads more
    than 0 on one of them, so the comparison covers it."""
    hit = set()
    for cfg in LOGS:
        shape = PodShape(next(iter(cfg["templates"].values())))
        for seed in range(12):
            log, acked, readback = _log(seed)
            out = check(log, cfg, NODES, lambda _k: shape, acked, readback)
            hit |= {k for k, v in out.items() if v}
    assert hit == set(OLD_CHECKS)
