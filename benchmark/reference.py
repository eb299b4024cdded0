"""The plain reference: the scheduling guarantees a configuration states,
checked on what the run's watch saw. It imports nothing of the program and
takes nothing the program made: node sizes, node labels and pod templates
come from the configuration file, placements from the ordered event log
(`informer.py`).

Replaying the log in the store's order rebuilds the cluster as it stood at
every change, which is the snapshot each batch was placed against. The
replay (`Replay`) is one pass, and each guarantee is a check that it drives:
a file `benchmark/checks/<name>.py` whose `CHECK` subclasses `Check` and
whose `LIMIT` is the most a correct run may read. A configuration names its
checks, in order, under "checks"; one that names none is held to
DEFAULT_CHECKS. A check is added by adding its file, found by name as the
metric readers are; nothing here names one. Each file's docstring says what it counts,
and PERF.md what each limit was set from.
"""

from __future__ import annotations

import importlib.util
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_CHECKS = ("missing", "unbound", "double_bind", "overcommit",
                  "false_unschedulable", "readback", "fill_gap")
HOST_KEY = "kubernetes.io/hostname"

_SUFFIX = {"": 1, "k": 10**3, "M": 10**6, "G": 10**9, "T": 10**12,
           "Ki": 2**10, "Mi": 2**20, "Gi": 2**30, "Ti": 2**40}


def quantity(s: str, milli: bool = False) -> int:
    """A Kubernetes quantity as an integer (of thousandths when `milli`)."""
    m = re.fullmatch(r"(\d+(?:\.\d+)?)(m|k|M|G|T|Ki|Mi|Gi|Ti)?", str(s).strip())
    if m is None:
        raise ValueError(f"unsupported quantity {s!r}")
    num, suf = m.group(1), m.group(2) or ""
    scale = 1000 if milli else 1
    if suf == "m":
        v = float(num) * scale / 1000
    else:
        v = float(num) * _SUFFIX[suf] * scale
    return int(round(v))


def node_labels(config: dict, names: list) -> dict:
    """name -> labels of each node. Every node carries kubernetes.io/hostname,
    as its kubelet sets it. Where the configuration's `nodes` has a
    `labelNodePrepareStrategy` (scheduler_perf's: {"labelKey",
    "labelValues"}), each node also carries that key, its values given in
    turn over the nodes in order: node i takes value i mod the number of
    values. Upstream draws a value per node at random; in turn, every seed
    labels the same nodes alike and each value labels as many nodes, to one."""
    strat = config["nodes"].get("labelNodePrepareStrategy")
    out = {}
    for i, n in enumerate(names):
        labels = {HOST_KEY: n}
        if strat is not None:
            vals = strat["labelValues"]
            labels[strat["labelKey"]] = vals[i % len(vals)]
        out[n] = labels
    return out


def selects(selector, labels: dict) -> bool:
    """Whether a Kubernetes labelSelector ({matchLabels, matchExpressions})
    selects `labels`. A null selector selects nothing, an empty one all."""
    if selector is None:
        return False
    for k, v in (selector.get("matchLabels") or {}).items():
        if labels.get(k) != v:
            return False
    for e in selector.get("matchExpressions") or []:
        op, has = e["operator"], e["key"] in labels
        val, vals = labels.get(e["key"]), e.get("values") or ()
        ok = {"In": has and val in vals, "NotIn": not has or val not in vals,
              "Exists": has, "DoesNotExist": not has}.get(op)
        if ok is None:
            raise ValueError(f"unsupported selector operator {op!r}")
        if not ok:
            return False
    return True


class PodShape:
    """What the reference knows of a pod: its template, and the cpu and
    memory it requests."""

    __slots__ = ("cpu", "mem", "template")

    def __init__(self, template: dict):
        self.template = template
        self.cpu = quantity(template["requests"]["cpu"], milli=True)
        self.mem = quantity(template["requests"]["memory"])


class Check:
    """One guarantee, driven by the replay. A subclass overrides the hooks
    it needs; `value` is what it reads when the log is done."""

    def __init__(self, replay: "Replay"):
        self.r = replay
        self.value = 0

    def bound(self, key, node):
        """A pod's first binding, once the replay has counted it on its node
        (a node the cluster does not have counts nothing)."""

    def rebound(self, key):
        """A pod bound a second time, or moved between nodes."""

    def unschedulable(self, key):
        """A pending pod was first marked unschedulable."""

    def deleted(self, key, node):
        """A pod was deleted, once the replay has freed what it held."""

    def delivery_end(self):
        """The last event of one watch delivery was replayed."""

    def finish(self, acked: set, readback: dict):
        """The log is done."""

    def admits(self, key, node) -> bool:
        """Whether this guarantee lets the pod bind to the node now; the
        refusals are judged by what every check admits."""
        return True


HOOKS = ("bound", "rebound", "unschedulable", "deleted",
         "delivery_end", "finish", "admits")


def load_check(name: str, root: str = ROOT):
    """The module of check `name`: `<root>/benchmark/checks/<name>.py`."""
    path = os.path.join(root, "benchmark", "checks", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_check_" + re.sub(r"\W", "_", name), path)
    if spec is None or spec.loader is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_names(config: dict) -> tuple:
    return tuple(config.get("checks") or DEFAULT_CHECKS)


def limits(config: dict, root: str = ROOT) -> dict:
    """{check: limit} for the checks the configuration is held to."""
    return {n: load_check(n, root).LIMIT for n in check_names(config)}


class Replay:
    """The cluster as the log rebuilds it, which every check reads:
    `used[node]` = [cpu, memory, pods] held, `node_of[key]` the node of each
    live pod (None while pending), `labels[node]`, and each pod's shape."""

    def __init__(self, config: dict, node_names: list, shape_of,
                 root: str = ROOT):
        cap = config["nodes"]["capacity"]
        self.config = config
        self.a_cpu = quantity(cap["cpu"], milli=True)
        self.a_mem = quantity(cap["memory"])
        self.a_pods = quantity(cap["pods"])
        self.labels = node_labels(config, node_names)
        self.used = {n: [0, 0, 0] for n in node_names}
        self.node_of: dict = {}
        self.shapes: dict = {}
        self.seen: set = set()
        self.ever_bound: set = set()
        self.unsched: set = set()
        self._shape_of = shape_of
        self.checks = {n: load_check(n, root).CHECK(self)
                       for n in check_names(config)}
        # each hook is called on the checks that override it
        self._hooks = {h: [getattr(c, h) for c in self.checks.values()
                           if getattr(type(c), h) is not getattr(Check, h)]
                       for h in HOOKS}

    def shape(self, key) -> PodShape:
        return self.shapes.get(key) or self._shape_of(key)

    def fits(self, sh: PodShape, node) -> bool:
        u = self.used[node]
        return (u[0] + sh.cpu <= self.a_cpu and u[1] + sh.mem <= self.a_mem
                and u[2] + 1 <= self.a_pods)

    def admits(self, key, node) -> bool:
        return all(a(key, node) for a in self._hooks["admits"])

    def run(self, log, acked: set, readback: dict) -> dict:
        """Replay `log` and return each check's reading, in order."""
        h = self._hooks
        group = None
        for op, key, node, g in log:
            if g != group:
                for f in h["delivery_end"]:
                    f()
                group = g
            if op == "A":
                self.seen.add(key)
                self.node_of[key] = None
                self.shapes[key] = self._shape_of(key)
            elif op == "B":
                if key in self.ever_bound:
                    for f in h["rebound"]:
                        f(key)
                    continue
                sh = self.shape(key)
                self.ever_bound.add(key)
                self.node_of[key] = node
                u = self.used.get(node)
                if u is not None:
                    u[0] += sh.cpu
                    u[1] += sh.mem
                    u[2] += 1
                for f in h["bound"]:
                    f(key, node)
            elif op == "X":
                for f in h["rebound"]:
                    f(key)
            elif op == "U":
                if key not in self.unsched and self.node_of.get(key, 0) is None:
                    self.unsched.add(key)
                    for f in h["unschedulable"]:
                        f(key)
            elif op == "D":
                n = self.node_of.pop(key, None)
                sh = self.shapes.pop(key, None)
                if n is not None and sh is not None and n in self.used:
                    u = self.used[n]
                    u[0] -= sh.cpu
                    u[1] -= sh.mem
                    u[2] -= 1
                for f in h["deleted"]:
                    f(key, n)
        for f in h["delivery_end"]:
            f()
        for f in h["finish"]:
            f(acked, readback)
        return {n: c.value for n, c in self.checks.items()}


def check(log, config: dict, node_names: list, shape_of, acked: set,
          readback: dict, root: str = ROOT) -> dict:
    """Replay `log` and count each guarantee's violations.

    shape_of(key) -> PodShape names the template each pod was made from;
    readback maps the keys of HTTP-created pods to the node an HTTP list
    read after the drain."""
    return Replay(config, node_names, shape_of, root).run(log, acked, readback)
