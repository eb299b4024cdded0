"""The plain reference: the scheduling guarantees a configuration states,
checked on what the run's watch saw. It imports nothing of the program and
takes nothing the program made: node sizes and pod requests come from the
configuration file, placements from the ordered event log (`informer.py`).

Replaying the log in the store's order rebuilds the cluster as it stood at
every change, which is the snapshot each batch was placed against, and
holds every answer to the guarantees:

  missing              an acknowledged create that never appeared
  unbound              a pod that appeared and was never bound or deleted
  double_bind          a pod bound a second time, or moved between nodes
  overcommit           a binding after which its node holds more cpu,
                       memory or pods than it can allocate
  false_unschedulable  a pod marked unschedulable while a node had room for
                       it
  readback             an HTTP-created pod whose node, listed over HTTP after
                       the drain, differs from the one its binding named
  fill_gap             where the configuration scores nodes least-allocated
                       (identical pods on identical nodes, so the emptiest
                       node is the one holding the fewest pods): for each
                       watch delivery that bound pods, the most pods any node
                       that received one held before the delivery, less the
                       fewest pods any node holds after it; the largest over
                       the run. A batch placed least-allocated first fills
                       every emptier node up to the level it places at, so
                       this reads 0, and more only by what the scheduler's
                       view lagged the store (deletes it had not yet seen)
                       or by equal integer scores of adjacent levels.

Each count is exact and its limit is 0; fill_gap's limit, FILL_GAP_LIMIT,
lies between what sound runs and a first-fit solver read (PERF.md).
"""

from __future__ import annotations

import re

CHECKS = ("missing", "unbound", "double_bind", "overcommit",
          "false_unschedulable", "readback", "fill_gap")
FILL_GAP_LIMIT = 12
LIMITS = {c: 0 for c in CHECKS} | {"fill_gap": FILL_GAP_LIMIT}

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


class PodShape:
    __slots__ = ("cpu", "mem")

    def __init__(self, template: dict):
        self.cpu = quantity(template["requests"]["cpu"], milli=True)
        self.mem = quantity(template["requests"]["memory"])


def check(log, config: dict, node_names: list, shape_of, acked: set,
          readback: dict) -> dict:
    """Replay `log` and count each guarantee's violations.

    shape_of(key) -> PodShape names the template each pod was made from;
    readback maps the keys of HTTP-created pods to the node an HTTP list
    read after the drain."""
    cap = config["nodes"]["capacity"]
    a_cpu, a_mem = quantity(cap["cpu"], milli=True), quantity(cap["memory"])
    a_pods = quantity(cap["pods"])
    least_allocated = config.get("scoring") == "least-allocated"
    if least_allocated:
        reqs = {(t["requests"]["cpu"], t["requests"]["memory"])
                for t in config["templates"].values()}
        if len(reqs) != 1:
            raise ValueError("fill_gap is judged on identical pods only")
    used = {n: [0, 0, 0] for n in node_names}
    out = {c: 0 for c in CHECKS}
    node_of: dict = {}  # live pods: key -> node or None
    seen: set = set()
    ever_bound: set = set()
    unsched: set = set()
    shapes: dict = {}
    before: dict = {}  # node -> pods it held when this delivery began
    group = None

    def close_delivery():
        if before and least_allocated:
            floor = min(u[2] for u in used.values())
            out["fill_gap"] = max(out["fill_gap"], max(before.values()) - floor)
        before.clear()

    def room(sh: PodShape) -> bool:
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
                out["overcommit"] += 1  # a node the cluster does not have
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
        want = node_of.get(key)
        if got != want:
            out["readback"] += 1
    return out
