"""spread_skew: each pod's DoNotSchedule topology spread constraints (its
template's `topologySpreadConstraints`), held as upstream's PodTopologySpread
filter states them (filtering.go): a pod may bind into domain z only if
matchNum(z) + selfMatch - minMatch <= maxSkew. matchNum counts the pods that
the constraint's labelSelector selects in the pod's namespace on the nodes
of z; selfMatch is 1 where it selects the pod itself; minMatch is the least
count over the domains, or 0 while there are fewer than minDomains; the
domains are the values of topologyKey over the nodes that carry it.

The watch delivers a batch's bindings together, not one by one, so the
check judges at the end of each delivery, on the cluster as it then stands:
for each domain a constrained pod bound into during the delivery,
count(z) - minMatch - maxSkew. The reading is the largest over the run, and
0 where none is positive. A batch that keeps the rule pod by pod reads 0
(the last pod into z saw count(z) - 1 and a minimum no higher than the one
now). It reads more only by what the scheduler's view lagged the store (a
delete it had not yet seen), or where a batch's bindings are split over
deliveries. `admits` asks the rule itself, so a pod that only the skew
refused is no false refusal.

Refused, as not modelled: a constraint with matchLabelKeys, and spread on a
template with node affinity or a node selector (nodeAffinityPolicy would
narrow the eligible nodes). Nodes without the key hold no domain and are not
judged.
"""

import json

from benchmark.reference import Check, selects

LIMIT = 5


class _Class:
    """The pods one (topologyKey, labelSelector) counts, and its domains."""

    def __init__(self, key, selector, domains):
        self.key = key
        self.selector = selector
        self.n_domains = len(domains)


class SpreadSkew(Check):
    def __init__(self, replay):
        super().__init__(replay)
        self.classes = []
        self._class_ix = {}
        self.counts = {}  # (class, namespace) -> {domain: pods}
        self.placed = {}  # key -> [(class, namespace, domain)] it was counted in
        self.touched = set()  # (rule, namespace, domain) bound into this delivery
        self._rules = {}  # shape -> [(class, maxSkew, minDomains, selfMatch)]
        self._counted = {}  # shape -> classes that count its pods
        # every class a template names counts pods from the first binding on
        for t in replay.config["templates"].values():
            self._rules_of(t)

    def _class(self, key, selector):
        ix = (key, json.dumps(selector, sort_keys=True))
        if ix not in self._class_ix:
            domains = {lab[key] for lab in self.r.labels.values() if key in lab}
            self._class_ix[ix] = len(self.classes)
            self.classes.append(_Class(key, selector, domains))
        return self._class_ix[ix]

    def _rules_of(self, t):
        rules = []
        for c in t.get("topologySpreadConstraints") or []:
            if c["whenUnsatisfiable"] != "DoNotSchedule":
                continue
            if c.get("matchLabelKeys"):
                raise ValueError("spread_skew: matchLabelKeys is not modelled")
            if "nodeSelector" in t or "nodeAffinity" in (t.get("affinity") or {}):
                raise ValueError("spread_skew: spread with node affinity is "
                                 "not modelled")
            sel = c.get("labelSelector")
            rules.append((self._class(c["topologyKey"], sel), int(c["maxSkew"]),
                          int(c.get("minDomains") or 1),
                          int(selects(sel, t.get("labels") or {}))))
        return rules

    def _shape(self, key):
        sh = self.r.shape(key)
        if sh not in self._rules:
            labels = sh.template.get("labels") or {}
            self._rules[sh] = self._rules_of(sh.template)
            self._counted[sh] = [i for i, c in enumerate(self.classes)
                                 if selects(c.selector, labels)]
        return sh

    def _min(self, ci, min_domains, counts):
        n = self.classes[ci].n_domains
        if n < min_domains or len(counts) < n:
            return 0
        return min(counts.values())

    def bound(self, key, node):
        labels = self.r.labels.get(node)
        if labels is None:
            return
        sh = self._shape(key)
        ns = key.split("/", 1)[0]
        mine = []
        for ci in self._counted[sh]:
            d = labels.get(self.classes[ci].key)
            if d is not None:
                c = self.counts.setdefault((ci, ns), {})
                c[d] = c.get(d, 0) + 1
                mine.append((ci, ns, d))
        if mine:
            self.placed[key] = mine
        for rule in self._rules[sh]:
            d = labels.get(self.classes[rule[0]].key)
            if d is not None:
                self.touched.add((rule, ns, d))

    def deleted(self, key, node):
        for ci, ns, d in self.placed.pop(key, ()):
            c = self.counts[(ci, ns)]
            c[d] -= 1
            if not c[d]:
                del c[d]

    def delivery_end(self):
        for (ci, max_skew, min_domains, _self), ns, d in self.touched:
            c = self.counts.get((ci, ns), {})
            over = c.get(d, 0) - self._min(ci, min_domains, c) - max_skew
            self.value = max(self.value, over)
        self.touched.clear()

    def admits(self, key, node):
        sh = self._shape(key)
        ns = key.split("/", 1)[0]
        labels = self.r.labels[node]
        for ci, max_skew, min_domains, self_match in self._rules[sh]:
            d = labels.get(self.classes[ci].key)
            if d is None:
                return False
            c = self.counts.get((ci, ns), {})
            if c.get(d, 0) + self_match - self._min(ci, min_domains, c) > max_skew:
                return False
        return True


CHECK = SpreadSkew
