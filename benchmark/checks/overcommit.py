"""overcommit: a binding after which its node holds more cpu, memory or
pods than it can allocate, or a binding to a node the cluster does not
have."""

from benchmark.reference import Check

LIMIT = 0


class Overcommit(Check):
    def bound(self, key, node):
        r = self.r
        u = r.used.get(node)
        if u is None or u[0] > r.a_cpu or u[1] > r.a_mem or u[2] > r.a_pods:
            self.value += 1


CHECK = Overcommit
