"""unbound: a pod that appeared and was never bound or deleted."""

from benchmark.reference import Check

LIMIT = 0


class Unbound(Check):
    def finish(self, acked, readback):
        self.value = sum(1 for n in self.r.node_of.values() if n is None)


CHECK = Unbound
