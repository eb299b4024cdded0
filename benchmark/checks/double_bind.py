"""double_bind: a pod bound a second time, or moved between nodes."""

from benchmark.reference import Check

LIMIT = 0


class DoubleBind(Check):
    def rebound(self, key):
        self.value += 1


CHECK = DoubleBind
