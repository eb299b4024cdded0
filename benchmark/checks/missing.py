"""missing: an acknowledged create that never appeared on the watch."""

from benchmark.reference import Check

LIMIT = 0


class Missing(Check):
    def finish(self, acked, readback):
        self.value = sum(1 for k in acked if k not in self.r.seen)


CHECK = Missing
