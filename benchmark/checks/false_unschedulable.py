"""false_unschedulable: a pod marked unschedulable while a node had room
for it, and every check the configuration names admitted it there (a pod
that only a constraint refused is no false refusal)."""

from benchmark.reference import Check

LIMIT = 0


class FalseUnschedulable(Check):
    def unschedulable(self, key):
        r = self.r
        sh = r.shape(key)
        if any(r.fits(sh, n) and r.admits(key, n) for n in r.used):
            self.value += 1


CHECK = FalseUnschedulable
