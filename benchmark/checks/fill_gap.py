"""fill_gap: where the configuration scores nodes least-allocated
(identical pods on identical nodes, so the emptiest node is the one holding
the fewest pods): for each watch delivery that bound pods, the most pods
any node that received one held before the delivery, less the fewest pods
any node holds after it; the largest over the run. A batch placed
least-allocated first fills every emptier node up to the level it places
at, so this reads 0, and more only by what the scheduler's view lagged the
store (deletes it had not yet seen) or by equal integer scores of adjacent
levels. Reads 0 where the configuration scores otherwise.

LIMIT lies between what sound runs and a first-fit solver read (PERF.md)."""

from benchmark.reference import Check

LIMIT = 12


class FillGap(Check):
    def __init__(self, replay):
        super().__init__(replay)
        self.before = {}  # node -> pods it held when this delivery began
        config = replay.config
        self.judged = config.get("scoring") == "least-allocated"
        if self.judged:
            reqs = {(t["requests"]["cpu"], t["requests"]["memory"])
                    for t in config["templates"].values()}
            if len(reqs) != 1:
                raise ValueError("fill_gap is judged on identical pods only")

    def bound(self, key, node):
        u = self.r.used.get(node)
        if u is not None:
            self.before.setdefault(node, u[2] - 1)

    def delivery_end(self):
        if self.before and self.judged:
            floor = min(u[2] for u in self.r.used.values())
            self.value = max(self.value, max(self.before.values()) - floor)
        self.before.clear()


CHECK = FillGap
