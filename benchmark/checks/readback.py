"""readback: an HTTP-created pod whose node, listed over HTTP after the
drain, differs from the one its binding named."""

from benchmark.reference import Check

LIMIT = 0


class Readback(Check):
    def finish(self, acked, readback):
        node_of = self.r.node_of
        self.value = sum(1 for key, got in readback.items()
                         if got != node_of.get(key))


CHECK = Readback
