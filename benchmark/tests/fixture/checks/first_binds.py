"""A check that exists only in the fixture: the pods bound at least once."""

from benchmark.reference import Check

LIMIT = 10**9


class FirstBinds(Check):
    def bound(self, key, node):
        self.value += 1


CHECK = FirstBinds
