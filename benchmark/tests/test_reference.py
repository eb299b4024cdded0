"""The plain reference on hand-made event logs: each guarantee's count."""

import pytest

from benchmark.reference import PodShape, check, quantity

PLAIN_T = {"requests": {"cpu": "400m", "memory": "500Mi"}}
CONFIG = {"nodes": {"capacity": {"cpu": "1", "memory": "2Gi", "pods": "3"}},
          "templates": {"plain": PLAIN_T}}
LEAST = dict(CONFIG, scoring="least-allocated")
NODES = ["a", "b", "c"]
PLAIN = PodShape(PLAIN_T)


def shape_of(_key):
    return PLAIN


def run(log, acked=(), readback=None, config=CONFIG):
    return check(log, config, NODES, shape_of, set(acked), readback or {})


def test_quantities():
    assert quantity("100m", milli=True) == 100
    assert quantity("4", milli=True) == 4000
    assert quantity("32Gi") == 32 * 2**30
    assert quantity("500Mi") == 500 * 2**20
    assert quantity("1.5k") == 1500
    with pytest.raises(ValueError):
        quantity("half")


def test_a_sound_log_counts_nothing():
    log = [("A", "p/1", None, 1), ("A", "p/2", None, 1),
           ("B", "p/1", "a", 2), ("B", "p/2", "a", 2),
           ("D", "p/1", None, 3), ("B", "p/3", "a", 4), ("A", "p/3", None, 0)]
    log = [("A", "p/3", None, 0)] + log[:-1]
    out = run(log, acked={"p/1", "p/2", "p/3"}, readback={"p/2": "a"})
    assert all(v == 0 for v in out.values()), out


def test_overcommit_on_cpu_counts_each_binding_over():
    log = [("A", f"p/{i}", None, 1) for i in range(3)]
    log += [("B", f"p/{i}", "a", 2) for i in range(3)]  # 1200m > 1000m
    assert run(log)["overcommit"] == 1


def test_a_delete_frees_room():
    log = [("A", f"p/{i}", None, 1) for i in range(3)]
    log += [("B", "p/0", "a", 2), ("B", "p/1", "a", 2), ("D", "p/0", None, 3),
            ("B", "p/2", "a", 4)]
    assert run(log)["overcommit"] == 0


def test_double_bind_and_move():
    log = [("A", "p/0", None, 1), ("B", "p/0", "a", 2), ("B", "p/0", "b", 3),
           ("X", "p/0", "c", 4)]
    assert run(log)["double_bind"] == 2


def test_missing_unbound_and_readback():
    log = [("A", "p/0", None, 1), ("A", "p/1", None, 1), ("B", "p/0", "a", 2)]
    out = run(log, acked={"p/0", "p/1", "p/9"},
              readback={"p/0": "b", "p/1": None})
    assert (out["missing"], out["unbound"], out["readback"]) == (1, 1, 1)


def test_fill_gap_reads_0_for_least_allocated_batches():
    # one delivery fills every empty node, then a second starts on the next
    # level only once the first level is full
    log = [("A", f"p/{i}", None, 1) for i in range(5)]
    log += [("B", "p/0", "a", 2), ("B", "p/1", "b", 2), ("B", "p/2", "c", 2),
            ("B", "p/3", "a", 2)]  # a at level 1 once b and c are
    log += [("B", "p/4", "b", 3)]
    assert run(log, config=LEAST)["fill_gap"] == 0


def test_fill_gap_reads_how_far_first_fit_piles():
    log = [("A", f"p/{i}", None, 1) for i in range(3)]
    log += [("B", f"p/{i}", "a", 2 + i) for i in range(2)]  # a: 0, then 1
    assert run(log, config=LEAST)["fill_gap"] == 1
    log += [("B", "p/2", "a", 9)]  # a holds 2 while b and c hold none
    assert run(log, config=LEAST)["fill_gap"] == 2


def test_fill_gap_counts_a_delete_the_scheduler_had_not_seen():
    log = [("A", f"p/{i}", None, 1) for i in range(4)]
    log += [("B", "p/0", "a", 2), ("B", "p/1", "b", 2), ("B", "p/2", "c", 2)]
    log += [("D", "p/0", None, 3), ("B", "p/3", "b", 4)]  # a emptied meanwhile
    assert run(log, config=LEAST)["fill_gap"] == 1


def test_fill_gap_is_judged_only_where_the_configuration_scores_so():
    log = [("A", f"p/{i}", None, 1) for i in range(2)]
    log += [("B", "p/0", "a", 2), ("B", "p/1", "a", 3)]
    assert run(log)["fill_gap"] == 0
    mixed = dict(LEAST, templates={"x": PLAIN_T,
                                   "y": {"requests": {"cpu": "1", "memory": "1Mi"}}})
    with pytest.raises(ValueError):
        run(log, config=mixed)


def test_false_unschedulable_only_when_a_node_had_room():
    log = [("A", "p/0", None, 1), ("U", "p/0", None, 2)]
    assert run(log)["false_unschedulable"] == 1
    full = [("A", f"p/{i}", None, 1) for i in range(7)]
    full += [("B", f"p/{i}", n, 2) for i, n in enumerate("aabbcc")]
    full += [("U", "p/6", None, 3), ("U", "p/6", None, 4)]
    assert run(full)["false_unschedulable"] == 0
