"""The plain reference on hand-made event logs: each guarantee's count."""

import pytest

from benchmark.reference import PodShape, check, load_check, quantity

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


# spread_skew: six nodes over three zones, given in turn (z1 z2 z3 z1 z2 z3)
ZONE = "topology.kubernetes.io/zone"
SPREAD_T = {"requests": {"cpu": "100m", "memory": "1Mi"}, "labels": {"foo": "bar"},
            "topologySpreadConstraints": [{
                "maxSkew": 1, "topologyKey": ZONE, "whenUnsatisfiable": "DoNotSchedule",
                "labelSelector": {"matchLabels": {"foo": "bar"}}}]}
FILL_T = {"requests": {"cpu": "1", "memory": "1Mi"}}
SPREAD_CFG = {
    "nodes": {"capacity": {"cpu": "1", "memory": "2Gi", "pods": "10"},
              "labelNodePrepareStrategy": {"labelKey": ZONE,
                                           "labelValues": ["z1", "z2", "z3"]}},
    "templates": {"spread": SPREAD_T, "fill": FILL_T},
    "checks": ["overcommit", "false_unschedulable", "spread_skew"]}
SIX = [f"n{i}" for i in range(6)]
IN_ZONE = {"z1": ["n0", "n3"], "z2": ["n1", "n4"], "z3": ["n2", "n5"]}
SHAPES = {"s": PodShape(SPREAD_T), "f": PodShape(FILL_T)}


def spread_run(log, config=SPREAD_CFG):
    def shape(key):
        return SHAPES[key.split("/", 1)[1][0]]

    return check(log, config, SIX, shape, set(), {})


def binds(zones, g, first=0, ns="default"):
    """Spread pods s<first>..., created and bound one to each zone named,
    in one delivery."""
    out = []
    for i, z in enumerate(zones):
        key = f"{ns}/s{first + i}"
        out += [("A", key, None, 0), ("B", key, IN_ZONE[z][i % 2], g)]
    return [e for e in out if e[0] == "A"] + [e for e in out if e[0] == "B"]


def _log(*parts):
    adds = [e for p in parts for e in p if e[0] == "A"]
    return adds + [e for p in parts for e in p if e[0] != "A"]


def test_spread_skew_reads_0_on_a_sound_placement():
    # within a delivery the order is free; only the end state is judged
    log = _log(binds(["z1", "z1", "z2", "z3"], 1), binds(["z2", "z3"], 2, 4),
               binds(["z1", "z2", "z3"], 3, 6))
    assert spread_run(log) == {"overcommit": 0, "false_unschedulable": 0,
                               "spread_skew": 0}


def test_a_binding_over_max_skew_counts():
    log = _log(binds(["z1", "z2", "z3"], 1), binds(["z1", "z1", "z1"], 2, 3))
    assert spread_run(log)["spread_skew"] == 2  # z1 4, least 1, maxSkew 1
    # pods its selector does not select, or of another namespace, count not
    other = [("A", "other/s9", None, 0), ("B", "other/s9", "n0", 3)]
    fills = [("A", f"default/f{i}", None, 0) for i in range(2)]
    fills += [("B", "default/f0", "n0", 3), ("B", "default/f1", "n3", 3)]
    log = _log(binds(["z2", "z3"], 1), other, fills, binds(["z1"], 4, 2))
    assert spread_run(log)["spread_skew"] == 0


def test_a_delete_the_scheduler_had_not_seen_stays_within_the_limit():
    # z1 z2 z3 hold 2 each; a z3 pod goes, and a batch placed on the view
    # before that puts one more into z1 (2 + 1 - 2 <= 1 there)
    log = _log(binds(["z1", "z2", "z3"] * 2, 1), binds(["z1"], 3, 6))
    log.insert(-1, ("D", "default/s2", None, 2))
    got = spread_run(log)["spread_skew"]
    assert 0 < got <= load_check("spread_skew").LIMIT


def test_a_pod_only_the_skew_refused_is_no_false_refusal():
    # z3's nodes are full; z1 and z2 hold one spread pod each and have room,
    # where a third would break maxSkew 1
    fills = [("A", f"default/f{i}", None, 0) for i in range(2)]
    fills += [("B", "default/f0", "n2", 1), ("B", "default/f1", "n5", 1)]
    log = _log(fills, binds(["z1", "z2"], 2),
               [("A", "default/s7", None, 0), ("U", "default/s7", None, 3)])
    assert spread_run(log)["false_unschedulable"] == 0
    fit_only = dict(SPREAD_CFG, checks=["false_unschedulable"])
    assert spread_run(log, fit_only)["false_unschedulable"] == 1
    # with room in z3 the refusal is false again
    assert spread_run(_log(binds(["z1", "z2"], 2),
                           [("A", "default/s7", None, 0),
                            ("U", "default/s7", None, 3)]))["false_unschedulable"] == 1


def test_spread_constraints_the_check_does_not_model_are_refused():
    t = dict(SPREAD_T, affinity={"nodeAffinity": {}})
    with pytest.raises(ValueError, match="node affinity"):
        spread_run([], dict(SPREAD_CFG, templates={"spread": t}))
