"""A configuration states its nodes' labels, its pods' scheduling
constraints and the checks it is held to in its own file: the fixture's
`spread-cfg` (three zones, a spread and an anti-affinity template, its checks
named) loads, builds and is checked from files alone."""

import json
import os

import pytest

from benchmark import catalog, deploy, probe, reference
from benchmark.tests.checkout import make_checkout

ZONE = "topology.kubernetes.io/zone"


def test_the_catalog_loads_it_from_files_alone(tmp_path):
    cell = catalog.load_cell("spread-cell", make_checkout(tmp_path))
    assert cell.config_name == "spread-cfg"
    assert cell.traffic["lifetime_mean_s"] is None
    assert reference.check_names(cell.config)[-1] == "spread_skew"
    lims = reference.limits(cell.config, cell.root)
    assert lims["spread_skew"] == reference.load_check("spread_skew").LIMIT
    assert "fill_gap" not in lims


def test_nodes_carry_the_zone_labels_in_turn(tmp_path):
    cfg = catalog.load_cell("spread-cell", make_checkout(tmp_path)).config
    names = deploy.node_names(cfg, 64)
    nodes = deploy.make_nodes(cfg, names)
    assert len(nodes) == 78
    zones = [n.metadata.labels[ZONE] for n in nodes]
    assert zones[:4] == ["moon-1", "moon-2", "moon-3", "moon-1"]
    assert {z: zones.count(z) for z in set(zones)} == {
        "moon-1": 26, "moon-2": 26, "moon-3": 26}
    assert all(n.metadata.labels["kubernetes.io/hostname"] == n.metadata.name
               for n in nodes)


def test_pods_carry_the_constraints(tmp_path):
    from kubernetes_tpu.api.types import Pod

    cfg = catalog.load_cell("spread-cell", make_checkout(tmp_path)).config
    spread, anti = cfg["templates"]["spread"], cfg["templates"]["anti"]
    p = deploy.PodFactory(spread).make(["m-1-0"], "1")[0]
    (tsc,) = p.spec.topology_spread_constraints
    assert (tsc.max_skew, tsc.topology_key, tsc.when_unsatisfiable) == (
        5, ZONE, "DoNotSchedule")
    assert tsc.selector.matches({"foo": "bar"}) and not tsc.selector.matches({})
    assert p.metadata.labels == {"foo": "bar"} and p.spec.affinity is None
    q = deploy.PodFactory(anti).make(["m-1-1"], "1")[0]
    (term,) = q.spec.affinity.pod_anti_affinity_required
    assert term.topology_key == "kubernetes.io/hostname"
    assert term.selector.matches({"app": "ha"})
    assert not q.spec.topology_spread_constraints
    # the HTTP probe's pods carry them too, as the API server reads them
    for t, want in ((spread, p), (anti, q)):
        got = Pod.from_dict(json.loads(probe.pod_body("x", t)))
        assert got.metadata.labels == want.metadata.labels
        assert got.spec.topology_spread_constraints == want.spec.topology_spread_constraints
        assert got.spec.affinity == want.spec.affinity


@pytest.mark.parametrize("bad,where", [
    ({"tolerations": []}, "tolerations"),
    ({"topologySpreadConstraint": []}, "topologySpreadConstraint"),
    ({"affinity": {"podAntiAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": [
            {"topologyKey": "x", "labelSelecter": {}}]}}}, "labelSelecter"),
    ({"topologySpreadConstraints": [{"maxSkew": 1, "topologyKey": ZONE,
                                     "whenUnsatisfiable": "DoNotSchedule",
                                     "labelSelector": {"matchLabel": {}}}]},
     "matchLabel"),
])
def test_an_unknown_template_field_is_refused_at_load(tmp_path, bad, where):
    root = make_checkout(tmp_path)
    path = os.path.join(root, "benchmark", "configs", "spread-cfg.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["templates"]["spread"].update(bad)
    with open(path, "w") as f:
        json.dump(cfg, f)
    with pytest.raises(ValueError, match=where):
        catalog.load_cell("spread-cell", root)


def test_a_check_added_as_a_file_is_found_by_name(tmp_path):
    root = make_checkout(tmp_path)
    cfg = catalog.load_cell("spread-cell", root).config
    cfg = dict(cfg, checks=["double_bind", "first_binds"])
    assert reference.load_check("first_binds", root).LIMIT == 10**9
    with pytest.raises(FileNotFoundError):
        reference.load_check("first_binds")  # not in the benchmark's own
    shape = reference.PodShape(cfg["templates"]["plain"])
    log = [("A", "default/i-0", None, 1), ("B", "default/i-0", "node-0", 2),
           ("B", "default/i-0", "node-1", 3)]
    got = reference.check(log, cfg, ["node-0", "node-1"], lambda _k: shape,
                          {"default/i-0"}, {}, root)
    assert got == {"double_bind": 1, "first_binds": 1}
    assert reference.limits(cfg, root) == {"double_bind": 0, "first_binds": 10**9}

