"""Codec round-trip fuzzing — the test/fuzz analog (SURVEY.md §4).

reference: test/fuzz roundtrip fuzzing of API codecs. Property: for every
resource kind, from_dict(to_dict(obj)) == to_dict-stable — serializing a
deserialized object again yields the identical wire form (the invariant the
apiserver's codecs enforce; a lossy field here silently corrupts PATCH
read-modify-write, which round 4's review actually caught by hand).
"""

import string

from hypothesis import given, settings, strategies as st

from kubernetes_tpu.api.serialize import from_dict, to_dict

name_st = st.text(alphabet=string.ascii_lowercase + "-", min_size=1, max_size=12)
label_key = st.text(alphabet=string.ascii_lowercase + ".-/", min_size=1, max_size=20)
label_val = st.text(alphabet=string.ascii_lowercase + string.digits + "-", max_size=15)
labels_st = st.dictionaries(label_key, label_val, max_size=4)
qty_st = st.sampled_from(["100m", "1", "2", "500m", "1Gi", "256Mi", "2G", "0"])


def meta_st():
    return st.fixed_dictionaries(
        {"name": name_st},
        optional={"namespace": name_st, "labels": labels_st,
                  "annotations": labels_st,
                  "resourceVersion": st.integers(0, 10**6),
                  "uid": name_st})


container_st = st.fixed_dictionaries(
    {"name": name_st},
    optional={
        "image": name_st,
        "imagePullPolicy": st.sampled_from(["Always", "IfNotPresent", "Never"]),
        "resources": st.fixed_dictionaries({}, optional={
            "requests": st.dictionaries(
                st.sampled_from(["cpu", "memory"]), qty_st, max_size=2),
            "limits": st.dictionaries(
                st.sampled_from(["cpu", "memory"]), qty_st, max_size=2)}),
        "ports": st.lists(st.fixed_dictionaries(
            {"containerPort": st.integers(1, 65535)},
            optional={"hostPort": st.integers(1, 65535),
                      "protocol": st.sampled_from(["TCP", "UDP"])}),
            max_size=2),
    })

pod_st = st.fixed_dictionaries(
    {"kind": st.just("Pod"), "metadata": meta_st(),
     "spec": st.fixed_dictionaries(
         {"containers": st.lists(container_st, min_size=1, max_size=2)},
         optional={
             "nodeName": name_st,
             "nodeSelector": labels_st,
             "priority": st.integers(-100, 10**6),
             "priorityClassName": name_st,
             "restartPolicy": st.sampled_from(["Always", "OnFailure", "Never"]),
             "terminationGracePeriodSeconds": st.integers(0, 300),
             "preemptionPolicy": st.sampled_from(
                 ["PreemptLowerPriority", "Never"]),
             "hostNetwork": st.booleans(),
             "serviceAccountName": name_st,
             "schedulingGates": st.lists(name_st, max_size=2),
             "tolerations": st.lists(st.fixed_dictionaries(
                 {"key": label_key},
                 optional={"operator": st.sampled_from(["Exists", "Equal"]),
                           "value": label_val,
                           "effect": st.sampled_from(
                               ["NoSchedule", "PreferNoSchedule", "NoExecute"]),
                           "tolerationSeconds": st.integers(0, 3600)}),
                 max_size=2),
             "resourceClaims": st.lists(st.fixed_dictionaries(
                 {"name": name_st, "resourceClaimName": name_st}), max_size=2),
         })},
)


def _stable(resource: str, doc: dict) -> None:
    """to_dict(from_dict(x)) must be a fixed point after one round."""
    once = to_dict(from_dict(resource, doc))
    twice = to_dict(from_dict(resource, once))
    assert once == twice, f"{resource} round-trip not stable:\n{once}\nvs\n{twice}"


@settings(max_examples=150, deadline=None)
@given(pod_st)
def test_pod_roundtrip_stable(doc):
    _stable("pods", doc)


node_st = st.fixed_dictionaries(
    {"kind": st.just("Node"), "metadata": meta_st()},
    optional={
        "spec": st.fixed_dictionaries({}, optional={
            "unschedulable": st.booleans(),
            "taints": st.lists(st.fixed_dictionaries(
                {"key": label_key, "effect": st.sampled_from(
                    ["NoSchedule", "PreferNoSchedule", "NoExecute"])},
                optional={"value": label_val}), max_size=2)}),
        "status": st.fixed_dictionaries({}, optional={
            "capacity": st.dictionaries(
                st.sampled_from(["cpu", "memory", "pods"]), qty_st, max_size=3),
            "allocatable": st.dictionaries(
                st.sampled_from(["cpu", "memory", "pods"]), qty_st, max_size=3)}),
    })


@settings(max_examples=100, deadline=None)
@given(node_st)
def test_node_roundtrip_stable(doc):
    _stable("nodes", doc)


claim_st = st.fixed_dictionaries(
    {"kind": st.just("ResourceClaim"), "metadata": meta_st(),
     "spec": st.fixed_dictionaries({"devices": st.fixed_dictionaries({
         "requests": st.lists(st.fixed_dictionaries(
             {"name": name_st, "deviceClassName": name_st},
             optional={"count": st.integers(1, 8)}), min_size=1, max_size=2)})})})


@settings(max_examples=60, deadline=None)
@given(claim_st)
def test_resourceclaim_roundtrip_stable(doc):
    _stable("resourceclaims", doc)


@settings(max_examples=60, deadline=None)
@given(st.fixed_dictionaries(
    {"kind": st.just("PriorityClass"), "metadata": meta_st(),
     "value": st.integers(-(10**9), 10**9)},
    optional={"globalDefault": st.booleans(),
              "preemptionPolicy": st.sampled_from(
                  ["PreemptLowerPriority", "Never"])}))
def test_priorityclass_roundtrip_stable(doc):
    _stable("priorityclasses", doc)


# ---- label-selector grammar + index-compression properties --------------------

_key_st = st.text(alphabet=string.ascii_lowercase + string.digits + "-._/",
                  min_size=1, max_size=12).filter(
    lambda s: not s.startswith(("-", ".", "/")))
_val_st = st.text(alphabet=string.ascii_lowercase + string.digits,
                  min_size=1, max_size=8)


@st.composite
def _selector_clause(draw):
    kind = draw(st.sampled_from(["eq", "ne", "in", "notin", "exists", "nexists"]))
    k = draw(_key_st)
    if kind == "eq":
        return f"{k}={draw(_val_st)}"
    if kind == "ne":
        return f"{k}!={draw(_val_st)}"
    if kind == "in":
        vals = draw(st.lists(_val_st, min_size=1, max_size=3))
        return f"{k} in ({','.join(vals)})"
    if kind == "notin":
        vals = draw(st.lists(_val_st, min_size=1, max_size=3))
        return f"{k} notin ({','.join(vals)})"
    if kind == "exists":
        return k
    return f"!{k}"


@settings(max_examples=150, deadline=None)
@given(st.lists(_selector_clause(), min_size=1, max_size=4),
       st.dictionaries(_key_st, _val_st, max_size=4))
def test_selector_grammar_parses_and_matches_consistently(clauses, labels):
    """Every grammatical selector parses, and matching equals the AND of its
    clauses evaluated through the same Requirement machinery."""
    from kubernetes_tpu.api.labels import parse_selector_string

    raw = ",".join(clauses)
    sel = parse_selector_string(raw)
    assert len(sel.requirements) == len(clauses)
    expect = all(r.matches(labels) for r in sel.requirements)
    assert sel.matches(labels) == expect


@settings(max_examples=200, deadline=None)
@given(st.sets(st.integers(min_value=0, max_value=200), max_size=40))
def test_compress_indexes_round_trips(indexes):
    """completedIndexes compression is lossless: expanding the ranges gives
    back exactly the input set."""
    from kubernetes_tpu.controllers.job import compress_indexes

    out = compress_indexes(indexes)
    expanded = set()
    for part in out.split(","):
        if not part:
            continue
        if "-" in part:
            lo, hi = part.split("-")
            expanded.update(range(int(lo), int(hi) + 1))
        else:
            expanded.add(int(part))
    assert expanded == set(indexes)
