"""Multi-slice / DCN-aware mesh construction and collective-locality checks.

The scale story (SURVEY.md §5 comm backend): one TPU slice is a set of chips
joined by ICI (terabit, microsecond); slices interconnect over DCN (gigabit,
millisecond). The reference spreads its scheduler fan-out over goroutines and
its HA over etcd/gRPC; the TPU-native equivalent is a HYBRID MESH whose outer
axis crosses slices (DCN) and whose inner axis stays inside a slice (ICI),
with shardings arranged so that:

  - the node axis — where every scan step runs segment-sums and a global
    argmax — lives on the INNER (ICI) axis: per-step collectives never leave
    a slice;
  - the pod/batch axis — embarrassingly parallel (one gather at the end) —
    lives on the OUTER (DCN) axis: DCN carries exactly one collective per
    batch, not one per scan step.

Axis names stay ("dp", "nodes") so every NamedSharding in sharded.py works
unchanged on a hybrid mesh; only the device placement underneath changes.

Multi-host bring-up: each host calls jax.distributed.initialize(...) and
jax.devices() then spans all slices; `make_hybrid_mesh()` groups by
`device.slice_index`. Single-host (and the CPU test rig) emulates slices by
folding the flat device list — the GSPMD partitioning and the collective
replica groups are identical either way, which is what the HLO locality test
asserts.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


def slice_topology(devices: Optional[Sequence] = None) -> Dict[int, List]:
    """Group devices by their slice (ICI domain). Real multi-slice TPU
    exposes `slice_index`; anything without one is a single ICI domain."""
    devices = list(devices if devices is not None else jax.devices())
    by_slice: Dict[int, List] = defaultdict(list)
    for d in devices:
        by_slice[getattr(d, "slice_index", 0) or 0].append(d)
    return dict(by_slice)


def make_hybrid_mesh(n_slices: Optional[int] = None,
                     devices: Optional[Sequence] = None) -> Mesh:
    """Mesh whose "dp" axis crosses slices (DCN) and "nodes" axis stays
    intra-slice (ICI). On hardware that reports slice_index the grouping is
    physical; otherwise `n_slices` folds the device list into emulated slices
    (the CPU rig and single-slice chips)."""
    devices = list(devices if devices is not None else jax.devices())
    groups = slice_topology(devices)
    if len(groups) > 1:
        sizes = {len(v) for v in groups.values()}
        if len(sizes) != 1:
            raise ValueError(f"uneven slices: { {k: len(v) for k, v in groups.items()} }")
        if n_slices is not None and n_slices != len(groups):
            raise ValueError(f"hardware has {len(groups)} slices, asked for {n_slices}")
        arr = np.array([groups[k] for k in sorted(groups)])
    else:
        n_slices = n_slices or 1
        if len(devices) % n_slices:
            raise ValueError(f"{len(devices)} devices do not fold into "
                             f"{n_slices} slices")
        arr = np.array(devices).reshape(n_slices, -1)
    return Mesh(arr, ("dp", "nodes"))


# ---- collective locality audit ------------------------------------------------

_OPS = r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
# v1 list format: replica_groups={{0,1,2,3},{4,5,6,7}}
_V1_RE = re.compile(_OPS + r"[^\n]*replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}")
# v2 iota format: replica_groups=[2,4]<=[8] or [4,2]<=[2,4]T(1,0)
_V2_RE = re.compile(
    _OPS + r"[^\n]*replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]"
    r"(?:T\(([\d,]+)\))?")


def _iota_groups(g: int, s: int, dims: List[int],
                 perm: Optional[List[int]]) -> List[List[int]]:
    """Expand the v2 iota replica-group spec: devices = iota(prod(dims))
    .reshape(dims).transpose(perm).flatten(), split into g rows of s."""
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if perm is not None:
        ids = ids.transpose(perm)
    return ids.reshape(g, s).tolist()


def collective_replica_groups(compiled_text: str) -> List[Tuple[str, List[List[int]]]]:
    """Parse (op, replica_groups) out of compiled HLO text — both the literal
    {{...}} and the iota [g,s]<=[dims]T(perm) spellings."""
    out: List[Tuple[str, List[List[int]]]] = []
    for m in _V1_RE.finditer(compiled_text):
        groups = [[int(x) for x in g.strip("{}").split(",") if x.strip() != ""]
                  for g in re.findall(r"\{[^}]*\}", m.group(2))]
        out.append((m.group(1), groups))
    for m in _V2_RE.finditer(compiled_text):
        g, s = int(m.group(2)), int(m.group(3))
        dims = [int(x) for x in m.group(4).split(",")]
        perm = [int(x) for x in m.group(5).split(",")] if m.group(5) else None
        out.append((m.group(1), _iota_groups(g, s, dims, perm)))
    # replica_groups={} means "one group of everything" — report as a single
    # group of -1 so audit treats it as crossing
    for m in re.finditer(_OPS + r"[^\n]*replica_groups=\{\}", compiled_text):
        out.append((m.group(1), [[-1, -2]]))
    return out


def audit_collectives(fn, mesh: Mesh, *args, dcn_ok: Sequence[str] = (),
                      **kwargs) -> Dict[str, int]:
    """Compile `fn` under `mesh` and verify every collective's replica group
    stays inside one slice (one row of the mesh's device array). Collectives
    named in `dcn_ok` (by HLO op) may cross. Returns {"ici": n, "dcn": n}
    counts; raises AssertionError when a non-exempt collective crosses DCN.

    This is the profile-free version of "look at the xplane and check which
    collectives ride which fabric": replica groups are decided at compile
    time, so locality is checkable without hardware."""
    with jax.sharding.set_mesh(mesh):
        compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    text = compiled.as_text()
    # device id -> slice row
    row_of: Dict[int, int] = {}
    for r, row in enumerate(mesh.devices):
        for d in row:
            row_of[d.id] = r
    counts = {"ici": 0, "dcn": 0}
    for op, groups in collective_replica_groups(text):
        # unknown ids (incl. the empty-replica_groups sentinel) keep their own
        # identity so a global collective reads as crossing, never as local
        crosses = any(len({row_of.get(i, i) for i in g}) > 1 for g in groups)
        if crosses:
            counts["dcn"] += 1
            if op not in dcn_ok:
                raise AssertionError(
                    f"{op} crosses slices (replica_groups={groups}); "
                    f"only {list(dcn_ok)} may ride DCN")
        else:
            counts["ici"] += 1
    return counts
