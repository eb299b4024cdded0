"""schedlint (ISSUE 5): the static-analysis tier-1 gate.

Three layers:
  (a) the whole-tree run — `kubernetes_tpu/` must carry ZERO unsuppressed
      findings and every inline suppression must have a written reason;
  (b) rule fixtures — every rule provably FIRES on its bad-code fixture and
      stays QUIET on the matching good-code fixture (an analyzer that stops
      firing is worse than none: it certifies rot);
  (c) a wall-time bound so the gate stays cheap.
"""

import os
import time

import pytest

from kubernetes_tpu.analysis.schedlint import (
    analyze_source,
    analyze_sources,
    package_root,
    run_paths,
)

# ---------------------------------------------------------------------------
# (a) the shipped tree is clean
# ---------------------------------------------------------------------------


def test_tree_is_clean_and_suppressions_carry_reasons():
    findings, stats = run_paths([package_root()])
    assert stats["parse_errors"] == 0
    # SL001 findings are reasonless suppressions; anything else is a real
    # invariant violation — both fail the gate
    assert findings == [], "\n".join(f.render() for f in findings)
    # the shipped tree documents its intentional exceptions inline (the
    # Watch._deliver* wake pings, LK002; shm.py's fresh-segment header
    # writes, SEQ002 — generations invisible until the control word flips)
    assert stats["suppressed"] >= 4
    # ISSUE 20: the interprocedural closure actually resolved something
    assert stats["callgraph_edges"] > 500, stats
    assert stats["resolve_depth"] >= 2, stats


def test_wall_time_stays_cheap():
    t0 = time.perf_counter()
    run_paths([package_root()])
    wall = time.perf_counter() - t0
    # ~170 files parse+analyze in a few seconds even on the co-scheduled
    # 2-core rig; 30s means the gate has become the slowest thing in tier-1
    assert wall < 30.0, wall


# ---------------------------------------------------------------------------
# (b) rule fixtures
# ---------------------------------------------------------------------------


def rules_of(findings):
    return {f.rule for f in findings}


LK001_BAD = '''
import threading

class APIStore:
    def __init__(self):
        self._lock = threading.RLock()
        self._pods_lock = threading.RLock()

    def inverted(self):
        with self._pods_lock:
            with self._lock:
                return 1

    def takes_global(self):
        with self._lock:
            return 2

    def inverted_via_call(self):
        with self._pods_lock:
            return self.takes_global()
'''

LK001_GOOD = '''
import threading

class APIStore:
    def __init__(self):
        self._lock = threading.RLock()
        self._pods_lock = threading.RLock()
        self._pods_pair = None

    def mandated_order(self):
        with self._lock:
            with self._pods_lock:
                return 1

    def pair(self):
        with self._pods_pair:
            return 2

    def two_phase(self):
        # bind_many's pattern: shard alone, RELEASE, then global+shard
        with self._pods_lock:
            x = 1
        with self._lock:
            with self._pods_lock:
                return x
'''


def test_lk001_fires_on_inversion_and_call_path():
    findings = [f for f in analyze_source(LK001_BAD) if f.rule == "LK001"]
    assert len(findings) == 2, findings
    assert any("call to" in f.message for f in findings)


def test_lk001_quiet_on_mandated_order():
    assert "LK001" not in rules_of(analyze_source(LK001_GOOD))


# LK001 generalized shard rule (ISSUE 15 satellite): the ordering table in
# store/store.py ranks _lock (0) -> _pods_lock (1) -> _nodes_lock (2);
# holding a shard, any acquisition of LOWER rank — the global lock or a
# lower-ranked shard, direct or via a resolved call path — is an inversion.

LK001_NODES_BAD = '''
import threading

class APIStore:
    def __init__(self):
        self._lock = threading.RLock()
        self._pods_lock = threading.RLock()
        self._nodes_lock = threading.RLock()

    def nodes_then_global(self):
        with self._nodes_lock:
            with self._lock:
                return 1

    def nodes_then_pods(self):
        with self._nodes_lock:
            with self._pods_lock:
                return 2

    def takes_pods_shard(self):
        with self._pods_lock:
            return 3

    def nodes_then_pods_via_call(self):
        with self._nodes_lock:
            return self.takes_pods_shard()
'''

LK001_NODES_GOOD = '''
import threading

class APIStore:
    def __init__(self):
        self._lock = threading.RLock()
        self._pods_lock = threading.RLock()
        self._nodes_lock = threading.RLock()
        self._nodes_pair = None
        self._store_chain = None

    def full_chain_order(self):
        with self._lock:
            with self._pods_lock:
                with self._nodes_lock:
                    return 1

    def pods_then_nodes(self):
        # ascending rank: legal without the global lock too
        with self._pods_lock:
            with self._nodes_lock:
                return 2

    def nodes_pair(self):
        with self._nodes_pair:
            return 3

    def chain(self):
        with self._store_chain:
            return 4
'''


def test_lk001_generalized_fires_on_nodes_shard_inversions():
    findings = [f for f in analyze_source(LK001_NODES_BAD)
                if f.rule == "LK001"]
    # nodes->global, nodes->pods (direct), nodes->pods (via call)
    assert len(findings) == 3, findings
    assert any("call to" in f.message for f in findings)
    assert any("higher-ranked" in f.message for f in findings)


def test_lk001_generalized_quiet_on_ascending_rank():
    assert "LK001" not in rules_of(analyze_source(LK001_NODES_GOOD))


# LK001 partition extension (ISSUE 12): the dispatch-layer locks
# (PartitionRouter._route_lock / PartitionedScheduler._dispatch_lock) are
# LEAF locks — a store-lock acquisition (direct or via any resolved call
# path) while one is held is an inversion.

LK001_PART_BAD = '''
import threading

class APIStore:
    def __init__(self):
        self._lock = threading.RLock()
        self._pods_lock = threading.RLock()

    def commit_rows(self):
        with self._lock:
            with self._pods_lock:
                return 1

class PartitionRouter:
    def __init__(self):
        self._route_lock = threading.Lock()
        self.store = APIStore()

    def bad_store_call_under_route_lock(self):
        with self._route_lock:
            # routing decisions must not reach into the store: commit_rows
            # takes the global+shard chain UNDER the leaf lock
            return self.store.commit_rows()

class PartitionedScheduler:
    def __init__(self):
        self._dispatch_lock = threading.Lock()
        self.store = APIStore()

    def bad_store_call_under_dispatch_lock(self):
        with self._dispatch_lock:
            return self.store.commit_rows()
'''

LK001_PART_GOOD = '''
import threading

class APIStore:
    def __init__(self):
        self._lock = threading.RLock()
        self._pods_lock = threading.RLock()

    def commit_rows(self):
        with self._lock:
            with self._pods_lock:
                return 1

class PartitionRouter:
    def __init__(self):
        self._route_lock = threading.Lock()
        self._overrides = {}
        self.store = APIStore()

    def decide_then_act(self, key):
        # the mandated shape: bookkeeping under the leaf lock, release,
        # THEN call the store
        with self._route_lock:
            target = self._overrides.get(key)
        if target is None:
            return self.store.commit_rows()
        return target

class PartitionedScheduler:
    def __init__(self):
        self._dispatch_lock = threading.Lock()
        self._parked = []

    def park(self, qp):
        with self._dispatch_lock:
            self._parked.append(qp)
'''


def test_lk001_fires_on_store_call_under_partition_lock():
    findings = [f for f in analyze_source(LK001_PART_BAD)
                if f.rule == "LK001"]
    assert len(findings) == 2, findings
    assert all("partition/dispatch leaf lock" in f.message
               for f in findings), findings


def test_lk001_quiet_on_decide_then_act_partition_shape():
    assert "LK001" not in rules_of(analyze_source(LK001_PART_GOOD))


LK002_BAD = '''
import threading
import time

class Store:
    def __init__(self):
        self._lock = threading.RLock()
        self.on_event = None

    def sleepy(self):
        with self._lock:
            time.sleep(0.1)

    def queue_put(self, work_q, item):
        with self._lock:
            work_q.put(item)

    def callback(self):
        with self._lock:
            cb = self.on_event
            cb()

    def _emit(self):
        self._deliver()

    def _deliver(self):
        time.sleep(1.0)  # blocking, reachable from the locked caller

    def locked_entry(self):
        with self._lock:
            self._emit()
'''

LK002_GOOD = '''
import threading
import time

class Store:
    def __init__(self):
        self._lock = threading.RLock()

    def nowait(self, work_q, item):
        with self._lock:
            work_q.put_nowait(item)

    def outside(self, work_q, item):
        with self._lock:
            payload = item
        work_q.put(payload)
        time.sleep(0.0)
'''


def test_lk002_fires_on_blocking_calls_under_lock():
    findings = [f for f in analyze_source(LK002_BAD) if f.rule == "LK002"]
    msgs = "\n".join(f.message for f in findings)
    assert len(findings) == 4, msgs
    assert "time.sleep" in msgs
    assert "queue .put" in msgs
    assert "watch callback" in msgs
    assert "reachable" in msgs  # the interprocedural one


def test_lk002_quiet_on_nowait_and_outside_lock():
    assert "LK002" not in rules_of(analyze_source(LK002_GOOD))


# ISSUE 11: the GIL-releasing native kernels (ctypes CDLL wrappers in
# native/hostsched.py) are blocking calls under LK002 — dropping the GIL
# inside a store lock region invites GIL/lock interleavings (the NATIVE LOCK
# RULE in store/store.py). The PyDLL commit-engine entries hold the GIL and
# stay legal under the locks.

LK002_NATIVE_BAD = '''
import threading

from kubernetes_tpu.native import native_commit_deltas, native_greedy_solve

class Store:
    def __init__(self):
        self._lock = threading.RLock()

    def scatter_under_lock(self, rows, nodes, raw, raw_nz, n):
        with self._lock:
            return native_commit_deltas(rows, nodes, raw, raw_nz, n)

    def solve_under_lock(self, cluster, batch):
        with self._lock:
            return native_greedy_solve(cluster, batch)
'''

LK002_NATIVE_GOOD = '''
import threading

from kubernetes_tpu.native import hostcommit, native_commit_deltas

class Store:
    def __init__(self):
        self._lock = threading.RLock()

    def scatter_outside(self, rows, nodes, raw, raw_nz, n):
        with self._lock:
            payload = (rows, nodes)
        return native_commit_deltas(rows, nodes, raw, raw_nz, n)

    def commit_under_lock(self, pods, bindings, prepared, errors):
        # the PyDLL commit engine HOLDS the GIL: legal under the store lock
        with self._lock:
            hostcommit.bind_prepare(pods, bindings, prepared, errors)
'''


def test_lk002_fires_on_native_kernel_under_lock():
    findings = [f for f in analyze_source(LK002_NATIVE_BAD)
                if f.rule == "LK002"]
    msgs = "\n".join(f.message for f in findings)
    assert len(findings) == 2, msgs
    assert "GIL-releasing native kernel" in msgs
    assert "native_commit_deltas" in msgs and "native_greedy_solve" in msgs


def test_lk002_quiet_on_pydll_commit_and_outside_lock():
    assert "LK002" not in rules_of(analyze_source(LK002_NATIVE_GOOD))


MU001_BAD = '''
def mutate_get(self):
    pod = self.store.get("pods", "default/a")
    pod.metadata.labels["x"] = "1"

def mutate_event(events):
    for ev in events:
        ev.obj.status.phase = "Failed"

def mutate_list_element(self):
    pods, _rv = self.store.list("pods")
    for p in pods:
        p.spec.node_name = "n1"

def mutate_forced(self, ev):
    payload = ev.obj
    object.__setattr__(payload, "type", "DELETED")
'''

MU001_GOOD = '''
import copy

def clone_then_mutate(self):
    pod = copy.deepcopy(self.store.get("pods", "default/a"))
    pod.metadata.labels["x"] = "1"

def read_only(events, out):
    for ev in events:
        out.append(ev.obj.metadata.name)

def sort_fresh_list(self):
    pods, _rv = self.store.list("pods")
    pods.sort(key=lambda p: p.metadata.name)
    return pods
'''


def test_mu001_fires_on_store_and_event_mutation():
    findings = [f for f in analyze_source(MU001_BAD) if f.rule == "MU001"]
    assert len(findings) == 4, findings


def test_mu001_quiet_on_clones_reads_and_container_ops():
    assert "MU001" not in rules_of(analyze_source(MU001_GOOD))


# MU001 columnar extension (ISSUE 15 satellite): the rows/views handed out
# by the columnar read path (`store.pod_columns()`) are store-returned
# READ-ONLY objects — writes through the view (element stores into its
# arrays/lists, mutator calls on its members) taint exactly like event
# objects; copies launder as usual.

MU001_COLUMNAR_BAD = '''
def poke_view_array(self):
    cols = self.store.pod_columns()
    cols.node_id[0] = 3

def poke_view_table(self):
    view = self.store.pod_columns()
    view.node_names.append("sneaky")

def poke_view_base(self):
    view = self.store.pod_columns()
    view.base[0].spec.node_name = "n1"
'''

MU001_COLUMNAR_GOOD = '''
def read_counts(self):
    cols = self.store.pod_columns()
    return int((cols.node_id >= 0).sum())

def copy_then_mutate(self):
    cols = self.store.pod_columns()
    mine = cols.node_id.copy()
    mine[0] = 3
    return mine

def stats_only(self):
    return self.store.columnar_stats()
'''


def test_mu001_fires_on_columnar_view_mutation():
    findings = [f for f in analyze_source(MU001_COLUMNAR_BAD)
                if f.rule == "MU001"]
    assert len(findings) == 3, findings


def test_mu001_quiet_on_columnar_reads_and_copies():
    assert "MU001" not in rules_of(analyze_source(MU001_COLUMNAR_GOOD))


# MU001 cache-rows extension (ISSUE 16 satellite): Cache.pod_columns() hands
# out a CacheColumnsView over the live scheduler-cache row table — the same
# read-only contract as the store view (runtime-enforced writeable=False
# numpy + this static rule).

MU001_CACHECOLS_BAD = '''
def poke_cache_view_array(self):
    cols = self.cache.pod_columns()
    cols.node_id[0] = 3

def poke_cache_view_pod(self):
    view = self.cache.pod_columns()
    view.pod[0].spec.node_name = "n1"

def poke_cache_view_index(self):
    view = self.cache.pod_columns()
    view.key2row.pop("default/p0")
'''

MU001_CACHECOLS_GOOD = '''
def read_cache_rows(self):
    cols = self.cache.pod_columns()
    return int((cols.node_id >= 0).sum())

def copy_then_mutate(self):
    cols = self.cache.pod_columns()
    mine = cols.node_id.copy()
    mine[0] = 3
    return mine

def stats_only(self):
    return self.cache.columnar_stats()
'''


def test_mu001_fires_on_cache_view_mutation():
    findings = [f for f in analyze_source(MU001_CACHECOLS_BAD)
                if f.rule == "MU001"]
    assert len(findings) == 3, findings


def test_mu001_quiet_on_cache_view_reads_and_copies():
    assert "MU001" not in rules_of(analyze_source(MU001_CACHECOLS_GOOD))


def test_cache_columns_view_is_runtime_readonly():
    """The CacheColumnsView numpy member enforces the contract at runtime,
    like the store's PodColumnsView (ro() writeable=False pattern)."""
    import pytest

    from kubernetes_tpu.scheduler.cachecols import (CacheColumns,
                                                    CacheColumnsView,
                                                    numpy_available)
    if not numpy_available():
        pytest.skip("numpy required")
    cols = CacheColumns()

    class _P:
        pass

    cols.insert("default/p0", _P(), "node-1")
    view = CacheColumnsView(cols)
    with pytest.raises(ValueError):
        view.node_id[0] = 7
    assert view.n == 1 and view.node_names[view.node_id[0]] == "node-1"


JT001_BAD = '''
import functools
import jax

@functools.partial(jax.jit, static_argnames=("k_slots",))
def solve(x, k_slots):
    return x[:k_slots]

def driver(x, members):
    return solve(x, k_slots=len(members))
'''

JT001_GOOD = '''
import functools
import jax

@functools.partial(jax.jit, static_argnames=("k_slots", "has_gang"))
def solve(x, k_slots, has_gang=False):
    return x[:k_slots]

def driver(x, members, gang):
    k_slots = 1 << (len(members) - 1).bit_length()  # pow2 bucket
    return solve(x, k_slots=k_slots, has_gang=bool(gang.size))
'''


def test_jt001_fires_on_raw_len_into_static_arg():
    findings = [f for f in analyze_source(JT001_BAD) if f.rule == "JT001"]
    assert len(findings) == 1, findings
    assert "k_slots" in findings[0].message


def test_jt001_quiet_on_bucketed_and_bool_gated_statics():
    assert "JT001" not in rules_of(analyze_source(JT001_GOOD))


JT002_BAD = '''
import functools
import jax
import jax.numpy as jnp
import numpy as np

@functools.partial(jax.jit, static_argnames=())
def solve(x):
    total = jnp.sum(x)
    host = float(total)          # host sync inside the traced body
    arr = np.asarray(x)          # numpy inside jit
    return host, arr

def helper(v):
    return v.item()              # host sync, traced via solve2

@jax.jit
def solve2(x):
    return helper(jnp.max(x))
'''

JT002_GOOD = '''
import functools
import jax
import jax.numpy as jnp
import numpy as np

@functools.partial(jax.jit, static_argnames=())
def solve(x):
    return jnp.sum(x).astype(jnp.float32)

def host_driver(x):
    out = solve(jnp.asarray(x))
    return float(out), np.asarray(out)   # host conversion OUTSIDE the jit
'''


def test_jt002_fires_on_host_sync_inside_jit_bodies():
    findings = [f for f in analyze_source(JT002_BAD) if f.rule == "JT002"]
    msgs = "\n".join(f.message for f in findings)
    assert len(findings) == 3, msgs
    assert "float()" in msgs and "numpy call" in msgs and ".item()" in msgs
    assert "traced via" in msgs  # helper reached through the call graph


def test_jt002_quiet_outside_the_jit_boundary():
    assert "JT002" not in rules_of(analyze_source(JT002_GOOD))


# ISSUE 8: the repair kernel's static-gate discipline. The propose-and-
# repair solver (models/repair.py) keys its jitted violation check on bool
# constraint gates and a pow2-bucketed pod axis; the bug class JT001 guards
# is someone keying it on the raw batch length or a raw round count instead
# (one compile per batch size / per repair round — tens of seconds each at
# TPU scale).

JT001_REPAIR_BAD = '''
import functools
import jax

@functools.partial(jax.jit, static_argnames=("pb", "has_affinity"))
def repair_check(node_of, pb, has_affinity=True):
    return node_of[:pb]

def check(assignment, violators):
    # raw lengths key the jit: a compile per batch size AND per violator
    # count — the exact retrace class the pow2 bucket exists to prevent
    return repair_check(assignment, pb=len(assignment),
                        has_affinity=len(violators) > 0)
'''

JT001_REPAIR_GOOD = '''
import functools
import jax

@functools.partial(jax.jit, static_argnames=("pb", "has_affinity", "has_ct"))
def repair_check(node_of, pb, has_affinity=True, has_ct=True):
    return node_of[:pb]

def check(assignment, batch, p):
    # the shipped discipline: pow2 pod-axis bucket (floored so small
    # batches share one shape) + bool constraint-family gates
    pb = max(256, 1 << (p - 1).bit_length())
    return repair_check(assignment, pb=pb,
                        has_affinity=bool(batch.ipa.has_any),
                        has_ct=bool(batch.ct_class.size))
'''


def test_jt001_fires_on_repair_kernel_raw_static_keys():
    findings = [f for f in analyze_source(JT001_REPAIR_BAD)
                if f.rule == "JT001"]
    assert len(findings) >= 1, findings
    assert any("pb" in f.message for f in findings)


def test_jt001_quiet_on_repair_kernel_shipped_gates():
    assert "JT001" not in rules_of(analyze_source(JT001_REPAIR_GOOD))


JT002_REPAIR_BAD = '''
import functools
import jax
import jax.numpy as jnp
import numpy as np

@functools.partial(jax.jit, static_argnames=("d_max",))
def repair_check(node_of, counts, d_max):
    placed = node_of >= 0
    host = np.nonzero(np.asarray(placed))[0]   # numpy readback INSIDE jit
    return host

def violators(node_of, counts, d_max):
    return repair_check(node_of, counts, d_max)
'''

JT002_REPAIR_GOOD = '''
import functools
import jax
import jax.numpy as jnp
import numpy as np

@functools.partial(jax.jit, static_argnames=("d_max",))
def repair_check(node_of, counts, d_max):
    return node_of >= 0

def violators(node_of, counts, d_max, p):
    # the shipped discipline: the host readback (_check's np.asarray +
    # nonzero) happens OUTSIDE the traced body, once per round
    v = repair_check(node_of, counts, d_max)
    return np.nonzero(np.asarray(v)[:p])[0]
'''


def test_jt002_fires_on_host_readback_inside_repair_kernel():
    findings = [f for f in analyze_source(JT002_REPAIR_BAD)
                if f.rule == "JT002"]
    assert len(findings) >= 1, findings


def test_jt002_quiet_on_host_readback_outside_repair_kernel():
    assert "JT002" not in rules_of(analyze_source(JT002_REPAIR_GOOD))


# ISSUE 14: the gang victim-cover / rank-adjacency kernels' static-gate
# discipline (models/gangcover.py). cover_curve keys on pow2 node/victim
# buckets and rank_align_kernel on the pow2 pod axis; the guarded bug class
# is keying either on a RAW slice size / victim count / batch length — one
# compile per cluster shape or per cover attempt.

JT001_GANGCOVER_BAD = '''
import functools
import jax

@functools.partial(jax.jit, static_argnames=("n_slots", "k_max"))
def cover_curve(free, v_node, n_slots, k_max):
    return free[:n_slots], v_node[:k_max]

def cover_curves(free, v_node):
    # raw slice-node and victim counts key the jit: a compile per slice
    # shape AND per candidate-victim count
    return cover_curve(free, v_node, n_slots=len(free),
                       k_max=len(v_node))
'''

JT001_GANGCOVER_GOOD = '''
import functools
import jax

@functools.partial(jax.jit, static_argnames=("n_slots", "k_max"))
def cover_curve(free, v_node, n_slots, k_max):
    return free[:n_slots], v_node[:k_max]

def cover_curves(free, v_node, ns, k):
    # the shipped discipline: pow2 buckets over both padded axes
    n_slots = 1 << max(0, ns - 1).bit_length()
    k_max = 1 << max(0, k - 1).bit_length()
    return cover_curve(free, v_node, n_slots=n_slots, k_max=k_max)
'''


def test_jt001_fires_on_gangcover_raw_static_keys():
    findings = [f for f in analyze_source(JT001_GANGCOVER_BAD)
                if f.rule == "JT001"]
    assert len(findings) >= 1, findings
    assert any("n_slots" in f.message or "k_max" in f.message
               for f in findings)


def test_jt001_quiet_on_gangcover_shipped_buckets():
    assert "JT001" not in rules_of(analyze_source(JT001_GANGCOVER_GOOD))


JT002_GANGCOVER_BAD = '''
import functools
import jax
import jax.numpy as jnp
import numpy as np

@functools.partial(jax.jit, static_argnames=("p_max",))
def rank_align_kernel(assignment, group_id, rank, pos_key, p_max):
    idx = jnp.arange(p_max)
    order_rank = jnp.lexsort((idx, rank, group_id))
    # host sort INSIDE the traced body: a device round-trip per call
    order_pos = np.lexsort((np.asarray(idx), np.asarray(pos_key)))
    return assignment[order_rank], order_pos
'''

JT002_GANGCOVER_GOOD = '''
import functools
import jax
import jax.numpy as jnp
import numpy as np

@functools.partial(jax.jit, static_argnames=("p_max",))
def rank_align_kernel(assignment, group_id, rank, pos_key, p_max):
    idx = jnp.arange(p_max)
    order_rank = jnp.lexsort((idx, rank, group_id))
    order_pos = jnp.lexsort((idx, pos_key, group_id))
    return jnp.zeros_like(assignment).at[order_rank].set(
        assignment[order_pos])

def rank_align(assignment, group_id, rank, pos_key, p):
    # the shipped discipline: numpy padding happens OUTSIDE the traced body
    p_max = 1 << max(0, p - 1).bit_length()
    a = np.asarray(assignment)
    return rank_align_kernel(a, group_id, rank, pos_key, p_max=p_max)
'''


def test_jt002_fires_on_host_sort_inside_gangcover_kernel():
    findings = [f for f in analyze_source(JT002_GANGCOVER_BAD)
                if f.rule == "JT002"]
    assert len(findings) >= 1, findings


def test_jt002_quiet_on_host_padding_outside_gangcover_kernel():
    assert "JT002" not in rules_of(analyze_source(JT002_GANGCOVER_GOOD))


JT001_DEFRAG_BAD = '''
import functools
import jax

@functools.partial(jax.jit, static_argnames=("n_slots", "v_max"))
def defrag_assign(free, headroom, target_ok, v_req, v_valid, n_slots, v_max):
    return free[:n_slots], v_req[:v_max]

def defrag_plan(free, headroom, target_ok, v_req):
    # raw node and victim counts key the jit: a compile per cluster size
    # AND per candidate-victim count — the rebalancer would recompile on
    # every cycle whose donor slice drains a different number of pods
    return defrag_assign(free, headroom, target_ok, v_req, v_req,
                         n_slots=len(free), v_max=len(v_req))
'''

JT001_DEFRAG_GOOD = '''
import functools
import jax

@functools.partial(jax.jit, static_argnames=("n_slots", "v_max"))
def defrag_assign(free, headroom, target_ok, v_req, v_valid, n_slots, v_max):
    return free[:n_slots], v_req[:v_max]

def defrag_plan(free, headroom, target_ok, v_req, ns, v):
    # the shipped discipline: pow2 buckets over both padded axes, so the
    # kernel compiles once per doubling, not once per cycle
    n_slots = 1 << max(0, ns - 1).bit_length()
    v_max = 1 << max(0, v - 1).bit_length()
    return defrag_assign(free, headroom, target_ok, v_req, v_req,
                         n_slots=n_slots, v_max=v_max)
'''


def test_jt001_fires_on_defrag_raw_static_keys():
    findings = [f for f in analyze_source(JT001_DEFRAG_BAD)
                if f.rule == "JT001"]
    assert len(findings) >= 1, findings
    assert any("n_slots" in f.message or "v_max" in f.message
               for f in findings)


def test_jt001_quiet_on_defrag_shipped_buckets():
    assert "JT001" not in rules_of(analyze_source(JT001_DEFRAG_GOOD))


JT002_DEFRAG_BAD = '''
import functools
import jax
import jax.numpy as jnp
import numpy as np

@functools.partial(jax.jit, static_argnames=("n_slots", "v_max"))
def defrag_assign(free, headroom, target_ok, v_req, v_valid, n_slots, v_max):
    def step(carry, xs):
        fr, hd = carry
        vr, valid = xs
        fits = (fr >= vr[None, :]).all(axis=1) & (hd > 0) & target_ok
        waste = jnp.sum(fr - vr[None, :], axis=1)
        # host argmin INSIDE the scan body: a device round-trip per victim
        tgt = int(np.argmin(np.where(np.asarray(fits),
                                     np.asarray(waste), 2**30)))
        fr = fr.at[tgt].add(-vr)
        hd = hd.at[tgt].add(-1)
        return (fr, hd), tgt
    _, out = jax.lax.scan(step, (free, headroom), (v_req, v_valid),
                          length=v_max)
    return out
'''

JT002_DEFRAG_GOOD = '''
import functools
import jax
import jax.numpy as jnp
import numpy as np

@functools.partial(jax.jit, static_argnames=("n_slots", "v_max"))
def defrag_assign(free, headroom, target_ok, v_req, v_valid, n_slots, v_max):
    def step(carry, xs):
        fr, hd = carry
        vr, valid = xs
        fits = (fr >= vr[None, :]).all(axis=1) & (hd > 0) & target_ok
        waste = jnp.sum(fr - vr[None, :], axis=1)
        key = jnp.where(fits, waste, jnp.int32(2**30))
        tgt = jnp.argmin(key).astype(jnp.int32)
        place = (key[tgt] < jnp.int32(2**30)) & valid
        fr = fr.at[tgt].add(-vr * place)
        hd = hd.at[tgt].add(-place.astype(hd.dtype))
        return (fr, hd), jnp.where(place, tgt, jnp.int32(-1))
    _, out = jax.lax.scan(step, (free, headroom), (v_req, v_valid),
                          length=v_max)
    return out

def defrag_plan(free, headroom, target_ok, v_req, ns, v):
    # the shipped discipline: numpy padding happens OUTSIDE the traced body
    n_slots = 1 << max(0, ns - 1).bit_length()
    v_max = 1 << max(0, v - 1).bit_length()
    free_p = np.zeros((n_slots, free.shape[1]), dtype=np.int32)
    free_p[:ns] = free
    return defrag_assign(free_p, headroom, target_ok, v_req, v_req,
                         n_slots=n_slots, v_max=v_max)
'''


def test_jt002_fires_on_host_argmin_inside_defrag_scan():
    findings = [f for f in analyze_source(JT002_DEFRAG_BAD)
                if f.rule == "JT002"]
    assert len(findings) >= 1, findings


def test_jt002_quiet_on_host_padding_outside_defrag_kernel():
    assert "JT002" not in rules_of(analyze_source(JT002_DEFRAG_GOOD))


HP001_BAD = '''
import time

def schedule_batch(self, qps, m):
    for qp in qps:
        t0 = time.perf_counter()
        self.place(qp)
        m.batch_stage_duration.observe(time.perf_counter() - t0, "pod")
'''

HP001_GOOD = '''
import time

def schedule_batch(self, qps, m):
    t0 = time.perf_counter()
    for qp in qps:
        self.place(qp)
    m.batch_stage_duration.observe(time.perf_counter() - t0, "batch")

def chunk_timing_ok(self, to_bind, m):
    # 3-arg range = CHUNK loop (pods/bind_chunk iterations): per-chunk
    # instrumentation is the recorder's own design
    for lo in range(0, len(to_bind), 4096):
        t0 = time.perf_counter()
        self.commit(to_bind[lo:lo + 4096])
        m.batch_stage_duration.observe(time.perf_counter() - t0, "bind")
'''

_HOT = "kubernetes_tpu/scheduler/batch.py"


def test_hp001_fires_on_per_pod_instrumentation():
    findings = [f for f in analyze_source(HP001_BAD, filename=_HOT)
                if f.rule == "HP001"]
    assert len(findings) >= 2, findings


def test_hp001_quiet_per_batch_and_per_chunk():
    assert "HP001" not in rules_of(analyze_source(HP001_GOOD, filename=_HOT))


def test_hp001_scoped_to_hot_files():
    # the same bad code outside scheduler/batch.py is not HP001's business
    assert "HP001" not in rules_of(
        analyze_source(HP001_BAD, filename="kubernetes_tpu/cli/ktl.py"))


# ISSUE 7: the pod tracer's per-pod lifecycle stamping is legal ONLY behind
# a membership check against the sampled set — the guard bounds the paying
# population to the K reservoir slots. Unguarded stamping in a pod-scale
# loop of podtrace.py is the same 100k-multiplier bug HP001 exists for.

HP001_TRACE_BAD = '''
def batch_popped(self, qps, now):
    for qp in qps:
        sp = self._live.get(qp.key)
        sp.stamp("pop", now)
'''

HP001_TRACE_GOOD = '''
def batch_popped(self, qps, now):
    for qp in qps:
        if qp.key in self._sampled:
            sp = self._live.get(qp.key)
            sp.stamp("pop", now)

def chunk_bound(self, items, t_commit, errkeys):
    for qp, _node, _a in items:
        if qp.key in self._sampled and qp.key not in errkeys:
            sp = self._live.get(qp.key)
            sp.stamp("bind_commit", t_commit)
'''

_TRACE = "kubernetes_tpu/scheduler/podtrace.py"


def test_hp001_fires_on_unguarded_tracer_stamp():
    findings = [f for f in analyze_source(HP001_TRACE_BAD, filename=_TRACE)
                if f.rule == "HP001"]
    assert len(findings) == 1, findings
    assert ".stamp()" in findings[0].message


def test_hp001_quiet_behind_sampled_membership_guard():
    assert "HP001" not in rules_of(
        analyze_source(HP001_TRACE_GOOD, filename=_TRACE))


# ISSUE 9: controllers/base.py reconcile loops are HP001 hot paths too — a
# per-key metrics observe (or per-event perf_counter) inside the workqueue
# drain or the watch-buffer drain is the same multiplier bug; the
# ReconcileRecorder taps are per LOOP (recorder.loop()/pump() around the
# whole drain).

HP001_CONTROLLER_BAD = '''
import time

def process(self, keys, m):
    for key in keys:
        t0 = time.perf_counter()
        self.sync(key)
        m.controller_reconcile_duration.observe(
            time.perf_counter() - t0, "key")

def pump(self, clock):
    for ev in self._watch.drain(10_000):
        clock.mark("event")
        self._mark(ev.obj.key)
'''

HP001_CONTROLLER_GOOD = '''
import time

def process(self, keys, recorder):
    t0 = time.perf_counter()
    for key in keys:
        self.sync(key)
    recorder.loop(keys=len(keys), errors=0, requeues=0,
                  seconds=time.perf_counter() - t0, depth=0)

def pump(self, recorder):
    t0 = time.perf_counter()
    n = 0
    for ev in self._watch.drain(10_000):
        self._mark(ev.obj.key)
        n += 1
    recorder.pump(n, time.perf_counter() - t0)
'''

_CTRL = "kubernetes_tpu/controllers/base.py"


def test_hp001_fires_on_per_key_reconcile_instrumentation():
    findings = [f for f in analyze_source(HP001_CONTROLLER_BAD,
                                          filename=_CTRL)
                if f.rule == "HP001"]
    # per-key perf_counter + observe in process(), per-event clock.mark in
    # the drain loop of pump() — all three are the multiplier bug
    assert len(findings) >= 3, findings


def test_hp001_quiet_on_per_loop_reconcile_taps():
    assert "HP001" not in rules_of(
        analyze_source(HP001_CONTROLLER_GOOD, filename=_CTRL))


# ISSUE 13: the steady-state telemetry files (obs/timeseries.py,
# obs/resource.py) are HP001 hot paths — their contract is one tap per
# WINDOW close / per SAMPLE tick. Someone "improving accuracy" by feeding
# the window per pod inside a pod-scale loop is the 100k multiplier bug.

HP001_OBS_BAD = '''
import time

def note_batch_per_pod(self, qps, m):
    for qp in qps:
        t0 = time.perf_counter()
        self._fold(qp)
        m.batch_stage_duration.observe(time.perf_counter() - t0, "pod")
'''

HP001_OBS_GOOD = '''
import time

def note_batch(self, stages, qps):
    t0 = time.perf_counter()
    with self._lock:
        w = self._advance_locked(t0)
        for name, sec in stages.items():
            w.stage_samples.setdefault(name, []).append(sec)
        w.pods += len(qps)
    self._bill(time.perf_counter() - t0)
'''


@pytest.mark.parametrize("hot", ["kubernetes_tpu/obs/timeseries.py",
                                 "kubernetes_tpu/obs/resource.py"])
def test_hp001_fires_on_per_pod_window_feed(hot):
    findings = [f for f in analyze_source(HP001_OBS_BAD, filename=hot)
                if f.rule == "HP001"]
    assert len(findings) >= 2, findings


def test_hp001_quiet_on_per_window_taps():
    assert "HP001" not in rules_of(analyze_source(
        HP001_OBS_GOOD, filename="kubernetes_tpu/obs/timeseries.py"))
    # the identical bad code OUTSIDE the hot files stays out of scope
    assert "HP001" not in rules_of(analyze_source(
        HP001_OBS_BAD, filename="kubernetes_tpu/obs/recorder.py"))


def test_hp001_controller_scope_is_base_py_only():
    # a concrete controller's sync() body is per-OBJECT by design (one key
    # at a time); only the base reconcile loops are the hot path
    assert "HP001" not in rules_of(analyze_source(
        HP001_CONTROLLER_BAD,
        filename="kubernetes_tpu/controllers/replicaset.py"))


def test_hp001_guard_does_not_launder_batch_py_metrics():
    # the sampled-set exception is for tracer STAMPS; a metrics observe per
    # pod is still a finding even when some unrelated guard wraps it —
    # unless that guard IS a sampled-set membership check
    src = '''
def schedule_batch(self, qps, m):
    for qp in qps:
        if qp.key in self._ready_set:
            m.batch_stage_duration.observe(0.1, "pod")
'''
    findings = [f for f in analyze_source(
        src, filename="kubernetes_tpu/scheduler/batch.py")
        if f.rule == "HP001"]
    assert len(findings) == 1, findings


# ---------------------------------------------------------------------------
# ISSUE 18: the trace timeline (obs/tracebuf.py + obs/critpath.py) is an
# HP001 hot path — taps are per batch / per chunk / per cycle / per window,
# never per pod outside a sampled-set check
# ---------------------------------------------------------------------------

HP001_TRACEBUF_BAD = '''
def feed(self, qps, tracebuf):
    for qp in qps:
        tracebuf.ACTIVE.instant("sched", "pod", args={"key": qp.key})
'''

HP001_TRACEBUF_GOOD = '''
def feed(self, qps, clock, t_fin, tracebuf):
    if tracebuf.ACTIVE is not None:
        tb = tracebuf.ACTIVE
        tb.note_batch("sched", t_end=t_fin, stages=clock.stages,
                      pods=len(qps), scheduled=len(qps),
                      outcome="scheduled", solver="fast")
    for qp in qps:
        if qp.key in self._sampled:
            tracebuf.ACTIVE.instant("sched", "sampled-pod")
'''


@pytest.mark.parametrize("hot", ["kubernetes_tpu/obs/tracebuf.py",
                                 "kubernetes_tpu/obs/critpath.py",
                                 "kubernetes_tpu/scheduler/batch.py"])
def test_hp001_fires_on_per_pod_trace_tap(hot):
    findings = [f for f in analyze_source(HP001_TRACEBUF_BAD, filename=hot)
                if f.rule == "HP001"]
    assert len(findings) == 1, findings


def test_hp001_quiet_on_per_batch_trace_tap_and_sampled_guard():
    assert "HP001" not in rules_of(analyze_source(
        HP001_TRACEBUF_GOOD, filename="kubernetes_tpu/obs/tracebuf.py"))
    # the identical per-pod tap OUTSIDE the hot files stays out of scope
    assert "HP001" not in rules_of(analyze_source(
        HP001_TRACEBUF_BAD, filename="kubernetes_tpu/cli/ktl.py"))


# ---------------------------------------------------------------------------
# profiler spans (obs/recorder.py): StageClock boundaries, solve parts and
# the outside stages' TraceMe spans are per batch / chunk / group taps
# ---------------------------------------------------------------------------

HP001_SPAN_BAD = '''
def feed(self, qps, clock, _span):
    for qp in qps:
        with _span("sched.pod"):
            clock.enter("pod")
'''

HP001_SPAN_GOOD = '''
def feed(self, qps, clock, _span, part):
    clock.enter("solve")
    with part("solve.readback"):
        rows = [qp.key for qp in qps]
    with _span("sched.bind"):
        for qp in qps:
            if qp.key in self._sampled:
                clock.enter("sampled")
    clock.drop(None)
'''


def test_hp001_fires_on_per_pod_span_tap():
    findings = [f for f in analyze_source(
        HP001_SPAN_BAD, filename="kubernetes_tpu/scheduler/batch.py")
        if f.rule == "HP001"]
    assert len(findings) == 2, findings


def test_hp001_quiet_on_per_batch_span_tap():
    assert "HP001" not in rules_of(analyze_source(
        HP001_SPAN_GOOD, filename="kubernetes_tpu/scheduler/batch.py"))


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

SUPPRESSED_WITH_REASON = '''
import threading
import time

class Store:
    def __init__(self):
        self._lock = threading.RLock()

    def sleepy(self):
        with self._lock:
            # schedlint: allow(LK002) test fixture: documented exception
            time.sleep(0.1)
'''

SUPPRESSED_BARE = '''
import threading
import time

class Store:
    def __init__(self):
        self._lock = threading.RLock()

    def sleepy(self):
        with self._lock:
            time.sleep(0.1)  # schedlint: allow(LK002)
'''


def test_suppression_with_reason_silences_the_finding():
    findings = analyze_source(SUPPRESSED_WITH_REASON)
    assert findings == [], findings


def test_bare_suppression_is_itself_a_finding():
    findings = analyze_source(SUPPRESSED_BARE)
    rules = rules_of(findings)
    assert "SL001" in rules          # reasonless suppression flagged
    assert "LK002" not in rules      # ... but it still suppresses


def test_wrong_rule_suppression_does_not_silence():
    src = SUPPRESSED_WITH_REASON.replace("allow(LK002)", "allow(MU001)")
    assert "LK002" in rules_of(analyze_source(src))


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------


def test_cli_json_exit_codes(tmp_path):
    import json
    import subprocess
    import sys

    bad = tmp_path / "bad.py"
    bad.write_text(MU001_BAD)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.analysis.schedlint",
         "--json", str(bad)],
        capture_output=True, text=True, cwd=repo, timeout=120)
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    assert doc["stats"]["findings"] == 4
    assert all(f["rule"] == "MU001" for f in doc["findings"])

    good = tmp_path / "good.py"
    good.write_text(MU001_GOOD)
    proc = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.analysis.schedlint",
         str(good)],
        capture_output=True, text=True, cwd=repo, timeout=120)
    assert proc.returncode == 0, proc.stdout

    # a typo'd path must NOT report a clean tree: exit 2 + a PARSE finding
    # (an analyzer that saw nothing must not certify anything)
    proc = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.analysis.schedlint",
         "--json", str(tmp_path / "no_such_dir")],
        capture_output=True, text=True, cwd=repo, timeout=120)
    assert proc.returncode == 2, (proc.returncode, proc.stdout)
    doc = json.loads(proc.stdout)
    assert doc["stats"]["findings"] == 1
    assert doc["findings"][0]["rule"] == "PARSE"


# ---------------------------------------------------------------------------
# MP001 / MP002 — cross-process hygiene (ISSUE 19)
# ---------------------------------------------------------------------------

MP001_BAD = """
import multiprocessing

def dispatch(out_q, pod, qps):
    out_q.put(("work", pod))            # bare pod object

def relay(conn, batch):
    conn.send([qp for qp in batch])     # laundered? no: comprehension is
                                        # not flagged, but the next line is
def relay2(conn, pods):
    conn.send(pods)                     # the whole pod list

def nested(out_q, qp):
    out_q.put_nowait({"item": (1, qp)}) # pod buried in a container
"""

MP001_GOOD = """
import multiprocessing

def dispatch(out_q, pod, rows):
    out_q.put(("work", pod.key, 3))     # field access extracts a scalar
    out_q.put(("rows", rows))
    out_q.put_nowait(("bind", [(1, 2, 3), (4, 5, 6)]))

def relay(conn, pod):
    conn.send(key_of(pod))              # a call launders (returns a key)
"""


def test_mp001_fires_on_pod_objects_crossing_process_boundary():
    findings = [f for f in analyze_source(MP001_BAD) if f.rule == "MP001"]
    assert len(findings) == 3, findings
    assert {f.line for f in findings} == {5, 11, 14}


def test_mp001_quiet_on_keys_rows_and_laundered_fields():
    assert "MP001" not in rules_of(analyze_source(MP001_GOOD))


def test_mp001_quiet_without_multiprocessing_import():
    # a plain thread-safe queue in a non-mp module is not a process
    # boundary — the rule must not fire on ordinary producer/consumer code
    src = """
import queue

def feed(q, pod):
    q.put(pod)
"""
    assert "MP001" not in rules_of(analyze_source(src))


MP002_BAD = """
from multiprocessing import shared_memory

class Seg:
    def start(self):
        self.seg = shared_memory.SharedMemory(
            name="x", create=True, size=64)

    def run(self):
        return bytes(self.seg.buf[:8])
"""

MP002_GOOD = """
from multiprocessing import shared_memory

class Seg:
    def start(self):
        self.seg = shared_memory.SharedMemory(
            name="x", create=True, size=64)

    def stop(self):
        self.seg.close()
        self.seg.unlink()
"""

MP002_GOOD_FINALLY = """
from multiprocessing import shared_memory

def once():
    seg = shared_memory.SharedMemory(name="x", create=True, size=64)
    try:
        return bytes(seg.buf[:8])
    finally:
        seg.close()
        seg.unlink()
"""

MP002_GOOD_ATTACH = """
from multiprocessing import shared_memory

def read(name):
    # attach (create=False default) is the READER side: it must never
    # unlink, so the rule does not demand a teardown pairing here
    seg = shared_memory.SharedMemory(name=name)
    return bytes(seg.buf[:8])
"""


def test_mp002_fires_on_create_without_teardown():
    findings = [f for f in analyze_source(MP002_BAD) if f.rule == "MP002"]
    assert len(findings) == 1, findings


def test_mp002_quiet_on_stop_path_and_finally_teardown():
    assert "MP002" not in rules_of(analyze_source(MP002_GOOD))
    assert "MP002" not in rules_of(analyze_source(MP002_GOOD_FINALLY))
    assert "MP002" not in rules_of(analyze_source(MP002_GOOD_ATTACH))


# ---------------------------------------------------------------------------
# ISSUE 20 tentpole: the interprocedural closure. The pinned LK002
# regression first — a blocking call ONE HELPER DEEP in another module,
# under a store lock, resolved through a module-qualified call
# (`helpers.pause(...)`). The legacy resolver (module_qualified=False)
# cannot see through it (top-level functions are not in the unique-method
# map), so the bug sails through; the whole-program resolver reports it
# with the full call chain, no suppression needed.
# ---------------------------------------------------------------------------

LK002_VIA_HELPERS_MOD = '''
import subprocess
import time

def pause_for_settle():
    time.sleep(0.5)

def spawn_flush(cmd):
    subprocess.run(cmd, check=True)
'''

LK002_VIA_STORE_MOD = '''
import threading

from fixturepkg import helpers

class Store:
    def __init__(self):
        self._lock = threading.RLock()

    def locked_settle(self):
        with self._lock:
            helpers.pause_for_settle()

    def locked_flush(self):
        with self._lock:
            helpers.spawn_flush(["sync"])
'''

LK002_VIA_STORE_GOOD_MOD = '''
import threading

from fixturepkg import helpers

class Store:
    def __init__(self):
        self._lock = threading.RLock()

    def settle_outside(self):
        with self._lock:
            payload = 1
        helpers.pause_for_settle()
        helpers.spawn_flush(["sync"])
        return payload
'''


def _lk002_via_sources(store_src):
    return [
        (LK002_VIA_HELPERS_MOD, "fixturepkg/helpers.py",
         "fixturepkg.helpers"),
        (store_src, "fixturepkg/store_mod.py", "fixturepkg.store_mod"),
    ]


def test_lk002_pinned_regression_old_resolver_misses_the_helper():
    # the documented MISS: before the module-qualified resolver, the
    # blocking helper in another module was invisible — zero findings
    findings = analyze_sources(_lk002_via_sources(LK002_VIA_STORE_MOD),
                               module_qualified=False)
    assert "LK002" not in rules_of(findings), findings


def test_lk002_pinned_regression_new_resolver_reports_the_chain():
    findings = [f for f in
                analyze_sources(_lk002_via_sources(LK002_VIA_STORE_MOD))
                if f.rule == "LK002"]
    msgs = "\n".join(f.message for f in findings)
    assert len(findings) == 2, msgs
    assert "time.sleep" in msgs and "subprocess.run" in msgs
    assert "blocks on the child process" in msgs
    # the full resolved path is printed, both ends module-qualified
    assert ("via call chain fixturepkg.store_mod.Store.locked_settle "
            "-> fixturepkg.helpers.pause_for_settle") in msgs
    assert ("via call chain fixturepkg.store_mod.Store.locked_flush "
            "-> fixturepkg.helpers.spawn_flush") in msgs
    # green suppression-free: the findings anchor in the helper module
    assert all(f.file == "fixturepkg/helpers.py" for f in findings)


def test_lk002_quiet_when_helper_called_outside_the_lock():
    findings = analyze_sources(
        _lk002_via_sources(LK002_VIA_STORE_GOOD_MOD))
    assert "LK002" not in rules_of(findings), findings


LK002_SUBPROCESS_BAD = '''
import subprocess
import threading

class Store:
    def __init__(self):
        self._lock = threading.RLock()

    def fork_under_lock(self, cmd):
        with self._lock:
            return subprocess.check_output(cmd)
'''


def test_lk002_fires_on_direct_subprocess_under_lock():
    findings = [f for f in analyze_source(LK002_SUBPROCESS_BAD)
                if f.rule == "LK002"]
    assert len(findings) == 1, findings
    assert "subprocess.check_output()" in findings[0].message
    assert "blocks on the child process" in findings[0].message


# ---------------------------------------------------------------------------
# ISSUE 20: HP001's via-call-chain form — unguarded per-pod call into a
# hot-file helper that instruments unconditionally, one or two hops deep
# ---------------------------------------------------------------------------

HP001_VIA_BAD = '''
class Batcher:
    def _note_pod(self, qp, m):
        m.batch_stage_duration.observe(0.1, "pod")

    def _account(self, qp, m):
        self._note_pod(qp, m)

    def schedule_batch(self, qps, m):
        for qp in qps:
            self._account(qp, m)
'''

HP001_VIA_GOOD = '''
class Batcher:
    def _note_pod(self, qp, m):
        m.batch_stage_duration.observe(0.1, "pod")

    def _requeue_failed(self, qp, m):
        m.batch_stage_duration.observe(0.1, "requeue")

    def _lookup(self, qp):
        return qp.key

    def schedule_batch(self, qps, m):
        for qp in qps:
            k = self._lookup(qp)
            self._requeue_failed(qp, m)
            if qp.key in self._sampled:
                self._note_pod(qp, m)
'''


def test_hp001_fires_via_call_chain_into_hot_helper():
    findings = [f for f in analyze_source(HP001_VIA_BAD, filename=_HOT)
                if f.rule == "HP001"]
    assert len(findings) == 1, findings
    msg = findings[0].message
    assert "via call chain" in msg
    assert ("schedule_batch -> " in msg and "_account" in msg
            and "_note_pod" in msg), msg
    assert ".observe()" in msg


def test_hp001_via_chain_quiet_on_terminal_path_and_sampled_guard():
    # _requeue_failed is a terminal-path helper by name; the _note_pod
    # call sits behind the sampled-set membership guard; _lookup does not
    # instrument — none of the three is the multiplier bug
    assert "HP001" not in rules_of(
        analyze_source(HP001_VIA_GOOD, filename=_HOT))


# ---------------------------------------------------------------------------
# ISSUE 20: MP001's via-helper form — a pod handed from the mp boundary
# module into a helper module that does the .put() — the pickle is the
# same, laundered through one call
# ---------------------------------------------------------------------------

MP001_VIA_MP_MOD = '''
import multiprocessing

from fixturepkg import shiputil

def dispatch(out_q, pod):
    shiputil.ship(out_q, pod)
'''

MP001_VIA_HELPER_MOD = '''
def ship(out_q, pod):
    out_q.put(("work", pod))
'''

MP001_VIA_GOOD_MP_MOD = '''
import multiprocessing

from fixturepkg import shiputil

def dispatch(out_q, pod):
    shiputil.ship(out_q, pod.key)
'''

MP001_VIA_GOOD_HELPER_MOD = '''
def ship(out_q, key):
    out_q.put(("work", key))
'''


def test_mp001_fires_via_helper_in_another_module():
    findings = [f for f in analyze_sources([
        (MP001_VIA_MP_MOD, "fixturepkg/mpmod.py", "fixturepkg.mpmod"),
        (MP001_VIA_HELPER_MOD, "fixturepkg/shiputil.py",
         "fixturepkg.shiputil"),
    ]) if f.rule == "MP001"]
    assert len(findings) == 1, findings
    msg = findings[0].message
    assert "reached via call chain" in msg
    assert ("fixturepkg.mpmod.dispatch -> fixturepkg.shiputil.ship"
            in msg), msg
    assert findings[0].file == "fixturepkg/shiputil.py"


def test_mp001_via_helper_quiet_when_only_the_key_crosses():
    findings = analyze_sources([
        (MP001_VIA_GOOD_MP_MOD, "fixturepkg/mpmod.py", "fixturepkg.mpmod"),
        (MP001_VIA_GOOD_HELPER_MOD, "fixturepkg/shiputil.py",
         "fixturepkg.shiputil"),
    ])
    assert "MP001" not in rules_of(findings), findings


# ---------------------------------------------------------------------------
# ISSUE 20: AL001/AL002 — steady-state allocation discipline (the static
# complement of the pod_obj_allocs == 0 runtime gauge)
# ---------------------------------------------------------------------------

AL001_BAD = '''
def schedule_batch(qps, rows):
    for qp in qps:
        pod = PodInfo(qp.key)
        snap = qp.pod.copy()
        rows.append((pod, snap))
'''

AL001_VIA_BAD = '''
def _expand(qp):
    return PodInfo(qp.key)

def schedule_batch(qps, rows):
    for qp in qps:
        rows.append(_expand(qp))
'''

AL_GOOD = '''
def schedule_batch(qps, cols_rows_ok, rows):
    for qp in qps:
        pod = qp.pod if cols_rows_ok else pod_bind_clone(qp.pod)
        rows.append(qp.row)
    try:
        commit(rows)
    except ValueError:
        failed = PodInfo(rows[0])
        _requeue_one(failed)
    return rows

def materialize_columnar_rows(rows):
    return [PodInfo(r) for r in rows]
'''

AL002_BAD = '''
def schedule_batch(qps):
    snapshot = [PodInfo(qp.key) for qp in qps]
    return snapshot
'''

AL002_GOOD = '''
def schedule_batch(qps, use_columnar):
    if not use_columnar:
        return [PodInfo(qp.key) for qp in qps]
    return [qp.row for qp in qps]
'''

_AL_HOT = "kubernetes_tpu/scheduler/batch.py"


def test_al001_fires_on_steady_state_pod_allocation():
    findings = [f for f in analyze_source(AL001_BAD, filename=_AL_HOT)
                if f.rule == "AL001"]
    msgs = "\n".join(f.message for f in findings)
    assert len(findings) == 2, msgs
    assert "PodInfo(...)" in msgs
    assert ".copy() of pod object" in msgs
    assert "zero-alloc steady-state path" in msgs


def test_al001_fires_via_call_chain_through_ungated_helper():
    findings = [f for f in analyze_source(AL001_VIA_BAD, filename=_AL_HOT)
                if f.rule == "AL001"]
    assert len(findings) == 1, findings
    msg = findings[0].message
    assert "via call chain" in msg
    assert "schedule_batch -> " in msg and "_expand" in msg, msg


def test_al_quiet_behind_gates_barriers_and_except_paths():
    # the gated ternary clone, the except-handler PodInfo (error paths are
    # not steady state), the requeue helper call out of the handler, and
    # the materialize* barrier function's comprehension are all declared
    # exits from the zero-alloc regime
    findings = analyze_source(AL_GOOD, filename=_AL_HOT)
    assert "AL001" not in rules_of(findings), findings
    assert "AL002" not in rules_of(findings), findings


def test_al002_fires_on_pod_materializing_comprehension():
    findings = [f for f in analyze_source(AL002_BAD, filename=_AL_HOT)
                if f.rule == "AL002"]
    assert len(findings) == 1, findings
    assert "materializes a pod object per element" in findings[0].message


def test_al002_quiet_behind_a_gate_predicate():
    assert "AL002" not in rules_of(
        analyze_source(AL002_GOOD, filename=_AL_HOT))


def test_al_rules_scoped_to_the_designated_hot_paths():
    # the identical allocation outside the designated files/functions is
    # not AL's business ...
    assert rules_of(analyze_source(
        AL001_BAD, filename="kubernetes_tpu/cli/ktl.py")).isdisjoint(
            {"AL001", "AL002"})
    # ... and cachecols.py is hot WHOLESALE (every function is a root)
    findings = [f for f in analyze_source(
        AL002_BAD.replace("schedule_batch", "refresh_rows"),
        filename="kubernetes_tpu/scheduler/cachecols.py")
        if f.rule == "AL002"]
    assert len(findings) == 1, findings


# ---------------------------------------------------------------------------
# ISSUE 20: SEQ001/SEQ002 — the shm seqlock protocol
# ---------------------------------------------------------------------------

SEQ001_BAD = '''
class Reader:
    def nrows(self):
        v0 = int(self._hdr[_H_VER])
        n = int(self._hdr[_H_NROWS])
        return n
'''

SEQ001_GOOD = '''
class Reader:
    def nrows(self):
        for _ in range(64):
            v0 = int(self._hdr[_H_VER])
            n = int(self._hdr[_H_NROWS])
            if v0 % 2 == 0 and int(self._hdr[_H_VER]) == v0:
                return n
        raise RuntimeError("torn read")
'''

SEQ001_ESCAPE_BAD = '''
class Reader:
    def column(self, name):
        v0 = int(self._hdr[_H_VER])
        arrs = self.arrays
        self._cached_view = arrs[name]
        return arrs[name]
'''

SEQ001_ESCAPE_GOOD = '''
class Reader:
    def column(self, name):
        for _ in range(64):
            v0 = int(self._hdr[_H_VER])
            arrs = self.arrays
            out = arrs[name].copy()
            if v0 % 2 == 0 and int(self._hdr[_H_VER]) == v0:
                return out
        raise RuntimeError("torn read")
'''

SEQ002_BAD = '''
class Arena:
    def publish(self, n):
        self._hdr[_H_NROWS] = n
        self._hdr[_H_VER] += 1
'''

SEQ002_COLS_BAD = '''
class Arena:
    def write_row(self, i, cpu):
        arrs = self.arrays
        arrs["cpu"][i] = cpu
'''

SEQ002_GOOD = '''
class Arena:
    def publish(self, n):
        self._hdr[_H_VER] += 1
        self._hdr[_H_NROWS] = n
        self._hdr[_H_VER] += 1

    def append_row(self, i, cpu, n):
        arrs = self.arrays
        arrs["cpu"][i] = cpu
        self.publish(n)
'''

_SEQ_FILE = "kubernetes_tpu/store/shm.py"


def test_seq001_fires_on_missing_version_recheck():
    findings = [f for f in analyze_source(SEQ001_BAD, filename=_SEQ_FILE)
                if f.rule == "SEQ001"]
    assert len(findings) == 1, findings
    assert "never re-checks" in findings[0].message


def test_seq001_quiet_on_the_retry_loop_shape():
    assert "SEQ001" not in rules_of(
        analyze_source(SEQ001_GOOD, filename=_SEQ_FILE))


def test_seq001_fires_on_raw_view_escaping_the_retry_scope():
    findings = [f for f in
                analyze_source(SEQ001_ESCAPE_BAD, filename=_SEQ_FILE)
                if f.rule == "SEQ001"]
    msgs = "\n".join(f.message for f in findings)
    # stored on self AND returned raw — both escapes
    assert len(findings) == 2, msgs
    assert "stored on self" in msgs and "returns raw" in msgs


def test_seq001_quiet_when_the_value_is_laundered_in_scope():
    assert "SEQ001" not in rules_of(
        analyze_source(SEQ001_ESCAPE_GOOD, filename=_SEQ_FILE))


def test_seq002_fires_on_one_sided_version_bump():
    findings = [f for f in analyze_source(SEQ002_BAD, filename=_SEQ_FILE)
                if f.rule == "SEQ002"]
    assert len(findings) == 1, findings
    assert "BOTH sides" in findings[0].message


def test_seq002_fires_on_unpublished_column_writes():
    findings = [f for f in
                analyze_source(SEQ002_COLS_BAD, filename=_SEQ_FILE)
                if f.rule == "SEQ002"]
    assert len(findings) == 1, findings
    assert "never calls .publish" in findings[0].message


def test_seq002_quiet_on_the_publish_shape():
    assert "SEQ002" not in rules_of(
        analyze_source(SEQ002_GOOD, filename=_SEQ_FILE))


def test_seq_rules_scoped_to_the_seqlock_files():
    findings = analyze_source(SEQ002_BAD,
                              filename="kubernetes_tpu/store/store.py")
    assert rules_of(findings).isdisjoint({"SEQ001", "SEQ002"}), findings


# ---------------------------------------------------------------------------
# ISSUE 20: the runtime lock-graph witness (store/lockgraph.py)
# ---------------------------------------------------------------------------


def test_lock_graph_witness_reports_seeded_inversion_with_both_stacks():
    from kubernetes_tpu.store.lockgraph import LockGraphWitness
    from kubernetes_tpu.store.store import _LockOrderState, _OrderedRLock

    # a deliberate inversion in a scratch SAME-RANK pair (equal rank
    # passes the runtime assertion, so both orders get witnessed),
    # isolated from the process-wide witness and lock stack
    w = LockGraphWitness()
    state = _LockOrderState()
    a = _OrderedRLock("scratch_a", 0, state, witness=w)
    b = _OrderedRLock("scratch_b", 0, state, witness=w)

    def forward_order():
        with a:
            with b:
                pass

    def reversed_order():
        with b:
            with a:
                pass

    forward_order()
    reversed_order()

    table = {"scratch_a": 0, "scratch_b": 1}
    report = w.diff(table)
    assert not report["clean"]
    assert len(report["violations"]) == 1, report["violations"]
    v = report["violations"][0]
    assert v["edge"] == "scratch_b -> scratch_a"
    # BOTH first-seen stacks: the offending edge's and its reverse's
    assert "reversed_order" in v["stack"]
    assert v["reverse_stack"] and "forward_order" in v["reverse_stack"]
    # both orders witnessed = a cycle, each edge carrying its first stack
    assert len(report["cycles"]) == 1, report["cycles"]
    assert "scratch_a" in report["cycles"][0]["cycle"]
    assert len(report["cycles"][0]["stacks"]) == 2
    text = w.render(table)
    assert "INVERSION" in text and "first acquisition stack" in text
    assert "CYCLE" in text


def test_lock_graph_witness_clean_on_the_mandated_order():
    from kubernetes_tpu.store.lockgraph import (ORDER_TABLE,
                                                LockGraphWitness)
    from kubernetes_tpu.store.store import _LockOrderState, _OrderedRLock

    w = LockGraphWitness()
    state = _LockOrderState()
    names = sorted(ORDER_TABLE, key=ORDER_TABLE.get)
    locks = [_OrderedRLock(n, ORDER_TABLE[n], state, witness=w)
             for n in names]
    for lk in locks:
        lk.acquire()
    for lk in reversed(locks):
        lk.release()
    report = w.diff()
    assert report["clean"], report
    assert report["edges"] == len(names) - 1
    assert "CLEAN against the LK001 ordering table" in w.render()


def test_lock_graph_export_roundtrip_renders_the_inversion(tmp_path):
    from kubernetes_tpu.analysis.schedlint import lock_graph_report
    from kubernetes_tpu.store.lockgraph import LockGraphWitness
    from kubernetes_tpu.store.store import _LockOrderState, _OrderedRLock

    w = LockGraphWitness()
    state = _LockOrderState()
    a = _OrderedRLock("scratch_a", 0, state, witness=w)
    b = _OrderedRLock("scratch_b", 0, state, witness=w)
    with b:
        with a:
            pass
    path = tmp_path / "lockgraph.json"
    w.export(str(path), {"scratch_a": 0, "scratch_b": 1})
    text, clean = lock_graph_report(str(path))
    assert not clean
    assert "INVERSION" in text and "scratch_b -> scratch_a" in text


def test_lock_graph_report_scratch_store_walks_the_mandated_chain():
    from kubernetes_tpu.analysis.schedlint import lock_graph_report

    text, clean = lock_graph_report()
    assert clean, text
    assert "CLEAN against the LK001 ordering table" in text


def test_store_acquisitions_record_into_the_process_witness():
    # the autouse STORE_LOCK_ORDER_CHECK fixture arms every test store;
    # exercising one must land its edges in the process-wide witness the
    # session-teardown gate diffs (tests/conftest.py)
    from kubernetes_tpu.store.lockgraph import WITNESS
    from kubernetes_tpu.store.store import APIStore

    store = APIStore()
    with store._lock:
        with store._pods_lock:
            pass
    key = ("_lock (global RV)", "_pods_lock (pods shard)")
    assert key in WITNESS.edges
    assert WITNESS.diff()["clean"]


# ---------------------------------------------------------------------------
# ISSUE 20: --diff scope and the baseline stats block
# ---------------------------------------------------------------------------


def test_diff_scope_merges_reverse_import_and_call_deps():
    from kubernetes_tpu.analysis.index import ProjectIndex
    from kubernetes_tpu.analysis.schedlint import diff_scope

    idx = ProjectIndex.from_sources([
        (LK002_VIA_HELPERS_MOD, "fixturepkg/helpers.py",
         "fixturepkg.helpers"),
        (LK002_VIA_STORE_GOOD_MOD, "fixturepkg/store_mod.py",
         "fixturepkg.store_mod"),
        ("def standalone():\n    return 1\n", "fixturepkg/other.py",
         "fixturepkg.other"),
    ])
    scope = diff_scope(idx, ["fixturepkg/helpers.py"])
    # the changed file itself + the module that imports/calls into it;
    # the unrelated module stays out of scope
    assert "fixturepkg/helpers.py" in scope
    assert "fixturepkg/store_mod.py" in scope
    assert "fixturepkg/other.py" not in scope


def test_cli_json_carries_baseline_and_callgraph_stats(tmp_path):
    import json
    import subprocess
    import sys

    bad = tmp_path / "bad.py"
    bad.write_text(MU001_BAD)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.analysis.schedlint",
         "--json", str(bad)],
        capture_output=True, text=True, cwd=repo, timeout=120)
    doc = json.loads(proc.stdout)
    base = doc["stats"]["baseline"]
    assert base["findings_by_rule"] == {"MU001": 4}
    assert base["suppression_count"] == 0
    assert base["parse_errors"] == []
    cg = doc["stats"]["callgraph"]
    assert cg["depth_cap"] == 12 and cg["fanout_cap"] == 64
    assert doc["stats"]["callgraph_edges"] == cg["edges"]

    sup = tmp_path / "sup.py"
    sup.write_text(SUPPRESSED_WITH_REASON)
    proc = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.analysis.schedlint",
         "--json", str(sup)],
        capture_output=True, text=True, cwd=repo, timeout=120)
    doc = json.loads(proc.stdout)
    base = doc["stats"]["baseline"]
    assert base["suppression_count"] == 1
    assert base["suppressions"][0]["rules"] == ["LK002"]
    assert "documented exception" in base["suppressions"][0]["reason"]


def test_cli_diff_mode_scopes_and_reports(tmp_path):
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "kubernetes_tpu.analysis.schedlint",
         "--json", "--diff", "HEAD"],
        capture_output=True, text=True, cwd=repo, timeout=300)
    assert proc.returncode == 0, proc.stdout[-2000:]
    doc = json.loads(proc.stdout)
    assert doc["stats"]["diff"]["ref"] == "HEAD"
    assert doc["stats"]["diff"]["scope_files"] <= doc["stats"]["files"]
    assert doc["stats"]["findings"] == 0
