"""FSM snapshot serialization: StateStore <-> JSON-safe dump.

Reference: nomad/fsm.go Snapshot/Restore + helper/snapshot. The dump is
the latest committed value of every primary table; secondary indexes
(allocs-by-node/job/eval, evals-by-job, deployments-by-job, token
secret index) are derivable and rebuilt on restore, so they never ride
the wire or disk.

FORMAT history:
  1  every alloc `wire_encode`d as its own row; AllocBlocks
     de-columnarized into per-position rows (O(K) host objects).
  2  columnar: AllocBlocks ride natively (already columnar batches);
     real alloc rows are parallel scalar columns + one packed
     resource-vector matrix + deduped job table + a sparse `extras`
     list for the rare fat fields. Restore rebuilds node usage with
     per-block numpy accumulation instead of a per-alloc Python loop.

Writers emit FORMAT=2; the reader accepts both (a format-1 dump from
the previous release restores bit-identically through the legacy path).

Snapshotting is split into capture (pin an MVCC generation — O(1),
safe to do under the raft node lock) and serialize (walk the pinned
generation — arbitrarily slow, done OFF the lock by the snapshot
thread; MVCC readers never block writers).
"""

from __future__ import annotations

from ..structs.alloc import BLOCK_SEP, Allocation, DesiredTransition
from ..structs.wire import wire_decode, wire_encode
from .mvcc import cons
from .store import BlockRef

FORMAT = 2

# scalar per-alloc fields that become parallel columns in FORMAT=2
_COL_FIELDS = (
    "id", "eval_id", "name", "namespace", "node_id", "node_name",
    "job_id", "job_version", "task_group", "desired_status",
    "desired_description", "client_status", "client_description",
    "deployment_id", "canary", "previous_allocation", "next_allocation",
    "follow_up_eval_id", "preempted_by_allocation", "allocated_at",
    "task_finished_at", "modify_time", "create_index", "modify_index",
    "alloc_modify_index",
)

# fat/rare fields: omitted per row unless they differ from the default
_EXTRA_FIELDS = (
    "allocated_ports", "allocated_devices", "allocated_cores",
    "desired_transition", "task_states", "deployment_status",
    "reschedule_tracker", "metrics",
)

_DEFAULT_TRANSITION = DesiredTransition()


def _extra_is_default(field: str, value) -> bool:
    if field == "desired_transition":
        return value is None or value == _DEFAULT_TRANSITION
    if field in ("deployment_status", "reschedule_tracker", "metrics"):
        return value is None
    return not value


def capture_store(store):
    """Pin an MVCC snapshot handle. O(1): just a generation acquire —
    cheap enough to run under the raft node lock. Pass the handle to
    `serialize_capture` later (off the lock) and it sees exactly the
    state at capture time; concurrent writers proceed unimpeded."""
    return store.snapshot()


def serialize_capture(store, snap, fmt: int = FORMAT) -> dict:
    """Serialize the pinned generation `snap` (does NOT release it)."""
    job_versions = []
    for (ns, jid, _ver), row in store._job_versions.iterate(snap.index):
        job_versions.append(row)
    out = {
        "format": fmt,
        "index": snap.index,
        "nodes": [wire_encode(n) for n in snap.nodes()],
        "jobs": [wire_encode(j) for j in snap.jobs()],
        "job_versions": [wire_encode(j) for j in job_versions],
        "evals": [wire_encode(e) for e in snap.evals()],
        "deployments": [wire_encode(d) for d in snap.deployments()],
        "acl_policies": [wire_encode(p) for p in snap.acl_policies()],
        "acl_tokens": [wire_encode(t) for t in snap.acl_tokens()],
        "acl_roles": [wire_encode(r) for r in snap.acl_roles()],
        "variables": [wire_encode(v)
                      for _, v in store._variables.iterate(snap.index)],
        "volumes": [wire_encode(v)
                    for _, v in store._volumes.iterate(snap.index)],
        "node_pools": [wire_encode(p)
                       for _, p in store._node_pools.iterate(snap.index)],
        "namespaces": [wire_encode(x) for _, x in
                       store._namespaces.iterate(snap.index)],
        "services": [wire_encode(r) for _, r in
                     store._services.iterate(snap.index)],
        "auth_methods": [wire_encode(m) for _, m in
                         store._auth_methods.iterate(snap.index)],
        "binding_rules": [wire_encode(r) for _, r in
                          store._binding_rules.iterate(snap.index)],
        "regions": [wire_encode(r) for _, r in
                    store._regions.iterate(snap.index)],
        "one_time_tokens": [
            {"secret": k, **row} for k, row in
            store._one_time_tokens.iterate(snap.index)],
        "scheduler_config": (
            wire_encode(snap.scheduler_configuration())
            if snap.scheduler_configuration() is not None else None),
        "scaling_events": [
            {"key": list(k), "events": list(v)}
            for k, v in store._scaling_events.iterate(snap.index)],
    }
    if fmt == 1:
        # legacy writer: de-columnarize blocks into per-position rows
        out["allocs"] = [wire_encode(a) for a in snap.allocs()]
    elif fmt == FORMAT:
        out["alloc_blocks"] = [wire_encode(b) for b in snap.alloc_blocks()]
        out["allocs_columnar"] = _dump_alloc_columns(store, snap)
    else:
        raise ValueError(f"cannot write snapshot format {fmt}")
    return out


def dump_store(store, fmt: int = FORMAT) -> dict:
    """Serialize the latest committed state. Takes its own snapshot."""
    with store.snapshot() as snap:
        return serialize_capture(store, snap, fmt=fmt)


def _dump_alloc_columns(store, snap) -> dict:
    """Real `_allocs` rows (standalone + promoted) as parallel columns.
    Block positions never materialize here — they ride in
    `alloc_blocks` natively."""
    import numpy as np

    cols = {f: [] for f in _COL_FIELDS}
    vecs = []
    vec_missing = []
    jobs = []
    job_slot_by_id = {}    # id(job) -> slot (identity fast path)
    job_slot_by_key = {}   # (ns, job_id, version) -> slot
    job_idx = []
    extras = []
    k = 0
    for _, a in store._allocs.iterate(snap.index):
        for f in _COL_FIELDS:
            cols[f].append(getattr(a, f))
        v = a.allocated_vec
        if v is None:
            vec_missing.append(k)
        else:
            vecs.append(np.asarray(v, dtype=np.float64))
        j = a.job
        if j is None:
            job_idx.append(-1)
        else:
            slot = job_slot_by_id.get(id(j))
            if slot is None:
                key = (a.namespace, a.job_id, a.job_version)
                slot = job_slot_by_key.get(key)
                if slot is None:
                    slot = len(jobs)
                    jobs.append(wire_encode(j))
                    job_slot_by_key[key] = slot
                job_slot_by_id[id(j)] = slot
            job_idx.append(slot)
        extra = None
        for f in _EXTRA_FIELDS:
            v = getattr(a, f)
            if not _extra_is_default(f, v):
                if extra is None:
                    extra = {}
                extra[f] = wire_encode(v)
        extras.append(extra)
        k += 1
    return {
        "n": k,
        "cols": cols,
        "vecs": wire_encode(np.stack(vecs)) if vecs else None,
        "vec_missing": vec_missing,
        "jobs": jobs,
        "job_idx": job_idx,
        "extras": extras,
    }


def _decode_alloc_columns(sec) -> list:
    if not sec:
        return []
    n = int(sec["n"])
    cols = sec["cols"]
    jobs = [wire_decode(j) for j in sec["jobs"]]
    job_idx = sec["job_idx"]
    mat = wire_decode(sec["vecs"]) if sec.get("vecs") is not None else None
    missing = set(sec.get("vec_missing", ()))
    extras = sec["extras"]
    col_lists = [cols[f] for f in _COL_FIELDS]
    out = []
    vrow = 0
    for i in range(n):
        a = Allocation(**{f: col[i]
                          for f, col in zip(_COL_FIELDS, col_lists)})
        if i in missing:
            a.allocated_vec = None
        else:
            a.allocated_vec = mat[vrow]
            vrow += 1
        ji = job_idx[i]
        if ji >= 0:
            a.job = jobs[ji]
        extra = extras[i]
        if extra:
            for f, v in extra.items():
                setattr(a, f, wire_decode(v))
        out.append(a)
    return out


def _promoted_positions(blocks, allocs) -> dict:
    """Real alloc ids that shadow a visible block position, as
    {block_id: [position, ...]}. These rows are reachable through the
    block's BlockRef index entries, so restore must not double-index
    them, and the block's usage contribution excludes them (their real
    row carries its own usage — exactly the promotion-time
    `_usage_apply(virtual_row, real_row)` transition)."""
    block_by_id = {b.id: b for b in blocks}
    promoted = {}
    for a in allocs:
        i = a.id.rfind(BLOCK_SEP)
        if i <= 0:
            continue
        b = block_by_id.get(a.id[:i])
        if b is None:
            continue
        try:
            p = int(a.id[i + 1:])
        except ValueError:
            continue
        if 0 <= p < b.size and b.visible(p):
            promoted.setdefault(b.id, []).append(p)
    return promoted


def _block_usage_into(blocks, promoted, usage) -> None:
    """Fold the blocks' placement usage into the per-node `usage` dict
    (vectorized: one numpy scatter-add per block, no per-position
    Python). A block row contributes `allocated_vec × counts[m]`, minus
    one vec per dropped or promoted position in the row; rejected rows
    contribute nothing."""
    import numpy as np

    if not blocks:
        return
    node_pos = {}
    node_list = []
    acc = None
    for b in blocks:
        n_rows = len(b.node_ids)
        if n_rows == 0:
            continue
        eff = np.asarray(b.counts, dtype=np.float64).copy()
        shadow = list(b.dropped) + promoted.get(b.id, [])
        if shadow:
            rows = np.searchsorted(
                b.offsets(), np.asarray(shadow, dtype=np.int64),
                side="right") - 1
            np.subtract.at(eff, rows, 1.0)
        if b.rejected_rows:
            eff[np.fromiter(b.rejected_rows, dtype=np.int64,
                            count=len(b.rejected_rows))] = 0.0
        vec = np.asarray(b.allocated_vec, dtype=np.float64)
        idx = np.empty(n_rows, dtype=np.int64)
        for m, nid in enumerate(b.node_ids):
            pos = node_pos.get(nid)
            if pos is None:
                pos = node_pos[nid] = len(node_list)
                node_list.append(nid)
            idx[m] = pos
        if acc is None:
            acc = np.zeros((max(len(node_list), 64), vec.shape[0]))
        elif len(node_list) > acc.shape[0]:
            grow = np.zeros((max(len(node_list), acc.shape[0] * 2),
                             acc.shape[1]))
            grow[:acc.shape[0]] = acc
            acc = grow
        np.add.at(acc, idx, eff[:, None] * vec[None, :])
    if acc is None:
        return
    for i, nid in enumerate(node_list):
        row = acc[i]
        if not row.any():
            continue
        prev = usage.get(nid)
        usage[nid] = row if prev is None else prev + row


def _block_dev_usage_into(blocks, promoted, dev_usage) -> None:
    """Fold the device instances of the blocks that carry them into the
    per-node `dev_usage` rows: a live node row's positions, less the
    dropped and promoted ones (a promoted row counts its own)."""
    for b in blocks:
        if b.devices is None:
            continue
        skip = set(promoted.get(b.id, ()))
        for m in b.live_rows():
            counts = b.device_counts_for_row(m, skip)
            if counts:
                row = dev_usage.setdefault(b.node_ids[m], {})
                for gid, n in counts.items():
                    row[gid] = row.get(gid, 0) + n


def restore_store(store, data: dict) -> None:
    """Replace the store's contents with a dump (restore-on-start and
    follower install-snapshot). Publishes at the dump's index. Accepts
    FORMAT=2 (columnar) and FORMAT=1 (legacy per-row) dumps."""
    fmt = data.get("format")
    if fmt not in (1, FORMAT):
        raise ValueError(f"unsupported snapshot format {data.get('format')}")
    index = int(data["index"])
    nodes = [wire_decode(x) for x in data.get("nodes", [])]
    jobs = [wire_decode(x) for x in data.get("jobs", [])]
    job_versions = [wire_decode(x) for x in data.get("job_versions", [])]
    evals = [wire_decode(x) for x in data.get("evals", [])]
    if fmt == 1:
        allocs = [wire_decode(x) for x in data.get("allocs", [])]
        blocks = []
    else:
        allocs = _decode_alloc_columns(data.get("allocs_columnar"))
        blocks = [wire_decode(x) for x in data.get("alloc_blocks", [])]
    deployments = [wire_decode(x) for x in data.get("deployments", [])]
    policies = [wire_decode(x) for x in data.get("acl_policies", [])]
    tokens = [wire_decode(x) for x in data.get("acl_tokens", [])]
    roles = [wire_decode(x) for x in data.get("acl_roles", [])]
    variables = [wire_decode(x) for x in data.get("variables", [])]
    volumes = [wire_decode(x) for x in data.get("volumes", [])]
    node_pools = [wire_decode(x) for x in data.get("node_pools", [])]
    namespaces = [wire_decode(x) for x in data.get("namespaces", [])]
    services = [wire_decode(x) for x in data.get("services", [])]
    auth_methods = [wire_decode(x) for x in data.get("auth_methods", [])]
    binding_rules = [wire_decode(x) for x in data.get("binding_rules", [])]
    regions = [wire_decode(x) for x in data.get("regions", [])]
    one_time_tokens = data.get("one_time_tokens", [])
    sched_cfg = (wire_decode(data["scheduler_config"])
                 if data.get("scheduler_config") is not None else None)
    scaling_events = data.get("scaling_events", [])

    with store._write_lock:
        # Generation choice must be deterministic across replicas AND
        # MVCC-safe for concurrent snapshot readers:
        # - store behind the dump (raft install / restart replay): land
        #   exactly at the dump index so replay stays deterministic;
        # - store ahead (operator restore of an older dump): take the
        #   next generation like any other mutation.
        gen = index if store._index < index else store._index + 1
        live = store._tracker.min_live(store._index)
        # never clear chains — live snapshots still read old versions;
        # keys absent from the dump get tombstones at the new generation
        new_keys = {
            id(store._nodes): {n.id for n in nodes},
            id(store._jobs): {(j.namespace, j.id) for j in jobs},
            id(store._job_versions): {(j.namespace, j.id, j.version)
                                      for j in job_versions},
            id(store._evals): {e.id for e in evals},
            id(store._allocs): {a.id for a in allocs},
            id(store._alloc_blocks): {b.id for b in blocks},
            id(store._deployments): {d.id for d in deployments},
            id(store._acl_policies): {p.name for p in policies},
            id(store._acl_tokens): {t.accessor_id for t in tokens},
            id(store._acl_secret_idx): {t.secret_id for t in tokens},
            id(store._acl_roles): {r.name for r in roles},
            id(store._variables): {(v.namespace, v.path) for v in variables},
            id(store._volumes): {(v.namespace, v.id) for v in volumes},
            id(store._node_pools): {p.name for p in node_pools},
            id(store._namespaces): {x.name for x in namespaces},
            id(store._services): {r.id for r in services},
            id(store._services_by_name): {(r.namespace, r.service_name)
                                          for r in services},
            id(store._auth_methods): {m.name for m in auth_methods},
            id(store._binding_rules): {r.id for r in binding_rules},
            id(store._regions): {r.name for r in regions},
            id(store._one_time_tokens): {o["secret"]
                                         for o in one_time_tokens},
            id(store._scheduler_config): ({"config"} if sched_cfg is not None
                                          else set()),
            id(store._scaling_events): {tuple(e["key"])
                                        for e in scaling_events},
        }
        for t in store._all_tables:
            keep = new_keys.get(id(t), set())
            for key in list(t._rows):
                if key not in keep:
                    t.delete(key, gen, live)
        for n in nodes:
            store._nodes.put(n.id, n, gen, live)
        for j in jobs:
            store._jobs.put((j.namespace, j.id), j, gen, live)
        for j in job_versions:
            store._job_versions.put((j.namespace, j.id, j.version), j, gen, live)
        for e in evals:
            store._evals.put(e.id, e, gen, live)
            _index_prepend(store._evals_by_job, (e.namespace, e.job_id),
                           e.id, gen)
        usage = {}
        dev_usage = {}
        promoted = _promoted_positions(blocks, allocs) if blocks else {}
        promoted_ids = {f"{bid}{BLOCK_SEP}{p}"
                        for bid, ps in promoted.items() for p in ps}
        for a in allocs:
            store._allocs.put(a.id, a, gen, live)
            if a.id not in promoted_ids:
                _index_prepend(store._allocs_by_node, a.node_id, a.id, gen)
                _index_prepend(store._allocs_by_job, (a.namespace, a.job_id),
                               a.id, gen)
                _index_prepend(store._allocs_by_eval, a.eval_id, a.id, gen)
            if not a.terminal_status():
                prev = usage.get(a.node_id)
                usage[a.node_id] = a.allocated_vec if prev is None else prev + a.allocated_vec
                if a.allocated_devices or a.allocated_cores:
                    from ..scheduler.devices import accumulate_dev_usage

                    accumulate_dev_usage(dev_usage.setdefault(a.node_id, {}), a)
        if blocks:
            _block_usage_into(blocks, promoted, usage)
            _block_dev_usage_into(blocks, promoted, dev_usage)
            for b in blocks:
                store._alloc_blocks.put(b.id, b, gen, live)
                for m in b.live_rows():
                    _index_prepend(store._allocs_by_node, b.node_ids[m],
                                   BlockRef(b.id, m), gen)
                _index_prepend(store._allocs_by_job,
                               (b.namespace, b.job_id), BlockRef(b.id), gen)
                _index_prepend(store._allocs_by_eval, b.eval_id,
                               BlockRef(b.id), gen)
        for node_id, vec in usage.items():
            store._node_usage.put(node_id, vec, gen, live)
        for node_id, row in dev_usage.items():
            store._node_dev_usage.put(node_id, row, gen, live)
        for d in deployments:
            store._deployments.put(d.id, d, gen, live)
            _index_prepend(store._deployments_by_job,
                           (d.namespace, d.job_id), d.id, gen)
        for p in policies:
            store._acl_policies.put(p.name, p, gen, live)
        for t in tokens:
            store._acl_tokens.put(t.accessor_id, t, gen, live)
            store._acl_secret_idx.put(t.secret_id, t.accessor_id, gen, live)
        for r in roles:
            store._acl_roles.put(r.name, r, gen, live)
        for v in variables:
            store._variables.put((v.namespace, v.path), v, gen, live)
        for v in volumes:
            store._volumes.put((v.namespace, v.id), v, gen, live)
        for p in node_pools:
            store._node_pools.put(p.name, p, gen, live)
        for x in namespaces:
            store._namespaces.put(x.name, x, gen, live)
        for r in services:
            store._services.put(r.id, r, gen, live)
            _index_prepend(store._services_by_name,
                           (r.namespace, r.service_name), r.id, gen)
            _index_prepend(store._services_by_alloc, r.alloc_id, r.id, gen)
        for m in auth_methods:
            store._auth_methods.put(m.name, m, gen, live)
        for r in binding_rules:
            store._binding_rules.put(r.id, r, gen, live)
        for r in regions:
            store._regions.put(r.name, r, gen, live)
        for o in one_time_tokens:
            store._one_time_tokens.put(
                o["secret"],
                {"accessor_id": o["accessor_id"],
                 "expires": float(o["expires"])},
                gen, live)
        if sched_cfg is not None:
            store._scheduler_config.put("config", sched_cfg, gen, live)
        for e in scaling_events:
            store._scaling_events.put(tuple(e["key"]),
                                      tuple(e["events"]), gen, live)
        store._next_gen = gen
        store._bump_node_set(gen)
        store._rebuild_usage_matrix()
        store._commit(gen, [("restore", None)])


def _index_prepend(table, key, value, gen: int) -> None:
    cell = table.get_latest(key)
    table.put(key, cons(value, cell), gen, 0)
