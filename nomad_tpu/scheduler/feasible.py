"""Feasibility checking (reference scheduler/feasible.go, 1,587 LoC).

Host-side implementation of the 15 constraint operators with exact
reference semantics (feasible.go:833 checkConstraint, :793 resolveTarget,
:880 checkOrder int->float->lexical fallback, :1050 set-contains comma
split + trim). Everything is exposed both per-node (oracle / host path)
and as vectorized masks over node lists (the shape the tensorizer ships
to the TPU kernels).
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ..structs import Constraint, Job, Node, TaskGroup, enums

# ---------------------------------------------------------------------------
# target resolution (reference feasible.go:793 resolveTarget)
# ---------------------------------------------------------------------------


def resolve_target(target: str, node: Node) -> Tuple[str, bool]:
    """Resolve an interpolation target like "${attr.kernel.name}" against a
    node. Returns (value, found). Non-${...} strings are literals."""
    if not target.startswith("${"):
        return target, True
    if target == "${node.unique.id}":
        return node.id, True
    if target == "${node.datacenter}":
        return node.datacenter, True
    if target == "${node.unique.name}":
        return node.name, True
    if target == "${node.class}":
        return node.node_class, True
    if target == "${node.pool}":
        return node.node_pool, True
    if target.startswith("${attr."):
        key = target[len("${attr."):-1]
        val = node.attributes.get(key)
        return ("" if val is None else str(val)), val is not None
    if target.startswith("${meta."):
        key = target[len("${meta."):-1]
        val = node.meta.get(key)
        return ("" if val is None else str(val)), val is not None
    if target.startswith("${device."):
        # device attribute targets are handled by the device allocator
        return "", False
    return "", False


def is_class_escaped(target: str) -> bool:
    """Whether a constraint target defeats computed-class memoization
    (reference scheduler/context.go:292-305 EvalEligibility escape set:
    anything node-unique)."""
    return (
        "${node.unique." in target
        or "${attr.unique." in target
        or "${meta.unique." in target
    )


# ---------------------------------------------------------------------------
# operator checks (reference feasible.go:833-1110)
# ---------------------------------------------------------------------------

_num_int = re.compile(r"^[+-]?\d+$")


def _check_order(operand: str, l: str, r: str) -> bool:
    """Integer comparison if both parse, else float, else lexical
    (reference feasible.go:880-940)."""
    if _num_int.match(l) and _num_int.match(r):
        li, ri = int(l), int(r)
    else:
        try:
            li, ri = float(l), float(r)
        except ValueError:
            li, ri = l, r
    if operand == "<":
        return li < ri
    if operand == "<=":
        return li <= ri
    if operand == ">":
        return li > ri
    if operand == ">=":
        return li >= ri
    return False


class _Version:
    """Minimal go-version-style version: dotted numeric segments with an
    optional -prerelease suffix (prerelease sorts before release)."""

    __slots__ = ("segments", "prerelease", "written")

    def __init__(self, s: str):
        s = s.strip().lstrip("v")
        if "+" in s:  # build metadata ignored
            s = s.split("+", 1)[0]
        if "-" in s:
            base, self.prerelease = s.split("-", 1)
        else:
            base, self.prerelease = s, ""
        segs = []
        for part in base.split("."):
            if not _num_int.match(part):
                raise ValueError(f"bad version segment {part!r} in {s!r}")
            segs.append(int(part))
        if not segs:
            raise ValueError(f"empty version {s!r}")
        self.written = len(segs)  # segments the user actually wrote ("~>" cares)
        while len(segs) < 3:
            segs.append(0)
        self.segments = tuple(segs)

    def _key(self):
        # a prerelease sorts before the release it prefixes
        return (self.segments, 0 if self.prerelease == "" else -1, self.prerelease)

    def __lt__(self, o):  # pragma: no cover - trivially exercised via cmp
        return (self.segments, self.prerelease == "", self.prerelease) < (
            o.segments, o.prerelease == "", o.prerelease)

    def cmp(self, o: "_Version") -> int:
        if self.segments != o.segments:
            return -1 if self.segments < o.segments else 1
        # equal segments: release > prerelease; prereleases compare lexically
        if self.prerelease == o.prerelease:
            return 0
        if self.prerelease == "":
            return 1
        if o.prerelease == "":
            return -1
        return -1 if self.prerelease < o.prerelease else 1


_ver_con = re.compile(r"^\s*(~>|>=|<=|!=|=|>|<)?\s*(.+?)\s*$")


def check_version_constraint(version_str: str, constraint_str: str,
                             cache: Optional[dict] = None) -> bool:
    """go-version style constraint check: comma-separated AND of
    "<op> <version>" clauses incl. pessimistic "~>"
    (reference feasible.go:948 checkVersionMatch)."""
    try:
        ver = _Version(version_str)
    except ValueError:
        return False
    key = constraint_str
    clauses = cache.get(key) if cache is not None else None
    if clauses is None:
        clauses = []
        try:
            for raw in constraint_str.split(","):
                m = _ver_con.match(raw)
                if not m or not m.group(2):
                    return False
                clauses.append((m.group(1) or "=", _Version(m.group(2))))
        except ValueError:
            clauses = False  # cache the parse failure
        if cache is not None:
            cache[key] = clauses
    if clauses is False:
        return False
    for op, target in clauses:
        c = ver.cmp(target)
        if op == "=" and c != 0:
            return False
        if op == "!=" and c == 0:
            return False
        if op == ">" and c != 1:
            return False
        if op == ">=" and c == -1:
            return False
        if op == "<" and c != -1:
            return False
        if op == "<=" and c == 1:
            return False
        if op == "~>":
            # pessimistic: >= target, < target with the second-to-last
            # *written* segment bumped ("~> 1.2" -> < 2.0.0, "~> 1.2.3"
            # -> < 1.3.0, "~> 1" -> < 2.0.0) — go-version semantics
            if c == -1:
                return False
            upper = list(target.segments)
            bump = max(0, target.written - 2)
            upper[bump] += 1
            for i in range(bump + 1, len(upper)):
                upper[i] = 0
            if ver.cmp(_Version(".".join(map(str, upper)))) != -1:
                return False
    return True


def _split_set(s: str) -> set:
    return {part.strip() for part in s.split(",")}


def check_constraint(operand: str, lval: str, rval: str, lfound: bool, rfound: bool,
                     regex_cache: Optional[dict] = None,
                     version_cache: Optional[dict] = None) -> bool:
    """Exact reference semantics (feasible.go:833 checkConstraint)."""
    if operand in (enums.CONSTRAINT_DISTINCT_HOSTS, enums.CONSTRAINT_DISTINCT_PROPERTY):
        return True  # handled by dedicated iterators
    if operand in ("=", "==", "is"):
        return lfound and rfound and lval == rval
    if operand in ("!=", "not"):
        # reference uses reflect.DeepEqual on possibly-missing values:
        # missing != present is true; missing != missing compares "" == ""
        if not lfound and not rfound:
            return False
        if lfound != rfound:
            return True
        return lval != rval
    if operand in ("<", "<=", ">", ">="):
        return lfound and rfound and _check_order(operand, lval, rval)
    if operand == enums.CONSTRAINT_IS_SET:
        return lfound
    if operand == enums.CONSTRAINT_IS_NOT_SET:
        return not lfound
    if operand in (enums.CONSTRAINT_VERSION, enums.CONSTRAINT_SEMVER):
        return lfound and rfound and check_version_constraint(lval, rval, version_cache)
    if operand == enums.CONSTRAINT_REGEX:
        if not (lfound and rfound):
            return False
        rx = regex_cache.get(rval) if regex_cache is not None else None
        if rx is None:
            try:
                rx = re.compile(rval)
            except re.error:
                if regex_cache is not None:
                    regex_cache[rval] = False
                return False
            if regex_cache is not None:
                regex_cache[rval] = rx
        if rx is False:
            return False
        return rx.search(lval) is not None
    if operand in (enums.CONSTRAINT_SET_CONTAINS, enums.CONSTRAINT_SET_CONTAINS_ALL):
        if not (lfound and rfound):
            return False
        have = _split_set(lval)
        return all(want in have for want in _split_set(rval))
    if operand == enums.CONSTRAINT_SET_CONTAINS_ANY:
        if not (lfound and rfound):
            return False
        have = _split_set(lval)
        return any(want in have for want in _split_set(rval))
    return False


def node_meets_constraint(c: Constraint, node: Node,
                          regex_cache: Optional[dict] = None,
                          version_cache: Optional[dict] = None) -> bool:
    lval, lfound = resolve_target(c.ltarget, node)
    rval, rfound = resolve_target(c.rtarget, node)
    return check_constraint(c.operand, lval, rval, lfound, rfound,
                            regex_cache, version_cache)


# ---------------------------------------------------------------------------
# vectorized masks — the bridge to the tensor layer
# ---------------------------------------------------------------------------


def constraint_mask(c: Constraint, nodes: Sequence[Node],
                    regex_cache: Optional[dict] = None,
                    version_cache: Optional[dict] = None) -> np.ndarray:
    """Boolean feasibility of one constraint over a node list. This is the
    host-side "precompile" step: regex/version/semver get parsed once and
    evaluated per *unique attribute value*, not per node."""
    out = np.empty(len(nodes), dtype=bool)
    memo: Dict[Tuple[str, bool, str, bool], bool] = {}
    for i, node in enumerate(nodes):
        lval, lfound = resolve_target(c.ltarget, node)
        rval, rfound = resolve_target(c.rtarget, node)
        key = (lval, lfound, rval, rfound)
        hit = memo.get(key)
        if hit is None:
            hit = check_constraint(c.operand, lval, rval, lfound, rfound,
                                   regex_cache, version_cache)
            memo[key] = hit
        out[i] = hit
    return out


def driver_mask(tg: TaskGroup, nodes: Sequence[Node]) -> np.ndarray:
    """DriverChecker (reference feasible.go:470): every task's driver must
    be present and healthy on the node."""
    drivers = {t.driver for t in tg.tasks}
    out = np.empty(len(nodes), dtype=bool)
    for i, node in enumerate(nodes):
        ok = True
        for d in drivers:
            if node.drivers.get(d):
                continue
            # fall back to fingerprinted attribute (reference checks
            # driver.<name> node attribute for compatibility)
            v = node.attributes.get(f"driver.{d}", "")
            if str(v).lower() in ("1", "true"):
                continue
            ok = False
            break
        out[i] = ok
    return out


def device_mask(tg: TaskGroup, nodes: Sequence[Node]) -> np.ndarray:
    """DeviceChecker (reference feasible.go:1259): the node must have
    enough instances of each requested device among its groups that
    match the ask's name and meet the ask's constraints (ignoring
    current usage: usage is checked during ranking/fit). Instances are
    counted over all such groups, as the count columns and DeviceIndex
    count them (scheduler/devices.py groups_capacity)."""
    from .devices import device_capacity

    asks = [d for t in tg.tasks for d in t.resources.devices]
    if not asks:
        return np.ones(len(nodes), dtype=bool)
    regex_cache: dict = {}
    version_cache: dict = {}
    out = np.empty(len(nodes), dtype=bool)
    for i, node in enumerate(nodes):
        out[i] = all(device_capacity(node, ask, regex_cache, version_cache)
                     >= ask.count for ask in asks)
    return out


def network_mask(tg: TaskGroup, nodes: Sequence[Node]) -> np.ndarray:
    """NetworkChecker (reference feasible.go:373): the requested network
    mode must be available on the node. "host" mode (and "" = default) is
    always available; "bridge" requires the bridge fingerprint; "cni/*"
    modes must be fingerprinted by name."""
    modes = set()
    for net in tg.networks:
        modes.add(net.mode or "host")
    for t in tg.tasks:
        for net in t.resources.networks:
            modes.add(net.mode or "host")
    modes.discard("host")
    if not modes:
        return np.ones(len(nodes), dtype=bool)
    out = np.empty(len(nodes), dtype=bool)
    for i, node in enumerate(nodes):
        have = {n.mode for n in node.resources.networks}
        ok = True
        for m in modes:
            if m in have:
                continue
            if m == "bridge" and str(node.attributes.get(
                    "network.bridge", "")).lower() in ("1", "true"):
                continue
            if m.startswith("cni/") and str(node.attributes.get(
                    f"plugins.cni.version.{m[4:]}", "")):
                continue
            ok = False
            break
        out[i] = ok
    return out


def host_volume_mask(tg: TaskGroup, nodes: Sequence[Node]) -> np.ndarray:
    """HostVolumeChecker (reference feasible.go:139): every host-type
    volume request must name a volume the node exposes; a read-write
    request needs a non-read-only host volume. Class-memoizable: host
    volumes ride the computed-class hash."""
    asks = [v for v in tg.volumes.values() if v.type == "host"]
    if not asks:
        return np.ones(len(nodes), dtype=bool)
    out = np.empty(len(nodes), dtype=bool)
    for i, node in enumerate(nodes):
        ok = True
        for req in asks:
            hv = node.host_volumes.get(req.source)
            if hv is None or (getattr(hv, "read_only", False)
                              and not req.read_only):
                ok = False
                break
        out[i] = ok
    return out


def csi_volume_mask(tg: TaskGroup, nodes: Sequence[Node],
                    snapshot, namespace: str = "default",
                    plan=None) -> np.ndarray:
    """CSIVolumeChecker (reference feasible.go:223): every csi-type
    request must name a registered volume whose topology admits the node
    and whose access mode has room for our claim. Writer exclusivity only
    counts LIVE claims not being stopped by the in-progress plan
    (volumes.live_blocking_writers) so updates/reschedules of the
    claiming job don't deadlock on their own claim while a scale-up's
    live sibling still blocks. NOT class-memoized — claims change
    independently of node classes."""
    from ..structs.volumes import MULTI_WRITER_MODES, live_blocking_writers

    asks = [v for v in tg.volumes.values() if v.type == "csi"]
    if not asks:
        return np.ones(len(nodes), dtype=bool)
    if snapshot is None:
        return np.zeros(len(nodes), dtype=bool)
    vols = []
    for req in asks:
        vol = snapshot.volume_by_id(req.source, namespace)
        if vol is None:
            return np.zeros(len(nodes), dtype=bool)
        if (not req.read_only and vol.access_mode not in MULTI_WRITER_MODES
                and live_blocking_writers(vol, snapshot, plan)):
            return np.zeros(len(nodes), dtype=bool)
        vols.append(vol)
    out = np.empty(len(nodes), dtype=bool)
    for i, node in enumerate(nodes):
        out[i] = all(v.schedulable_on(node.id) for v in vols)
    return out


def reserved_ports_mask(tg: TaskGroup, nodes: Sequence[Node],
                        proposed_allocs_fn) -> np.ndarray:
    """Static-port feasibility: every reserved port the group asks for
    must be free on the node given its proposed allocs (reference does
    this inside BinPackIterator via NetworkIndex; host-side here so the
    tensor path can fold it into the feasibility mask)."""
    asks = tg.combined_resources().reserved_port_asks()
    if not asks:
        return np.ones(len(nodes), dtype=bool)
    from ..structs.network import NetworkIndex

    want = [p for _, p in asks]
    out = np.empty(len(nodes), dtype=bool)
    for i, node in enumerate(nodes):
        idx = NetworkIndex(node)
        idx.add_allocs(proposed_allocs_fn(node.id))
        out[i] = not any(p in idx.used for p in want)
    return out


def job_constraints(job: Job, tg: TaskGroup) -> List[Constraint]:
    """Merged constraint set: job-level + group-level + every task's
    (reference stack pushes job then tg constraints through the chain)."""
    out = list(job.constraints) + list(tg.constraints)
    for t in tg.tasks:
        out.extend(t.constraints)
    return out


def feasible_mask_static(job: Job, tg: TaskGroup, nodes: Sequence[Node],
                         regex_cache: Optional[dict] = None,
                         version_cache: Optional[dict] = None) -> np.ndarray:
    """The node-attribute-only part of the feasibility mask: constraints
    + drivers + devices + network modes + host volumes. Depends only on
    node identity/attributes — cacheable per (task-group signature,
    node-set version) by the tensor layer (tg_mask_signature)."""
    mask = driver_mask(tg, nodes)
    if not mask.any():
        return mask
    mask &= device_mask(tg, nodes)
    mask &= network_mask(tg, nodes)
    mask &= host_volume_mask(tg, nodes)
    for c in job_constraints(job, tg):
        if not mask.any():
            break
        mask &= constraint_mask(c, nodes, regex_cache, version_cache)
    return mask


def tg_mask_signature(job: Job, tg: TaskGroup) -> tuple:
    """Cache key capturing every input of feasible_mask_static other than
    the node set itself."""
    drivers = tuple(sorted({t.driver for t in tg.tasks}))
    devs = tuple(sorted((d.name, d.count,
                         tuple((c.ltarget, c.operand, c.rtarget)
                               for c in d.constraints))
                        for t in tg.tasks for d in t.resources.devices))
    modes = set()
    for net in tg.networks:
        modes.add(net.mode or "host")
    for t in tg.tasks:
        for net in t.resources.networks:
            modes.add(net.mode or "host")
    hvols = tuple(sorted((v.source, v.read_only)
                         for v in tg.volumes.values() if v.type == "host"))
    cons = tuple((c.ltarget, c.operand, c.rtarget)
                 for c in job_constraints(job, tg))
    return (drivers, devs, tuple(sorted(modes)), hvols, cons)


def feasible_mask(job: Job, tg: TaskGroup, nodes: Sequence[Node],
                  regex_cache: Optional[dict] = None,
                  version_cache: Optional[dict] = None,
                  snapshot=None, plan=None) -> np.ndarray:
    """Full boolean feasibility mask for one task group over a node list:
    constraints + drivers + devices + volumes. Datacenter/pool/readiness
    filtering is assumed done upstream (reference readyNodesInDCsAndPool).
    `snapshot` powers the csi-volume claim check; without it csi-volume
    groups mask everything out."""
    mask = feasible_mask_static(job, tg, nodes, regex_cache, version_cache)
    if any(v.type == "csi" for v in tg.volumes.values()):
        mask = mask & csi_volume_mask(tg, nodes, snapshot, job.namespace, plan)
    return mask


# ---------------------------------------------------------------------------
# distinct_hosts / distinct_property (reference feasible.go:542,649)
# ---------------------------------------------------------------------------


def has_distinct_hosts(job: Job, tg: TaskGroup) -> bool:
    return any(
        c.operand == enums.CONSTRAINT_DISTINCT_HOSTS and _truthy(c.rtarget)
        for c in list(job.constraints) + list(tg.constraints)
    )


def _truthy(rtarget: str) -> bool:
    return rtarget in ("", "true", "True", "1")


def distinct_property_constraints(job: Job, tg: TaskGroup) -> List[Constraint]:
    return [
        c for c in list(job.constraints) + list(tg.constraints)
        if c.operand == enums.CONSTRAINT_DISTINCT_PROPERTY
    ]


def distinct_hosts_flags(job: Job, tg: TaskGroup) -> Tuple[bool, bool]:
    """(job_level, tg_level) distinct_hosts enablement — the single source
    of truth shared by the host iterator and the tensor lowering."""
    job_level = any(
        c.operand == enums.CONSTRAINT_DISTINCT_HOSTS and _truthy(c.rtarget)
        for c in job.constraints)
    tg_level = any(
        c.operand == enums.CONSTRAINT_DISTINCT_HOSTS and _truthy(c.rtarget)
        for c in tg.constraints)
    return job_level, tg_level


def distinct_hosts_mask(job: Job, tg: TaskGroup, nodes: Sequence[Node],
                        proposed_by_node) -> np.ndarray:
    """Mask out nodes already carrying an alloc of this job (job-level) or
    this task group (group-level) (reference feasible.go:542
    DistinctHostsIterator)."""
    job_level, tg_level = distinct_hosts_flags(job, tg)
    if not job_level and not tg_level:
        return np.ones(len(nodes), dtype=bool)
    out = np.ones(len(nodes), dtype=bool)
    for i, node in enumerate(nodes):
        for alloc in proposed_by_node(node.id):
            if alloc.job_id != job.id or alloc.namespace != job.namespace:
                continue
            if job_level or (tg_level and alloc.task_group == tg.name):
                out[i] = False
                break
    return out


def distinct_property_mask(job: Job, tg: TaskGroup, nodes: Sequence[Node],
                           all_job_allocs, node_by_id) -> np.ndarray:
    """Limit allocs per distinct value of a node property
    (reference scheduler/propertyset.go). rtarget is the max count per
    value (default 1)."""
    constraints = distinct_property_constraints(job, tg)
    if not constraints:
        return np.ones(len(nodes), dtype=bool)
    out = np.ones(len(nodes), dtype=bool)
    live_allocs = [a for a in all_job_allocs
                   if not a.terminal_status()]
    for c in constraints:
        try:
            limit = int(c.rtarget) if c.rtarget else 1
        except ValueError:
            limit = 1
        # count existing allocs per property value
        counts: Dict[str, int] = {}
        for alloc in live_allocs:
            anode = node_by_id(alloc.node_id)
            if anode is None:
                continue
            val, found = resolve_target(c.ltarget, anode)
            if found:
                counts[val] = counts.get(val, 0) + 1
        for i, node in enumerate(nodes):
            val, found = resolve_target(c.ltarget, node)
            if not found or counts.get(val, 0) >= limit:
                out[i] = False
    return out
