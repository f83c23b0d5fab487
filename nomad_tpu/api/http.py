"""HTTP agent API (reference command/agent/http.go:382-528).

Serves the /v1/* surface over an in-process core.Server. Implements the
reference's blocking-query protocol: pass ?index=N&wait=SECONDS and the
GET parks until the state store passes index N (or the wait expires),
responses carry X-Nomad-Index (command/agent/http.go blocking queries).
"""

from __future__ import annotations

import base64
import json
import logging
import os
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from ..core.loadctl import (RetryLater, TIER_COMMIT, TIER_READ, TIER_SUBMIT,
                            bind_deadline, bind_tier, deadline_expired)
from ..obs import TRACER
from ..structs import enums
from ..structs.job import Job
from ..structs.node import DrainStrategy
from .codec import from_dict, to_dict
from .jobspec import _validate

log = logging.getLogger("nomad_tpu.api")

MAX_BLOCK_S = 30.0
# nomadload HTTP hardening: reject oversized bodies (413) and malformed
# JSON (400) BEFORE touching a store snapshot or any endpoint logic
MAX_BODY_BYTES = 8 << 20


class BodyTooLarge(Exception):
    pass


class MalformedBody(Exception):
    pass

_WAIT_UNITS = {"ms": 1e-3, "s": 1.0, "m": 60.0, "h": 3600.0}


def _parse_wait(raw: str) -> Optional[float]:
    """Blocking-query ``wait`` values: plain seconds or a Go-style
    duration ("10s", "250ms", "1m") — the reference client sends the
    latter. None for empty/garbage; the caller picks the policy (a
    long-poll falls back to its default, the event stream 400s before
    committing the chunked response)."""
    raw = (raw or "").strip()
    for unit in ("ms", "s", "m", "h"):
        if raw.endswith(unit):
            try:
                return float(raw[:-len(unit)]) * _WAIT_UNITS[unit]
            except ValueError:
                return None
    try:
        return float(raw) if raw else None
    except ValueError:
        return None

# /v1/agent/monitor may lower the framework logger level while streams
# are attached; overlapping streams refcount the original level so the
# LAST one restores it (a plain save/restore pair leaves the process
# stuck at the lowest level after interleaved streams)
_monitor_lock = threading.Lock()
_monitor_state: Dict[int, list] = {}  # id(logger) -> [count, orig_level]


def _monitor_level_push(logger, level: int) -> None:
    with _monitor_lock:
        st = _monitor_state.get(id(logger))
        if st is None:
            st = _monitor_state[id(logger)] = [0, logger.level]
        st[0] += 1
        # only ever LOWER the effective level: a coarse monitor stream
        # must not suppress the agent's own warnings
        if logger.getEffectiveLevel() > level:
            logger.setLevel(level)


def _monitor_level_pop(logger) -> None:
    with _monitor_lock:
        st = _monitor_state.get(id(logger))
        if st is None:
            return
        st[0] -= 1
        if st[0] <= 0:
            logger.setLevel(st[1])
            del _monitor_state[id(logger)]



def _token_wire(token) -> dict:
    """The ACL-token response shape every token-returning route shares
    (bootstrap, create, login, OIDC, one-time exchange)."""
    return {
        "accessor_id": token.accessor_id,
        "secret_id": token.secret_id,
        "type": token.type,
        "policies": token.policies, "roles": token.roles,
        "expiration_time": token.expiration_time}


class HTTPAgent:
    """The agent HTTP server. Start with port=0 for an ephemeral port."""

    def __init__(self, server, host: str = "127.0.0.1", port: int = 4646,
                 writer=None, clients=None):
        self.server = server
        # In a replicated deployment `writer` is the ReplicatedServer
        # facade: mutating verbs route to the raft leader (local or over
        # the socket transport) while reads stay on the local replica's
        # store — the reference's HTTP-agent -> RPC forward split.
        self.writer = writer if writer is not None else server
        # co-located client agents (dev/agent mode): serve their log
        # files and host stats directly (the reference forwards these
        # routes over server->client RPC instead)
        self.clients = list(clients or [])
        agent = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # quiet
                if agent.server.logger:
                    agent.server.logger.debug("http: " + fmt, *args)

            # per-request read state (reset at the top of each verb —
            # handler instances persist across keep-alive requests)
            _read_index: Optional[int] = None
            _known_leader: Optional[bool] = None
            _last_contact_ms: Optional[int] = None
            _degraded: bool = False

            def _reply(self, code: int, payload, index: Optional[int] = None):
                body = json.dumps(to_dict(payload)).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                if index is None:
                    # the index of the snapshot the payload was read
                    # from (_route_get stamps it) — NOT latest_index,
                    # which can be ahead of the data and make a watcher
                    # skip a wakeup
                    index = self._read_index
                self.send_header("X-Nomad-Index",
                                 str(index if index is not None
                                     else agent.server.store.latest_index))
                if self._known_leader is not None:
                    self.send_header("X-Nomad-KnownLeader",
                                     "true" if self._known_leader
                                     else "false")
                if self._last_contact_ms is not None:
                    self.send_header("X-Nomad-LastContact",
                                     str(self._last_contact_ms))
                if self._degraded:
                    # brownout: this read skipped the read-index round
                    # and may be stale — say so truthfully
                    self.send_header("X-Nomad-Consistency-Degraded",
                                     "true")
                self.end_headers()
                self.wfile.write(body)

            def _error(self, code: int, msg: str):
                self._reply(code, {"error": msg})

            def _body(self) -> dict:
                length = int(self.headers.get("Content-Length") or 0)
                if not length:
                    return {}
                if length > MAX_BODY_BYTES:
                    # refuse before reading: the bytes never enter the
                    # process (the keep-alive connection is closed since
                    # the unread body would corrupt the next request)
                    raise BodyTooLarge(f"{length} bytes > {MAX_BODY_BYTES}")
                raw = self.rfile.read(length)
                try:
                    return json.loads(raw)
                except ValueError as e:
                    raise MalformedBody(str(e)) from None

            def _bound_ctx(self, tier: int):
                """Bind the request's (deadline, tier) from headers for
                the duration of the verb (nomadload deadline
                propagation: X-Nomad-Deadline is an absolute epoch
                timestamp stamped by the client from its timeout)."""
                raw = self.headers.get("X-Nomad-Deadline", "")
                dl = None
                if raw:
                    try:
                        dl = float(raw)
                    except ValueError:
                        dl = None
                return bind_deadline(dl), bind_tier(tier)

            def _retry_later(self, e: RetryLater) -> None:
                """429 + Retry-After: the admission plane shed this
                request; the client backs off within its retry budget."""
                body = json.dumps({"error": str(e)}).encode()
                self.send_response(429)
                self.send_header("Content-Type", "application/json")
                self.send_header("Retry-After", f"{max(e.after, 0.0):.3f}")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _block(self, q: dict) -> None:
                """Blocking query: park until the store moves past index
                (the waiter table wakes us on the exact commit — no
                20 ms poll loop, no latency floor)."""
                want = int(q.get("index", ["0"])[0] or 0)
                if want <= 0:
                    return
                parsed = _parse_wait(q.get("wait", [""])[0])
                wait = min(parsed if parsed is not None else 5.0,
                           MAX_BLOCK_S)
                with TRACER.span("read.index_wait", want=want):
                    agent.server.store.watches.wait_min_index(
                        want + 1, wait)

            def _acl(self):
                """Resolve X-Nomad-Token -> ACL (None when ACLs are off;
                reference command/agent/http.go token extraction)."""
                if not agent.server.acl_enabled:
                    return None
                secret = self.headers.get("X-Nomad-Token", "")
                try:
                    acl = agent.server.resolve_token(secret)
                except PermissionError:
                    acl = None
                if acl is None:
                    from ..acl.policy import DENY_ALL_ACL

                    return DENY_ALL_ACL
                return acl

            def _maybe_forward_region(self, method, path, q, body=None):
                """?region=X for a foreign region proxies the request to
                that region's agent (reference nomad/rpc.go forwardRegion;
                ours rides the HTTP surface). -> True when handled."""
                region = q.get("region", [""])[0]
                if not region or region == agent.server.config.region:
                    return False
                addr = agent.server.region_address(region)
                if addr is None:
                    self._error(404, f"unknown region {region!r}")
                    return True
                from urllib.parse import urlencode
                import urllib.error
                import urllib.request as _rq

                # keep repeated params (topic filters etc.): doseq
                fq = {k: v for k, v in q.items() if k != "region"}
                url = f"{addr}{path}"
                if fq:
                    url += "?" + urlencode(fq, doseq=True)
                headers = {"Content-Type": "application/json"}
                tok = self.headers.get("X-Nomad-Token", "")
                if tok:
                    headers["X-Nomad-Token"] = tok
                req = _rq.Request(
                    url, method=method,
                    data=json.dumps(body).encode() if body is not None
                    else None,
                    headers=headers)
                # the timeout must outlast a forwarded blocking query or
                # stream wait, or healthy long-polls turn into 502s
                try:
                    fwait = _parse_wait(fq.get("wait", [""])[0])
                except IndexError:
                    fwait = None
                wait = min(fwait if fwait is not None else 60.0, 600.0)
                committed = False
                try:
                    with _rq.urlopen(req, timeout=wait + 30.0) as resp:
                        self.send_response(resp.status)
                        self.send_header("Content-Type", "application/json")
                        idx = resp.headers.get("X-Nomad-Index")
                        if idx:
                            # blocking-query clients park on this
                            self.send_header("X-Nomad-Index", idx)
                        length = resp.headers.get("Content-Length")
                        if length is not None:
                            self.send_header("Content-Length", length)
                            committed = True
                            self.end_headers()
                            self.wfile.write(resp.read())
                        else:
                            # streaming upstream (event stream/monitor):
                            # relay chunks as they arrive
                            self.send_header("Transfer-Encoding", "chunked")
                            committed = True
                            self.end_headers()
                            while True:
                                chunk = resp.read(65536)
                                if not chunk:
                                    break
                                self.wfile.write(
                                    f"{len(chunk):x}\r\n".encode()
                                    + chunk + b"\r\n")
                                self.wfile.flush()
                            self.wfile.write(b"0\r\n\r\n")
                except urllib.error.HTTPError as e:
                    data = e.read()
                    self.send_response(e.code)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)
                except (OSError, ValueError) as e:
                    # ValueError: malformed registered address. A
                    # mid-stream failure must NOT inject a second
                    # response into a committed chunked body — just
                    # drop the connection
                    if not committed:
                        try:
                            self._error(502,
                                        f"region {region!r} failed: {e}")
                        except OSError:
                            log.debug("client gone before 502 for region "
                                      "%s could be written", region,
                                      exc_info=True)
                    else:
                        log.debug("relay to region %s failed mid-stream",
                                  region, exc_info=True)
                except Exception:
                    # e.g. http.client.IncompleteRead mid-relay: same
                    # rule — never write a second response
                    if not committed:
                        raise
                    log.debug("relay to region %s failed after response "
                              "was committed", region, exc_info=True)
                return True

            def do_GET(self):
                try:
                    self._read_index = None
                    self._known_leader = None
                    self._last_contact_ms = None
                    self._degraded = False
                    url = urlparse(self.path)
                    if url.path in ("/", "/ui", "/ui/"):
                        # the embedded dashboard (reference serves the
                        # Ember app from bindata the same way)
                        from .ui import UI_HTML

                        body = UI_HTML.encode()
                        self.send_response(200)
                        self.send_header("Content-Type",
                                         "text/html; charset=utf-8")
                        self.send_header("Content-Length", str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                        return
                    q = parse_qs(url.query)
                    if self._maybe_forward_region("GET", url.path, q):
                        return
                    b_dl, b_tier = self._bound_ctx(TIER_READ)
                    with b_dl, b_tier:
                        if deadline_expired():
                            return self._error(
                                504, "request deadline passed")
                        agent._admit_http(TIER_READ, "http_get")
                        acl = self._acl()
                        if url.path == "/v1/event/stream":
                            # the stream carries payloads from every
                            # namespace; management-only under ACLs
                            if acl is not None and not acl.management:
                                return self._error(403, "Permission denied")
                            return agent._route_event_stream(self, q)
                        if url.path == "/v1/agent/monitor":
                            if acl is not None and not acl.allow_agent_read():
                                return self._error(403, "Permission denied")
                            return agent._route_monitor(self, q)
                        if agent._setup_read(self, q):
                            return  # no leader / read index timed out
                        self._block(q)
                        agent._route_get(self, url.path, q, acl)
                except RetryLater as e:
                    self._retry_later(e)
                except PermissionError as e:
                    self._error(403, str(e))
                except Exception as e:
                    # the client only sees str(e); keep the traceback
                    log.debug("GET %s -> 500", self.path, exc_info=True)
                    self._error(500, str(e))

            def do_POST(self):
                try:
                    self._read_index = None
                    self._known_leader = None
                    self._last_contact_ms = None
                    self._degraded = False
                    url = urlparse(self.path)
                    q = parse_qs(url.query)
                    # body-size / JSON fast-reject runs BEFORE any store
                    # snapshot or endpoint work (nomadload hardening)
                    body = self._body()
                    if self._maybe_forward_region("POST", url.path, q,
                                                  body):
                        return
                    tier = agent._http_tier(url.path)
                    b_dl, b_tier = self._bound_ctx(tier)
                    with b_dl, b_tier:
                        if deadline_expired():
                            return self._error(
                                504, "request deadline passed")
                        agent._admit_http(tier, "http_write")
                        agent._route_post(self, url.path, q, body,
                                          self._acl())
                except BodyTooLarge as e:
                    self.close_connection = True
                    self._error(413, f"request body too large: {e}")
                except MalformedBody as e:
                    self._error(400, f"malformed JSON body: {e}")
                except RetryLater as e:
                    self._retry_later(e)
                except PermissionError as e:
                    self._error(403, str(e))
                except Exception as e:
                    log.debug("POST %s -> 500", self.path, exc_info=True)
                    self._error(500, str(e))

            do_PUT = do_POST

            def do_DELETE(self):
                try:
                    self._read_index = None
                    self._known_leader = None
                    self._last_contact_ms = None
                    self._degraded = False
                    url = urlparse(self.path)
                    q = parse_qs(url.query)
                    if self._maybe_forward_region("DELETE", url.path, q):
                        return
                    tier = agent._http_tier(url.path)
                    b_dl, b_tier = self._bound_ctx(tier)
                    with b_dl, b_tier:
                        if deadline_expired():
                            return self._error(
                                504, "request deadline passed")
                        agent._admit_http(tier, "http_write")
                        agent._route_delete(self, url.path, q, self._acl())
                except RetryLater as e:
                    self._retry_later(e)
                except PermissionError as e:
                    self._error(403, str(e))
                except Exception as e:
                    log.debug("DELETE %s -> 500", self.path, exc_info=True)
                    self._error(500, str(e))

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._httpd.daemon_threads = True
        self.address = f"http://{host}:{self._httpd.server_port}"
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle --

    def start(self) -> "HTTPAgent":
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True, name="http-agent")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=2.0)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- routing (reference http.go registerHandlers) --

    @staticmethod
    def _http_tier(path: str) -> int:
        """Admission tier of a mutating HTTP route: alloc/node
        lifecycle updates are commit-tier (they answer running
        workloads); everything else a write submits new work."""
        if path.startswith(("/v1/allocation/", "/v1/node/", "/v1/nodes")):
            return TIER_COMMIT
        return TIER_SUBMIT

    def _admit_http(self, tier: int, source: str) -> None:
        """Ingress admission (nomadload): raises RetryLater -> 429."""
        adm = getattr(self.server, "loadctl", None)
        if adm is not None:
            adm.admit(tier, source=source)

    @staticmethod
    def _ns_allowed(acl, ns: str, cap: str) -> bool:
        return acl is None or acl.allow_namespace_operation(ns, cap)

    def _setup_read(self, h, q: dict) -> bool:
        """Read-consistency negotiation for GETs on a replicated server
        (reference api/api.go QueryOptions AllowStale/consistency modes).
        Three modes, all answered by THIS server — reads never forward:

        - ``?stale=true``: serve immediately from the local replica,
          staleness bounded by X-Nomad-LastContact.
        - default: read-index protocol — the leader (one hop away at
          most) confirms leadership via its held lease and names a read
          index; we serve once the local FSM has applied past it.
        - ``?consistent=true``: same, but the leader must prove
          leadership with a full heartbeat round (no lease shortcut).

        Returns True when the request was fully handled here (503 no
        leader / 500 timeout); False to continue into the route."""
        raft = getattr(self.writer, "raft", None)
        if raft is None:
            return False  # standalone server: local reads are the truth
        from ..core.metrics import REGISTRY

        h._known_leader = self.writer.known_leader()
        lc = self.writer.last_contact()
        h._last_contact_ms = int(min(lc, 10 ** 6) * 1000)
        if raft.is_leader():
            REGISTRY.incr("nomad.reads.leader")
        else:
            REGISTRY.incr("nomad.reads.follower")
        if q.get("stale", [""])[0] == "true":
            REGISTRY.incr("nomad.reads.stale")
            return False
        adm = getattr(self.server, "loadctl", None)
        if adm is not None and adm.degraded():
            # brownout: answer from the local replica without the
            # read-index round trip; the response carries
            # X-Nomad-Consistency-Degraded so the client knows the
            # consistency contract was downgraded, and LastContact
            # still bounds the staleness
            REGISTRY.incr("nomad.reads.degraded")
            h._degraded = True
            return False
        consistent = q.get("consistent", [""])[0] == "true"
        from ..raft.node import NotLeaderError

        try:
            with TRACER.span("read.index_wait", mode="read_index"):
                idx = self.writer.read_index(consistent=consistent,
                                             timeout=2.0)
                self.writer.wait_applied(idx, timeout=5.0)
        except NotLeaderError:
            REGISTRY.incr("nomad.reads.no_leader")
            h._known_leader = self.writer.known_leader()
            h._reply(503, {"error": "no cluster leader"})
            return True
        except TimeoutError as e:
            h._reply(500, {"error": f"read index wait: {e}"})
            return True
        return False

    def _route_get(self, h, path: str, q: dict, acl=None) -> None:
        from ..acl import policy as aclp

        snap = self.server.store.snapshot()
        # X-Nomad-Index must be the index of THIS snapshot — the default
        # (latest_index at reply time) can run ahead of the payload and
        # make a blocking-query client skip a change
        h._read_index = snap.index
        ns = q.get("namespace", ["default"])[0]
        prefix = q.get("prefix", [""])[0]

        # coarse read gating per route family (job_endpoint/node_endpoint
        # authorization in the reference)
        if path.startswith(("/v1/jobs", "/v1/allocation", "/v1/evaluation")) \
                and not path.startswith("/v1/jobs/"):
            # cross-namespace lists and by-id fetches: the query-param ns is
            # not the object's ns, so reject only tokens that can read
            # nowhere; rows/objects are authorized below against their own
            # namespace (the reference does the same post-lookup check)
            if acl is not None and not acl.allow_namespace_any(aclp.CAP_READ_JOB):
                return h._error(403, "Permission denied")
        elif path.startswith("/v1/job/"):
            # job routes look up by (query ns, id): gate on that ns
            if not self._ns_allowed(acl, ns, aclp.CAP_READ_JOB):
                return h._error(403, "Permission denied")
        elif path.startswith(("/v1/nodes", "/v1/node/")):
            if acl is not None and not acl.allow_node_read():
                return h._error(403, "Permission denied")
        elif (path.startswith("/v1/agent")
                or path in ("/v1/metrics", "/v1/traces")):
            if acl is not None and not acl.allow_agent_read():
                return h._error(403, "Permission denied")
        elif path.startswith("/v1/operator"):
            if acl is not None and not acl.allow_operator_read():
                return h._error(403, "Permission denied")
        elif path.startswith(("/v1/var", "/v1/vars")):
            if not self._ns_allowed(acl, ns, aclp.CAP_VARIABLES_READ):
                return h._error(403, "Permission denied")
        elif path.startswith("/v1/volume"):
            if not self._ns_allowed(acl, ns, aclp.CAP_READ_JOB):
                return h._error(403, "Permission denied")
        elif path.startswith(("/v1/services", "/v1/service/")):
            # the catalog exposes addresses/ports: read-job in the ns
            # (reference service registration list ACL)
            if not self._ns_allowed(acl, ns, aclp.CAP_READ_JOB):
                return h._error(403, "Permission denied")
        elif path.startswith("/v1/acl"):
            if acl is not None and not acl.management:
                return h._error(403, "Permission denied")

        if path == "/v1/namespaces":
            # filtered to namespaces where the token holds ANY capability
            # (reference namespace_endpoint.go list filtering)
            return h._reply(200, [
                n for n in snap.namespaces()
                if acl is None or acl.allow_namespace(n.name)])
        if m := re.fullmatch(r"/v1/namespace/([^/]+)", path):
            if acl is not None and not acl.allow_namespace(m.group(1)):
                return h._error(403, "Permission denied")
            nsp = snap.namespace(m.group(1))
            if nsp is None:
                return h._error(404, "namespace not found")
            return h._reply(200, nsp)
        if path == "/v1/node/pools":
            return h._reply(200, list(snap.node_pools()))
        if m := re.fullmatch(r"/v1/node/pool/([^/]+)", path):
            pool = snap.node_pool(m.group(1))
            if pool is None:
                return h._error(404, "node pool not found")
            return h._reply(200, pool)
        if path == "/v1/scaling/policies":
            if not self._ns_allowed(acl, ns, aclp.CAP_READ_JOB):
                return h._error(403, "Permission denied")
            return h._reply(200, self.server.scaling_policies(ns))
        if m := re.fullmatch(r"/v1/scaling/policy/(.+)", path):
            for pol in self.server.scaling_policies(None):
                if pol["id"] == m.group(1):
                    # authorize against the POLICY's namespace, not a
                    # caller-chosen query param
                    if not self._ns_allowed(acl, pol["namespace"],
                                            aclp.CAP_READ_JOB):
                        return h._error(403, "Permission denied")
                    return h._reply(200, pol)
            return h._error(404, "scaling policy not found")
        if m := re.fullmatch(r"/v1/job/(.+)/scale", path):
            # (.+): dispatch children carry '/' in their ids; the
            # /v1/job/ family pre-gate above already authorized READ
            job = snap.job_by_id(m.group(1), ns)
            if job is None:
                return h._error(404, "job not found")
            return h._reply(200, {
                "job_id": job.id,
                "task_groups": {tg.name: {
                    "desired": tg.count,
                    "scaling": ({"min": tg.scaling.min,
                                 "max": tg.scaling.max,
                                 "enabled": tg.scaling.enabled}
                                if tg.scaling else None)}
                    for tg in job.task_groups},
                "events": snap.scaling_events(job.id, ns)})
        if path == "/v1/regions":
            # known region names, own region first (reference
            # /v1/regions via serf WAN members)
            names = [self.server.config.region]
            names += sorted(r.name for r in snap.regions()
                            if r.name != self.server.config.region)
            return h._reply(200, names)
        if path == "/v1/operator/regions":
            return h._reply(200, [
                {"name": r.name, "address": r.address}
                for r in snap.regions()])
        if path == "/v1/services":
            # service catalog summary (reference
            # /v1/services ServiceRegistrationListRPC)
            by_name = {}
            for reg in snap.service_registrations(ns):
                e = by_name.setdefault(reg.service_name,
                                       {"service_name": reg.service_name,
                                        "namespace": reg.namespace,
                                        "tags": set(), "instances": 0})
                e["instances"] += 1
                e["tags"].update(reg.tags)
            return h._reply(200, [
                {**e, "tags": sorted(e["tags"])}
                for e in sorted(by_name.values(),
                                key=lambda x: x["service_name"])])
        if m := re.fullmatch(r"/v1/service/([^/]+)", path):
            regs = snap.service_by_name(m.group(1), ns)
            if not regs:
                return h._error(404, "service not found")
            return h._reply(200, regs)
        if path == "/v1/volumes":
            return h._reply(200, [
                {"id": v.id, "namespace": v.namespace, "name": v.name,
                 "access_mode": v.access_mode, "claims": len(v.claims)}
                for v in snap.volumes(ns)])
        if m := re.fullmatch(r"/v1/volume/csi/([^/]+)", path):
            vol = snap.volume_by_id(m.group(1), ns)
            if vol is None:
                return h._error(404, "volume not found")
            return h._reply(200, vol)
        if path == "/v1/vars":
            return h._reply(200, self.server.list_variables(ns, prefix))
        if m := re.fullmatch(r"/v1/var/(.+)", path):
            items = self.server.get_variable(m.group(1), ns)
            if items is None:
                return h._error(404, "variable not found")
            return h._reply(200, {"path": m.group(1), "items": items})
        if path == "/v1/acl/policies":
            return h._reply(200, [
                {"name": p.name, "description": p.description}
                for p in snap.acl_policies()])
        if m := re.fullmatch(r"/v1/acl/policy/([^/]+)", path):
            pol = snap.acl_policy(m.group(1))
            if pol is None:
                return h._error(404, "policy not found")
            return h._reply(200, pol)
        if path == "/v1/acl/tokens":
            return h._reply(200, [
                {"accessor_id": t.accessor_id, "name": t.name,
                 "type": t.type, "policies": t.policies,
                 "roles": getattr(t, "roles", [])}
                for t in snap.acl_tokens()])
        if path == "/v1/acl/auth-methods":
            # trimmed stubs: config carries the JWT validation keys,
            # which must never leave the server (reference returns
            # ACLAuthMethodStub for the list)
            return h._reply(200, [
                {"name": m.name, "type": m.type, "default": m.default,
                 "max_token_ttl_s": m.max_token_ttl_s}
                for m in snap.auth_methods()])
        if path == "/v1/acl/binding-rules":
            return h._reply(200, list(snap.binding_rules()))
        if path == "/v1/acl/roles":
            return h._reply(200, list(snap.acl_roles()))
        if m := re.fullmatch(r"/v1/acl/role/([^/]+)", path):
            role = snap.acl_role(m.group(1))
            if role is None:
                return h._error(404, "role not found")
            return h._reply(200, role)

        # list endpoints span namespaces, so the coarse per-route gate above
        # is not enough: filter rows to namespaces the token can read, and
        # authorize single-object fetches against the object's own namespace
        # (the reference job/alloc endpoints do the same post-lookup check)
        _ns_cache: dict = {}

        def ns_ok(obj_ns: str) -> bool:
            # memoized: called once per row on list endpoints
            hit = _ns_cache.get(obj_ns)
            if hit is None:
                hit = _ns_cache[obj_ns] = \
                    self._ns_allowed(acl, obj_ns, aclp.CAP_READ_JOB)
            return hit

        if path == "/v1/jobs":
            jobs = [j for j in snap.jobs()
                    if j.id.startswith(prefix) and ns_ok(j.namespace)]
            return h._reply(200, [self._job_stub(j, snap) for j in jobs])
        # job ids may contain '/' (dispatched children are
        # "<parent>/dispatch-<ts>-<id>"): suffixed routes match first,
        # then the greedy plain route takes whatever remains
        if m := re.fullmatch(r"/v1/job/(.+)/versions", path):
            if snap.job_by_id(m.group(1), ns) is None:
                return h._error(404, "job not found")
            return h._reply(200, [
                {"version": j.version, "stable": j.stable,
                 "submit_time": j.submit_time,
                 "job_modify_index": j.job_modify_index}
                for j in snap.job_versions(m.group(1), ns)])
        if m := re.fullmatch(r"/v1/job/(.+)/allocations", path):
            return h._reply(200, [self._alloc_stub(a) for a in
                                  snap.allocs_by_job(m.group(1), ns)])
        if m := re.fullmatch(r"/v1/job/(.+)/evaluations", path):
            return h._reply(200, snap.evals_by_job(m.group(1), ns))
        if m := re.fullmatch(r"/v1/job/(.+)/deployments", path):
            return h._reply(200, snap.deployments_by_job(m.group(1), ns))
        if m := re.fullmatch(r"/v1/job/(.+)", path):
            job = snap.job_by_id(m.group(1), ns)
            if job is None:
                return h._error(404, "job not found")
            return h._reply(200, job)

        if path == "/v1/deployments":
            return h._reply(200, [d for d in snap.deployments()
                                  if ns_ok(d.namespace)])
        if m := re.fullmatch(r"/v1/deployment/([^/]+)", path):
            dep = snap.deployment_by_id(m.group(1))
            if dep is None:
                return h._error(404, "deployment not found")
            if not ns_ok(dep.namespace):
                return h._error(403, "Permission denied")
            return h._reply(200, dep)

        if path == "/v1/operator/snapshot":
            # the dump holds token secrets: management only
            if acl is not None and not acl.management:
                return h._error(403, "Permission denied")
            return h._reply(200, self.server.store.dump())

        if path == "/v1/nodes":
            return h._reply(200, [self._node_stub(n, snap)
                                  for n in snap.nodes()])
        if m := re.fullmatch(r"/v1/node/([^/]+)", path):
            node = snap.node_by_id(m.group(1))
            if node is None:
                return h._error(404, "node not found")
            return h._reply(200, node)
        if m := re.fullmatch(r"/v1/node/([^/]+)/allocations", path):
            return h._reply(200, [self._alloc_stub(a) for a in
                                  snap.allocs_by_node(m.group(1))
                                  if ns_ok(a.namespace)])

        if path == "/v1/allocations":
            allocs = [a for a in snap.allocs()
                      if a.id.startswith(prefix) and ns_ok(a.namespace)]
            return h._reply(200, [self._alloc_stub(a) for a in allocs])
        if m := re.fullmatch(r"/v1/allocation/([^/]+)", path):
            alloc = snap.alloc_by_id(m.group(1))
            if alloc is None:
                return h._error(404, "alloc not found")
            if not ns_ok(alloc.namespace):
                return h._error(403, "Permission denied")
            return h._reply(200, alloc)

        if path == "/v1/evaluations":
            return h._reply(200, [e for e in snap.evals() if ns_ok(e.namespace)])
        if m := re.fullmatch(r"/v1/evaluation/([^/]+)", path):
            ev = snap.eval_by_id(m.group(1))
            if ev is None:
                return h._error(404, "eval not found")
            if not ns_ok(ev.namespace):
                return h._error(403, "Permission denied")
            return h._reply(200, ev)

        if path == "/v1/client/stats":
            if acl is not None and not acl.allow_node_read():
                return h._error(403, "Permission denied")
            # per-instance device stats ride beside host stats (reference
            # client/devicemanager stats surfaced in client stats)
            return h._reply(200, [
                {**c.hoststats.latest(),
                 "device_stats": c.device_manager.latest_stats()
                 if getattr(c, "device_manager", None) is not None else {}}
                for c in self.clients])
        if m := re.fullmatch(r"/v1/client/fs/(ls|cat|stat)/([^/]+)", path):
            return self._route_fs(h, m.group(1), m.group(2), q, acl)
        if m := re.fullmatch(r"/v1/client/exec/([^/]+)/stdout", path):
            from ..acl import policy as aclp
            from ..client.execstream import SESSIONS

            sess = SESSIONS.get(m.group(1))
            if sess is None:
                return h._error(404, "no such exec session")
            # the session's own namespace only — never a caller-chosen
            # fallback (sessions are namespace-bound at creation)
            if not sess.namespace or not self._ns_allowed(
                    acl, sess.namespace, aclp.CAP_ALLOC_EXEC):
                return h._error(403, "Permission denied")
            offset = int(q.get("offset", ["0"])[0] or 0)
            wait_s = min(float(q.get("wait_s", ["10"])[0] or 10), 30.0)
            data, nxt, exited, code = sess.read_output(offset, wait_s)
            return h._reply(200, {
                "data": base64.b64encode(data).decode("ascii"),
                "offset": nxt, "exited": exited, "exit_code": code})
        if m := re.fullmatch(r"/v1/client/fs/logs/([^/]+)", path):
            # authorized post-lookup against the alloc's own namespace
            return self._route_logs(h, m.group(1), q, snap, acl)
        if path == "/v1/search":
            # prefix search across object types, scoped to the request
            # namespace (reference nomad/search_endpoint.go; POST there,
            # GET here rides the blocking-query plumbing)
            context = q.get("context", ["all"])[0]
            contexts = ("all", "jobs", "nodes", "allocs", "evals",
                        "deployments")
            if context not in contexts:
                return h._error(400, f"invalid context {context!r}; "
                                     f"one of {contexts}")
            limit = 20  # reference truncates at 20 per context

            def take(it):
                out, truncated = [], False
                for x in it:
                    if len(out) >= limit:
                        truncated = True
                        break
                    out.append(x)
                return out, truncated

            def visible(obj_ns: str) -> bool:
                return obj_ns == ns and ns_ok(obj_ns)

            results: Dict[str, list] = {}
            trunc: Dict[str, bool] = {}
            if context in ("all", "jobs"):
                results["jobs"], trunc["jobs"] = take(
                    j.id for j in snap.jobs()
                    if j.id.startswith(prefix) and visible(j.namespace))
            if context in ("all", "nodes"):
                if acl is not None and not acl.allow_node_read():
                    if context == "nodes":
                        return h._error(403, "Permission denied")
                    results["nodes"], trunc["nodes"] = [], False
                else:
                    results["nodes"], trunc["nodes"] = take(
                        n.id for n in snap.nodes()
                        if n.id.startswith(prefix)
                        or n.name.startswith(prefix))
            if context in ("all", "allocs"):
                results["allocs"], trunc["allocs"] = take(
                    a.id for a in snap.allocs()
                    if a.id.startswith(prefix) and visible(a.namespace))
            if context in ("all", "evals"):
                results["evals"], trunc["evals"] = take(
                    e.id for e in snap.evals()
                    if e.id.startswith(prefix) and visible(e.namespace))
            if context in ("all", "deployments"):
                results["deployments"], trunc["deployments"] = take(
                    d.id for d in snap.deployments()
                    if d.id.startswith(prefix) and visible(d.namespace))
            return h._reply(200, {"matches": results, "truncations": trunc})
        if path == "/v1/status/leader":
            raft = getattr(self.writer, "raft", None)
            if raft is not None:
                return h._reply(200, {
                    "leader": raft.leader_id or "",
                    "is_leader": self.writer.is_leader()})
            return h._reply(200, "local")
        if path == "/v1/agent/members":
            # server membership (reference agent_endpoint.go members,
            # backed by serf; ours by the gossip agent when running,
            # else the raft configuration, else just this server)
            gossip = getattr(self.writer, "gossip", None)
            if gossip is not None:
                return h._reply(200, {
                    "members": [
                        {"name": mid, "status": m.get("status", ""),
                         "gossip_addr": m.get("gossip", ""),
                         "meta": m.get("meta") or {}}
                        for mid, m in sorted(gossip.snapshot().items())]})
            raft = getattr(self.writer, "raft", None)
            if raft is not None:
                return h._reply(200, {
                    "members": [
                        {"name": sid, "status": "alive",
                         "rpc_addr": addr, "meta": {}}
                        for sid, addr in sorted(raft.servers.items())]})
            return h._reply(200, {"members": [
                {"name": "local", "status": "alive", "meta": {}}]})
        if path == "/v1/agent/self":
            if self.server.workers:
                from ..tensor.backend import device
                from ..tensor.solver import get_service

                # which device the placement solves run on, and what
                # the solver service did there
                dev, solver = device().as_dict(), dict(get_service().stats)
            else:
                # a server that never schedules (--workers 0) resolved no
                # backend, and asking for one here would open the device
                dev, solver = {"platform": "none", "kind": "", "count": 0}, {}
            return h._reply(200, {
                "stats": {
                    "broker": self.server.broker.stats,
                    "plan_applier": self.server.plan_applier.stats,
                    "blocked_evals": self.server.blocked.blocked_count(),
                    "device": dev,
                    "solver": solver,
                },
                "version": "0.1.0",
            })
        if path == "/v1/agent/pprof/threads":
            # goroutine-dump analog: every thread's current stack
            # (reference /v1/agent/pprof goroutine profile,
            # command/agent/pprof/; agent:read-gated by the /v1/agent
            # prefix check above)
            import sys as _sys
            import threading as _threading
            import traceback as _traceback

            names = {t.ident: t.name for t in _threading.enumerate()}
            dump = []
            for tid, frame in _sys._current_frames().items():
                dump.append(f"thread {names.get(tid, '?')} ({tid}):\n"
                            + "".join(_traceback.format_stack(frame)))
            return h._reply(200, {"threads": len(dump),
                                  "dump": "\n".join(dump)})
        if path == "/v1/agent/pprof/profile":
            # statistical CPU profile: sample every thread's stack for
            # ?seconds=S, emit collapsed stacks with sample counts (the
            # pprof-profile analog a maintainer can flamegraph)
            import sys as _sys
            import traceback as _traceback

            try:
                seconds = min(float(q.get("seconds", ["5"])[0] or 5), 30.0)
                hz = min(max(float(q.get("hz", ["100"])[0] or 100), 1.0),
                         500.0)
            except ValueError:
                return h._error(400, "bad seconds/hz")
            counts: Dict[str, int] = {}
            me = threading.get_ident()
            deadline = time.time() + seconds
            samples = 0
            while time.time() < deadline:
                for tid, frame in _sys._current_frames().items():
                    if tid == me:
                        continue  # don't profile the profiler
                    stack = ";".join(
                        f"{f.name}@{os.path.basename(f.filename)}:{f.lineno}"
                        for f in _traceback.extract_stack(frame))
                    counts[stack] = counts.get(stack, 0) + 1
                samples += 1
                time.sleep(1.0 / hz)
            top = sorted(counts.items(), key=lambda kv: -kv[1])
            return h._reply(200, {
                "seconds": seconds, "samples": samples,
                "collapsed": [f"{stack} {n}" for stack, n in top[:500]]})
        if path == "/v1/operator/raft/configuration":
            # peer set + leadership (reference operator_endpoint.go
            # RaftGetConfiguration); authorization rides the coarse
            # /v1/operator gate above like its sibling routes
            raft = getattr(self.writer, "raft", None)
            if raft is None:
                return h._reply(200, {"servers": [], "leader": "",
                                      "term": 0, "commit_index": 0,
                                      "last_applied": 0, "mode": "single"})
            transport = getattr(self.writer, "transport", None)
            addrs = dict(getattr(transport, "peer_addrs", None) or {})
            # live membership (dynamic config changes land here first)
            addrs.update({k: v for k, v in raft.servers.items() if v})
            servers = [{"id": raft.id, "address": addrs.get(raft.id, "local"),
                        "leader": raft.is_leader(), "self": True}]
            for p in raft.peers:
                servers.append({"id": p, "address": addrs.get(p, "local"),
                                "leader": p == raft.leader_id, "self": False})
            return h._reply(200, {"servers": servers,
                                  "leader": raft.leader_id or "",
                                  "term": raft.current_term,
                                  "commit_index": raft.commit_index,
                                  "last_applied": raft.last_applied,
                                  "mode": "raft"})
        if path == "/v1/operator/scheduler/configuration":
            return h._reply(200, self.server.sched_config)
        if path == "/v1/metrics":
            from ..core.metrics import REGISTRY, prometheus_text

            metrics = {
                "broker": self.server.broker.stats,
                "plan": self.server.plan_applier.stats,
                "plan_bad_nodes": self.server.plan_applier.bad_nodes.stats,
                "heartbeats_active": self.server.heartbeats.active(),
                # live gauges under the reference's metric names
                # (operations/metrics-reference.mdx)
                "nomad.broker.total_unacked":
                    self.server.broker.unacked_count(),
                "nomad.blocked_evals.total_blocked":
                    self.server.blocked.blocked_count(),
                # read-path fan-out gauges (sampled live; the wakeup
                # counters/histograms come in via REGISTRY.dump)
                "nomad.reads.parked":
                    self.server.store.watches.parked(),
                "nomad.reads.event_waiters":
                    self.server.events.waiter_count(),
                "nomad.state.live_snapshots":
                    self.server.store._tracker.live_count(),
                **REGISTRY.dump(),
            }
            if q.get("format", [""])[0] == "prometheus":
                body = prometheus_text(metrics).encode()
                h.send_response(200)
                h.send_header("Content-Type",
                              "text/plain; version=0.0.4")
                h.send_header("Content-Length", str(len(body)))
                h.end_headers()
                h.wfile.write(body)
                return
            return h._reply(200, metrics)
        if path == "/v1/traces":
            from ..obs import TRACER
            from ..obs.export import chrome_trace, phase_breakdown

            spans = TRACER.spans()
            limit = int(q.get("limit", ["500"])[0])
            body = {
                "enabled": TRACER.enabled,
                "total_spans": len(spans),
                # records overwritten in full rings: spans the trace
                # below can no longer hold
                "dropped": TRACER.dropped,
                "phases": phase_breakdown(spans),
                # newest spans last, Chrome trace_event format — paste
                # the traceEvents list into chrome://tracing / Perfetto
                "trace": chrome_trace(spans[-limit:] if limit else spans),
            }
            return h._reply(200, body)
        h._error(404, f"no such route {path}")

    def _find_runner(self, alloc_id: str):
        for client in self.clients:
            runner = client.runners.get(alloc_id)
            if runner is not None:
                return runner
        return None

    def _route_fs(self, h, op: str, alloc_id: str, q: dict, acl=None) -> None:
        """Alloc filesystem access (reference client/allocdir fs APIs,
        CLI `alloc fs`; read-fs capability)."""
        from ..acl import policy as aclp
        from ..client import execstream

        runner = self._find_runner(alloc_id)
        if runner is None:
            return h._error(404, "alloc not on this agent")
        # authorize against the ALLOC's namespace, not a caller-chosen
        # query param (reference post-lookup authorization; same shape
        # as _route_logs)
        if not self._ns_allowed(acl, runner.alloc.namespace,
                                aclp.CAP_READ_FS):
            return h._error(403, "Permission denied")
        root = runner.allocdir.root
        rel = q.get("path", ["/"])[0]
        try:
            if op == "ls":
                return h._reply(200, execstream.fs_list(root, rel))
            if op == "stat":
                return h._reply(200, execstream.fs_stat(root, rel))
            offset = max(int(q.get("offset", ["0"])[0] or 0), 0)
            limit = max(min(int(q.get("limit", ["65536"])[0] or 65536),
                            1 << 20), 0)
            data = execstream.fs_read(root, rel, offset, limit)
            return h._reply(200, {
                "data": base64.b64encode(data).decode("ascii"),
                "offset": offset + len(data)})
        except PermissionError as e:
            return h._error(403, str(e))
        except FileNotFoundError:
            return h._error(404, f"no such path {rel!r}")
        except (IsADirectoryError, NotADirectoryError, OSError) as e:
            return h._error(400, str(e))

    def _route_logs(self, h, alloc_id: str, q: dict, snap, acl=None) -> None:
        """Task log read across the rotated files (reference
        /v1/client/fs/logs/<alloc>; CLI `alloc logs`)."""

        from ..acl import policy as aclp
        from ..client.allocdir import AllocDir
        from ..client.logmon import read_log

        alloc = snap.alloc_by_id(alloc_id)
        if alloc is None:
            return h._error(404, "alloc not found")
        if not self._ns_allowed(acl, alloc.namespace, aclp.CAP_READ_LOGS):
            return h._error(403, "Permission denied")
        task = q.get("task", [""])[0]
        if not task and alloc.job is not None:
            tg = alloc.job.lookup_task_group(alloc.task_group)
            if tg is not None and tg.tasks:
                task = tg.tasks[0].name
        kind = q.get("type", ["stdout"])[0]
        offset = int(q.get("offset", ["0"])[0] or 0)
        limit = min(int(q.get("limit", ["65536"])[0] or 65536), 1 << 20)
        import os

        for client in self.clients:
            runner = client.runners.get(alloc_id)
            log_dir = (runner.allocdir.logs if runner is not None
                       else AllocDir(client.config.data_dir, alloc_id).logs)
            if runner is None and not os.path.isdir(log_dir):
                continue
            out = read_log(log_dir, task, kind, offset=offset, limit=limit)
            return h._reply(200, {
                "task": task, "type": kind, "offset": out["offset"],
                "size": out["size"],
                "data": base64.b64encode(out["data"]).decode("ascii")})
        return h._error(404, "alloc logs not on this agent")

    def _route_post(self, h, path: str, q: dict, body: dict, acl=None) -> None:
        from ..acl import policy as aclp

        ns = q.get("namespace", ["default"])[0]
        if path.startswith(("/v1/jobs", "/v1/job/")):
            # dispatch has its own capability (reference acl: dispatch-job
            # grants dispatch without general submit rights)
            cap = (aclp.CAP_DISPATCH_JOB if path.endswith("/dispatch")
                   else aclp.CAP_SUBMIT_JOB)
            if not self._ns_allowed(acl, ns, cap):
                return h._error(403, "Permission denied")
        elif path.startswith("/v1/node/pool"):
            # pool definitions steer scheduling cluster-wide: operator
            # write, matching the DELETE side
            if acl is not None and not acl.allow_operator_write():
                return h._error(403, "Permission denied")
        elif path.startswith(("/v1/nodes", "/v1/node/")):
            if acl is not None and not acl.allow_node_write():
                return h._error(403, "Permission denied")
        elif path.startswith("/v1/operator"):
            if acl is not None and not acl.allow_operator_write():
                return h._error(403, "Permission denied")
        elif path.startswith("/v1/var"):
            if not self._ns_allowed(acl, ns, aclp.CAP_VARIABLES_WRITE):
                return h._error(403, "Permission denied")
        elif path.startswith("/v1/volume"):
            if not self._ns_allowed(acl, ns, aclp.CAP_SUBMIT_JOB):
                return h._error(403, "Permission denied")
        elif path.startswith("/v1/deployment"):
            # Authorize against the deployment's OWN namespace, not the
            # query param — otherwise submit-job in any one namespace
            # grants promote/fail everywhere (ref deployment_endpoint.go:134).
            if m := re.fullmatch(r"/v1/deployment/(?:promote|fail)/([^/]+)", path):
                dep = self.server.store.snapshot().deployment_by_id(m.group(1))
                if dep is None:
                    return h._error(404, "deployment not found")
                if not self._ns_allowed(acl, dep.namespace, aclp.CAP_SUBMIT_JOB):
                    return h._error(403, "Permission denied")
            elif not self._ns_allowed(acl, ns, aclp.CAP_SUBMIT_JOB):
                return h._error(403, "Permission denied")
        elif path.startswith("/v1/acl") and path not in (
                "/v1/acl/bootstrap", "/v1/acl/login",
                "/v1/acl/token/onetime", "/v1/acl/token/onetime/exchange",
                "/v1/acl/oidc/auth-url", "/v1/acl/oidc/complete-auth"):
            if acl is not None and not acl.management:
                return h._error(403, "Permission denied")

        if path == "/v1/acl/oidc/auth-url":
            # OIDC step 1: provider authorization URL + request state
            # (reference acl_endpoint.go OIDCAuthURL; unauthenticated)
            try:
                out = self.writer.oidc_auth_url(
                    body.get("auth_method", ""),
                    body.get("redirect_uri", ""),
                    body.get("client_nonce", ""))
            except PermissionError as e:
                return h._error(403, str(e))
            except ValueError as e:
                return h._error(400, str(e))
            return h._reply(200, out)
        if path == "/v1/acl/oidc/complete-auth":
            # OIDC step 2: code -> id_token -> bound ACL token
            # (reference acl_endpoint.go OIDCCompleteAuth)
            try:
                token = self.writer.oidc_complete_auth(
                    body.get("auth_method", ""),
                    body.get("state", ""),
                    body.get("code", ""),
                    body.get("redirect_uri", ""),
                    body.get("client_nonce", ""))
            except PermissionError as e:
                return h._error(403, str(e))
            except ValueError as e:
                return h._error(400, str(e))
            return h._reply(200, _token_wire(token))
        if path == "/v1/acl/token/onetime":
            # mint a single-use stand-in for the CALLER's token
            # (reference acl_endpoint.go UpsertOneTimeToken)
            secret = h.headers.get("X-Nomad-Token", "")
            try:
                out = self.writer.create_one_time_token(secret)
            except PermissionError as e:
                return h._error(403, str(e))
            return h._reply(200, out)
        if path == "/v1/acl/token/onetime/exchange":
            # unauthenticated by design: the ott IS the credential
            try:
                token = self.writer.exchange_one_time_token(
                    (body or {}).get("one_time_secret", ""))
            except PermissionError as e:
                return h._error(403, str(e))
            return h._reply(200, _token_wire(token))
        if path == "/v1/acl/login":
            # SSO: exchange an external JWT for an ephemeral token —
            # unauthenticated by design (reference acl_endpoint.go Login)
            try:
                token = self.writer.acl_login(
                    body.get("auth_method", ""),
                    body.get("login_token", ""))
            except PermissionError as e:
                return h._error(403, str(e))
            return h._reply(200, _token_wire(token))
        if m := re.fullmatch(r"/v1/acl/auth-method/([^/]+)", path):
            try:
                method = dict(body or {})
                method["name"] = m.group(1)
                self.writer.upsert_auth_method(method)
            except (ValueError, TypeError) as e:
                return h._error(400, str(e))
            return h._reply(200, {"ok": True})
        if path == "/v1/acl/binding-rule":
            try:
                rule = self.writer.upsert_binding_rule(dict(body or {}))
            except (ValueError, TypeError) as e:
                return h._error(400, str(e))
            return h._reply(200, {"id": rule.id})
        if path == "/v1/acl/bootstrap":
            token = self.writer.acl_bootstrap()
            return h._reply(200, {"accessor_id": token.accessor_id,
                                  "secret_id": token.secret_id,
                                  "type": token.type})
        if m := re.fullmatch(r"/v1/acl/policy/([^/]+)", path):
            self.writer.upsert_acl_policy(
                m.group(1), body.get("rules", body.get("Rules", "{}")),
                body.get("description", ""))
            return h._reply(200, {"ok": True})
        if path == "/v1/acl/token":
            try:
                token = self.writer.create_acl_token(
                    body.get("name", ""), body.get("policies", []),
                    body.get("type", "client"),
                    roles=body.get("roles", []))
            except ValueError as e:
                return h._error(400, str(e))
            return h._reply(200, {"accessor_id": token.accessor_id,
                                  "secret_id": token.secret_id})
        if m := re.fullmatch(r"/v1/acl/role/([^/]+)", path):
            try:
                self.writer.upsert_acl_role(
                    m.group(1), body.get("policies", []),
                    body.get("description", ""))
            except ValueError as e:
                return h._error(400, str(e))
            return h._reply(200, {"ok": True})
        if m := re.fullmatch(r"/v1/var/(.+)", path):
            try:
                self.writer.put_variable(m.group(1), body.get("items", {}), ns)
            except ValueError as e:  # e.g. unknown namespace
                return h._error(400, str(e))
            return h._reply(200, {"ok": True})
        if m := re.fullmatch(r"/v1/namespace/([^/]+)", path):
            from ..structs.operator import Namespace

            if acl is not None and not acl.allow_operator_write():
                return h._error(403, "Permission denied")
            nsp = from_dict(Namespace, body.get("namespace") or body)
            nsp.name = m.group(1)
            try:
                self.writer.upsert_namespace(nsp)
            except ValueError as e:
                return h._error(400, str(e))
            return h._reply(200, {"ok": True})
        if m := re.fullmatch(r"/v1/node/pool/([^/]+)", path):
            from ..structs.operator import NodePool

            pool = from_dict(NodePool, body.get("node_pool") or body)
            pool.name = m.group(1)
            try:
                self.writer.upsert_node_pool(pool)
            except ValueError as e:
                return h._error(400, str(e))
            return h._reply(200, {"ok": True})
        if m := re.fullmatch(r"/v1/volume/csi/([^/]+)", path):
            from ..structs.volumes import Volume

            vol = from_dict(Volume, body.get("volume") or body)
            vol.id = m.group(1)
            vol.namespace = ns
            vol.claims = {}  # store-owned; never accepted from clients
            try:
                self.writer.register_volume(vol)
            except ValueError as e:  # e.g. unknown namespace
                return h._error(400, str(e))
            return h._reply(200, {"ok": True})

        if path == "/v1/jobs/parse":
            # server-side jobspec parsing (reference /v1/jobs/parse,
            # command/agent/job_endpoint.go JobsParseRequest): HCL in,
            # canonical api.Job JSON out — no registration
            from .jobspec import parse_hcl_like, parse_json

            spec = (body or {}).get("job_hcl", "")
            if not spec:
                return h._error(400, "job_hcl is required")
            try:
                if spec.lstrip().startswith("{"):
                    job = parse_json(spec)
                else:
                    job = parse_hcl_like(
                        spec, variables=(body or {}).get("variables"))
            except ValueError as e:
                return h._error(400, str(e))
            return h._reply(200, job)
        if path == "/v1/jobs":
            data = body.get("job") or body.get("Job") or body
            job = from_dict(Job, data)
            _validate(job)
            try:
                eval_id = self.writer.register_job(job)
            except ValueError as e:  # e.g. unknown namespace
                return h._error(400, str(e))
            return h._reply(200, {"eval_id": eval_id, "job_id": job.id})
        if m := re.fullmatch(r"/v1/allocation/([^/]+)/stop", path):
            snap0 = self.server.store.snapshot()
            alloc = snap0.alloc_by_id(m.group(1))
            if alloc is None:
                return h._error(404, "alloc not found")
            if not self._ns_allowed(acl, alloc.namespace,
                                    aclp.CAP_ALLOC_LIFECYCLE):
                return h._error(403, "Permission denied")
            try:
                eval_id = self.writer.stop_alloc(m.group(1))
            except KeyError:
                return h._error(404, "alloc not found")
            except ValueError as e:
                return h._error(400, str(e))
            return h._reply(200, {"eval_id": eval_id})
        if m := re.fullmatch(r"/v1/job/(.+)/dispatch", path):
            import binascii

            try:
                payload = base64.b64decode(body.get("payload", "") or "",
                                           validate=True)
                out = self.writer.dispatch_job(
                    m.group(1), payload=payload,
                    meta=body.get("meta") or {}, namespace=ns)
            except KeyError:
                return h._error(404, "job not found")
            except (ValueError, binascii.Error) as e:
                return h._error(400, str(e))
            return h._reply(200, out)
        if m := re.fullmatch(r"/v1/job/(.+)/scale", path):
            try:
                eval_id = self.writer.scale_job(
                    m.group(1), body.get("task_group", ""),
                    int(body.get("count")
                        if body.get("count") is not None else -1),
                    namespace=ns)
            except KeyError:
                return h._error(404, "job not found")
            except (ValueError, TypeError) as e:
                return h._error(400, str(e))
            return h._reply(200, {"eval_id": eval_id})
        if m := re.fullmatch(r"/v1/job/(.+)/revert", path):
            try:
                eval_id = self.writer.revert_job(
                    m.group(1), int(body.get("job_version", -1)
                                    if body.get("job_version") is not None
                                    else -1), namespace=ns)
            except KeyError as e:
                return h._error(404, str(e))
            except (ValueError, TypeError) as e:
                return h._error(400, str(e))
            return h._reply(200, {"eval_id": eval_id})
        if m := re.fullmatch(r"/v1/job/(.+)/plan", path):
            data = body.get("job") or body.get("Job") or body
            job = from_dict(Job, data)
            job.id = m.group(1)
            # the gate above authorized the query-param namespace; a
            # body-supplied one would let a token probe other namespaces
            job.namespace = ns
            _validate(job)
            # dry-run: local snapshot state is enough on any replica
            return h._reply(200, self.server.plan_job(job))
        if m := re.fullmatch(r"/v1/job/(.+)/evaluate", path):
            ns = q.get("namespace", ["default"])[0]
            snap = self.server.store.snapshot()
            job = snap.job_by_id(m.group(1), ns)
            if job is None:
                return h._error(404, "job not found")
            eval_id = self.writer.create_job_eval(job, enums.TRIGGER_JOB_REGISTER)
            return h._reply(200, {"eval_id": eval_id})
        if m := re.fullmatch(r"/v1/node/([^/]+)/drain", path):
            spec = body.get("drain_spec")
            strategy = None
            if spec is not None:
                strategy = from_dict(DrainStrategy, spec)
            self.writer.update_node_drain(m.group(1), strategy,
                                          bool(body.get("mark_eligible")))
            return h._reply(200, {"ok": True})
        if m := re.fullmatch(r"/v1/node/([^/]+)/eligibility", path):
            self.writer.update_node_eligibility(m.group(1),
                                                body.get("eligibility", ""))
            return h._reply(200, {"ok": True})
        if path == "/v1/system/gc":
            # force a GC pass (reference /v1/system/gc -> CoreJobForceGC);
            # via the writer: GC mutates state, so a follower forwards
            if acl is not None and not acl.allow_operator_write():
                return h._error(403, "Permission denied")
            return h._reply(200, self.writer.force_gc())
        if path == "/v1/operator/scheduler/configuration":
            from ..structs.operator import SchedulerConfiguration

            cfg = from_dict(SchedulerConfiguration, body)
            self.writer.set_scheduler_config(cfg)
            return h._reply(200, {"updated": True})
        if m := re.fullmatch(r"/v1/client/allocation/([^/]+)/exec", path):
            # interactive exec into a running alloc (reference
            # api/allocations_exec.go websocket -> driver pty; here an
            # exec session polled over HTTP — see client/execstream.py)
            runner = self._find_runner(m.group(1))
            if runner is None:
                return h._error(404, "alloc not on this agent")
            if not self._ns_allowed(acl, runner.alloc.namespace,
                                    aclp.CAP_ALLOC_EXEC):
                return h._error(403, "Permission denied")
            command = list((body or {}).get("command") or [])
            if not command:
                return h._error(400, "missing command")
            task = (body or {}).get("task", "")
            if not task and runner.tg is not None and runner.tg.tasks:
                task = runner.tg.tasks[0].name
            from ..client import taskenv
            from ..client.execstream import SESSIONS

            task_obj = next((t for t in (runner.tg.tasks if runner.tg else [])
                             if t.name == task), None)
            if task_obj is None:
                return h._error(404, f"no such task {task!r}")
            task_dir = runner.allocdir.task_dir(task)
            if not os.path.isdir(task_dir):
                return h._error(409, f"task {task!r} has not started yet")
            env = taskenv.build_env(runner.alloc, task_obj, runner.node,
                                    task_dir, runner.allocdir.shared)
            env = {**{"PATH": os.environ.get("PATH", os.defpath)}, **env}
            try:
                sess = SESSIONS.create(
                    command, task_dir, env,
                    tty=bool((body or {}).get("tty")),
                    namespace=runner.alloc.namespace)
            except OSError as e:
                return h._error(400, f"exec failed: {e}")
            return h._reply(200, {"session_id": sess.id})
        if m := re.fullmatch(r"/v1/client/exec/([^/]+)/stdin", path):
            from ..client.execstream import SESSIONS

            sess = SESSIONS.get(m.group(1))
            if sess is None:
                return h._error(404, "no such exec session")
            # the session's own namespace only — never a caller-chosen
            # fallback (sessions are namespace-bound at creation)
            if not sess.namespace or not self._ns_allowed(
                    acl, sess.namespace, aclp.CAP_ALLOC_EXEC):
                return h._error(403, "Permission denied")
            data = base64.b64decode((body or {}).get("data", "") or "")
            written = sess.write_stdin(data) if data else 0
            if (body or {}).get("close"):
                sess.close_stdin()
            return h._reply(200, {"written": written,
                                  "exited": sess.exited})
        if m := re.fullmatch(r"/v1/operator/region/([^/]+)", path):
            try:
                self.writer.upsert_region({"name": m.group(1),
                                           "address": (body or {}).get(
                                               "address", "")})
            except ValueError as e:
                return h._error(400, str(e))
            return h._reply(200, {"ok": True})
        if path == "/v1/agent/join":
            # tell this RUNNING agent to join an existing cluster
            # (reference `nomad server join` -> /v1/agent/join, gated
            # behind agent:write)
            if acl is not None and not acl.allow_operator_write():
                return h._error(403, "Permission denied")
            addr = (body or {}).get("address", "")
            join = getattr(self.writer, "join", None)
            if join is None:
                return h._error(400, "not a raft server")
            if not addr:
                return h._error(400, "missing address")
            join(addr)
            return h._reply(200, {"joined": addr})
        if path == "/v1/operator/snapshot":
            # whole-state restore (reference operator_snapshot_restore);
            # the dump holds token secrets: management only
            if acl is not None and not acl.management:
                return h._error(403, "Permission denied")
            self.server.store.restore_dump(body)
            return h._reply(200, {"restored": True,
                                  "index": self.server.store.latest_index})
        if m := re.fullmatch(r"/v1/deployment/promote/([^/]+)", path):
            try:
                eval_id = self.writer.promote_deployment(
                    m.group(1), groups=body.get("groups"))
            except KeyError as e:
                return h._error(404, str(e))
            except ValueError as e:
                return h._error(400, str(e))
            return h._reply(200, {"eval_id": eval_id})
        if m := re.fullmatch(r"/v1/deployment/fail/([^/]+)", path):
            try:
                self.writer.fail_deployment(m.group(1))
            except KeyError as e:
                return h._error(404, str(e))
            except ValueError as e:
                return h._error(400, str(e))
            return h._reply(200, {"ok": True})
        h._error(404, f"no such route {path}")

    def _route_delete(self, h, path: str, q: dict, acl=None) -> None:
        from ..acl import policy as aclp

        ns = q.get("namespace", ["default"])[0]
        if m := re.fullmatch(r"/v1/client/exec/([^/]+)", path):
            from ..acl import policy as aclp2
            from ..client.execstream import SESSIONS

            sess = SESSIONS.get(m.group(1))
            if sess is not None and (
                    not sess.namespace or not self._ns_allowed(
                        acl, sess.namespace, aclp2.CAP_ALLOC_EXEC)):
                return h._error(403, "Permission denied")
            SESSIONS.remove(m.group(1))
            return h._reply(200, {"closed": True})
        if path == "/v1/operator/raft/peer":
            # remove a server from the raft configuration (reference
            # `operator raft remove-peer`, operator_endpoint.go)
            if acl is not None and not acl.allow_operator_write():
                return h._error(403, "Permission denied")
            sid = q.get("id", [""])[0]
            remove = getattr(self.writer, "remove_peer", None)
            if remove is None:
                return h._error(400, "not a raft server")
            if not sid:
                return h._error(400, "missing id")
            try:
                remove(sid)
            except ValueError as e:
                return h._error(400, str(e))
            except KeyError as e:
                return h._error(404, str(e))
            return h._reply(200, {"removed": sid})
        if m := re.fullmatch(r"/v1/job/(.+)", path):
            if not self._ns_allowed(acl, ns, aclp.CAP_SUBMIT_JOB):
                return h._error(403, "Permission denied")
            purge = q.get("purge", ["false"])[0] in ("true", "1")
            eval_id = self.writer.deregister_job(m.group(1), ns, purge=purge)
            return h._reply(200, {"eval_id": eval_id})
        if m := re.fullmatch(r"/v1/var/(.+)", path):
            if not self._ns_allowed(acl, ns, aclp.CAP_VARIABLES_WRITE):
                return h._error(403, "Permission denied")
            self.writer.delete_variable(m.group(1), ns)
            return h._reply(200, {"ok": True})
        if m := re.fullmatch(r"/v1/node/pool/([^/]+)", path):
            if acl is not None and not acl.allow_operator_write():
                return h._error(403, "Permission denied")
            try:
                self.writer.delete_node_pool(m.group(1))
            except ValueError as e:
                return h._error(409, str(e))
            return h._reply(200, {"ok": True})
        if m := re.fullmatch(r"/v1/acl/role/([^/]+)", path):
            if acl is not None and not acl.management:
                return h._error(403, "Permission denied")
            self.writer.delete_acl_role(m.group(1))
            return h._reply(200, {"ok": True})
        if m := re.fullmatch(r"/v1/acl/auth-method/([^/]+)", path):
            if acl is not None and not acl.management:
                return h._error(403, "Permission denied")
            self.writer.delete_auth_method(m.group(1))
            return h._reply(200, {"ok": True})
        if m := re.fullmatch(r"/v1/operator/region/([^/]+)", path):
            if acl is not None and not acl.allow_operator_write():
                return h._error(403, "Permission denied")
            self.writer.delete_region(m.group(1))
            return h._reply(200, {"ok": True})
        if m := re.fullmatch(r"/v1/acl/binding-rule/([^/]+)", path):
            if acl is not None and not acl.management:
                return h._error(403, "Permission denied")
            self.writer.delete_binding_rule(m.group(1))
            return h._reply(200, {"ok": True})
        if m := re.fullmatch(r"/v1/namespace/([^/]+)", path):
            if acl is not None and not acl.allow_operator_write():
                return h._error(403, "Permission denied")
            try:
                self.writer.delete_namespace(m.group(1))
            except KeyError as e:
                return h._error(404, str(e))
            except ValueError as e:
                return h._error(409, str(e))
            return h._reply(200, {"ok": True})
        if m := re.fullmatch(r"/v1/volume/csi/([^/]+)", path):
            if not self._ns_allowed(acl, ns, aclp.CAP_SUBMIT_JOB):
                return h._error(403, "Permission denied")
            force = q.get("force", ["false"])[0] in ("true", "1")
            try:
                self.writer.deregister_volume(m.group(1), ns, force=force)
            except ValueError as e:
                return h._error(409, str(e))
            return h._reply(200, {"ok": True})
        h._error(404, f"no such route {path}")

    # -- event stream (reference /v1/event/stream, nomad/stream/) --

    @staticmethod
    def _start_chunked(h, q: dict):
        """Parse stream params BEFORE committing the response (a bad
        `wait` must be a clean 400, not a second response injected onto
        a committed chunked connection), then send the chunked headers.
        -> (write_chunk, deadline)."""
        raw = q.get("wait", [""])[0]
        wait = _parse_wait(raw)
        if wait is None:
            if raw:
                h._error(400, "invalid wait")
                return None, None
            wait = 60.0
        wait = min(wait, 600.0)
        deadline = time.time() + wait
        h.send_response(200)
        h.send_header("Content-Type", "application/json")
        h.send_header("Transfer-Encoding", "chunked")
        h.end_headers()

        def write_chunk(payload: bytes) -> None:
            h.wfile.write(f"{len(payload):x}\r\n".encode()
                          + payload + b"\r\n")
            h.wfile.flush()

        return write_chunk, deadline

    def _route_monitor(self, h, q: dict) -> None:
        """Live agent log streaming (reference `nomad monitor`,
        command/agent/monitor/): attaches a handler to the framework
        loggers and streams ndjson records until the wait expires."""
        import logging
        import queue as _queue

        level = getattr(logging,
                        q.get("log_level", ["info"])[0].upper(),
                        logging.INFO)
        buf: "_queue.Queue" = _queue.Queue(maxsize=1024)

        class _H(logging.Handler):
            def emit(self, record):
                try:
                    buf.put_nowait({
                        "ts": record.created,
                        "level": record.levelname,
                        "name": record.name,
                        "message": record.getMessage(),
                    })
                except _queue.Full:
                    pass  # a slow consumer drops lines, never blocks

        # attach BEFORE the headers go out: the client treats the 200
        # as "subscribed" and may log-and-assert immediately
        handler = _H(level=level)
        logger = logging.getLogger("nomad_tpu")
        _monitor_level_push(logger, level)
        logger.addHandler(handler)
        write_chunk, deadline = self._start_chunked(h, q)
        if write_chunk is None:
            logger.removeHandler(handler)
            _monitor_level_pop(logger)
            return
        try:
            while time.time() < deadline:
                try:
                    rec = buf.get(timeout=0.5)
                except _queue.Empty:
                    continue
                write_chunk(json.dumps(rec).encode() + b"\n")
        except (BrokenPipeError, ConnectionResetError):
            pass
        finally:
            logger.removeHandler(handler)
            _monitor_level_pop(logger)
            try:
                write_chunk(b"")
            except OSError:
                pass

    def _route_event_stream(self, h, q: dict) -> None:
        """ndjson event stream with topic filters:
        ?topic=Node&topic=Job:job-id (reference event_endpoint.go)."""
        topics: Dict[str, list] = {}
        for t in q.get("topic", []):
            if ":" in t:
                topic, key = t.split(":", 1)
            else:
                topic, key = t, "*"
            topics.setdefault(topic, []).append(key)
        # subscribe BEFORE the headers commit: events published in the
        # header-to-subscribe window must not be lost (same ordering the
        # monitor route uses for its log handler)
        sub = self.server.events.subscribe(topics or None)
        write_chunk, deadline = self._start_chunked(h, q)
        if write_chunk is None:
            sub.close()
            return
        try:
            while time.time() < deadline:
                events = sub.next_events(timeout=0.5)
                if sub.truncated:
                    # the ring lapped this stream: surface the gap as an
                    # in-band marker so the client re-lists from a fresh
                    # snapshot instead of trusting a holey delta stream
                    sub.truncated = False
                    write_chunk(json.dumps(
                        {"Topic": "Truncation", "Type": "resync-required",
                         "Key": "", "Index": 0,
                         "Payload": None}).encode() + b"\n")
                for e in events:
                    line = json.dumps({
                        "Topic": e.topic, "Type": e.type, "Key": e.key,
                        "Index": e.index,
                        "Payload": to_dict(e.payload),
                    }).encode() + b"\n"
                    write_chunk(line)
        except (BrokenPipeError, ConnectionResetError):
            pass
        finally:
            sub.close()
            try:
                write_chunk(b"")  # terminating chunk
            except OSError:
                pass

    # -- stubs (reference api list endpoints return trimmed rows) --

    def _job_stub(self, job, snap) -> dict:
        summary: Dict[str, int] = {}
        for a in snap.allocs_by_job(job.id, job.namespace):
            if not a.terminal_status():
                summary[a.client_status] = summary.get(a.client_status, 0) + 1
        return {
            "id": job.id, "name": job.name, "type": job.type,
            "priority": job.priority, "status": job.status,
            "namespace": job.namespace, "stop": job.stop,
            "alloc_summary": summary,
        }

    def _node_stub(self, node, snap=None) -> dict:
        out = {
            "id": node.id, "name": node.name, "datacenter": node.datacenter,
            "node_class": node.node_class, "node_pool": node.node_pool,
            "status": node.status,
            "scheduling_eligibility": node.scheduling_eligibility,
            "drain": node.drain,
        }
        if snap is not None:
            u = snap.node_usage(node.id)
            cap = float(node.resources.cpu) or 1.0
            out["cpu_frac"] = round(float(u[0]) / cap, 4) \
                if u is not None else 0.0
        return out

    def _alloc_stub(self, a) -> dict:
        return {
            "id": a.id, "name": a.name, "job_id": a.job_id,
            "task_group": a.task_group, "node_id": a.node_id,
            "desired_status": a.desired_status,
            "client_status": a.client_status,
            "create_index": a.create_index, "modify_index": a.modify_index,
        }
