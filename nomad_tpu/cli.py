"""CLI (reference command/, 221 command files — the operational core).

  nomad-tpu agent -dev [--clients N] [--port P] [--algorithm A]
  nomad-tpu job run <spec.{json,hcl,nomad}>
  nomad-tpu job status [<job_id>]
  nomad-tpu job stop [-purge] <job_id>
  nomad-tpu node status [<node_id>]
  nomad-tpu node drain -enable|-disable <node_id>
  nomad-tpu node eligibility -enable|-disable <node_id>
  nomad-tpu alloc status <alloc_id>
  nomad-tpu eval status <eval_id>
  nomad-tpu operator scheduler get-config
  nomad-tpu operator scheduler set-config -scheduler-algorithm <alg>

Run via `python -m nomad_tpu ...`. Talks HTTP to the agent like the
reference CLI does (NOMAD_ADDR / --address).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time


def _client(args):
    from .api.client import ApiClient

    return ApiClient(address=args.address, namespace=args.namespace,
                     token=getattr(args, "token", "") or "")


def _p(obj) -> None:
    print(json.dumps(obj, indent=2, default=str))


# -- agent -------------------------------------------------------------------


AGENT_FLAG_KEYS = ("data_dir", "port", "workers", "algorithm",
                   "server_id", "peers", "clients", "region",
                   "authoritative_region", "plugin_dir")


def _parse_peers(spec: str) -> dict:
    return dict(p.split("=", 1) for p in spec.split(","))


class Agent:
    """What `agent` serves, started: the scheduling server (replicated
    when `--peers` is given), its HTTP agent and local clients, on the
    backend the bootstrap resolved. `cmd_agent` runs it until a signal;
    `chip_smoke.py` drives the same object.

    `--workers 0` is a server that makes no scheduling decision
    (upstream's `num_schedulers = 0`): it votes, replicates and serves
    reads, resolves no backend and opens no device, and says
    `device=none` on its start line. On a host with one chip that is
    every server but the one that schedules."""

    def __init__(self, args):
        from .api.http import HTTPAgent
        from .client import Client, ClientConfig
        from .core import Server, ServerConfig
        from .structs.operator import SchedulerConfiguration
        from .tensor.backend import bootstrap
        from .utils import gcpolicy

        # full collector passes walk what is new, not the whole heap
        gcpolicy.install()
        # before the first compile; a tpu-* algorithm whose backend fell
        # to the CPU by itself raises here and the agent never starts.
        # A server without scheduler workers never compiles: it must not
        # take the chip from the server on this host that does
        self.device = bootstrap(args.algorithm) if args.workers > 0 else None
        cfg = ServerConfig(
            num_workers=args.workers,
            gossip_key=getattr(args, "gossip_key", "") or "",
            region=getattr(args, "region", "global"),
            authoritative_region=getattr(args, "authoritative_region", ""),
            sched_config=SchedulerConfiguration(
                scheduler_algorithm=args.algorithm))

        self.replicated = self.transport = None
        if args.peers:
            # multi-server mode: raft over the socket transport (reference
            # `nomad agent -server -bootstrap-expect N`)
            from .raft.cluster import ReplicatedServer
            from .raft.transport import SocketTransport

            peers = _parse_peers(args.peers)
            self.transport = SocketTransport(
                args.server_id, peers[args.server_id], peers).start()
            joining = bool(getattr(args, "join", ""))
            cleanup = getattr(args, "dead_server_cleanup", 0.0) or None
            gossip_bind = getattr(args, "gossip", "") or None
            gossip_seeds = [a for a in
                            (getattr(args, "retry_join", "") or "").split(",")
                            if a]
            self.replicated = ReplicatedServer(
                args.server_id, list(peers), self.transport, cfg,
                data_dir=args.data_dir or None,
                bootstrap=not joining and not gossip_seeds,
                dead_server_cleanup_s=cleanup,
                gossip_bind=gossip_bind, gossip_seeds=gossip_seeds)
            self.replicated.start()
            if joining:
                self.replicated.join(args.join)
            self.server = self.replicated.server
            endpoint = self.replicated
        else:
            self.server = Server(cfg)
            self.server.start()
            endpoint = self.server

        # HTTP first: the status/leader endpoint must be observable while
        # the clients wait out the initial leader election to register
        self.http = HTTPAgent(self.server, port=args.port,
                              writer=self.replicated).start()
        self.clients = []
        for i in range(args.clients):
            c = Client(endpoint, ClientConfig(
                data_dir=os.path.join(args.data_dir, f"client{i}")
                if args.data_dir else "",
                plugin_dir=getattr(args, "plugin_dir", "")))
            c.start()
            self.clients.append(c)
        self.http.clients = self.clients  # /v1/client/* for local clients
        if self.replicated is not None:
            # WAN gossip members read this to maintain the region registry
            self.replicated.set_gossip_http(self.http.address)
        self.start_line = (
            f"agent started: {self.http.address} "
            f"(workers={args.workers} clients={args.clients} "
            f"algorithm={args.algorithm} device={self.device or 'none'}"
            + (f" server-id={args.server_id}" if self.replicated else "")
            + ")")

    def stop(self) -> None:
        self.http.stop()
        for c in self.clients:
            c.stop()
        if self.replicated is not None:
            self.replicated.stop()
            self.transport.stop()
        else:
            self.server.stop()


def cmd_agent(args) -> int:
    from .tensor.backend import BackendError

    if args.config:
        from .agent_config import apply_to_args, load_agent_config

        file_cfg = load_agent_config(args.config)
        # defaults come from the parser itself (by parsing a bare
        # `agent` invocation — subparser defaults are invisible to the
        # top-level get_default) so the merge can't drift from the
        # declared flag defaults
        defaults_ns = build_parser().parse_args(["agent"])
        defaults = {k: getattr(defaults_ns, k) for k in AGENT_FLAG_KEYS}
        apply_to_args(file_cfg, args, defaults)

    if args.peers and args.server_id not in _parse_peers(args.peers):
        print(f"--server-id {args.server_id!r} not in --peers", file=sys.stderr)
        return 1
    try:
        agent = Agent(args)
    except BackendError as e:
        print(f"agent failed to start: {e}", file=sys.stderr)
        return 1
    server, replicated = agent.server, agent.replicated
    print(agent.start_line, flush=True)
    stop = []
    reload_req = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    if args.config and hasattr(signal, "SIGHUP"):
        signal.signal(signal.SIGHUP, lambda *a: reload_req.append(1))
    try:
        while not stop:
            if reload_req:
                reload_req.clear()
                # live reload (reference agent.go:1360): the scheduler
                # configuration is the hot-swappable subset
                try:
                    import copy as _copy

                    from .agent_config import load_agent_config

                    fc = load_agent_config(args.config)
                    if fc.algorithm:
                        # mutate only the algorithm on a copy of the
                        # CURRENT config: a reload must not reset
                        # operator-set fields (pause, preemption, ...)
                        new_cfg = _copy.deepcopy(server.sched_config)
                        new_cfg.scheduler_algorithm = fc.algorithm
                        target = replicated if replicated is not None else server
                        target.set_scheduler_config(new_cfg)
                        print(f"config reloaded: algorithm={fc.algorithm}",
                              flush=True)
                except Exception as e:
                    print(f"config reload failed: {e}", flush=True)
            time.sleep(0.2)
    finally:
        agent.stop()
    return 0


# -- job ---------------------------------------------------------------------


def cmd_job_validate(args) -> int:
    """Parse + validate a jobspec locally (reference
    command/job_validate.go; the API twin is POST /v1/jobs/parse)."""
    from .api.codec import to_dict
    from .api.jobspec import parse_file

    try:
        job = parse_file(args.spec, variables=_spec_vars(args))
    except (OSError, ValueError) as e:
        print(f"Job validation failed: {e}", file=sys.stderr)
        return 1
    if getattr(args, "as_json", False):
        _p(to_dict(job))
    else:
        groups = ", ".join(f"{tg.name}[{tg.count}]"
                           for tg in job.task_groups)
        print(f"Job validation successful: {job.id!r} "
              f"({job.type}; groups: {groups})")
    return 0


def cmd_job_plan(args) -> int:
    """Dry-run the update and print per-group desired changes
    (reference command/job_plan.go)."""
    from .api.jobspec import parse_file

    job = parse_file(args.spec, variables=_spec_vars(args))
    out = _client(args).plan_job(job)
    diff = out.get("diff", {})
    print(f"Job: {out.get('job_id')!r} (version {out.get('job_version')}, "
          f"{diff.get('type', '?')})")
    for f in diff.get("fields", [])[:40]:
        print(f"  ~ {f}")
    print("\nScheduler dry-run:")
    for tg, ann in sorted((out.get("annotations") or {}).items()):
        parts = [f"{k}: {v}" for k, v in sorted(ann.items()) if v]
        print(f"  group {tg!r}: " + (", ".join(parts) if parts else "no changes"))
    failed = out.get("failed_tg_allocs") or {}
    for tg, m in failed.items():
        print(f"  group {tg!r}: {m.get('coalesced_failures', 0) + 1} "
              f"WOULD FAIL to place (filtered {m.get('nodes_filtered')}, "
              f"exhausted {m.get('nodes_exhausted')})")
    return 1 if failed else 0


def _spec_vars(args) -> dict:
    out = {}
    for kv in getattr(args, "var", None) or []:
        if "=" not in kv:
            print(f"invalid -var {kv!r}: expected key=value", file=sys.stderr)
            raise SystemExit(2)
        k, v = kv.split("=", 1)
        out[k] = v
    return out


def cmd_job_run(args) -> int:
    from .api.jobspec import parse_file

    job = parse_file(args.spec, variables=_spec_vars(args))
    eval_id = _client(args).register_job(job)
    print(f"job {job.id!r} registered, evaluation {eval_id}")
    if args.detach:
        return 0
    return _monitor_eval(args, eval_id)


def _monitor_eval(args, eval_id: str) -> int:
    api = _client(args)
    deadline = time.time() + 30
    while time.time() < deadline:
        ev = api.evaluation(eval_id)
        if ev["status"] in ("complete", "failed", "canceled"):
            print(f"evaluation {eval_id} -> {ev['status']} "
                  f"{ev.get('status_description', '')}".strip())
            if ev.get("blocked_eval"):
                print(f"  blocked eval created: {ev['blocked_eval']}")
            for tg, m in (ev.get("failed_tg_allocs") or {}).items():
                print(f"  group {tg!r}: {m.get('coalesced_failures', 0) + 1} "
                      f"unplaced (filtered {m.get('nodes_filtered')}, "
                      f"exhausted {m.get('nodes_exhausted')})")
            return 0 if ev["status"] == "complete" else 1
        time.sleep(0.2)
    print(f"evaluation {eval_id} still in progress")
    return 1


def cmd_job_dispatch(args) -> int:
    """Dispatch a parameterized job (reference command/job_dispatch.go)."""
    payload = b""
    if args.payload_file:
        with open(args.payload_file, "rb") as f:
            payload = f.read()
    meta = dict(kv.split("=", 1) for kv in args.meta or [])
    out = _client(args).dispatch_job(args.job_id, payload=payload, meta=meta)
    print(f"dispatched {out['dispatched_job_id']!r}, "
          f"evaluation {out['eval_id']}")
    if args.detach:
        return 0
    return _monitor_eval(args, out["eval_id"])


def cmd_job_scale(args) -> int:
    eval_id = _client(args).scale_job(args.job_id, args.group, args.count)
    print(f"job {args.job_id!r} group {args.group!r} scaled to "
          f"{args.count}, evaluation {eval_id}")
    return _monitor_eval(args, eval_id) if not args.detach else 0


def cmd_job_revert(args) -> int:
    eval_id = _client(args).revert_job(args.job_id, args.version)
    print(f"job {args.job_id!r} reverted to version {args.version}, "
          f"evaluation {eval_id}")
    return _monitor_eval(args, eval_id) if not args.detach else 0


def cmd_job_history(args) -> int:
    for v in _client(args).job_versions(args.job_id):
        print(f"version {v['version']:4d}  stable={v['stable']}  "
              f"index={v['job_modify_index']}")
    return 0


def cmd_job_status(args) -> int:
    api = _client(args)
    if not args.job_id:
        _p(api.list_jobs())
        return 0
    job = api.job(args.job_id)
    allocs = api.job_allocations(args.job_id)
    print(f"ID       = {job['id']}\nType     = {job['type']}\n"
          f"Priority = {job['priority']}\nStatus   = {job['status']}")
    print("\nAllocations")
    for a in allocs:
        print(f"{a['id'][:8]}  {a['task_group']:12} {a['node_id'][:8]}  "
              f"{a['desired_status']:6} {a['client_status']}")
    return 0


def cmd_job_stop(args) -> int:
    eval_id = _client(args).deregister_job(args.job_id, purge=args.purge)
    print(f"job {args.job_id!r} stopped, evaluation {eval_id}")
    return 0


# -- node --------------------------------------------------------------------


def cmd_node_status(args) -> int:
    api = _client(args)
    if not args.node_id:
        _p(api.list_nodes())
        return 0
    _p(api.node(args.node_id))
    return 0


def cmd_node_drain(args) -> int:
    api = _client(args)
    if args.enable:
        api.drain_node(args.node_id, drain_spec={"deadline_s": args.deadline})
        print(f"node {args.node_id} draining")
    else:
        api.drain_node(args.node_id, drain_spec=None, mark_eligible=True)
        print(f"node {args.node_id} drain disabled")
    return 0


def cmd_node_eligibility(args) -> int:
    _client(args).set_node_eligibility(args.node_id, args.enable)
    print(f"node {args.node_id} "
          f"{'eligible' if args.enable else 'ineligible'}")
    return 0


# -- alloc / eval / operator -------------------------------------------------


def cmd_alloc_status(args) -> int:
    _p(_client(args).allocation(args.alloc_id))
    return 0


def cmd_alloc_stop(args) -> int:
    """Stop and reschedule one allocation (reference command/alloc_stop.go)."""
    eval_id = _client(args).stop_alloc(args.alloc_id)
    print(f"alloc {args.alloc_id} stopping, evaluation {eval_id}")
    return _monitor_eval(args, eval_id) if not args.detach else 0


def cmd_alloc_logs(args) -> int:
    """Print a task's captured output (reference command/alloc_logs.go)."""
    out = _client(args).alloc_logs(
        args.alloc_id, task=args.task,
        log_type="stderr" if args.stderr else "stdout",
        offset=args.offset)
    sys.stdout.write(out["data"].decode(errors="replace"))
    return 0


def cmd_alloc_exec(args) -> int:
    """Interactive command in a running allocation (reference
    command/alloc_exec.go over the exec-session HTTP surface)."""
    import threading

    api = _client(args)
    sid = api.alloc_exec_start(args.alloc_id, args.command, task=args.task,
                               tty=args.tty)
    done = threading.Event()

    def pump_stdin():
        try:
            while not done.is_set():
                line = sys.stdin.readline()
                if not line:
                    api.alloc_exec_stdin(sid, b"", close=True)
                    return
                api.alloc_exec_stdin(sid, line.encode())
        except Exception:
            pass

    t = threading.Thread(target=pump_stdin, daemon=True)
    if not sys.stdin.isatty() or args.interactive:
        t.start()
    offset = 0
    exit_code = 0
    try:
        while True:
            out = api.alloc_exec_output(sid, offset=offset, wait_s=10.0)
            if out["data"]:
                sys.stdout.buffer.write(out["data"])
                sys.stdout.buffer.flush()
            offset = out["offset"]
            if out.get("exited"):
                exit_code = int(out.get("exit_code") or 0)
                break
    finally:
        done.set()
        try:
            api.alloc_exec_close(sid)
        except Exception:
            pass
    return exit_code


def cmd_alloc_fs(args) -> int:
    """Browse/read an allocation's filesystem (reference
    command/alloc_fs.go)."""
    api = _client(args)
    st = api.alloc_fs_stat(args.alloc_id, args.path or "/")
    if st["is_dir"]:
        for e in api.alloc_fs_ls(args.alloc_id, args.path or "/"):
            kind = "d" if e["is_dir"] else "-"
            print(f"{kind} {e['size']:>10}  {e['name']}")
        return 0
    offset = 0
    while True:
        data = api.alloc_fs_cat(args.alloc_id, args.path, offset=offset)
        if not data:
            break
        sys.stdout.buffer.write(data)
        offset += len(data)
    sys.stdout.buffer.flush()
    return 0


def cmd_eval_status(args) -> int:
    _p(_client(args).evaluation(args.eval_id))
    return 0


def cmd_operator_snapshot(args) -> int:
    api = _client(args)
    if args.op == "save":
        data = api.snapshot_save()
        with open(args.file, "w") as f:
            json.dump(data, f)
        print(f"snapshot saved to {args.file} (index {data.get('index')})")
        return 0
    with open(args.file) as f:
        data = json.load(f)
    index = api.snapshot_restore(data)
    print(f"snapshot restored at index {index}")
    return 0


def cmd_operator_debug(args) -> int:
    """Capture a support bundle a maintainer can triage from (reference
    command/operator_debug.go): cluster state, metrics, thread dumps, a
    sampled CPU profile, recent events, and a monitor-log slice, packed
    into one tar.gz."""
    import io
    import tarfile
    import urllib.request

    out_path = args.output or f"nomad-debug-{int(time.time())}.tar.gz"
    dur = max(1.0, min(args.duration, 30.0))
    token = getattr(args, "token", "") or ""

    def _get_json(path: str, timeout: float = 15.0):
        req = urllib.request.Request(f"{args.address}{path}",
                                     headers={"X-Nomad-Token": token})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read())

    captures = {
        "agent_self.json": lambda: _get_json("/v1/agent/self"),
        "leader.json": lambda: _get_json("/v1/status/leader"),
        "members.json": lambda: _get_json("/v1/agent/members"),
        "raft_configuration.json":
            lambda: _get_json("/v1/operator/raft/configuration"),
        "scheduler_config.json":
            lambda: _get_json("/v1/operator/scheduler/configuration"),
        "jobs.json": lambda: _get_json("/v1/jobs"),
        "nodes.json": lambda: _get_json("/v1/nodes"),
        "evals.json": lambda: _get_json("/v1/evaluations"),
        "deployments.json": lambda: _get_json("/v1/deployments"),
        "threads.json": lambda: _get_json("/v1/agent/pprof/threads"),
        "profile.json":
            lambda: _get_json(f"/v1/agent/pprof/profile?seconds={dur}",
                              timeout=dur + 30.0),
    }

    with tarfile.open(out_path, "w:gz") as tar:
        def add(name: str, payload) -> None:
            if isinstance(payload, (dict, list)):
                data = json.dumps(payload, indent=2, default=str).encode()
            else:
                data = str(payload).encode()
            info = tarfile.TarInfo(f"nomad-debug/{name}")
            info.size = len(data)
            info.mtime = int(time.time())
            tar.addfile(info, io.BytesIO(data))

        for name, fn in captures.items():
            try:
                add(name, fn())
            except Exception as e:
                add(name + ".error", f"{type(e).__name__}: {e}")
        # prometheus metrics ride raw (non-JSON body)
        try:
            req = urllib.request.Request(
                f"{args.address}/v1/metrics?format=prometheus",
                headers={"X-Nomad-Token": getattr(args, "token", "") or ""})
            add("metrics.prom",
                urllib.request.urlopen(req, timeout=15).read().decode())
        except Exception as e:
            add("metrics.prom.error", f"{type(e).__name__}: {e}")
        # a short live log slice (the monitor stream)
        try:
            req = urllib.request.Request(
                f"{args.address}/v1/agent/monitor?wait={dur}"
                "&log_level=debug",
                headers={"X-Nomad-Token": getattr(args, "token", "") or ""})
            lines = []
            with urllib.request.urlopen(req, timeout=dur + 15) as resp:
                deadline = time.time() + dur
                while time.time() < deadline:
                    line = resp.readline()
                    if not line:
                        break
                    lines.append(line.decode(errors="replace"))
            add("monitor.log", "".join(lines))
        except Exception as e:
            add("monitor.log.error", f"{type(e).__name__}: {e}")
    print(f"debug bundle written to {out_path}")
    return 0


def cmd_operator_scheduler(args) -> int:
    api = _client(args)
    if args.op == "get-config":
        _p(api.scheduler_configuration())
        return 0
    cfg = dict(api.scheduler_configuration())
    if args.scheduler_algorithm:
        cfg["scheduler_algorithm"] = args.scheduler_algorithm
    api.set_scheduler_configuration(cfg)
    print("scheduler configuration updated")
    return 0


def cmd_service(args) -> int:
    """Service catalog (reference command/service_list.go / service_info.go)."""
    api = _client(args)
    if args.op == "list":
        for s in api.list_services():
            print(f"{s['service_name']}\t{s['instances']} instance(s)\t"
                  f"tags={','.join(s['tags']) or '-'}")
        return 0
    if not args.name:
        print("service info requires a name", file=sys.stderr)
        return 2
    for reg in api.service(args.name):
        print(f"{reg['id']}\t{reg['address']}:{reg['port']}\t"
              f"node={reg['node_id'][:8]}\talloc={reg['alloc_id'][:8]}")
    return 0


def cmd_monitor(args) -> int:
    """Stream agent logs (reference command/monitor.go)."""
    import urllib.error
    import urllib.request

    url = (f"{args.address}/v1/agent/monitor?wait={args.wait}"
           f"&log_level={args.log_level}")
    headers = {}
    token = getattr(args, "token", "")
    if token:
        # agent:read-gated with ACLs on, like every _client() route
        headers["X-Nomad-Token"] = token
    req = urllib.request.Request(url, headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=args.wait + 30) as resp:
            while True:
                line = resp.readline()
                if not line:
                    return 0
                try:
                    rec = json.loads(line)
                    ts = time.strftime("%H:%M:%S",
                                       time.localtime(rec["ts"]))
                    print(f"{ts} [{rec['level']}] {rec['name']}: "
                          f"{rec['message']}", flush=True)
                except (ValueError, KeyError):
                    continue
    except KeyboardInterrupt:
        return 0
    except urllib.error.URLError as e:
        print(f"monitor failed: {e}", file=sys.stderr)
        return 1


def _oidc_login(api, args) -> int:
    """OIDC authorization-code flow (reference command/login.go): start
    a localhost callback listener, hand the user the provider auth URL,
    wait for the redirect, complete the exchange server-side."""
    import secrets as _secrets
    import threading
    import webbrowser
    from http.server import BaseHTTPRequestHandler, HTTPServer
    from urllib.parse import parse_qs, urlparse

    got: dict = {}
    done = threading.Event()

    class CB(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            u = urlparse(self.path)
            if u.path != "/oidc/callback":
                # stray fetches (favicon) must not clobber the code
                self.send_response(404)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return
            q = parse_qs(u.query)
            got["code"] = (q.get("code") or [""])[0]
            got["state"] = (q.get("state") or [""])[0]
            body = b"Login complete. You can close this tab."
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            done.set()

    srv = HTTPServer(("127.0.0.1", args.callback_port), CB)
    port = srv.server_port
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    redirect_uri = f"http://127.0.0.1:{port}/oidc/callback"
    nonce = _secrets.token_hex(16)
    out, _ = api._request("POST", "/v1/acl/oidc/auth-url", body={
        "auth_method": args.method, "redirect_uri": redirect_uri,
        "client_nonce": nonce})
    url = out["auth_url"]
    if args.no_browser:
        print(f"Open the following URL to authenticate:\n{url}",
              file=sys.stderr, flush=True)
    else:
        print(f"Opening browser for {url}", file=sys.stderr, flush=True)
        webbrowser.open(url)
    if not done.wait(timeout=300.0):
        srv.shutdown()
        print("timed out waiting for the OIDC callback", file=sys.stderr)
        return 1
    srv.shutdown()
    token, _ = api._request("POST", "/v1/acl/oidc/complete-auth", body={
        "auth_method": args.method, "state": got.get("state", ""),
        "code": got.get("code", ""), "redirect_uri": redirect_uri,
        "client_nonce": nonce})
    _p(token)
    return 0


def cmd_acl(args) -> int:
    """ACL operations (reference command/acl_*.go): bootstrap, SSO
    login, auth methods, binding rules."""
    api = _client(args)
    if args.acl_cmd == "bootstrap":
        _p(api._request("POST", "/v1/acl/bootstrap")[0])
        return 0
    if args.acl_cmd == "login":
        if getattr(args, "login_type", "jwt") == "oidc":
            return _oidc_login(api, args)
        if not args.login_token:
            print("acl login -type=jwt requires a login token argument",
                  file=sys.stderr)
            return 2
        token = args.login_token
        if token == "-":
            token = sys.stdin.read().strip()
        _p(api.acl_login(args.method, token))
        return 0
    if args.acl_cmd == "auth-method":
        if args.op == "list":
            _p(api.list_auth_methods())
        elif args.op == "delete":
            api.delete_auth_method(args.name)
            print(f"auth method {args.name} deleted")
        else:  # apply
            body = json.load(open(args.spec)) if args.spec else {}
            api.upsert_auth_method(args.name, body)
            print(f"auth method {args.name} applied")
        return 0
    if args.acl_cmd == "binding-rule":
        if args.op == "list":
            _p(api.list_binding_rules())
        elif args.op == "delete":
            api.delete_binding_rule(args.name)
            print(f"binding rule {args.name} deleted")
        else:
            body = json.load(open(args.spec)) if args.spec else {}
            rid = api.upsert_binding_rule(body)
            print(f"binding rule {rid} applied")
        return 0
    return 2


def cmd_operator_raft(args) -> int:
    """Raft membership operations (reference command/operator_raft_*.go)."""
    api = _client(args)
    if args.op == "list-peers":
        cfg = api.raft_configuration()
        for s in cfg.get("servers", []):
            mark = " (leader)" if s.get("leader") else ""
            print(f"{s['id']}\t{s['address']}{mark}")
        return 0
    if not args.peer_id:
        print("remove-peer requires -peer-id", file=sys.stderr)
        return 2
    api.raft_remove_peer(args.peer_id)
    print(f"peer {args.peer_id} removed")
    return 0


def cmd_region(args) -> int:
    """Federated regions (reference command/regions.go + operator)."""
    api = _client(args)
    if args.op == "list":
        for name in api.get("/v1/regions")[0]:
            print(name)
        return 0
    if args.op == "delete":
        api._request("DELETE", f"/v1/operator/region/{args.name}")
        print(f"region {args.name} deleted")
        return 0
    api._request("POST", f"/v1/operator/region/{args.name}",
                 {"address": args.region_address})
    print(f"region {args.name} -> {args.region_address}")
    return 0


def cmd_server_join(args) -> int:
    """Tell the local agent's server to join a cluster (reference
    command/server_join.go)."""
    api = _client(args)
    api.agent_join(args.join_addr)
    print(f"joined via {args.join_addr}")
    return 0


def cmd_deployment(args) -> int:
    """Deployment operations (reference command/deployment_*.go)."""
    api = _client(args)
    if args.op != "list" and not args.dep_id:
        print(f"deployment {args.op} requires a deployment id",
              file=sys.stderr)
        return 2
    if args.op == "list":
        for d in api.list_deployments():
            print(f"{d['id'][:8]}  {d['job_id']:24} v{d['job_version']}  "
                  f"{d['status']}")
        return 0
    if args.op == "status":
        _p(api.deployment(args.dep_id))
        return 0
    if args.op == "promote":
        eval_id = api.promote_deployment(args.dep_id)
        print(f"deployment {args.dep_id} promoted, evaluation {eval_id}")
        return 0
    api.fail_deployment(args.dep_id)
    print(f"deployment {args.dep_id} failed")
    return 0


# -- namespaces / pools / vars / system --------------------------------------


def cmd_namespace(args) -> int:
    api = _client(args)
    if args.op == "list":
        for n in api.list_namespaces():
            print(f"{n['name']:20} {n.get('description', '')}")
    elif args.op == "apply":
        api.apply_namespace(args.name, args.description)
        print(f"namespace {args.name!r} applied")
    else:
        api.delete_namespace(args.name)
        print(f"namespace {args.name!r} deleted")
    return 0


def cmd_node_pool(args) -> int:
    api = _client(args)
    if args.op == "list":
        for p in api.list_node_pools():
            sc = p.get("scheduler_configuration") or {}
            print(f"{p['name']:20} {p.get('description', '')} "
                  f"{('alg=' + sc['scheduler_algorithm']) if sc.get('scheduler_algorithm') else ''}")
    elif args.op == "apply":
        body = {"description": args.description}
        if args.scheduler_algorithm:
            body["scheduler_configuration"] = {
                "scheduler_algorithm": args.scheduler_algorithm}
        api.apply_node_pool(args.name, body)
        print(f"node pool {args.name!r} applied")
    else:
        api.delete_node_pool(args.name)
        print(f"node pool {args.name!r} deleted")
    return 0


def cmd_var(args) -> int:
    api = _client(args)
    if args.op == "list":
        for v in api.list_variables():
            print(v)
    elif args.op == "get":
        _p(api.get_variable(args.path))
    elif args.op == "put":
        items = dict(kv.split("=", 1) for kv in args.items)
        api.put_variable(args.path, items)
        print(f"var {args.path!r} written")
    else:
        api.delete_variable(args.path)
        print(f"var {args.path!r} deleted")
    return 0


def cmd_volume(args) -> int:
    api = _client(args)
    if args.op == "list":
        for v in api.list_volumes():
            print(f"{v['id']:24} {v['access_mode']:24} claims={v['claims']}")
    elif args.op == "register":
        body = {"name": args.vol_id, "access_mode": args.access_mode}
        api.register_volume(args.vol_id, body)
        print(f"volume {args.vol_id!r} registered")
    else:
        api.deregister_volume(args.vol_id, force=args.force)
        print(f"volume {args.vol_id!r} deregistered")
    return 0


def cmd_system_gc(args) -> int:
    _p(_client(args).system_gc())
    return 0


# -- parser ------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="nomad-tpu")
    p.add_argument("--address", default=os.environ.get("NOMAD_ADDR",
                                                       "http://127.0.0.1:4646"))
    p.add_argument("--namespace", default=os.environ.get("NOMAD_NAMESPACE",
                                                         "default"))
    p.add_argument("--token", default=os.environ.get("NOMAD_TOKEN", ""),
                   help="ACL secret (X-Nomad-Token; env NOMAD_TOKEN)")
    sub = p.add_subparsers(dest="cmd", required=True)

    ag = sub.add_parser("agent", help="run an agent (server+clients+http)")
    ag.add_argument("-dev", action="store_true", dest="dev")
    ag.add_argument("-config", "--config", default="",
                    help="agent config file (HCL-shaped or .json); "
                         "flags override file values; SIGHUP reloads")
    ag.add_argument("--clients", type=int, default=1)
    ag.add_argument("--workers", type=int, default=2,
                    help="scheduler workers on this server; 0 makes it a "
                         "server that never schedules (upstream's "
                         "num_schedulers = 0): it resolves no backend, "
                         "opens no device and starts with device=none")
    ag.add_argument("--port", type=int, default=4646)
    ag.add_argument("--algorithm", default="binpack")
    ag.add_argument("--data-dir", default="")
    ag.add_argument("--region", default="global",
                    help="this cluster's federation region name")
    ag.add_argument("--authoritative-region", dest="authoritative_region",
                    default="", help="region to replicate ACL metadata from")
    ag.add_argument("--plugin-dir", default="",
                    help="directory of external driver plugin executables")
    ag.add_argument("--server-id", default="server-0",
                    help="this server's id in a multi-server cluster")
    ag.add_argument("--peers", default="",
                    help="raft peer set 'id=host:port,id=host:port,...' "
                         "(enables multi-server mode)")
    ag.add_argument("--join", default="",
                    help="address of any live cluster member; this server "
                         "joins that cluster instead of bootstrapping "
                         "(use with --peers listing only itself)")
    ag.add_argument("--gossip", default="",
                    help="gossip bind addr host:port (enables serf-style "
                         "membership, reference nomad/serf.go)")
    ag.add_argument("--retry-join", dest="retry_join", default="",
                    help="comma-separated gossip seed addresses to join via")
    ag.add_argument("--gossip-key", dest="gossip_key", default="",
                    help="shared secret authenticating gossip datagrams")
    ag.add_argument("--dead-server-cleanup", type=float, default=0.0,
                    help="autopilot: remove a server unreachable this many "
                         "seconds (0 = disabled; reference nomad/autopilot.go)")
    ag.set_defaults(fn=cmd_agent)

    job = sub.add_parser("job").add_subparsers(dest="job_cmd", required=True)
    jr = job.add_parser("run")
    jr.add_argument("spec")
    jr.add_argument("-detach", action="store_true")
    jr.add_argument("-var", action="append", dest="var",
                    help="key=value jobspec variable (repeatable)")
    jr.set_defaults(fn=cmd_job_run)
    jp = job.add_parser("plan")
    jp.add_argument("spec")
    jp.add_argument("-var", action="append", dest="var")
    jp.set_defaults(fn=cmd_job_plan)
    jv = job.add_parser("validate", help="parse + validate a jobspec "
                        "without submitting (reference job validate)")
    jv.add_argument("spec")
    jv.add_argument("-var", action="append", dest="var")
    jv.add_argument("-json", action="store_true", dest="as_json",
                    help="print the canonical parsed job as JSON")
    jv.set_defaults(fn=cmd_job_validate)
    jd = job.add_parser("dispatch")
    jd.add_argument("job_id")
    jd.add_argument("--payload-file", default="")
    jd.add_argument("--meta", action="append",
                    help="key=value dispatch metadata (repeatable)")
    jd.add_argument("-detach", action="store_true")
    jd.set_defaults(fn=cmd_job_dispatch)
    jsc = job.add_parser("scale")
    jsc.add_argument("job_id")
    jsc.add_argument("group")
    jsc.add_argument("count", type=int)
    jsc.add_argument("-detach", action="store_true")
    jsc.set_defaults(fn=cmd_job_scale)
    jrv = job.add_parser("revert")
    jrv.add_argument("job_id")
    jrv.add_argument("version", type=int)
    jrv.add_argument("-detach", action="store_true")
    jrv.set_defaults(fn=cmd_job_revert)
    jh = job.add_parser("history")
    jh.add_argument("job_id")
    jh.set_defaults(fn=cmd_job_history)
    js = job.add_parser("status")
    js.add_argument("job_id", nargs="?", default="")
    js.set_defaults(fn=cmd_job_status)
    jst = job.add_parser("stop")
    jst.add_argument("job_id")
    jst.add_argument("-purge", action="store_true")
    jst.set_defaults(fn=cmd_job_stop)

    node = sub.add_parser("node").add_subparsers(dest="node_cmd", required=True)
    ns = node.add_parser("status")
    ns.add_argument("node_id", nargs="?", default="")
    ns.set_defaults(fn=cmd_node_status)
    nd = node.add_parser("drain")
    nd.add_argument("node_id")
    g = nd.add_mutually_exclusive_group(required=True)
    g.add_argument("-enable", action="store_true", dest="enable")
    g.add_argument("-disable", action="store_false", dest="enable")
    nd.add_argument("--deadline", type=float, default=3600.0)
    nd.set_defaults(fn=cmd_node_drain)
    ne = node.add_parser("eligibility")
    ne.add_argument("node_id")
    g2 = ne.add_mutually_exclusive_group(required=True)
    g2.add_argument("-enable", action="store_true", dest="enable")
    g2.add_argument("-disable", action="store_false", dest="enable")
    ne.set_defaults(fn=cmd_node_eligibility)

    al = sub.add_parser("alloc").add_subparsers(dest="alloc_cmd", required=True)
    als = al.add_parser("status")
    als.add_argument("alloc_id")
    als.set_defaults(fn=cmd_alloc_status)
    alstop = al.add_parser("stop")
    alstop.add_argument("alloc_id")
    alstop.add_argument("-detach", action="store_true")
    alstop.set_defaults(fn=cmd_alloc_stop)
    allog = al.add_parser("logs")
    allog.add_argument("alloc_id")
    allog.add_argument("task", nargs="?", default="")
    allog.add_argument("-stderr", action="store_true")
    allog.add_argument("--offset", type=int, default=0)
    allog.set_defaults(fn=cmd_alloc_logs)
    alex = al.add_parser("exec")
    alex.add_argument("-task", default="")
    alex.add_argument("-tty", action="store_true")
    alex.add_argument("-i", dest="interactive", action="store_true",
                      help="forward stdin when attached to a terminal")
    alex.add_argument("alloc_id")
    alex.add_argument("command", nargs="+")
    alex.set_defaults(fn=cmd_alloc_exec)
    alfs = al.add_parser("fs")
    alfs.add_argument("alloc_id")
    alfs.add_argument("path", nargs="?", default="/")
    alfs.set_defaults(fn=cmd_alloc_fs)

    ev = sub.add_parser("eval").add_subparsers(dest="eval_cmd", required=True)
    evs = ev.add_parser("status")
    evs.add_argument("eval_id")
    evs.set_defaults(fn=cmd_eval_status)

    dep = sub.add_parser("deployment")
    dep.add_argument("op", choices=["list", "status", "promote", "fail"])
    dep.add_argument("dep_id", nargs="?", default="")
    dep.set_defaults(fn=cmd_deployment)

    nsp = sub.add_parser("namespace")
    nsp.add_argument("op", choices=["list", "apply", "delete"])
    nsp.add_argument("name", nargs="?", default="")
    nsp.add_argument("-description", default="")
    nsp.set_defaults(fn=cmd_namespace)

    npool = sub.add_parser("node-pool")
    npool.add_argument("op", choices=["list", "apply", "delete"])
    npool.add_argument("name", nargs="?", default="")
    npool.add_argument("-description", default="")
    npool.add_argument("-scheduler-algorithm", dest="scheduler_algorithm",
                       default="")
    npool.set_defaults(fn=cmd_node_pool)

    var = sub.add_parser("var")
    var.add_argument("op", choices=["list", "get", "put", "delete"])
    var.add_argument("path", nargs="?", default="")
    var.add_argument("items", nargs="*", help="key=value (for put)")
    var.set_defaults(fn=cmd_var)

    vol = sub.add_parser("volume")
    vol.add_argument("op", choices=["list", "register", "deregister"])
    vol.add_argument("vol_id", nargs="?", default="")
    vol.add_argument("-access-mode", dest="access_mode",
                     default="single-node-writer")
    vol.add_argument("-force", action="store_true")
    vol.set_defaults(fn=cmd_volume)

    system = sub.add_parser("system").add_subparsers(dest="system_cmd",
                                                     required=True)
    sgc = system.add_parser("gc")
    sgc.set_defaults(fn=cmd_system_gc)

    op = sub.add_parser("operator").add_subparsers(dest="op_cmd", required=True)
    osched = op.add_parser("scheduler")
    osched.add_argument("op", choices=["get-config", "set-config"])
    osched.add_argument("-scheduler-algorithm", dest="scheduler_algorithm",
                        default="")
    osched.set_defaults(fn=cmd_operator_scheduler)
    osnap = op.add_parser("snapshot")
    osnap.add_argument("op", choices=["save", "restore"])
    osnap.add_argument("file")
    osnap.set_defaults(fn=cmd_operator_snapshot)
    oraft = op.add_parser("raft")
    oraft.add_argument("op", choices=["list-peers", "remove-peer"])
    oraft.add_argument("-peer-id", dest="peer_id", default="")
    oraft.set_defaults(fn=cmd_operator_raft)
    odebug = op.add_parser("debug", help="capture a support bundle")
    odebug.add_argument("-output", default="",
                        help="bundle path (default nomad-debug-<ts>.tar.gz)")
    odebug.add_argument("-duration", type=float, default=5.0,
                        help="seconds of CPU profile + log capture")
    odebug.set_defaults(fn=cmd_operator_debug)

    mon = sub.add_parser("monitor")
    mon.add_argument("-log-level", dest="log_level", default="info")
    mon.add_argument("-wait", type=int, default=600)
    mon.set_defaults(fn=cmd_monitor)

    aclp = sub.add_parser("acl").add_subparsers(dest="acl_cmd", required=True)
    ab = aclp.add_parser("bootstrap")
    ab.set_defaults(fn=cmd_acl)
    alog = aclp.add_parser("login")
    alog.add_argument("-method", required=True)
    alog.add_argument("-type", dest="login_type", default="jwt",
                      choices=("jwt", "oidc"),
                      help="jwt: exchange a provided JWT; oidc: browser "
                           "authorization-code flow with a local callback")
    alog.add_argument("-callback-port", type=int, default=0,
                      help="oidc: local callback port (0 = ephemeral)")
    alog.add_argument("-no-browser", action="store_true",
                      help="oidc: print the auth URL instead of opening "
                           "a browser")
    alog.add_argument("login_token", nargs="?", default="",
                      help="external JWT ('-' reads from stdin; "
                           "jwt type only)")
    alog.set_defaults(fn=cmd_acl)
    for kind in ("auth-method", "binding-rule"):
        ap = aclp.add_parser(kind)
        ap.add_argument("op", choices=["apply", "list", "delete"])
        ap.add_argument("name", nargs="?", default="")
        ap.add_argument("-spec", default="",
                        help="JSON config file for apply")
        ap.set_defaults(fn=cmd_acl)

    svc = sub.add_parser("service")
    svc.add_argument("op", choices=["list", "info"])
    svc.add_argument("name", nargs="?", default="")
    svc.set_defaults(fn=cmd_service)

    reg = sub.add_parser("region")
    reg.add_argument("op", choices=["list", "apply", "delete"])
    reg.add_argument("name", nargs="?", default="")
    # dest must NOT collide with the global --address (the agent to
    # talk to) or apply would target the region being registered
    reg.add_argument("-region-address", dest="region_address", default="")
    reg.set_defaults(fn=cmd_region)

    server = sub.add_parser("server").add_subparsers(dest="server_cmd",
                                                     required=True)
    sjoin = server.add_parser("join")
    sjoin.add_argument("join_addr")
    sjoin.set_defaults(fn=cmd_server_join)

    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
