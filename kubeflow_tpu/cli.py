"""``python -m kubeflow_tpu.cli`` — the kubectl/kfctl-style command line.

The L7 status surface of the rebuild (SURVEY.md §2.1#7: UI parity is status
reporting, not a web app). Two modes:

- **server**: run the platform (control plane + REST gateway) in the
  foreground; every other command talks to it over HTTP.
- **run**: one-shot — spin an in-process control plane, apply manifests,
  wait for the workloads to finish, print the outcome. No server needed.

Commands: server, apply, get, describe, delete, logs, events, metrics,
run, exec (run a cell in a Notebook session), lint (static analysis).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Optional

import yaml

from kubeflow_tpu.core.headers import USER_HEADER

DEFAULT_SERVER = "http://127.0.0.1:8134"


def _req(server: str, method: str, path: str, body: Optional[bytes] = None,
         user: Optional[str] = None) -> Any:
    req = urllib.request.Request(server + path, data=body, method=method)
    if user:
        req.add_header(USER_HEADER, user)
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            data = resp.read()
    except urllib.error.HTTPError as e:
        detail = e.read().decode(errors="replace")
        raise SystemExit(f"error: {e.code} {detail}")
    except urllib.error.URLError as e:
        raise SystemExit(
            f"error: cannot reach {server} ({e.reason}); "
            "start one with: python -m kubeflow_tpu.cli server")
    ctype = resp.headers.get("Content-Type", "")
    return json.loads(data) if "json" in ctype else data.decode(errors="replace")


def _phase_of(manifest: dict) -> str:
    status = manifest.get("status") or {}
    phase = status.get("phase")
    if phase:
        return str(phase)
    for cond in reversed(status.get("conditions") or []):
        if cond.get("status"):
            return str(cond.get("type"))
    return "Pending"


def _cluster_of(args):
    if args.chips is None:
        return None
    from kubeflow_tpu.runtime.topology import detect_local_cluster

    return detect_local_cluster(num_chips=args.chips, platform=args.platform)


def cmd_server(args) -> int:
    from kubeflow_tpu.operator.control_plane import (
        ControlPlane, ControlPlaneConfig,
    )
    from kubeflow_tpu.platform.api_server import ApiServer

    cp = ControlPlane(ControlPlaneConfig(
        base_dir=args.base_dir, platform=args.platform,
        cluster=_cluster_of(args)))
    cp.start()
    api = ApiServer(cp, port=args.port)
    api.start()
    print(f"kftpu platform up: api={api.url} base_dir={cp.config.base_dir}")
    try:
        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        print("shutting down")
    finally:
        api.stop()
        cp.stop()
    return 0


def cmd_apply(args) -> int:
    with open(args.file) as f:
        docs = [d for d in yaml.safe_load_all(f) if d]
    for doc in docs:
        out = _req(args.server, "POST", "/apis",
                   json.dumps(doc).encode(), user=args.user)
        print(f"{out['kind']}/{out['metadata']['namespace']}/"
              f"{out['metadata']['name']} applied")
    return 0


def cmd_get(args) -> int:
    if args.name:
        out = _req(args.server, "GET",
                   f"/apis/{args.kind}/{args.namespace}/{args.name}")
        print(yaml.safe_dump(out, sort_keys=False) if args.output == "yaml"
              else json.dumps(out, indent=2, default=str))
        return 0
    out = _req(args.server, "GET",
               f"/apis/{args.kind}?namespace={args.namespace}")
    items = out["items"]
    if args.output == "yaml":
        print(yaml.safe_dump_all(items, sort_keys=False))
        return 0
    rows = [(m["metadata"]["namespace"], m["metadata"]["name"], _phase_of(m))
            for m in items]
    if not rows:
        print(f"no {args.kind} in namespace {args.namespace}")
        return 0
    w = max(len(r[1]) for r in rows)
    print(f"{'NAMESPACE':12} {'NAME':{w}} PHASE")
    for ns, name, phase in rows:
        print(f"{ns:12} {name:{w}} {phase}")
    return 0


def cmd_describe(args) -> int:
    out = _req(args.server, "GET",
               f"/apis/{args.kind}/{args.namespace}/{args.name}")
    print(yaml.safe_dump(out, sort_keys=False))
    ref = f"{out['kind']}/{args.namespace}/{args.name}"
    evs = _req(args.server, "GET", f"/events?ref={ref}")["items"]
    if evs:
        print("Events:")
        for e in evs:
            print(f"  {e['type']:8} {e['reason']:20} x{e['count']} "
                  f"{e['message']}")
    return 0


def cmd_delete(args) -> int:
    out = _req(args.server, "DELETE",
               f"/apis/{args.kind}/{args.namespace}/{args.name}",
               user=args.user)
    print(out["deleted"], "deleted")
    return 0


def cmd_logs(args) -> int:
    out = _req(args.server, "GET",
               f"/logs/{args.namespace}/{args.job}/{args.worker}")
    print(out, end="")
    return 0


def cmd_events(args) -> int:
    evs = _req(args.server, "GET", "/events")["items"]
    for e in evs[-args.tail:]:
        print(f"{e['type']:8} {e['object_ref']:40} {e['reason']:20} "
              f"{e['message']}")
    return 0


def cmd_metrics(args) -> int:
    print(_req(args.server, "GET", "/metrics"), end="")
    return 0


def cmd_trace(args) -> int:
    """Pretty-print a trace dump: a file saved from any ``/debug/traces``
    endpoint (or its ``?chrome=1`` Chrome export), or — with no file — the
    platform server's live ``/debug/traces``. ``--slowest N`` keeps the N
    slowest traces by root duration."""
    from kubeflow_tpu.obs.trace import format_dump, load_dump

    if args.file is not None:
        doc = load_dump(args.file)
    else:
        path = "/debug/traces"
        if args.slowest is not None:
            path += f"?slowest={int(args.slowest)}"
        doc = _req(args.server, "GET", path)
    if args.slowest is not None and "traces" in doc:
        traces = [t for t in doc["traces"] if t.get("root")]
        traces.sort(key=lambda t: t["root"].get("duration_ms") or 0.0,
                    reverse=True)
        doc = {"traces": traces[:int(args.slowest)]}
    print(format_dump(doc))
    return 0


def cmd_dashboard(args) -> int:
    """One aggregated view of the whole platform (centraldashboard analog):
    per-namespace per-kind counts with condition rollups + recent events."""
    data = _req(args.server, "GET", "/dashboard")
    print(f"{'NAMESPACE':16} {'KIND':20} {'COUNT':>5}  STATES")
    for ns, info in sorted(data["namespaces"].items()):
        for kind, row in sorted(info["kinds"].items()):
            states = ", ".join(f"{s}={n}" for s, n
                               in sorted(row["by_state"].items()))
            print(f"{ns:16} {kind:20} {row['total']:>5}  {states}")
    if data["recent_events"] and args.tail > 0:
        print("\nRECENT EVENTS")
        for e in data["recent_events"][-args.tail:]:
            print(f"{e['type']:8} {e['object_ref']:40} {e['reason']:20} "
                  f"{e['message']}")
    return 0


def cmd_volumes(args) -> int:
    """Volume browser (pvcviewer/volumes-web-app analog over the REST
    surface): list volumes, list one volume's files, or print a file."""
    from urllib.parse import quote

    ns = quote(args.namespace, safe="")
    if args.volume is None:
        got = _req(args.server, "GET", f"/volumes/{ns}", user=args.user)
        for v in got["volumes"]:
            print(f"{v['name']:40} {v['used_bytes']:>12} bytes")
        return 0
    vol = quote(args.volume, safe="")
    if args.path is None:
        got = _req(args.server, "GET", f"/volumes/{ns}/{vol}",
                   user=args.user)
        for f in got["files"]:
            print(f"{f['path']:50} {f['bytes']:>12} bytes")
        return 0
    out = _req(args.server, "GET",
               f"/volumes/{ns}/{vol}/files/{quote(args.path)}",
               user=args.user)
    print(out, end="" if isinstance(out, str) else "\n")
    return 0


def cmd_artifacts(args) -> int:
    """Registered artifact:// names → versions → shape/size — what an
    operator checks before pointing a storageUri or dataset_uri at one.
    ``kftpu artifacts gc`` runs platform GC (retention + mark-and-sweep)."""
    if args.name == "gc":
        body = {"dry_run": bool(args.dry_run)}
        if args.keep_last is not None:
            body["keep_last"] = args.keep_last
        if args.min_age is not None:
            body["min_age_s"] = args.min_age
        rep = _req(args.server, "POST", "/artifacts/gc",
                   body=json.dumps(body).encode(),
                   user=getattr(args, "user", None))
        verb = "would sweep" if rep["dry_run"] else "swept"
        print(f"{verb} {rep['swept_blobs']} blobs "
              f"({rep['swept_bytes'] / 1e6:.1f} MB) + {rep['swept_trees']} "
              f"materialized trees; live {rep['live_blobs']} blobs "
              f"({rep['live_bytes'] / 1e6:.1f} MB)")
        for pv in rep["pruned_versions"]:
            print(f"  pruned {pv}")
        if rep["retired_lineage"]:
            print(f"  retired lineage artifacts: {rep['retired_lineage']}")
        return 0
    if not args.name:
        items = _req(args.server, "GET", "/artifacts")["items"]
        if not items:
            print("no registered artifacts")
            return 0
        for n, d in sorted(items.items()):
            if d.get("kind") == "broken":
                # The server degrades dangling register entries (blob
                # pruned outside the platform) instead of 500ing — the
                # listing must survive the same state.
                print(f"{n:30} BROKEN: {d.get('error', 'missing blob')}")
                continue
            print(f"{n:30} {d['versions']} version(s)  "
                  f"latest=@{d['latest']} ({d['kind']}, "
                  f"{d.get('bytes', 0) / 1e6:.1f} MB)")
        return 0
    info = _req(args.server, "GET", f"/artifacts/{args.name}")
    print(f"{'VERSION':10} {'KIND':6} {'SIZE':>10}  URI")
    for v, d in info["versions"].items():
        if d.get("kind") == "broken":
            print(f"{v:10} BROKEN  {d.get('error', 'missing blob')}")
            continue
        extra = f" ({d['files']} files)" if d["kind"] == "tree" else ""
        print(f"{v:10} {d['kind']:6} {d.get('bytes', 0) / 1e6:9.1f}M  "
              f"artifact://{args.name}@{v}{extra}")
    return 0


def cmd_exec(args) -> int:
    out = _req(args.server, "GET",
               f"/apis/Notebook/{args.namespace}/{args.name}")
    url = (out.get("status") or {}).get("url") or ""
    if not url.startswith("unix://"):
        raise SystemExit(f"notebook {args.name} has no running session "
                         f"(phase={_phase_of(out)})")
    from kubeflow_tpu.workspace.session_main import exec_code

    res = exec_code(url[len("unix://"):], args.code)
    sys.stdout.write(res.get("output", ""))
    if not res.get("ok"):
        sys.stderr.write(res.get("error", ""))
        return 1
    return 0


_TERMINAL_KINDS = {"JAXJob", "PipelineRun", "Experiment"}


def cmd_run(args) -> int:
    """One-shot: in-process platform, apply, wait, report."""
    from kubeflow_tpu.core.manifest import load_manifests
    from kubeflow_tpu.operator.control_plane import (
        ControlPlane, ControlPlaneConfig,
    )

    objs = load_manifests(args.file)
    cp = ControlPlane(ControlPlaneConfig(base_dir=args.base_dir,
                                         platform=args.platform,
                                         cluster=_cluster_of(args)))
    cp.start()
    rc = 0
    try:
        waiting = []
        for obj in objs:
            cp.apply(obj)
            print(f"{obj.kind}/{obj.metadata.key} applied")
            if obj.kind in _TERMINAL_KINDS:
                waiting.append(obj)
        deadline = time.monotonic() + args.timeout
        for obj in waiting:
            while time.monotonic() < deadline:
                cur = cp.store.try_get(type(obj), obj.metadata.name,
                                       obj.metadata.namespace)
                if cur is None:
                    break
                status = cur.status
                if status.has_condition("Succeeded"):
                    print(f"{obj.kind}/{obj.metadata.key} Succeeded")
                    break
                if status.has_condition("Failed"):
                    cond = status.get_condition("Failed")
                    print(f"{obj.kind}/{obj.metadata.key} FAILED: "
                          f"{cond.reason if cond else ''}")
                    rc = 1
                    break
                time.sleep(0.3)
            else:
                print(f"{obj.kind}/{obj.metadata.key} timed out")
                rc = 1
    finally:
        cp.stop()
    return rc


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="kftpu", description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)

    def common(sp):
        sp.add_argument("--server", default=DEFAULT_SERVER)
        sp.add_argument("-n", "--namespace", default="default")
        sp.add_argument("--user", default=None)

    sp = sub.add_parser("server", help="run the platform in the foreground")
    sp.add_argument("--port", type=int, default=8134)
    sp.add_argument("--base-dir", default=None)
    sp.add_argument("--platform", default="cpu",
                    help="where workers run: cpu (virtual devices) or tpu")
    sp.add_argument("--chips", type=int, default=None,
                    help="cluster size override (default: detect)")
    sp.set_defaults(fn=cmd_server)

    sp = sub.add_parser("apply", help="apply manifests from a file")
    sp.add_argument("-f", "--file", required=True)
    common(sp)
    sp.set_defaults(fn=cmd_apply)

    sp = sub.add_parser("get", help="list or fetch objects")
    sp.add_argument("kind")
    sp.add_argument("name", nargs="?")
    sp.add_argument("-o", "--output", choices=("table", "yaml"),
                    default="table")
    common(sp)
    sp.set_defaults(fn=cmd_get)

    sp = sub.add_parser("describe", help="manifest + events")
    sp.add_argument("kind")
    sp.add_argument("name")
    common(sp)
    sp.set_defaults(fn=cmd_describe)

    sp = sub.add_parser("delete")
    sp.add_argument("kind")
    sp.add_argument("name")
    common(sp)
    sp.set_defaults(fn=cmd_delete)

    sp = sub.add_parser("logs", help="tail a worker log")
    sp.add_argument("job")
    sp.add_argument("--worker", type=int, default=0)
    common(sp)
    sp.set_defaults(fn=cmd_logs)

    sp = sub.add_parser("events")
    sp.add_argument("--tail", type=int, default=50)
    common(sp)
    sp.set_defaults(fn=cmd_events)

    sp = sub.add_parser("trace", help="pretty-print a trace dump "
                                      "(/debug/traces JSON or Chrome export)")
    sp.add_argument("file", nargs="?", default=None,
                    help="dump file; omit to fetch the server's live traces")
    sp.add_argument("--slowest", type=int, default=None,
                    help="show only the N slowest traces")
    common(sp)
    sp.set_defaults(fn=cmd_trace)

    sp = sub.add_parser("metrics", help="Prometheus metrics")
    common(sp)
    sp.set_defaults(fn=cmd_metrics)

    sp = sub.add_parser("dashboard",
                        help="aggregated per-namespace platform view")
    sp.add_argument("--tail", type=int, default=10,
                    help="recent events to show")
    common(sp)
    sp.set_defaults(fn=cmd_dashboard)

    sp = sub.add_parser("volumes", help="browse per-workload storage")
    sp.add_argument("volume", nargs="?")
    sp.add_argument("path", nargs="?")
    common(sp)
    sp.set_defaults(fn=cmd_volumes)

    sp = sub.add_parser("artifacts",
                        help="browse the artifact register (artifact:// "
                             "names, versions, sizes); 'artifacts gc' "
                             "prunes + sweeps the store")
    sp.add_argument("name", nargs="?")
    sp.add_argument("--keep-last", type=int, default=None,
                    help="gc: retain only the newest N versions per name")
    sp.add_argument("--min-age", type=float, default=None,
                    help="gc: grace window seconds (default 600)")
    sp.add_argument("--dry-run", action="store_true",
                    help="gc: report only, delete nothing")
    common(sp)
    sp.set_defaults(fn=cmd_artifacts)

    sp = sub.add_parser("exec", help="run a cell in a notebook session")
    sp.add_argument("name")
    sp.add_argument("-c", "--code", required=True)
    common(sp)
    sp.set_defaults(fn=cmd_exec)

    # NOTE: "lint" is dispatched in main() before this parser runs (its
    # flags are the analyzer's own); listed here only so --help shows it.
    sub.add_parser(
        "lint",
        help="static analysis: device-hygiene + lock-discipline + "
             "sharding/SPMD + resource-pairing + metric-name rules "
             "(kubeflow_tpu/analysis; see 'kftpu lint --help')")

    sp = sub.add_parser("run", help="one-shot: apply manifests and wait")
    sp.add_argument("-f", "--file", required=True)
    sp.add_argument("--timeout", type=float, default=600.0)
    sp.add_argument("--base-dir", default=None)
    sp.add_argument("--platform", default="cpu",
                    help="where workers run: cpu (virtual devices) or tpu")
    sp.add_argument("--chips", type=int, default=None,
                    help="cluster size override (default: detect)")
    sp.set_defaults(fn=cmd_run)

    return p


def main(argv: Optional[list[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv[:1] == ["lint"]:
        # The analyzer owns its flag set (paths, --json, --baseline, ...);
        # forwarding through argparse REMAINDER mangles leading options.
        from kubeflow_tpu.analysis.core import main as lint_main

        return lint_main(argv[1:])
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
