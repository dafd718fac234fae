"""CLI (reference role: ray/scripts/scripts.py — `ray status/list/
timeline/job`). argparse, no click dependency.

Usage: python -m ray_tpu.scripts.cli <command> [...]
"""

from __future__ import annotations

import argparse
import json
import sys


def cmd_status(args):
    import ray_tpu

    ray_tpu.init(ignore_reinit_error=True)
    from ray_tpu.util.state import (
        summarize_actors,
        summarize_objects,
        summarize_tasks,
    )

    print(json.dumps({
        "cluster_resources": ray_tpu.cluster_resources(),
        "available_resources": ray_tpu.available_resources(),
        "tasks": summarize_tasks(),
        "actors": summarize_actors(),
        "objects": summarize_objects(),
    }, indent=2))


def cmd_list(args):
    import ray_tpu

    ray_tpu.init(ignore_reinit_error=True)
    from ray_tpu.util import state

    fn = {
        "tasks": state.list_tasks,
        "actors": state.list_actors,
        "objects": state.list_objects,
        "placement-groups": state.list_placement_groups,
    }[args.resource]
    rows = fn(limit=args.limit)
    for r in rows:
        print(json.dumps(r.__dict__ if hasattr(r, "__dict__") else r,
                         default=str))


def cmd_timeline(args):
    import ray_tpu

    ray_tpu.init(ignore_reinit_error=True)
    from ray_tpu.util.state import get_timeline

    trace = get_timeline()
    with open(args.output, "w") as f:
        json.dump(trace, f)
    print(f"wrote {len(trace)} events to {args.output}")


def cmd_job(args):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient()
    if args.job_cmd == "submit":
        job_id = client.submit_job(entrypoint=" ".join(args.entrypoint))
        print(job_id)
        for chunk in client.tail_job_logs(job_id):
            sys.stdout.write(chunk)
        info = client.get_job_info(job_id)
        print(f"job {job_id}: {info.status}")
        raise SystemExit(0 if info.return_code == 0 else 1)
    raise SystemExit(f"unknown job command {args.job_cmd!r}")


def cmd_start(args):
    """Run a cluster role (reference: `ray start`). ``--head`` serves the
    control plane (persisted for fault tolerance; drivers attach with
    ray_tpu.init(address="host:port")); ``--address=host:port`` joins this
    machine's worker pool to that head as a node daemon."""
    if args.head:
        import os

        from ray_tpu._private.head_service import HeadService, run_standby
        from ray_tpu._private.transport import token_dir

        state = args.state or os.path.join(
            token_dir(), f"head_state_{args.port}.log")
        if args.standby_of:
            token = os.environ.get("RAY_TPU_CLUSTER_TOKEN")
            if not token or not args.state:
                # --state must be EXPLICIT here: the per-port default
                # would give the standby its own (empty) log, so
                # promotion would serve empty state at a non-superseding
                # epoch — silent data loss, not failover.
                raise SystemExit(
                    "--standby-of needs an explicit --state (the SAME "
                    "log file the primary serves) and the cluster "
                    "token in RAY_TPU_CLUSTER_TOKEN")
            print(f"ray_tpu head standing by for {args.standby_of}",
                  flush=True)
            run_standby(args.standby_of, token)
            print("ray_tpu standby promoting: primary unreachable",
                  flush=True)
        svc = HeadService(args.host, args.port, state_path=state)
        print(f"ray_tpu head listening on {svc.host}:{svc.port} "
              f"(token file {svc.token_file})", flush=True)
        try:
            svc.serve_forever()
        except KeyboardInterrupt:
            svc.shutdown()
        return
    if args.address:
        import json

        from ray_tpu._private.node_daemon import NodeDaemon

        daemon = NodeDaemon(
            args.address, num_cpus=args.num_cpus,
            resources=json.loads(args.resources))
        print(f"ray_tpu node {daemon.worker.node_id.hex()[:16]} joined "
              f"{args.address}", flush=True)
        daemon.run_forever()
        return
    raise SystemExit("pass --head to serve the control plane or "
                     "--address=host:port to join as a node")


def cmd_autoscale(args):
    """Run the cluster autoscaler against a head (reference:
    `ray start --autoscaling-config` / the monitor process). The config
    file is JSON: {"node_types": [{"name", "resources", "min_workers",
    "max_workers"}], "idle_timeout_s": 5.0}."""
    import json
    import time as _time

    from ray_tpu.autoscaler import ClusterAutoscaler, NodeTypeConfig

    with open(args.config) as f:
        cfg = json.load(f)
    types = [NodeTypeConfig(
        name=t["name"], resources=dict(t["resources"]),
        min_workers=int(t.get("min_workers", 0)),
        max_workers=int(t.get("max_workers", 10)))
        for t in cfg["node_types"]]
    scaler = ClusterAutoscaler(
        args.address, types,
        idle_timeout_s=float(cfg.get("idle_timeout_s", 5.0)))
    print(f"ray_tpu autoscaler managing {len(types)} node type(s) "
          f"against {args.address}", flush=True)
    try:
        while True:
            _time.sleep(1.0)
    except KeyboardInterrupt:
        scaler.shutdown()


def cmd_logs(args):
    """List or print worker log files of a session (reference: `ray logs`).
    """
    import os

    from ray_tpu._private.log_monitor import latest_session_dir, \
        list_log_files

    session = args.session or latest_session_dir()
    log_dir = os.path.join(session, "logs")
    if args.filename:
        path = os.path.join(log_dir, args.filename)
        with open(path, "r", errors="replace") as f:
            print(f.read(), end="")
        return
    print(f"session: {session}")
    for fname in list_log_files(log_dir):
        size = os.path.getsize(os.path.join(log_dir, fname))
        print(f"  {fname}  ({size} bytes)")


def cmd_debug(args):
    """One-command postmortem collection (reference: `ray stack` +
    dashboard state dumps): pull every live process's flight bundle
    and write one directory-per-incident archive. Requires the flight
    recorder armed (RAY_TPU_FLIGHT=1 / RAY_TPU_PROFILE=1) in the
    processes being dumped; this process arms itself so its own
    bundle is always present."""
    import os

    os.environ.setdefault("RAY_TPU_FLIGHT", "1")
    import ray_tpu

    kwargs = {"ignore_reinit_error": True}
    if args.address:
        kwargs.update(num_cpus=0, num_tpus=0, address=args.address)
    ray_tpu.init(**kwargs)
    incident = ray_tpu.debug_dump(args.output)
    import json as _json

    manifest = {}
    try:
        with open(os.path.join(incident, "manifest.json")) as f:
            manifest = _json.load(f)
    except OSError:
        pass
    print(json.dumps({
        "incident_dir": incident,
        "num_processes": manifest.get("num_processes", 0),
        "sources": sorted(manifest.get("sources", {})),
    }, indent=2))


def cmd_version(args):
    import ray_tpu

    print(ray_tpu.__version__)


def main(argv=None):
    parser = argparse.ArgumentParser(prog="ray-tpu")
    sub = parser.add_subparsers(dest="command", required=True)

    sub.add_parser("status").set_defaults(fn=cmd_status)
    p = sub.add_parser("list")
    p.add_argument("resource", choices=[
        "tasks", "actors", "objects", "placement-groups"])
    p.add_argument("--limit", type=int, default=100)
    p.set_defaults(fn=cmd_list)
    p = sub.add_parser("timeline")
    p.add_argument("--output", default="timeline.json")
    p.set_defaults(fn=cmd_timeline)
    p = sub.add_parser("job")
    p.add_argument("job_cmd", choices=["submit"])
    p.add_argument("entrypoint", nargs=argparse.REMAINDER)
    p.set_defaults(fn=cmd_job)
    p = sub.add_parser("start")
    p.add_argument("--head", action="store_true")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=6380)
    p.add_argument("--state", default=None,
                   help="head FT append-log path (--head only)")
    p.add_argument("--standby-of", default=None, metavar="HOST:PORT",
                   help="run --head as a warm standby: serve only "
                        "after this primary (sharing --state and the "
                        "cluster token) stops answering")
    p.add_argument("--address", default=None, help="join head as a node")
    p.add_argument("--num-cpus", type=int, default=2)
    p.add_argument("--resources", default="{}")
    p.set_defaults(fn=cmd_start)
    p = sub.add_parser("autoscale")
    p.add_argument("--address", required=True, help="head host:port")
    p.add_argument("--config", required=True,
                   help="JSON autoscaling config (node_types)")
    p.set_defaults(fn=cmd_autoscale)
    p = sub.add_parser("logs")
    p.add_argument("filename", nargs="?", default=None)
    p.add_argument("--session", default=None)
    p.set_defaults(fn=cmd_logs)
    p = sub.add_parser("debug")
    p.add_argument("--address", default=None,
                   help="head host:port (omit for a local runtime)")
    p.add_argument("--output", default=None,
                   help="archive root (default <session>/debug_dumps)")
    p.set_defaults(fn=cmd_debug)
    sub.add_parser("version").set_defaults(fn=cmd_version)

    args = parser.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
