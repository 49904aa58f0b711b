"""CLI: ``python -m tools.dkmon {status|watch|check|top}`` against a live
flightdeck exporter (``--address``), a daemon (``--daemon``), or an
incident JSONL log (``--incidents``).  ``top`` is the accounting view:
per-tenant tokens/sec, page-seconds, queue p99, and share-of-fleet from a
process's ``/ledger`` or the daemon's fleet-merged ``ledger_status``.

``check`` is the automation gate: exit 0 when nothing is firing, 2 when
any alert fires, 3 on a source error.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from tools.dkmon import (
    fetch_address,
    fetch_daemon,
    fetch_ledger_address,
    fetch_ledger_daemon,
    firing_from_incidents,
    firing_rows,
    load_incidents,
    render_status,
    render_top,
)


def _add_source_args(p: argparse.ArgumentParser) -> None:
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--address", metavar="HOST:PORT",
                     help="a flightdeck exporter's /slo endpoint")
    src.add_argument("--daemon", metavar="HOST:PORT",
                     help="a PunchcardServer (slo_status verb)")
    src.add_argument("--incidents", metavar="PATH",
                     help="an incident JSONL log (post-hoc gating)")
    p.add_argument("--secret", default="",
                   help="daemon shared secret (with --daemon)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit the raw payload as JSON instead of a table")


def _fetch(args) -> dict:
    if args.address:
        return fetch_address(args.address)
    if args.daemon:
        host, _, port = args.daemon.rpartition(":")
        return fetch_daemon(host or "127.0.0.1", int(port),
                            secret=args.secret)
    records = load_incidents(args.incidents)
    return {"engines": {}, "incidents": records,
            "firing": firing_from_incidents(records)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.dkmon",
        description="SLO monitor for the distkeras_tpu signal plane",
    )
    sub = parser.add_subparsers(dest="cmd", required=True)
    status = sub.add_parser(
        "status", help="one-shot table of objectives and burn rates")
    _add_source_args(status)
    watch = sub.add_parser(
        "watch", help="poll a live source and re-render the table")
    _add_source_args(watch)
    watch.add_argument("--interval", type=float, default=2.0,
                       help="seconds between polls (default 2)")
    watch.add_argument("--count", type=int, default=0,
                       help="stop after N polls (default: run until ^C)")
    check = sub.add_parser(
        "check", help="exit 0 clean, 2 on any firing alert (the CI gate)")
    _add_source_args(check)
    top = sub.add_parser(
        "top", help="per-tenant accounting table (ledger), hottest first")
    src = top.add_mutually_exclusive_group(required=True)
    src.add_argument("--address", metavar="HOST:PORT",
                     help="a flightdeck exporter's /ledger endpoint")
    src.add_argument("--daemon", metavar="HOST:PORT",
                     help="a PunchcardServer (fleet-merged ledger_status)")
    top.add_argument("--secret", default="",
                     help="daemon shared secret (with --daemon)")
    top.add_argument("--json", action="store_true", dest="as_json",
                     help="emit the raw ledger payload as JSON")
    args = parser.parse_args(argv)

    if args.cmd == "top":
        try:
            if args.address:
                payload = fetch_ledger_address(args.address)
            else:
                host, _, port = args.daemon.rpartition(":")
                payload = fetch_ledger_daemon(host or "127.0.0.1", int(port),
                                              secret=args.secret)
        except (OSError, ValueError) as e:
            print(f"dkmon: error: {e}", file=sys.stderr)
            return 3
        if args.as_json:
            print(json.dumps(payload, indent=1))
        else:
            print(render_top(payload))
        return 0

    if args.cmd == "watch":
        n = 0
        try:
            while True:
                rc = _render_once(args)
                n += 1
                if rc or (args.count and n >= args.count):
                    return rc
                time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0

    if args.cmd == "status":
        return _render_once(args)

    # check
    try:
        payload = _fetch(args)
    except (OSError, ValueError) as e:
        print(f"dkmon: error: {e}", file=sys.stderr)
        return 3
    firing = (payload.get("firing")
              if payload.get("firing") is not None
              else firing_rows(payload.get("engines") or {}))
    if args.as_json:
        print(json.dumps({"firing": firing, "count": len(firing)}, indent=1))
    elif firing:
        for row in firing:
            name = row.get("objective") or row.get("name")
            owner = row.get("engine") or row.get("source") or ""
            print(f"dkmon: FIRING {name} ({owner}) "
                  f"burn_fast={row.get('burn_fast')}", file=sys.stderr)
    if firing:
        return 2
    print("dkmon: ok — no firing alerts")
    return 0


def _render_once(args) -> int:
    try:
        payload = _fetch(args)
    except (OSError, ValueError) as e:
        print(f"dkmon: error: {e}", file=sys.stderr)
        return 3
    if args.as_json:
        print(json.dumps(payload, indent=1))
        return 0
    engines = payload.get("engines") or {}
    if not engines and payload.get("incidents") is not None:
        firing = payload.get("firing") or []
        print(f"{len(payload['incidents'])} incident record(s), "
              f"{len(firing)} unresolved fire(s)")
        for rec in firing:
            print(f"  FIRING {rec.get('objective')} ({rec.get('source')}) "
                  f"since {rec.get('unix', 0):.0f}")
        return 0
    print(render_status(engines, payload.get("incidents")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
