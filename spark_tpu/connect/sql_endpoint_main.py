"""sparktpu-sqlserver entry point (HiveThriftServer2.main role).

Serves SQL over the JSON-lines endpoint with the full serving stack:
session-per-connection isolation, fair-scheduler pools, and graceful
drain — SIGTERM (and Ctrl-C) stop accepting statements immediately
(typed SERVER_DRAINING errors on the wire), let in-flight queries
finish and flush their query profiles, then exit.

Every connection authenticates with the server's token (`--token`, or
one generated here): the first stdout line is {"host", "port", "token"},
and a client passes it to `sql_endpoint.connect(host, port, token=...)`.
"""

from __future__ import annotations

import argparse
import json
import secrets
import signal
import threading


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="sparktpu-sqlserver")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=10000)
    p.add_argument("--token", default=None,
                   help="the secret every connection's first line must "
                        "carry; generated if omitted")
    p.add_argument("--conf", action="append", default=[], metavar="K=V")
    p.add_argument("--cache-dir", default=None, metavar="DIR",
                   help="enable the persistent caches rooted here "
                        "(spark.tpu.cache.dir): the XLA compile cache "
                        "makes a server RESTART warm — known plans pay "
                        "no cold compiles — and the result cache answers "
                        "repeated identical queries with zero kernel "
                        "launches, shared across all connections")
    p.add_argument("--pools", default=None, metavar="DECLS",
                   help="fair-scheduler pool declarations "
                        "'name[:weight],...' (spark.tpu.scheduler.pools); "
                        "connections pick a pool with "
                        "SET spark.tpu.scheduler.pool=<name>")
    p.add_argument("--session-mode", choices=("isolated", "shared"),
                   default=None,
                   help="session model (spark.tpu.serve.sessionMode): "
                        "'isolated' (default) clones one session per "
                        "connection; 'shared' keeps the legacy "
                        "one-session-for-all behavior")
    args = p.parse_args(argv)

    from ..api.session import TpuSession
    from .sql_endpoint import SQLEndpoint

    conf = dict(kv.split("=", 1) for kv in args.conf if "=" in kv)
    if args.cache_dir:
        conf.setdefault("spark.tpu.cache.dir", args.cache_dir)
    if args.pools:
        conf.setdefault("spark.tpu.scheduler.pools", args.pools)
    if args.session_mode:
        conf.setdefault("spark.tpu.serve.sessionMode", args.session_mode)
    session = TpuSession("sqlserver", conf)
    ep = SQLEndpoint(session, host=args.host, port=args.port,
                     token=args.token or secrets.token_hex(16)).start()
    print(json.dumps({"host": ep.host, "port": ep.port,
                      "token": ep.token}), flush=True)

    stop_evt = threading.Event()

    def _on_term(signum, frame):  # graceful drain on SIGTERM
        stop_evt.set()

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except (ValueError, OSError):
        pass  # non-main thread / restricted platform: Ctrl-C still works
    try:
        stop_evt.wait()
    except KeyboardInterrupt:
        pass
    drained = ep.stop()  # reject new, finish in-flight, flush profiles
    print(json.dumps({"stopped": True, "drained": bool(drained),
                      "status": ep.service.status()}), flush=True)
    session.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
