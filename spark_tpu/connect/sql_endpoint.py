"""SQL endpoint for external tools + DB-API client.

Role of the reference's HiveThriftServer2
(sql/hive-thriftserver/.../HiveThriftServer2.scala:149 + the
SparkSQLOperationManager): a long-running server external tools connect
to with plain SQL and get tabular results back — the JDBC/ODBC
endpoint role. The wire protocol is newline-delimited JSON over TCP
(one request object per line, one response object per line) instead of
Thrift, and `spark_tpu.connect.sql_endpoint.connect()` provides a
DB-API 2.0 connection/cursor so Python tools (and anything that speaks
DB-API) can query the engine like any database:

    conn = connect("127.0.0.1", port, token=token)
    cur = conn.cursor()
    cur.execute("select k, sum(v) from t group by k")
    cur.fetchall()

Authentication: a server started with a `token` runs nothing for a
connection until its first line is `{"auth": <token>}` with the same
token (compared in constant time); anything else first is answered with
a typed UNAUTHENTICATED error and the connection is closed. A server
without a token binds loopback addresses only. `connect(...,
token=...)` sends that first line.

Session model (spark_tpu/serve/): each connection gets its OWN cloned
session (TpuSession.newSession) — SET and temp views are
connection-local while the KernelCache, warehouse catalog and
persistent caches stay shared, the reference ThriftServer's
session-per-connection model. Temp views registered on the server
session read through to every connection. The legacy
all-connections-share-one-session behavior is an opt-in: start the
server with spark.tpu.serve.sessionMode=shared, or send
{"session": "shared"} on a connection before its first statement.

Queries are admitted through weighted fair-scheduler pools
(spark.tpu.scheduler.pools; a connection picks its pool with
`SET spark.tpu.scheduler.pool=<name>`), with bounded queues,
queue-timeout rejection, and plan-time HBM admission. A
{"status": true} request returns the per-pool live serving status
(queued/running/rejected, latency percentiles, SLO findings).
stop() drains gracefully: new statements are rejected with a typed
SERVER_DRAINING error while in-flight queries finish and flush their
query profiles.

Tracing (obs/tracing.py): every request is an `endpoint.request` span
(line read to response flushed) on the connection's session's tracer,
and a result set's way onto the wire an `endpoint.encode` span (Arrow
table to rows to the JSON line written and flushed; `rows`, `bytes`).
The server session's counters `endpoint.requests` and
`endpoint.auth_refused` count the lines served and the connections
turned away."""

from __future__ import annotations

import hmac
import ipaddress
import json
import socket
import socketserver
import threading
from typing import Any

UNAUTHENTICATED = "UNAUTHENTICATED"


def _json_cell(v) -> Any:
    import datetime
    import decimal

    if isinstance(v, (datetime.datetime, datetime.date)):
        return v.isoformat()
    if isinstance(v, decimal.Decimal):
        return str(v)
    if isinstance(v, bytes):
        return v.decode("utf-8", "replace")
    return v


def _is_loopback(host: str) -> bool:
    if host == "localhost":
        return True
    try:
        return ipaddress.ip_address(host).is_loopback
    except ValueError:
        return False


class SQLEndpoint:
    """JSON-lines SQL server over a serving session pool (see module
    docstring: a token, session-per-connection, fair-scheduler pool
    admission, graceful drain)."""

    def __init__(self, session, host: str = "127.0.0.1", port: int = 0,
                 service=None, token: str | None = None):
        from ..serve.service import QueryService

        if not token and not _is_loopback(host):
            raise ValueError(
                f"SQLEndpoint will not bind {host!r} without a token: "
                "anyone who reaches the port could run SQL (pass token=, "
                "or bind a loopback address)")
        self.session = session
        self.token = token or None
        self.service = service if service is not None \
            else QueryService(session)
        outer = self

        class Handler(socketserver.StreamRequestHandler):
            def handle(self):
                # per-connection session, cloned lazily on the first
                # statement so a {"session": "shared"} opt-in sent
                # first binds the connection to the server session
                state = {"session": None,
                         "authenticated": outer.token is None}
                try:
                    for line in self.rfile:
                        line = line.strip()
                        if line and not outer._serve(line, state,
                                                     self.wfile):
                            return
                finally:
                    outer.service.close_session(state["session"])

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((host, port), Handler)
        self.host, self.port = self._server.server_address
        self._thread: threading.Thread | None = None

    # -- one line of one connection ---------------------------------------
    def _serve(self, line: bytes, state: dict, wfile) -> bool:
        """Answer one request line; False closes the connection."""
        if not state["authenticated"]:
            # nothing is parsed further, planned or run for a connection
            # that has not shown the token
            if self._authenticates(line):
                state["authenticated"] = True
                _send(wfile, {"ok": True, "auth": True})
                return True
            self.session._metrics.add("endpoint.auth_refused")
            _send(wfile, {"error": "the connection's first line must be "
                                   "{\"auth\": <the server's token>}",
                          "error_class": UNAUTHENTICATED})
            return False
        self.session._metrics.add("endpoint.requests")
        sess = None
        try:
            req = json.loads(line)
            if req.get("sql") or req.get("session"):
                sess = self._conn_session(state, req)
        except Exception as e:  # protocol-level failure, or draining
            _send(wfile, _error_resp(e))
            return True
        tracer = (sess if sess is not None else self.session).tracer
        with tracer.span("endpoint.request", cat="serve"):
            try:
                out = self._run(req, sess)
                if not isinstance(out, dict):
                    with tracer.span("endpoint.encode", cat="serve",
                                     args={"rows": out.num_rows}) as sp:
                        payload = _encode_table(out)
                        sp.set_args({"bytes": len(payload)})
                        wfile.write(payload)
                        wfile.flush()
                    return True
            except Exception as e:
                out = _error_resp(e)
            _send(wfile, out)
        return True

    def _authenticates(self, line: bytes) -> bool:
        try:
            offered = json.loads(line).get("auth")
        except Exception:
            return False
        return isinstance(offered, str) and hmac.compare_digest(
            offered.encode(), self.token.encode())

    def _conn_session(self, state: dict, req: dict):
        if req.get("session") == "shared" \
                and state["session"] is not self.session:
            # explicit opt-in rebinds the connection (legacy behavior)
            self.service.close_session(state["session"])
            state["session"] = self.service.open_session("shared")
        if state["session"] is None:
            state["session"] = self.service.open_session()
        return state["session"]

    def _run(self, req: dict, sess):
        """The response to one request: a dict, or the Arrow table of a
        statement's result for `_serve` to encode."""
        if "auth" in req:
            # a client's token sent again, or to a server that asks for
            # none: acknowledged, nothing to do
            return {"ok": True, "auth": True}
        if req.get("status"):
            return {"status": self.service.status()}
        if req.get("metrics"):
            # Prometheus text scrape over the SQL wire — same payload
            # the history server's /metrics serves; "" while the export
            # switch is off so tools can distinguish disabled from empty
            from ..obs import export as _export

            return {"metrics": _export.render_prometheus()
                    if _export.ENABLED else "",
                    "enabled": _export.ENABLED}
        if req.get("bundles"):
            # black-box bundle index over the SQL wire (obs/blackbox):
            # recent anomaly-captured bundles, newest first — empty
            # list with the capture layer unarmed
            from ..config import OBS_BUNDLE_DIR
            from ..obs import blackbox

            bdir = str(self.service.session.conf.get(
                OBS_BUNDLE_DIR) or "")
            return {"bundles": blackbox.list_bundles(bdir)[:16]
                    if bdir else [],
                    "enabled": blackbox.ENABLED}
        sql = req.get("sql")
        if not sql:
            if req.get("session"):
                # session-mode-only request: bound by _serve, acknowledged
                return {"ok": True, "session": req.get("session")}
            return {"error": "request must carry a 'sql' field"}
        t = self.service.execute_sql(sess, sql)
        if t is None or not hasattr(t, "column_names"):
            return {"columns": [], "types": [], "rows": []}
        return t

    def start(self) -> "SQLEndpoint":
        # race-lint: ignore[bare-submit] — HTTP accept loop for the whole
        # endpoint; per-request queries enter their own scope downstream
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name="sql-endpoint")
        self._thread.start()
        return self

    def stop(self, drain_timeout: float | None = None) -> bool:
        """Graceful drain then socket close: new statements are
        rejected with SERVER_DRAINING the moment this is called;
        in-flight and already-queued queries get the drain budget
        (spark.tpu.serve.drainTimeout) to finish — and flush their
        query profiles — before the listener closes. Returns True when
        everything quiesced inside the budget."""
        try:
            drained = self.service.drain(drain_timeout)
        except Exception:
            drained = False
        self._server.shutdown()
        self._server.server_close()
        return drained


def _send(wfile, resp: dict) -> None:
    wfile.write((json.dumps(resp) + "\n").encode())
    wfile.flush()


def _encode_table(t) -> bytes:
    """A result set as one response line: rows as JSON arrays, decimals
    as strings, each column's Arrow type beside its name."""
    cols = t.column_names
    pylists = [c.to_pylist() for c in t.columns]
    rows = [[_json_cell(v) for v in row]
            for row in zip(*pylists)] if cols else []
    return (json.dumps({"columns": cols,
                        "types": [str(c.type) for c in t.columns],
                        "rows": rows}) + "\n").encode()


def _error_resp(e: Exception) -> dict:
    resp = {"error": f"{type(e).__name__}: {e}"}
    ec = getattr(e, "error_class", None)
    if ec:
        resp["error_class"] = ec
    return resp


# -- DB-API 2.0 client ------------------------------------------------------

apilevel = "2.0"
threadsafety = 1
paramstyle = "format"


class Error(Exception):
    """DB-API error; `error_class` carries the server's stable error
    condition (e.g. SERVER_DRAINING, ADMISSION_TIMEOUT) when one rode
    the wire."""

    def __init__(self, message: str, error_class: str | None = None):
        super().__init__(message)
        self.error_class = error_class


class Cursor:
    def __init__(self, conn: "Connection"):
        self._conn = conn
        self.description = None
        self.rowcount = -1
        self._rows: list = []
        self._pos = 0
        self.arraysize = 1

    def execute(self, sql: str, params=None) -> "Cursor":
        if params:
            # substitute ONLY %s placeholders — a literal % elsewhere in
            # the SQL (LIKE 'a%') must not be treated as a format spec
            parts = sql.split("%s")
            if len(parts) - 1 != len(params):
                raise Error(
                    f"{len(params)} parameters for "
                    f"{len(parts) - 1} %s placeholders")
            out = [parts[0]]
            for p, tail in zip(params, parts[1:]):
                out.append(_sql_quote(p))
                out.append(tail)
            sql = "".join(out)
        resp = self._conn._request({"sql": sql})
        if resp.get("error"):
            raise Error(resp["error"], resp.get("error_class"))
        cols = resp.get("columns", [])
        types = resp.get("types", [])
        self.description = [(c, t, None, None, None, None, None)
                            for c, t in zip(cols, types)] or None
        self._rows = [tuple(r) for r in resp.get("rows", [])]
        self.rowcount = len(self._rows)
        self._pos = 0
        return self

    def fetchone(self):
        if self._pos >= len(self._rows):
            return None
        row = self._rows[self._pos]
        self._pos += 1
        return row

    def fetchmany(self, size=None):
        size = size or self.arraysize
        out = self._rows[self._pos:self._pos + size]
        self._pos += len(out)
        return out

    def fetchall(self):
        out = self._rows[self._pos:]
        self._pos = len(self._rows)
        return out

    def close(self):
        self._rows = []

    def __iter__(self):
        while True:
            r = self.fetchone()
            if r is None:
                return
            yield r


def _sql_quote(v) -> str:
    if v is None:
        return "NULL"
    if isinstance(v, str):
        return "'" + v.replace("'", "''") + "'"
    return str(v)


class Connection:
    """`timeout` is the socket's: it bounds the wait for one response,
    so a client whose first statement compiles a program on the server
    passes one that outlasts the compile."""

    def __init__(self, host: str, port: int, timeout: float = 60.0,
                 token: str | None = None):
        self._sock = socket.create_connection((host, port),
                                              timeout=timeout)
        self._file = self._sock.makefile("rwb")
        self._lock = threading.Lock()
        if token is not None:
            try:
                resp = self._request({"auth": token})
                if resp.get("error"):
                    raise Error(resp["error"], resp.get("error_class"))
            except BaseException:
                self.close()
                raise

    def _request(self, req: dict) -> dict:
        with self._lock:
            self._file.write((json.dumps(req) + "\n").encode())
            self._file.flush()
            line = self._file.readline()
        if not line:
            raise Error("server closed the connection")
        return json.loads(line)

    def cursor(self) -> Cursor:
        return Cursor(self)

    def use_shared_session(self) -> None:
        """Opt this connection into the legacy shared server session
        (SET / temp views visible across connections)."""
        resp = self._request({"session": "shared"})
        if resp.get("error"):
            raise Error(resp["error"], resp.get("error_class"))

    def server_status(self) -> dict:
        """Per-pool live serving status (queued/running/rejected,
        latency percentiles, SLO findings)."""
        resp = self._request({"status": True})
        if resp.get("error"):
            raise Error(resp["error"], resp.get("error_class"))
        return resp.get("status", {})

    def server_metrics(self) -> str:
        """Prometheus text scrape of the server's metrics registry
        ("" when spark.tpu.metrics.export is off server-side)."""
        resp = self._request({"metrics": True})
        if resp.get("error"):
            raise Error(resp["error"], resp.get("error_class"))
        return resp.get("metrics", "")

    def commit(self) -> None:
        pass        # autocommit semantics

    def rollback(self) -> None:
        raise Error("transactions are not supported")

    def close(self) -> None:
        try:
            self._file.close()
        finally:
            self._sock.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def connect(host: str = "127.0.0.1", port: int = 10000,
            timeout: float = 60.0, token: str | None = None) -> Connection:
    return Connection(host, port, timeout, token)
