"""Entry `endpoint`: the tenants of a long-running SQL server — PERF.md
§1's user (b). The server session is the one `run.py` registered the
tables on; `SQLEndpoint` serves it on a loopback port of this process
(one process, so the profiler, the spans and the counters see the
server) behind a token made for the run, and a client is the DB-API
connection of `spark_tpu.connect.sql_endpoint.connect(host, port,
token=..., timeout=...)`: one connection a stream, opened before the
warm-up and kept, so the server's handler thread and the tenant's cloned
session are the warmed ones. A timed query is `cursor.execute(text)` and
`fetchall()`; an error on the wire (a rejection, a drain, a timeout)
raises and is the record's `error`.

At set-up the entry shows that the door is shut: a connection without
the token, and one with a wrong token, are each refused before anything
runs, or the run ends there. A program whose `SQLEndpoint` takes no
token cannot run the configuration.

`sessions()` gives the server's session and every tenant's the service
has opened; the entry keeps them, so that their spans outlive their
connections. Each time it is asked (the harness asks right before and
right after the window) and when it stops, it notes the serving counters
of them all with the time: `NOTES`, which `metrics/statements_shed.py`
reads."""

from __future__ import annotations

import inspect
import secrets
import sys
import time
from decimal import Decimal

# a response waits for the statement, and a cold statement for its
# programs: q3's two compile for 157.6 + 173.7 s on the chip (PERF.md §5)
RESPONSE_TIMEOUT_S = 3600.0

SUMMED = ("serve.granted", "serve.rejected_full", "serve.rejected_timeout",
          "endpoint.requests", "endpoint.auth_refused")
PEAKS = ("serve.running_peak",)

NOTES: list = []     # (time.perf_counter(), {counter: value over sessions})


def serving_counters(sessions: list) -> dict:
    out = dict.fromkeys(SUMMED + PEAKS, 0)
    for s in sessions:
        counters = s._metrics.snapshot()["counters"]
        for k in SUMMED:
            out[k] += int(counters.get(k, 0))
        for k in PEAKS:
            out[k] = max(out[k], int(counters.get(k, 0)))
    return out


class Entry:
    def __init__(self, session, config: dict):
        from spark_tpu.connect import sql_endpoint

        if "token" not in inspect.signature(
                sql_endpoint.SQLEndpoint).parameters:
            raise SystemExit(
                f"[perfbench] cannot run configuration {config['name']!r}: "
                "it guarantees that no statement runs for a connection "
                "that has not authenticated, and this program's "
                "SQLEndpoint takes no token")
        self.session = session
        self.token = secrets.token_hex(16)
        self.endpoint = sql_endpoint.SQLEndpoint(
            session, host="127.0.0.1", port=0, token=self.token).start()
        self._seen = [session]
        del NOTES[:]
        try:
            self._door_is_shut(sql_endpoint)
        except BaseException:
            self.endpoint.stop(0.0)
            raise

    def _door_is_shut(self, sql_endpoint) -> None:
        """No token, a wrong token: refused, and no statement runs."""
        before = serving_counters([self.session])
        for token in (None, secrets.token_hex(16)):
            try:
                with sql_endpoint.connect(
                        self.endpoint.host, self.endpoint.port,
                        timeout=30.0, token=token) as conn:
                    conn.cursor().execute("select 1")
            except sql_endpoint.Error as e:
                if e.error_class == "UNAUTHENTICATED":
                    continue
                raise
            raise SystemExit("[perfbench] the endpoint ran a statement for "
                             "a connection that had not authenticated")
        after = serving_counters([self.session])
        if after["endpoint.auth_refused"] \
                != before["endpoint.auth_refused"] + 2 \
                or after["endpoint.requests"] != before["endpoint.requests"]:
            raise SystemExit("[perfbench] two refused connections moved "
                             f"the counters from {before} to {after}")

    def sessions(self) -> list:
        """Every session whose counters the window may have moved: the
        server's, and each tenant's."""
        for s in self.endpoint.service.sessions():
            if not any(s is seen for seen in self._seen):
                self._seen.append(s)
        NOTES.append((time.perf_counter(), serving_counters(self._seen)))
        return list(self._seen)

    def client(self, stream: int) -> "Client":
        return Client(self.endpoint.host, self.endpoint.port, self.token)

    def stop(self) -> None:
        """Notes the counters once more, says on stderr what went over
        the wire (for PERF.md: no metric reads it), and drains."""
        self.sessions()
        from spark_tpu.obs.tracing import recorded_spans

        sent = [(s["args"]["rows"], s["args"]["bytes"], s["dur_ms"])
                for s in recorded_spans() if s["name"] == "endpoint.encode"]
        print(f"[perfbench] serving counters {NOTES[-1][1]}; result sets "
              f"encoded (rows, bytes, ms): {sent}", file=sys.stderr,
              flush=True)
        self.endpoint.stop()


def _cast(arrow_type: str):
    """What turns a cell of the wire's JSON back into the value
    `toArrow().to_pylist()` gives for a column of this Arrow type."""
    if arrow_type.startswith("decimal"):
        return Decimal
    if arrow_type in ("double", "float", "halffloat"):
        return float
    return lambda v: v          # int, str, bool: JSON's own


class Client:
    def __init__(self, host: str, port: int, token: str):
        from spark_tpu.connect import sql_endpoint

        self.conn = sql_endpoint.connect(
            host, port, timeout=RESPONSE_TIMEOUT_S, token=token)
        self.cursor = self.conn.cursor()

    def run(self, text: str, annotate):
        """One timed query: (the cursor's description and rows, what the
        host saw)."""
        with annotate("execute"):
            self.cursor.execute(text)
        with annotate("fetchall"):
            rows = self.cursor.fetchall()
        return (list(self.cursor.description or ()), rows), {}

    def rows(self, raw) -> list:
        description, rows = raw
        casts = [_cast(d[1]) for d in description]
        return [tuple(None if v is None else cast(v)
                      for cast, v in zip(casts, row)) for row in rows]

    def close(self) -> None:
        self.conn.close()
