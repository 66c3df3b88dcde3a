"""Entry `session`: the client owns the SparkSession and calls
`session.sql(text).toArrow()` — PERF.md §1's user (a)."""

from __future__ import annotations


class Entry:
    def __init__(self, session, config: dict):
        self.session = session

    def sessions(self) -> list:
        """Every session whose counters the window may have moved."""
        return [self.session]

    def client(self, stream: int) -> "Client":
        return Client(self.session)

    def stop(self) -> None:
        pass


class Client:
    def __init__(self, session):
        self.session = session

    def run(self, text: str, annotate):
        """One timed query: (what it returned, what the host saw)."""
        with annotate("session.sql"):
            df = self.session.sql(text)
        with annotate("toArrow"):
            table = df.toArrow()
        return table, {"phase_times": dict(df.query_execution.phase_times)}

    def rows(self, table) -> list:
        cols = [c.to_pylist() for c in table.columns]
        return list(zip(*cols)) if cols else []

    def close(self) -> None:
        pass
