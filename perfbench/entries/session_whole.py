"""Entry `session_whole`: entry `session` (the same client, the same
`session.sql(text).toArrow()`), for a configuration that states which
tier the planner gives its reports (`planned`: {query: tier}) and which
warm-start manifest its deployment restarts onto (`warm_start`).

It asks the planner at set-up, which runs nothing, and a program that
plans one of the reports elsewhere cannot run the configuration: the run
ends there, with the planner's reason, and prints no result. (Without
the whole-query lowering of window functions the reports of
`tpcds_sf10_window` leave the whole-query tier, and one run of them by
stages outlasts a run's time limit many times over: PERF.md 6, PR 29.)
Only queries that `session.sql` plans without executing anything are
named there: a query with a materialised CTE would run the CTE.

Then it adds to the manifest of the session's warm-start directory
(`spark.tpu.cache.dir`) the configuration's records that the manifest
does not have yet: the join capacities each of the reports' plans ended
with, by plan fingerprint, as the engine records them at a query's
close. A plan whose fingerprint neither holds (another scale, a later
planner) climbs its capacity ladder once, and the engine adds its own
record."""

from __future__ import annotations

import json
import os

from perfbench import spec
from perfbench.entries.session import Client, Entry as SessionEntry

__all__ = ["Client", "Entry"]


class Entry(SessionEntry):
    def __init__(self, session, config: dict):
        super().__init__(session, config)
        for query, tier in config["planned"].items():
            physical = session.sql(spec.query_text(query)) \
                .query_execution.physical
            dec = getattr(physical, "decision", None) \
                or getattr(physical, "_tier_decision", None)
            if dec is None or dec.tier != tier:
                raise SystemExit(
                    f"[perfbench] cannot run configuration "
                    f"{config['name']!r}: it states that {query} is planned "
                    f"on the {tier!r} tier, and this program plans it on "
                    f"{getattr(dec, 'tier', None)!r} "
                    f"({getattr(dec, 'reason', 'no TierDecision')})")
        manifest = os.path.join(
            config["session_conf"]["spark.tpu.cache.dir"], "manifest.jsonl")
        os.makedirs(os.path.dirname(manifest), exist_ok=True)
        with open(manifest, "a+") as have, \
                open(os.path.join(spec.ROOT, config["warm_start"])) as ours:
            have.seek(0)
            known = {json.loads(line)["fp"] for line in have if line.strip()}
            have.writelines(line for line in ours
                            if json.loads(line)["fp"] not in known)
