"""Static analysis for the TPU engine (role of the reference's
EXPLAIN CODEGEN / debugCodegen surface plus the lint layer the reference
spreads across Catalyst checks and scalastyle rules).

Three cooperating passes:

  * analysis.lint — AST-level source lint over spark_tpu/ for host-sync,
    recompile, and fusion-break hazards in operator/kernel hot paths
    (CLI: dev/tpulint.py, baseline: dev/tpulint_baseline.json).
  * analysis.plan_lint — plan/trace-level analyzer over an optimized
    physical plan: predicts kernel launches per batch per stage, explains
    why stage boundaries did or did not fuse, and flags recompile and
    dtype-overflow hazards (surfaced via df.explain("analysis") and
    QueryExecution.analysis_report()).
  * analysis.race_lint — whole-repo concurrency model: shared-mutation
    races, lock-order cycles, contextvar-losing thread spawns, and
    worker re-init gaps (CLI: dev/racecheck.py, baseline:
    dev/race_baseline.json; runtime cross-check: utils/lockwatch.py,
    driven by tests/test_race_lint.py).
"""

from .lint import (  # noqa: F401
    Violation, lint_paths, lint_source, load_baseline, new_violations,
    write_baseline,
)
from .plan_lint import AnalysisReport, analyze_plan  # noqa: F401
from .race_lint import (  # noqa: F401
    RepoModel, build_model, build_model_from_sources,
)
