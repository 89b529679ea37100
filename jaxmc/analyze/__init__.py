r"""jaxmc.analyze — static analysis over the TLA+ AST (ISSUE 9).

Three consumers, one parse-time pass:

  bounds inference   (analyze/bounds.py)  interval/type fixpoint over
      the next-state relation; finite per-variable summaries flow into
      compile/pack.build_lane_plan as PROVEN lane widths (gauge
      `analyze.proven_lanes`), replacing sampled+guarded widths where
      the proof converges.  JAXMC_ANALYZE_BOUNDS=0 disables.
  demotion prediction (analyze/verdicts.py)  the kernel2 CompileError
      classification as a syntactic scan; backend/bfs.py skips building arms with
      a verdict (gauge `analyze.arm_verdicts`, counter
      `analyze.predicted_demotions`), with the exact build-time reason
      wording.  JAXMC_ANALYZE_PREDICT=0 disables.
  corpus linter       (analyze/lint.py)  spec/cfg diagnostics with
      stable codes; `python -m jaxmc.analyze lint`, `check
      --analyze={off,warn,strict}`, the serve daemon's submit-time
      rejection, and `make lint-corpus` all consume it.
  independence        (analyze/independence.py, ISSUE 15)  per-arm
      read/write footprints down to container ELEMENTS and a
      conservative commutativity matrix; feeds the fused-group
      regrouping planner (default ON, JAXMC_ANALYZE_INDEP=0 opts out)
      and the opt-in --por persistent-set frontier filter.

`python -m jaxmc.analyze pylint` is the repo's own Python static
analysis fallback (unused imports/locals) for containers without ruff;
ruff.toml carries the equivalent rule selection for hosts that have it.
"""

from __future__ import annotations

import os

_OFF = ("0", "off", "no", "false", "disabled")


def bounds_enabled() -> bool:
    """Static bounds -> proven pack lanes (JAXMC_ANALYZE_BOUNDS)."""
    return os.environ.get("JAXMC_ANALYZE_BOUNDS", "1").strip().lower() \
        not in _OFF


def predict_enabled() -> bool:
    """Static per-arm demotion verdicts (JAXMC_ANALYZE_PREDICT)."""
    return os.environ.get("JAXMC_ANALYZE_PREDICT", "1").strip().lower() \
        not in _OFF


from .bounds import (BoundsReport, EB, Iv, dead_arms,  # noqa: E402
                     infer_state_bounds, state_space_estimate)
from .verdicts import predict_arm_demotions  # noqa: E402
from .lint import Diagnostic, lint_pair  # noqa: E402
from .independence import (IndependenceReport,  # noqa: E402
                           independence_report, indep_enabled,
                           por_refusal)

__all__ = [
    "BoundsReport", "EB", "IndependenceReport", "Iv", "Diagnostic",
    "bounds_enabled", "dead_arms", "indep_enabled",
    "independence_report", "infer_state_bounds", "lint_pair",
    "por_refusal", "predict_arm_demotions", "predict_enabled",
    "state_space_estimate",
]
