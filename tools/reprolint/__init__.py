"""reprolint -- AST contract checker for this repo's reproducibility,
parity and thread-ownership invariants.

Rule families (see ``python -m tools.reprolint --list-rules``):

  D1xx  determinism   no wall-clock / stdlib-random / unseeded-RNG reads
  P2xx  parity        pinned Gram/row-dot primitives, traced round fns
  T3xx  threads       ``# owner:`` / ``# worker:`` cohort-pipeline contract
  U5xx  reachability  configs/models modules must justify their existence
  W4xx  quickstart    no first-party DeprecationWarnings (dynamic, opt-in)

DESIGN.md section 9 maps each rule to the design invariant it enforces.
"""
from tools.reprolint.findings import Finding  # noqa: F401
from tools.reprolint.rules import ALL_RULES, lint_file  # noqa: F401
