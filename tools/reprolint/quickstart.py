"""W401: quickstart must raise no first-party DeprecationWarnings.

The one dynamic reprolint rule (it executes ``examples/quickstart.py``
under a recording warnings filter, so it imports jax and takes seconds --
hence opt-in via ``--quickstart`` rather than part of the static pass).
The quickstart, the ``repro.api`` execution paths it exercises, and
everything they import must raise no DeprecationWarning of their own.
Third-party DeprecationWarnings (jax/numpy churn) are outside our control:
reported as notes, never fatal.
"""
from __future__ import annotations

import pathlib
import runpy
import sys
import warnings
from typing import List, Optional, Tuple

from tools.reprolint.findings import Finding

RULE_ID = "W401"
HINT = "replace the deprecated first-party call the warning names"

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


def check_quickstart(root: pathlib.Path = REPO_ROOT,
                     target: Optional[pathlib.Path] = None,
                     ) -> Tuple[List[Finding], List[str]]:
    """(first-party DeprecationWarning findings, third-party notes)."""
    target = target or (root / "examples" / "quickstart.py")
    # targets run as __main__ and may parse sys.argv (e.g. serve_lm.py);
    # hide this CLI's own flags from them for the duration of the run
    saved_argv = sys.argv
    sys.argv = [str(target)]
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            runpy.run_path(str(target), run_name="__main__")
    finally:
        sys.argv = saved_argv
    findings: List[Finding] = []
    notes: List[str] = []
    for w in caught:
        if not issubclass(w.category, DeprecationWarning):
            continue
        resolved = pathlib.Path(w.filename).resolve()
        # a repo-local virtualenv still lives under root; installed packages
        # are never first-party code
        vendored = ("site-packages" in str(resolved)
                    or "dist-packages" in str(resolved))
        try:
            rel = resolved.relative_to(root.resolve()).as_posix()
        except ValueError:
            rel = None
        if rel is not None and not vendored:
            findings.append(Finding(
                rule=RULE_ID, path=rel, line=w.lineno,
                message="first-party DeprecationWarning from the quickstart "
                        "path", context="<quickstart>",
                snippet=str(w.message), hint=HINT))
        else:
            notes.append(f"{w.filename}:{w.lineno}: {w.message}")
    return findings, notes

