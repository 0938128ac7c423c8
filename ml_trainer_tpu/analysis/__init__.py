"""graft-lint: static analysis for the SPMD programs and the host code.

Two front ends over one findings/report/baseline surface:

* jaxpr contract checks (``jaxpr_checks``) — traced-program invariants:
  collective uniformity across switch branches, bf16 dtype policy,
  donation/aliasing audit, trace-time host-sync detection;
* the AST lint pack (``ast_checks``) — host-side concurrency and
  hygiene: lock-order cycles, unguarded shared state, device ops in
  host-only modules, host syncs in hot loops, unused imports.

``scripts/graft_lint.py`` is the CLI; ``docs/graft_lint_baseline.json``
the committed clean-tree artifact; ``tests/test_analysis.py`` the hard
gate on new findings.
"""

from __future__ import annotations

import os
from typing import Optional

from ml_trainer_tpu.analysis.findings import (  # noqa: F401
    Finding,
    Report,
    baseline_payload,
    diff_against_baseline,
    fingerprint,
    load_baseline,
)
from ml_trainer_tpu.analysis.ast_checks import (  # noqa: F401
    LintConfig,
    modules_from_sources,
    run_ast_checks,
    scan_tree,
)
from ml_trainer_tpu.analysis.jaxpr_checks import (  # noqa: F401
    audit_donation,
    check_collective_uniformity,
    check_dtype_policy,
    check_program,
    check_traceable,
    collective_sequence,
)

BASELINE_RELPATH = os.path.join("docs", "graft_lint_baseline.json")


def repo_root() -> str:
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def default_baseline_path() -> str:
    return os.path.join(repo_root(), BASELINE_RELPATH)


def lint_baseline_payload() -> dict:
    """Flight-recorder context provider: the committed lint baseline's
    fingerprint rides along on every dump, so post-mortems know exactly
    which contract set the crashed build was checked against."""
    baseline = load_baseline(default_baseline_path())
    if baseline is None:
        return {"present": False}
    return {
        "present": True,
        "fingerprint": baseline.get("fingerprint"),
        "findings": sum((baseline.get("counts") or {}).values()),
    }


def register_flight_context(flight=None) -> None:
    """Attach the lint-baseline fingerprint to future flight dumps."""
    if flight is None:
        from ml_trainer_tpu.telemetry.flight import get_recorder

        flight = get_recorder()
    flight.register_context_provider("lint_baseline", lint_baseline_payload)
