"""Lint orchestration: sweep the plan-point space through the rules.

``lint_all`` enumerates every registered (kernel × engine) pair at a
representative bucket/batch, builds one :class:`PointContext` per point,
and runs the selected rules — point-scope rules on every point,
kernel-scope rules once per kernel, global registry-hygiene rules once
per sweep.  Nothing is compiled: each point costs an abstract trace (and
one un-compiled lowering when HLO rules are on).

Rule selection accepts exact IDs or prefixes — ``"R3"`` selects the
whole transfer family, ``"R202"`` one rule.  A rule that *crashes* (as
opposed to firing) is reported as an error finding under its own ID: a
lint pass that silently loses a rule is itself a hazard.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Iterable, List, Optional, Sequence, Tuple

from repro.kernels.wavefront import VMEM_CAP_BYTES

from .context import PointContext
from .findings import ERROR, Finding, Report
from .hygiene import GLOBAL_RULES
from .points import PlanPoint, enumerate_points, point_for
from .rules import POINT_RULES, Rule

ALL_RULES: List[Rule] = POINT_RULES + GLOBAL_RULES
RULES_BY_ID = {r.id: r for r in ALL_RULES}


@dataclasses.dataclass
class LintConfig:
    """Budgets and thresholds the R3xx/R4xx rules judge against."""
    vmem_budget_bytes: int = VMEM_CAP_BYTES   # most a Pallas kernel asks for
    tb_budget_bytes: int = 256 << 20      # per-block traceback store
    const_warn_bytes: int = 128 << 10     # captured-constant thresholds
    const_error_bytes: int = 16 << 20
    hlo_rules: bool = True                # run lowering-level rules (R303)


def select_rules(rules: Optional[Iterable[str]] = None,
                 ignore: Optional[Iterable[str]] = None) -> List[Rule]:
    """Resolve ID/prefix selections against the rule registry."""
    def match(rule: Rule, pats: Iterable[str]) -> bool:
        return any(rule.id.startswith(p.upper()) for p in pats)

    selected = [r for r in ALL_RULES if rules is None or match(r, rules)]
    if ignore:
        selected = [r for r in selected if not match(r, ignore)]
    if rules is not None:
        unmatched = [p for p in rules
                     if not any(r.id.startswith(p.upper())
                                for r in ALL_RULES)]
        if unmatched:
            raise ValueError(
                f"unknown rule selector(s) {unmatched}; known rules: "
                f"{sorted(RULES_BY_ID)}")
    return selected


def _run_rule(rule: Rule, report: Report, *args) -> None:
    try:
        report.extend(rule.fn(*args))
    except Exception as e:                      # a crashed rule is a finding
        where = ""
        if args and isinstance(args[0], PointContext):
            where = args[0].point.label
        report.findings.append(Finding(
            rule.id, ERROR,
            f"lint rule crashed: {type(e).__name__}: {e}", where))


def lint_point(point: PlanPoint, config: Optional[LintConfig] = None,
               rules: Optional[Iterable[str]] = None,
               ignore: Optional[Iterable[str]] = None) -> Report:
    """Run the point- and kernel-scope rules on one plan point."""
    cfg = config or LintConfig()
    selected = [r for r in select_rules(rules, ignore)
                if r.scope in ("point", "kernel")]
    report = Report(points=1, rules_run=[r.id for r in selected])
    ctx = PointContext(point)
    for rule in selected:
        _run_rule(rule, report, ctx, cfg)
    return report


def lint_all(kernels: Optional[Iterable] = None,
             engines: Optional[Iterable[str]] = None,
             bucket: Tuple[int, int] = (64, 64),
             batch_size: Optional[int] = 4,
             rules: Optional[Iterable[str]] = None,
             ignore: Optional[Iterable[str]] = None,
             config: Optional[LintConfig] = None,
             points: Optional[Sequence[PlanPoint]] = None) -> Report:
    """Sweep the registered plan-point space (or an explicit ``points``
    list) through the selected rules.  Returns a :class:`Report`; CI
    treats ``report.ok`` (no error-severity findings) as the gate."""
    cfg = config or LintConfig()
    selected = select_rules(rules, ignore)
    t0 = time.perf_counter()
    if points is None:
        points, skipped = enumerate_points(kernels, engines, bucket,
                                           batch_size)
    else:
        points, skipped = list(points), []
    report = Report(points=len(points), skipped=skipped,
                    rules_run=[r.id for r in selected])

    point_rules = [r for r in selected if r.scope == "point"]
    kernel_rules = [r for r in selected if r.scope == "kernel"]
    global_rules = [r for r in selected if r.scope == "global"]

    seen_kernels = set()
    for point in points:
        ctx = PointContext(point)
        if point.kernel not in seen_kernels:
            seen_kernels.add(point.kernel)
            for rule in kernel_rules:
                _run_rule(rule, report, ctx, cfg)
        for rule in point_rules:
            _run_rule(rule, report, ctx, cfg)
    for rule in global_rules:
        _run_rule(rule, report, cfg)

    report.elapsed_s = time.perf_counter() - t0
    return report
