"""Framework-specific static analysis (``tools/analyze.py`` is the CLI).

Three analyzer families over the framework's own hazard classes — the bug
shapes that burned review rounds across the serving/gateway PRs:

* :mod:`~paddle_tpu.analysis.concurrency` — ``unguarded-mutation``,
  ``lock-order-cycle``, ``blocking-call-in-lock`` over the threaded
  subsystems (``serving/``, ``serving/gateway/``, ``core/``).
* :mod:`~paddle_tpu.analysis.compiled` — ``traced-branch``,
  ``traced-cast``, ``mutable-global-capture``, ``shape-from-data``,
  ``use-after-donate`` in functions reachable from ``jax.jit`` /
  ``@to_static`` entry points.
* :mod:`~paddle_tpu.analysis.registry` — ``undefined-flag``,
  ``dead-flag``, ``unknown-metric-key`` against ``core/flags.py`` and the
  metric-namespace registries.
* :mod:`~paddle_tpu.analysis.hygiene` — ``broad-except`` over the whole
  package.

Findings not covered by an inline
``# analysis: allow(<rule>) — <reason>`` suppression or a
``tools/analysis_baseline.json`` entry fail the tier-1 gate
(``tests/test_static_analysis.py``). See docs/static_analysis.md.
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence

from .common import (BaselineEntry, Finding, Report, SourceFile,  # noqa: F401
                     load_baseline, load_corpus, save_baseline)
from .compiled import CompiledCodeAnalyzer
from .concurrency import ConcurrencyAnalyzer
from .hygiene import HygieneAnalyzer
from .registry import RegistryAnalyzer

#: default corpus roots, relative to the repo root (tests/ is excluded:
#: the fixture corpus under tests/fixtures/analysis is deliberately bad)
DEFAULT_PATHS = ("paddle_tpu", "tools", "examples")


def all_analyzers(full_corpus: bool = True):
    return [ConcurrencyAnalyzer(), CompiledCodeAnalyzer(),
            RegistryAnalyzer(full_corpus=full_corpus), HygieneAnalyzer()]


def all_rules() -> List[str]:
    out: List[str] = []
    for a in all_analyzers():
        out.extend(a.rules)
    return out


def run_analysis(paths: Optional[Sequence[str]] = None, *,
                 root: str, rules: Optional[Sequence[str]] = None,
                 full_corpus: Optional[bool] = None,
                 corpus: Optional[List[SourceFile]] = None) -> Report:
    """Run every analyzer over ``paths`` (default: the whole framework).

    ``rules`` filters the reported rule set. ``full_corpus=False`` (implied
    when ``paths`` is an explicit subset) disables the global-view
    ``dead-flag`` rule. Returns a :class:`Report` whose ``findings`` are
    already inline-suppression-filtered (suppressed ones are kept in
    ``report.suppressed``); baseline filtering is the caller's second step
    (``report.apply_baseline``)."""
    t0 = time.perf_counter()
    if full_corpus is None:
        full_corpus = paths is None
    if corpus is None:
        corpus = load_corpus(list(paths or DEFAULT_PATHS), root)
    by_path = {sf.relpath: sf for sf in corpus}
    report = Report(files=len(corpus))
    for sf in corpus:
        if sf.parse_error is not None:
            report.parse_errors[sf.relpath] = sf.parse_error

    raw: List[Finding] = []
    for analyzer in all_analyzers(full_corpus=full_corpus):
        raw.extend(analyzer.analyze(corpus))
    if rules:
        keep = set(rules)
        raw = [f for f in raw if f.rule in keep]

    for f in sorted(raw, key=lambda f: (f.path, f.line, f.rule)):
        sf = by_path.get(f.path)
        sup = sf.suppression_for(f.rule, f.line) if sf is not None else None
        if sup is not None:
            sup.used = True
            if not sup.reason:
                report.findings.append(Finding(
                    "suppression-missing-reason", f.path, sup.line,
                    f.scope,
                    f"allow({f.rule}) has no reason: suppressions must "
                    f"say WHY (`# analysis: allow({f.rule}) — <reason>`)"))
            report.suppressed.append(f)
        else:
            report.findings.append(f)
    report.elapsed = time.perf_counter() - t0
    return report
