#!/usr/bin/env python3
"""Lint: every emitted metric name appears exactly once in the canonical
metric name table, and every recorded trace span/event name appears
exactly once in the canonical trace table (areal_tpu/observability/
table.py).

"Emitted" = any string literal passed as the first argument of a
``.counter("...")`` / ``.gauge("...")`` / ``.histogram("...")`` call, or
as the SECOND argument (the first is the trace id) of a
``.event(tid, "...")`` / ``.span_begin(...)`` / ``.span_end(...)`` /
``.span(...)`` call, or as the FIRST argument of a phase span
(``phase("areal....")``, ``clock.phase("areal....")``) or of a device
region (``region("areal....")``), anywhere under
``areal_tpu/`` or in ``__graft_entry__.py`` — found by
AST walk (so formatting/aliasing of
the registry/tracer object doesn't matter, and dynamically computed
names are rejected by construction: names must be literals or the
scrape/trace vocabulary becomes unauditable).

The human-facing tables in ``docs/observability.md`` are diffed against
the canonical tables too (both directions): docs cannot silently drift
when a metric or span is added, renamed, or retired.  Metric names are
``areal_*`` identifiers; trace names are dotted ``layer.name`` pairs,
phase spans dotted names under the prefix ``areal.`` — disjoint
vocabularies, one doc page.

Exit code 0 = clean; 1 = violations (each printed, one per line).  Run in
tier-1 via tests/observability/test_metric_names_lint.py.
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import Dict, List, Set, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_REGISTRY_METHODS = ("counter", "gauge", "histogram")
#: tracer recording methods: first arg is the trace id, SECOND is the
#: canonical span/event name
_TRACER_METHODS = ("event", "span_begin", "span_end", "span")
#: phase spans (observability/tracing.phase, PhaseClock.phase): the name
#: is the FIRST argument, and the call may be a bare ``phase(...)``
_PHASE_FUNCTIONS = ("phase",)
#: device regions (observability/tracing.region): same call shape
_REGION_FUNCTIONS = ("region",)
#: what keeps a phase span's or a region's name apart from the flight
#: recorder's
PHASE_PREFIX = "areal."

#: files whose registry-shaped calls are not metric emissions; currently
#: none — even registry.py's own set_stats emission (areal_stats) is real
_SKIP_FILES: Tuple[str, ...] = ()


def _iter_source_files() -> List[str]:
    out = [os.path.join(REPO_ROOT, "__graft_entry__.py")]
    for dirpath, _, filenames in os.walk(
        os.path.join(REPO_ROOT, "areal_tpu")
    ):
        for f in filenames:
            if f.endswith(".py"):
                out.append(os.path.join(dirpath, f))
    return sorted(out)


def _collect(
    methods: Tuple[str, ...],
    arg_idx: int,
    bare: bool = False,
    sources: Dict[str, str] | None = None,
) -> Dict[str, List[Tuple[str, int]]]:
    """{name: [(rel_path, lineno), ...]} of string literals at position
    ``arg_idx`` of ``.method(...)`` calls (and, with ``bare``, of plain
    ``method(...)`` calls), plus non-literal call sites under the
    sentinel key ``<non-literal>``.  ``sources`` ({rel_path: text})
    stands in for the repository's files (the lint's own test)."""
    emitted: Dict[str, List[Tuple[str, int]]] = {}
    if sources is None:
        sources = {}
        for path in _iter_source_files():
            with open(path) as f:
                sources[os.path.relpath(path, REPO_ROOT)] = f.read()
    for rel, text in sorted(sources.items()):
        if rel in _SKIP_FILES:
            continue
        try:
            tree = ast.parse(text, filename=rel)
        except SyntaxError as e:
            emitted.setdefault("<syntax-error>", []).append(
                (rel, e.lineno or 0)
            )
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if isinstance(fn, ast.Attribute):
                called = fn.attr
            elif bare and isinstance(fn, ast.Name):
                called = fn.id
            else:
                continue
            if called not in methods or len(node.args) <= arg_idx:
                continue
            arg = node.args[arg_idx]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                emitted.setdefault(arg.value, []).append((rel, node.lineno))
            else:
                emitted.setdefault("<non-literal>", []).append(
                    (rel, node.lineno)
                )
    return emitted


def collect_emitted_names() -> Dict[str, List[Tuple[str, int]]]:
    return _collect(_REGISTRY_METHODS, 0)


def collect_trace_names() -> Dict[str, List[Tuple[str, int]]]:
    """Span/event name literals recorded through the tracer API (second
    positional argument — the first is the trace id)."""
    return _collect(_TRACER_METHODS, 1)


def collect_phase_names(
    sources: Dict[str, str] | None = None,
) -> Dict[str, List[Tuple[str, int]]]:
    """Phase span name literals: the first argument of ``phase(...)`` /
    ``<clock>.phase(...)``."""
    return _collect(_PHASE_FUNCTIONS, 0, bare=True, sources=sources)


def collect_region_names(
    sources: Dict[str, str] | None = None,
) -> Dict[str, List[Tuple[str, int]]]:
    """Device region name literals: the first argument of
    ``region(...)`` / ``tracing.region(...)``."""
    return _collect(_REGION_FUNCTIONS, 0, bare=True, sources=sources)


def phase_vocabulary_problems(
    phases: Dict[str, List[Tuple[str, int]]], table, kind: str = "phase"
) -> List[str]:
    """Phase spans against TRACE_TABLE's ``"phase"`` entries, both ways:
    every literal at a ``phase(...)`` site is declared with that kind and
    carries the prefix, and every declared phase is recorded somewhere.
    With ``kind="region"``, the same for ``region(...)`` sites and the
    ``"region"`` entries.  A pure function of its inputs, so the tier-1
    test can feed it a fabricated site."""
    problems: List[str] = []
    what = "phase span" if kind == "phase" else kind
    declared = {spec.name for spec in table if spec.kind == kind}
    for name in sorted(declared):
        if not name.startswith(PHASE_PREFIX):
            problems.append(
                f"{what} {name} in TRACE_TABLE lacks the prefix "
                f"{PHASE_PREFIX!r} that keeps it apart from the flight "
                "recorder's names"
            )
    for name, sites in sorted(phases.items()):
        where = ", ".join(f"{p}:{ln}" for p, ln in sites)
        if name == "<non-literal>":
            problems.append(
                f"non-literal {what} name at {where} — {kind} names "
                "must be string literals so the table lint can see them"
            )
        elif name != "<syntax-error>" and name not in declared:
            problems.append(
                f"{what} {name} ({where}) is missing from "
                "areal_tpu/observability/table.py TRACE_TABLE (kind "
                f'"{kind}")'
            )
    for name in sorted(declared - set(phases)):
        problems.append(
            f"trace table entry {name} ({kind}) is never recorded "
            "anywhere under areal_tpu/ or __graft_entry__.py "
            "(dead vocabulary — remove it or wire the span)"
        )
    return problems


DOCS_TABLE = os.path.join(REPO_ROOT, "docs", "observability.md")

#: a documented metric: a backticked `areal_*` name inside a markdown
#: table row.  Rows may document several names at once
#: ("| `areal_host_load1` / `areal_host_load5` | ...") — every backticked
#: name on the row counts.
_DOC_NAME_RE = re.compile(r"`(areal_[a-z0-9_]+)`")

#: a documented trace span/event: a backticked dotted `layer.name` inside
#: a markdown table row (flight-recorder names contain exactly one dot,
#: phase spans two or three; metric names never do, so the vocabularies
#: cannot collide)
_DOC_TRACE_RE = re.compile(r"`([a-z_]+(?:\.[a-z_]+)+)`")


def collect_documented_names(path: str = DOCS_TABLE) -> Set[str]:
    """Names documented in docs/observability.md's metric table (markdown
    rows whose first cell is a backticked ``areal_*`` name)."""
    out: Set[str] = set()
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            if not line.lstrip().startswith("| `areal_"):
                continue
            out.update(_DOC_NAME_RE.findall(line))
    return out


def collect_documented_trace_names(path: str = DOCS_TABLE) -> Set[str]:
    """Trace names documented in docs/observability.md: markdown table
    rows whose first cell is EXACTLY one backticked dotted name (prose
    cells that merely mention a dotted identifier don't count)."""
    out: Set[str] = set()
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            stripped = line.lstrip()
            if not stripped.startswith("| `"):
                continue
            cell = stripped.split("|")[1].strip()
            m = _DOC_TRACE_RE.fullmatch(cell)
            if m:
                out.add(m.group(1))
    return out


def slo_vocabulary_problems(families: Dict[str, str], table) -> List[str]:
    """The ``areal_slo_*`` digest vocabulary, linted BOTH ways:

    * every family in ``latency.SLO_FAMILIES`` must exist in
      METRIC_TABLE as a *histogram* labeled exactly ``(workload,)`` —
      the digest merge rebuilds percentiles from scraped histogram
      buckets, so a family declared as any other shape silently breaks
      fleet merging;
    * every ``areal_slo_*`` METRIC_TABLE entry must be in SLO_FAMILIES —
      an SLO-prefixed metric outside the digest plane would LOOK
      mergeable to operators but never reach the fleet rows.

    Split out (pure function of its inputs) so the tier-1 test can feed
    it fabricated mismatches."""
    problems: List[str] = []
    by_name = {spec.name: spec for spec in table}
    for name in sorted(families):
        spec = by_name.get(name)
        if spec is None:
            problems.append(
                f"SLO family {name} (latency.SLO_FAMILIES) is missing "
                "from METRIC_TABLE"
            )
            continue
        if spec.type != "histogram":
            problems.append(
                f"SLO family {name} must be a histogram (digest "
                f"transport), table declares {spec.type!r}"
            )
        if tuple(spec.labels) != ("workload",):
            problems.append(
                f"SLO family {name} must be labeled exactly "
                f"('workload',), table declares {tuple(spec.labels)!r}"
            )
    for spec in table:
        if spec.name.startswith("areal_slo_") and spec.name not in families:
            problems.append(
                f"METRIC_TABLE entry {spec.name} uses the areal_slo_ "
                "prefix but is not in latency.SLO_FAMILIES — it would "
                "never merge into the fleet percentile rows"
            )
    return problems


def collect_stall_kind_sites() -> Dict[str, List[Tuple[str, int]]]:
    """{kind: [(rel_path, lineno), ...]} of stall-``kind`` emission
    sites: a string literal either (a) passed as the ``kind=`` keyword of
    an ``.inc(...)`` call, or (b) passed as the first argument of a
    ``stall_kind(...)`` call (the validate-identity marker emission sites
    wrap computed kinds in).  A non-literal first arg to ``stall_kind``
    is collected under ``<non-literal>`` — computed ``kind=`` keywords on
    ``.inc`` are NOT flagged, because routing them through
    ``stall_kind("literal")`` upstream is exactly the supported pattern
    (runtime membership check + lintable literal)."""
    sites: Dict[str, List[Tuple[str, int]]] = {}
    for path in _iter_source_files():
        rel = os.path.relpath(path, REPO_ROOT)
        if rel in _SKIP_FILES:
            continue
        with open(path) as f:
            try:
                tree = ast.parse(f.read(), filename=rel)
            except SyntaxError:
                continue  # already reported by the metric pass
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr == "inc":
                for kw in node.keywords:
                    if kw.arg != "kind":
                        continue
                    if isinstance(kw.value, ast.Constant) and isinstance(
                        kw.value.value, str
                    ):
                        sites.setdefault(kw.value.value, []).append(
                            (rel, node.lineno)
                        )
            is_stall_kind = (
                isinstance(fn, ast.Name) and fn.id == "stall_kind"
            ) or (isinstance(fn, ast.Attribute) and fn.attr == "stall_kind")
            if is_stall_kind and node.args:
                arg = node.args[0]
                if isinstance(arg, ast.Constant) and isinstance(
                    arg.value, str
                ):
                    sites.setdefault(arg.value, []).append(
                        (rel, node.lineno)
                    )
                else:
                    sites.setdefault("<non-literal>", []).append(
                        (rel, node.lineno)
                    )
    return sites


def collect_documented_stall_kinds(path: str = DOCS_TABLE) -> Set[str]:
    """Stall kinds documented in docs/observability.md: every backticked
    lowercase identifier (other than the metric name itself) on the
    ``areal_trace_stall_total`` metric-table row."""
    out: Set[str] = set()
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            stripped = line.lstrip()
            if not stripped.startswith("| `areal_trace_stall_total`"):
                continue
            for m in re.findall(r"`([a-z][a-z0-9_]*)`", stripped):
                if m not in ("areal_trace_stall_total", "kind", "counter"):
                    out.add(m)
    return out


def stall_vocabulary_problems(
    sites: Dict[str, List[Tuple[str, int]]],
    kinds: Tuple[str, ...],
    documented: Set[str],
) -> List[str]:
    """The ``areal_trace_stall_total{kind=}`` vocabulary, linted BOTH
    ways against ``table.STALL_KINDS`` and against the docs row:

    * every literal kind at an emission site must be in STALL_KINDS (an
      unlisted kind would pass the registry's label check — ``kind`` is a
      free-form label value — but be invisible to dashboards keyed on the
      documented vocabulary);
    * every STALL_KINDS entry must be emitted somewhere (dead vocabulary
      otherwise);
    * the docs row for ``areal_trace_stall_total`` must enumerate exactly
      STALL_KINDS.

    Split out (pure function of its inputs) so the tier-1 test can feed
    it fabricated mismatches."""
    problems: List[str] = []
    for kind, where_list in sorted(sites.items()):
        where = ", ".join(f"{p}:{ln}" for p, ln in where_list)
        if kind == "<non-literal>":
            problems.append(
                f"non-literal stall_kind(...) argument at {where} — wrap "
                "each candidate kind literal in stall_kind(\"...\") so "
                "the vocabulary lint can see it"
            )
            continue
        if kind not in kinds:
            problems.append(
                f"stall kind {kind!r} ({where}) is missing from "
                "areal_tpu/observability/table.py STALL_KIND_TABLE"
            )
    emitted = set(sites) - {"<non-literal>"}
    for kind in sorted(set(kinds) - emitted):
        problems.append(
            f"STALL_KIND_TABLE entry {kind!r} is never emitted anywhere "
            "under areal_tpu/ or __graft_entry__.py (dead "
            "vocabulary — remove it or wire the emission)"
        )
    for kind in sorted(set(kinds) - documented):
        problems.append(
            f"stall kind {kind!r} is in STALL_KIND_TABLE but missing "
            "from the docs/observability.md areal_trace_stall_total row"
        )
    for kind in sorted(documented - set(kinds)):
        problems.append(
            f"docs/observability.md documents stall kind {kind!r}, which "
            "is not in STALL_KIND_TABLE (stale doc row — remove it or "
            "add the table entry)"
        )
    return problems


def collect_admit_stop_sites(
    sources: Dict[str, str] | None = None,
) -> Dict[str, List[Tuple[str, int]]]:
    """``admit_stopped_by`` literals: the first argument of
    ``admit_stop(...)`` (``table.admit_stop``, the validate-identity
    marker every site wraps its literal in)."""
    return _collect(("admit_stop",), 0, bare=True, sources=sources)


def collect_documented_row(first_cell: str, path: str = DOCS_TABLE) -> Set[str]:
    """Every other backticked lowercase identifier on the
    docs/observability.md table row whose first cell is
    ```first_cell```."""
    out: Set[str] = set()
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            if line.lstrip().startswith(f"| `{first_cell}`"):
                out.update(re.findall(r"`([a-z][a-z0-9_]*)`", line))
    return out - {first_cell}


def admit_stop_vocabulary_problems(
    sites: Dict[str, List[Tuple[str, int]]],
    stops: Tuple[str, ...],
    documented: Set[str],
) -> List[str]:
    """A step record's ``admit_stopped_by`` against
    ``table.ADMIT_STOPS`` and the docs row, both ways, in
    :func:`stall_vocabulary_problems`' manner."""
    problems: List[str] = []
    for stop, where_list in sorted(sites.items()):
        where = ", ".join(f"{p}:{ln}" for p, ln in where_list)
        if stop == "<non-literal>":
            problems.append(
                f"non-literal admit_stop(...) argument at {where} — "
                "wrap each literal so the vocabulary lint can see it"
            )
        elif stop != "<syntax-error>" and stop not in stops:
            problems.append(
                f"admit stop {stop!r} ({where}) is missing from "
                "areal_tpu/observability/table.py ADMIT_STOP_TABLE"
            )
    for stop in sorted(set(stops) - set(sites)):
        problems.append(
            f"ADMIT_STOP_TABLE entry {stop!r} is never used anywhere "
            "under areal_tpu/ (dead vocabulary — remove it or wire it)"
        )
    for stop in sorted(set(stops) - documented):
        problems.append(
            f"admit stop {stop!r} is in ADMIT_STOP_TABLE but missing "
            "from the docs/observability.md admit_stopped_by row"
        )
    for stop in sorted(documented - set(stops)):
        problems.append(
            f"docs/observability.md documents admit stop {stop!r}, "
            "which is not in ADMIT_STOP_TABLE"
        )
    return problems


def record_field_problems(path: str = DOCS_TABLE) -> List[str]:
    """The fields of the step records (``table.LAP_RECORD``,
    ``ENGINE_STEP_RECORD``, ``TRAIN_BATCH_RECORD`` and what a stack stated
    by kind adds to it, ``TRAIN_BATCH_RECORD_BY_KIND``) against the rows of
    the docs' "Step records" section, both ways."""
    from areal_tpu.observability import table

    declared = (
        set(table.LAP_RECORD) | set(table.ENGINE_STEP_RECORD)
        | set(table.TRAIN_BATCH_RECORD)
        | set(table.TRAIN_BATCH_RECORD_BY_KIND)
    )
    problems = [
        f"STEP_DELTAS entry {name!r} is not in ENGINE_STEP_RECORD"
        for name in table.STEP_DELTAS
        if name not in table.ENGINE_STEP_RECORD
    ]
    documented: Set[str] = set()
    in_section = False
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                if line.startswith("## "):
                    in_section = line.strip() == "## Step records"
                elif in_section and line.startswith("| `"):
                    documented.update(
                        re.findall(r"`([a-z][a-z0-9_]*)`", line.split("|")[1])
                    )
    for name in sorted(declared - documented):
        problems.append(
            f"step record field {name} is declared in table.py but has "
            'no row in docs/observability.md, "Step records"'
        )
    for name in sorted(documented - declared):
        problems.append(
            f'docs/observability.md, "Step records", has a row for '
            f"{name}, which no record in table.py declares"
        )
    return problems


def run_lint() -> List[str]:
    """Returns a list of violation messages (empty = clean)."""
    sys.path.insert(0, REPO_ROOT)
    from areal_tpu.observability.table import METRIC_TABLE

    problems: List[str] = []
    counts: Dict[str, int] = {}
    for spec in METRIC_TABLE:
        counts[spec.name] = counts.get(spec.name, 0) + 1
    for name, n in sorted(counts.items()):
        if n != 1:
            problems.append(
                f"table: {name} appears {n} times in METRIC_TABLE "
                "(must be exactly once)"
            )

    emitted = collect_emitted_names()
    for name, sites in sorted(emitted.items()):
        where = ", ".join(f"{p}:{ln}" for p, ln in sites)
        if name == "<non-literal>":
            problems.append(
                f"non-literal metric name at {where} — metric names must "
                "be string literals so the table lint can see them"
            )
            continue
        if name == "<syntax-error>":
            problems.append(f"unparseable source: {where}")
            continue
        if counts.get(name, 0) == 0:
            problems.append(
                f"emitted metric {name} ({where}) is missing from "
                "areal_tpu/observability/table.py METRIC_TABLE"
            )

    emitted_names = set(emitted) - {"<non-literal>", "<syntax-error>"}
    for name in sorted(set(counts) - emitted_names):
        problems.append(
            f"table entry {name} is never emitted anywhere under "
            "areal_tpu/ (dead vocabulary — remove it or wire "
            "the instrument)"
        )

    # docs table drift: the markdown table in docs/observability.md must
    # document exactly the canonical vocabulary
    documented = collect_documented_names()
    for name in sorted(set(counts) - documented):
        problems.append(
            f"metric {name} is in METRIC_TABLE but missing from the "
            "docs/observability.md metric table"
        )
    for name in sorted(documented - set(counts)):
        problems.append(
            f"docs/observability.md documents {name}, which is not in "
            "areal_tpu/observability/table.py METRIC_TABLE (stale doc "
            "row — remove it or add the table entry)"
        )

    # -- areal_slo_* digest vocabulary (latency.py <-> table, both ways) ----
    from areal_tpu.observability.latency import SLO_FAMILIES

    problems.extend(slo_vocabulary_problems(SLO_FAMILIES, METRIC_TABLE))

    # -- stall-kind vocabulary (emission sites <-> STALL_KINDS <-> docs) ----
    from areal_tpu.observability.table import STALL_KINDS

    problems.extend(
        stall_vocabulary_problems(
            collect_stall_kind_sites(),
            STALL_KINDS,
            collect_documented_stall_kinds(),
        )
    )

    # -- a step record's admit_stopped_by, and the records' fields ----------
    from areal_tpu.observability.table import ADMIT_STOPS

    problems.extend(
        admit_stop_vocabulary_problems(
            collect_admit_stop_sites(),
            ADMIT_STOPS,
            collect_documented_row("admit_stopped_by"),
        )
    )
    problems.extend(record_field_problems())

    # -- trace span/event vocabulary (same discipline, second table) --------
    from areal_tpu.observability.table import TRACE_TABLE

    tcounts: Dict[str, int] = {}
    for spec in TRACE_TABLE:
        tcounts[spec.name] = tcounts.get(spec.name, 0) + 1
    for name, n in sorted(tcounts.items()):
        if n != 1:
            problems.append(
                f"trace table: {name} appears {n} times in TRACE_TABLE "
                "(must be exactly once)"
            )
    # phase spans: the same table, their own call shape
    problems.extend(
        phase_vocabulary_problems(collect_phase_names(), TRACE_TABLE)
    )
    # device regions: the same table and discipline, kind "region"
    problems.extend(
        phase_vocabulary_problems(
            collect_region_names(), TRACE_TABLE, kind="region"
        )
    )
    recorder = {
        s.name for s in TRACE_TABLE if s.kind not in ("phase", "region")
    }
    traced = collect_trace_names()
    for name, sites in sorted(traced.items()):
        where = ", ".join(f"{p}:{ln}" for p, ln in sites)
        if name == "<non-literal>":
            problems.append(
                f"non-literal trace span/event name at {where} — trace "
                "names must be string literals so the table lint can see "
                "them"
            )
            continue
        if name == "<syntax-error>":
            continue  # already reported by the metric pass
        if name not in recorder:
            problems.append(
                f"recorded trace name {name} ({where}) is missing from "
                "areal_tpu/observability/table.py TRACE_TABLE"
            )
    traced_names = set(traced) - {"<non-literal>", "<syntax-error>"}
    for name in sorted(recorder - traced_names):
        problems.append(
            f"trace table entry {name} is never recorded anywhere under "
            "areal_tpu/ or __graft_entry__.py (dead "
            "vocabulary — remove it or wire the instrument)"
        )
    tdocumented = collect_documented_trace_names()
    for name in sorted(set(tcounts) - tdocumented):
        problems.append(
            f"trace name {name} is in TRACE_TABLE but missing from the "
            "docs/observability.md trace table"
        )
    for name in sorted(tdocumented - set(tcounts)):
        problems.append(
            f"docs/observability.md documents trace name {name}, which "
            "is not in TRACE_TABLE (stale doc row — remove it or add "
            "the table entry)"
        )
    return problems


def main() -> int:
    problems = run_lint()
    for p in problems:
        print(p)
    if problems:
        print(f"check_metric_names: {len(problems)} problem(s)")
        return 1
    print("check_metric_names: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
