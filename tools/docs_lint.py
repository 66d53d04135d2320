"""Docs lint: every relative markdown link in README/docs must resolve.

Checks ``[text](target)`` links in README.md, docs/**/*.md, EXPERIMENTS.md,
and ROADMAP.md: external (``http``/``mailto``) and intra-page (``#``)
targets are skipped; everything else must exist on disk relative to the
linking file (anchors stripped).  Exits non-zero listing broken links.

Also checks benchmark-record coverage: every ``BENCH_*.json`` a CI step
produces (parsed from .github/workflows/ci.yml) must be mentioned in
EXPERIMENTS.md alongside its producer script, so the recorded perf
trajectory stays documented as producers are added.

And telemetry schema sync: every field named in the ``ROUND_EVENT_FIELDS``
literal of ``src/repro/core/telemetry.py`` must appear backticked in the
"Telemetry dataflow" section of docs/architecture.md — the recorder can't
grow an undocumented signal.  Likewise every named scope of the ``SCOPES``
literal must appear backticked in PERF.md, whose layer table says which
metric reads it.

  python tools/docs_lint.py

CI pairs this with ``python -m compileall -q src`` as the docs-lint step.
"""
from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
ROOT = Path(__file__).resolve().parent.parent


def doc_files() -> list[Path]:
    files = [ROOT / "README.md", ROOT / "EXPERIMENTS.md", ROOT / "ROADMAP.md"]
    files += sorted((ROOT / "docs").glob("**/*.md"))
    return [f for f in files if f.exists()]


def broken_links() -> list[str]:
    broken = []
    for md in doc_files():
        text = md.read_text()
        # fenced code blocks may contain pseudo-links (e.g. mermaid)
        text = re.sub(r"```.*?```", "", text, flags=re.S)
        for target in LINK.findall(text):
            if target.startswith(("http://", "https://", "mailto:", "#")):
                continue
            path = (md.parent / target.split("#", 1)[0]).resolve()
            if not path.exists():
                broken.append(f"{md.relative_to(ROOT)}: {target}")
    return broken


# producers live under benchmarks/ or tools/; tools take a positional
# input (e.g. trace_report.py telemetry.jsonl) before --json.  Argument
# whitespace is [ \t] only so the match can't leak across CI steps.
BENCH_STEP = re.compile(
    r"python\s+((?:benchmarks|tools)/\w+\.py)(?:[ \t]+(?!--json)\S+)*"
    r"[ \t]+--json[ \t]+(BENCH_\w+\.json)"
)


def undocumented_benchmarks() -> list[str]:
    """CI-produced BENCH_*.json records that EXPERIMENTS.md never mentions."""
    ci = ROOT / ".github" / "workflows" / "ci.yml"
    exp = ROOT / "EXPERIMENTS.md"
    if not ci.exists() or not exp.exists():
        return []
    text = exp.read_text()
    missing = []
    for script, record in BENCH_STEP.findall(ci.read_text()):
        if record not in text:
            missing.append(f"{record} ({script})")
        elif script.split("/")[-1] not in text:
            missing.append(f"{script} (produces {record})")
    return missing


FIELDS_LITERAL = re.compile(r"ROUND_EVENT_FIELDS\s*=\s*(\([^)]*\))", re.S)
TELEMETRY_HEADING = "## Telemetry dataflow"


def telemetry_schema_drift() -> list[str]:
    """docs/architecture.md's telemetry field table must cover exactly the
    keys the recorder emits — parsed from the ROUND_EVENT_FIELDS literal in
    core/telemetry.py (kept a pure literal so this check needs no jax)."""
    src = ROOT / "src" / "repro" / "core" / "telemetry.py"
    doc = ROOT / "docs" / "architecture.md"
    if not src.exists() or not doc.exists():
        return []
    m = FIELDS_LITERAL.search(src.read_text())
    if not m:
        return ["src/repro/core/telemetry.py: ROUND_EVENT_FIELDS literal "
                "not found (the docs sync check parses it textually)"]
    fields = set(ast.literal_eval(m.group(1)))
    text = doc.read_text()
    if TELEMETRY_HEADING not in text:
        return [f"docs/architecture.md: missing '{TELEMETRY_HEADING}' "
                f"section documenting the round-event schema"]
    section = text.split(TELEMETRY_HEADING, 1)[1].split("\n## ", 1)[0]
    documented = set(re.findall(r"`(\w+)`", section))
    drift = []
    for f in sorted(fields - documented):
        drift.append(f"docs/architecture.md §Telemetry dataflow: round-event "
                     f"field `{f}` is emitted but undocumented")
    return drift


SCOPES_LITERAL = re.compile(r"^SCOPES\s*=\s*(\([^)]*\))", re.S | re.M)


def undocumented_scopes() -> list[str]:
    """Every named scope of the SCOPES literal in core/telemetry.py must
    appear backticked in PERF.md (parsed textually, like the fields)."""
    src = ROOT / "src" / "repro" / "core" / "telemetry.py"
    perf = ROOT / "PERF.md"
    if not src.exists() or not perf.exists():
        return []
    m = SCOPES_LITERAL.search(src.read_text())
    if not m:
        return ["src/repro/core/telemetry.py: SCOPES literal not found "
                "(the docs sync check parses it textually)"]
    documented = set(re.findall(r"`([^`\s]+)`", perf.read_text()))
    return [f"PERF.md: named scope `{s}` is in the program but not "
            f"documented" for s in ast.literal_eval(m.group(1))
            if s not in documented]


def main() -> int:
    bad = broken_links()
    for b in bad:
        print(f"BROKEN LINK  {b}")
    undoc = undocumented_benchmarks()
    for u in undoc:
        print(f"UNDOCUMENTED BENCH RECORD  {u} — add it to EXPERIMENTS.md")
    drift = telemetry_schema_drift()
    for d in drift:
        print(f"TELEMETRY SCHEMA DRIFT  {d}")
    scopes = undocumented_scopes()
    for u in scopes:
        print(f"UNDOCUMENTED SCOPE  {u}")
    files = len(doc_files())
    if bad or undoc or drift or scopes:
        print(f"{len(bad)} broken link(s), {len(undoc)} undocumented "
              f"benchmark record(s), {len(drift)} schema drift(s), "
              f"{len(scopes)} undocumented scope(s) "
              f"across {files} file(s)")
        return 1
    print(f"docs lint OK ({files} files)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
