"""graphcheck rule engine — compiled-graph contracts GRC000–GRC006.

tracecheck (docs/design.md #9) polices what the *source* may say; these
rules police what the *compiled program* actually is.  Every rule runs
against artifacts jax hands back for a registered entrypoint — the
ClosedJaxpr, the lowered StableHLO text, and (for budgets) the compiled
executable's memory analysis:

* GRC000 fingerprint drift — the trace-level op census at the canonical
  registry shapes no longer matches the committed golden for the
  running jax version (reported with a primitive-level diff).
* GRC001 memory budget — ``memory_analysis().temp_size_in_bytes`` at
  the declared big shapes exceeds the ``budgets.py`` bound.
* GRC002 materialisation — a streaming entrypoint holds an intermediate
  with >= 2 axes at dataset extent (the [n, n]-class block the whole
  streaming architecture exists to avoid).
* GRC003 collective census — psum/shard_map counts differ from the
  spec's declaration (zero for single-device entrypoints: a collective
  smuggled into backend code is the runtime twin of TRC004).
* GRC004 transfer census — any device_put/callback/infeed-class
  primitive inside a hot trace (each one is a host round-trip the fused
  dispatch was supposed to have absorbed).
* GRC005 donation — fewer ``tf.aliasing_output`` attributes in the
  lowered program than declared donated leaves (a lost donation doubles
  the carry footprint silently).
* GRC006 dtype discipline — more narrowing float->float
  ``convert_element_type`` ops than the spec's audited allowance
  (silent precision loss inside reduction chains).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence

import jax

from . import budgets as budgets_mod
from . import fingerprint as fp
from .entrypoints import GraphSpec, registry

__all__ = ["Finding", "Report", "ALL_RULES", "RULE_DOCS", "analyze",
           "format_human", "report_to_json"]

ALL_RULES = ("GRC000", "GRC001", "GRC002", "GRC003", "GRC004", "GRC005",
             "GRC006")

RULE_DOCS = {
    "GRC000": "golden fingerprint drift (op census changed at canonical "
              "shapes)",
    "GRC001": "compiled peak-temp exceeds the declared memory budget",
    "GRC002": "materialised [n, n]-class intermediate in a streaming "
              "entrypoint",
    "GRC003": "collective census differs from the declared psum/shard_map "
              "counts",
    "GRC004": "transfer-class primitive (device_put/callback/infeed) in a "
              "hot trace",
    "GRC005": "declared donated buffers do not alias in the lowered "
              "program",
    "GRC006": "unaudited narrowing float convert in the trace",
}

# Primitives that cross the host<->device boundary from inside a trace.
TRANSFER_PRIMS = frozenset({
    "device_put", "pure_callback", "io_callback", "debug_callback",
    "callback", "infeed", "outfeed", "copy_to_host_async",
})

# Collectives counted by GRC003; jax spells the all-reduce `psum` or
# `psum2` depending on the axis-name context, and `psum_invariant` inside
# a `shard_map` with `check_vma=True`; one declared key covers all three.
COLLECTIVE_PRIMS = {"psum": ("psum", "psum2", "psum_invariant"),
                    "shard_map": ("shard_map",)}

_FLOAT_BITS = {"float64": 64, "float32": 32, "float16": 16,
               "bfloat16": 16}


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    entrypoint: str
    message: str


@dataclasses.dataclass
class Report:
    findings: List[Finding]
    entrypoints: List[str]
    notes: List[str]
    skipped_budgets: bool = False

    @property
    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out


def _narrowing(converts: Iterable) -> List:
    out = []
    for src, dst in converts:
        sb, db = _FLOAT_BITS.get(src), _FLOAT_BITS.get(dst)
        if sb is not None and db is not None and db < sb:
            out.append((src, dst))
    return out


def _check_jaxpr_rules(spec: GraphSpec, sv: fp.Survey,
                       findings: List[Finding]) -> None:
    # GRC002 — materialisation in streaming entrypoints
    if "streaming" in spec.tags:
        seen = set()
        for prim, shape in sv.big_outs:
            big_axes = sum(1 for s in shape if s >= spec.n)
            if big_axes >= 2 and (prim, shape) not in seen:
                seen.add((prim, shape))
                findings.append(Finding(
                    "GRC002", spec.name,
                    f"materialised intermediate {list(shape)} from "
                    f"'{prim}' (>= 2 axes at dataset extent n={spec.n})"))
    # GRC003 — collective census
    for prim, spellings in COLLECTIVE_PRIMS.items():
        declared = int(spec.collectives.get(prim, 0))
        got = sum(sv.census.get(s, 0) for s in spellings)
        if got != declared:
            findings.append(Finding(
                "GRC003", spec.name,
                f"{prim} count {got} != declared {declared}"))
    # GRC004 — transfer census (const-staged device_puts are constant
    # placement, not runtime round-trips; Survey separates them)
    for prim in sorted(TRANSFER_PRIMS & set(sv.census)):
        count = sv.runtime_puts if prim == "device_put" \
            else sv.census[prim]
        if count > 0:
            findings.append(Finding(
                "GRC004", spec.name,
                f"transfer primitive '{prim}' x{count} inside a hot "
                f"trace"))
    # GRC006 — narrowing converts
    narrowing = _narrowing(sv.converts)
    if len(narrowing) > spec.allowed_narrowing:
        findings.append(Finding(
            "GRC006", spec.name,
            f"{len(narrowing)} narrowing float convert(s) "
            f"{sorted(set(narrowing))}, allowance "
            f"{spec.allowed_narrowing}"))


def _check_donation(spec: GraphSpec, lowered_text: str,
                    findings: List[Finding]) -> None:
    if spec.donated_leaves <= 0:
        return
    got = lowered_text.count("tf.aliasing_output")
    if got < spec.donated_leaves:
        findings.append(Finding(
            "GRC005", spec.name,
            f"{got} aliased buffer(s) in the lowered program, declared "
            f"{spec.donated_leaves} donated leaves — a donation was "
            f"dropped"))


def _check_budget(spec: GraphSpec, findings: List[Finding],
                  notes: List[str]) -> None:
    fn, args, kw = spec.build_big()
    compiled = fn.lower(*args, **kw).compile()
    ma = compiled.memory_analysis()
    if ma is None or not hasattr(ma, "temp_size_in_bytes"):
        notes.append(f"{spec.name}: memory_analysis unavailable on this "
                     f"backend; GRC001 not evaluated")
        return
    temp = int(ma.temp_size_in_bytes)
    bound = budgets_mod.budget_bytes(spec.budget)
    if temp > bound:
        findings.append(Finding(
            "GRC001", spec.name,
            f"compiled peak temp {temp:,} B exceeds budget {bound:,} B "
            f"[{budgets_mod.budget_doc(spec.budget)}] at "
            f"{budgets_mod.shape_for(spec.budget)}"))


def _check_drift(spec: GraphSpec, print_doc: Dict, golden_doc,
                 findings: List[Finding], notes: List[str]) -> None:
    vgold = fp.golden_for_version(golden_doc)
    if vgold is None:
        return  # version-level note emitted once by analyze()
    old = vgold.get(spec.name)
    if old is None:
        findings.append(Finding(
            "GRC000", spec.name,
            f"no committed golden fingerprint for jax "
            f"{jax.__version__} — regenerate with {fp.GOLDEN_ENV}=1"))
        return
    if old.get("hash") != print_doc.get("hash"):
        diff = fp.diff_fingerprints(old, print_doc)
        findings.append(Finding(
            "GRC000", spec.name,
            "compiled-graph drift vs committed golden:\n" + diff))


def analyze(specs: Optional[Sequence[GraphSpec]] = None, *,
            golden_doc: Optional[Dict] = None,
            rules: Optional[Sequence[str]] = None,
            with_budgets: bool = True) -> "tuple[Report, Dict[str, Dict]]":
    """Run the rule engine; returns (report, fingerprints-by-name)."""
    specs = registry() if specs is None else specs
    active = set(ALL_RULES if rules is None else rules)
    findings: List[Finding] = []
    notes: List[str] = []
    prints: Dict[str, Dict] = {}

    if "GRC000" in active and golden_doc is not None and \
            fp.golden_for_version(golden_doc) is None:
        notes.append(
            f"no goldens committed for jax {jax.__version__} "
            f"(have: {sorted(golden_doc.get('goldens', {}))}); "
            f"GRC000 drift not evaluated")

    for spec in specs:
        fn, args, kw = spec.build()
        traced = fn.trace(*args, **kw)
        closed = traced.jaxpr
        sv = fp.survey(closed)
        doc = fp.fingerprint(closed, sv)
        prints[spec.name] = doc

        ruled: List[Finding] = []
        _check_jaxpr_rules(spec, sv, ruled)
        if "GRC005" in active and spec.donated_leaves > 0:
            _check_donation(spec, traced.lower().as_text(), ruled)
        if "GRC001" in active and spec.budget is not None and with_budgets:
            _check_budget(spec, ruled, notes)
        if "GRC000" in active and golden_doc is not None:
            _check_drift(spec, doc, golden_doc, ruled, notes)
        findings.extend(f for f in ruled if f.rule in active)

    if not with_budgets:
        skipped = [s.name for s in specs if s.budget is not None]
        if skipped and "GRC001" in active:
            notes.append(f"budgets skipped for {len(skipped)} "
                         f"entrypoint(s) (--skip-budgets)")
    report = Report(findings=findings, entrypoints=[s.name for s in specs],
                    notes=notes, skipped_budgets=not with_budgets)
    return report, prints


def format_human(report: Report) -> str:
    lines = []
    for f in report.findings:
        lines.append(f"{f.rule} {f.entrypoint}: {f.message}")
    for n in report.notes:
        lines.append(f"note: {n}")
    lines.append(f"{len(report.findings)} finding(s) across "
                 f"{len(report.entrypoints)} entrypoint(s)")
    return "\n".join(lines)


def report_to_json(report: Report, prints: Optional[Dict] = None) -> Dict:
    doc = {
        "tool": "graphcheck",
        "version": 1,
        "jax": jax.__version__,
        "entrypoints": report.entrypoints,
        "counts": report.counts,
        "findings": [dataclasses.asdict(f) for f in report.findings],
        "notes": list(report.notes),
    }
    if prints is not None:
        doc["fingerprints"] = prints
    return doc
