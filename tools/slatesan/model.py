"""Finding/report model shared by the analyses, the jitcache hook,
and the CLI.

A :class:`SanFinding` is anchored to an *equation path*: the chain of
sub-jaxpr labels from the top-level jaxpr down to the equation
(``jit:potrf/shard_map/eqn[12]``), so a finding names the exact eqn
in the exact sub-program — the IR analog of slatelint's
``path:line:col``.  :class:`SanReport` is the per-program verdict the
jitcache hook persists into a slatecache entry's ``meta.json`` and
restores on disk hits; it round-trips through plain JSON dicts.
"""

from __future__ import annotations

from dataclasses import dataclass, field

# Canonical analysis names, in report order.  The first four inspect
# traced jaxprs; "schedule" inspects host-level plans/DAGs
# (tools/slatesan/schedule.py) and is marked skipped on jaxpr reports.
ANALYSES = ("collective", "donation", "precision", "vmem", "schedule")

SAN_VERSION = 1


@dataclass(frozen=True)
class SanFinding:
    """One verifier violation at an equation in a traced program."""
    analysis: str          # one of ANALYSES
    path: str              # sub-jaxpr chain, e.g. "jit:potrf/shard_map"
    eqn: int               # eqn index within that sub-jaxpr (-1 = whole)
    primitive: str         # primitive at the anchor eqn ("" = none)
    message: str
    routine: str = ""      # filled in by the recording layer

    def format(self) -> str:
        where = f"{self.path}/eqn[{self.eqn}]" if self.eqn >= 0 else self.path
        head = f"{self.routine}: " if self.routine else ""
        prim = f" ({self.primitive})" if self.primitive else ""
        return f"{head}[{self.analysis}] {where}{prim}: {self.message}"

    def to_dict(self) -> dict:
        return {"analysis": self.analysis, "path": self.path,
                "eqn": self.eqn, "primitive": self.primitive,
                "message": self.message, "routine": self.routine}

    @classmethod
    def from_dict(cls, d: dict) -> "SanFinding":
        return cls(analysis=d.get("analysis", "?"),
                   path=d.get("path", ""), eqn=int(d.get("eqn", -1)),
                   primitive=d.get("primitive", ""),
                   message=d.get("message", ""),
                   routine=d.get("routine", ""))


@dataclass
class SanReport:
    """Per-program verdict: findings plus which analyses ran.

    ``skipped`` lists analyses that could not apply (e.g. precision
    with no tier static) — distinct from "ran and found nothing".
    """
    findings: list[SanFinding] = field(default_factory=list)
    skipped: list[str] = field(default_factory=list)
    tier: str | None = None

    @property
    def ok(self) -> bool:
        return not self.findings

    def verdict_for(self, analysis: str) -> str:
        if analysis in self.skipped:
            return "skip"
        if any(f.analysis == analysis for f in self.findings):
            return "finding"
        return "ok"

    def counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for f in self.findings:
            out[f.analysis] = out.get(f.analysis, 0) + 1
        return out

    def to_dict(self) -> dict:
        return {"version": SAN_VERSION,
                "verdict": "ok" if self.ok else "fail",
                "tier": self.tier,
                "skipped": list(self.skipped),
                "counts": self.counts(),
                "findings": [f.to_dict() for f in self.findings]}

    @classmethod
    def from_dict(cls, d: dict) -> "SanReport":
        return cls(findings=[SanFinding.from_dict(x)
                             for x in d.get("findings", [])],
                   skipped=list(d.get("skipped", [])),
                   tier=d.get("tier"))
