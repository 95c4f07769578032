"""slatepulse SLO attainment: ``python -m slate_tpu.obs slo``.

Renders a per-(tenant, slo_class) attainment table from an
``obs.dump()`` metrics snapshot (the same document ``/vars`` serves
live):

* goodput verdict counts from the ``serve.goodput`` counters
  (in_slo | late | shed — the scheduler attributes every terminal
  request to exactly one);
* exact tail latencies (p50/p99) from the log-bucket
  ``serve.latency_s{stage="e2e"}`` histograms — entries for the same
  (tenant, slo_class) are merged bucket-by-bucket, which is exact
  because every log histogram shares one fixed bucket grid;
* **tail attribution**: per-stage p99 from ``serve.stage_s``, and the
  stage whose p99 dominates — "interactive p99 is queue-bound" is a
  table cell, not a spelunking session.

Accepts a raw snapshot, a bench RESULT document (reads
``detail.obs``), or a flight bundle (reads ``metrics``).  ``--json``
emits the machine-readable report for CI gates.
"""

from __future__ import annotations

import json

from . import metrics as _metrics

E2E_SERIES = "serve.latency_s"
STAGE_SERIES = "serve.stage_s"
VERDICTS = ("in_slo", "late", "shed")


def _obs_snapshot(doc: dict) -> dict:
    """Find the metrics snapshot inside whatever document we were
    handed (snapshot / bench RESULT / flight bundle)."""
    if "counters" in doc or "histograms" in doc:
        return doc
    detail = doc.get("detail")
    if isinstance(detail, dict) and isinstance(detail.get("obs"), dict):
        return detail["obs"]
    if isinstance(doc.get("obs"), dict):
        return doc["obs"]                  # serve soak --report files
    if isinstance(doc.get("metrics"), dict):
        return doc["metrics"]
    raise ValueError("no metrics snapshot in document "
                     "(expected obs.dump / bench RESULT / flight "
                     "bundle)")


def _q(buckets: list, q: float) -> float | None:
    if not buckets:
        return None
    return _metrics.quantile_from_buckets(buckets, q)


def attainment(doc: dict) -> dict:
    """The attainment report: one row per (tenant, slo_class) plus a
    ``total`` row.  ``rows[*]["stage_p99_s"]`` maps stage name → exact
    p99 seconds; ``p99_stage`` names the dominating stage."""
    snap = _obs_snapshot(doc)
    keys: set[tuple] = set()
    verd: dict[tuple, dict] = {}
    for c in snap.get("counters", []):
        if c.get("name") != "serve.goodput":
            continue
        lb = c.get("labels") or {}
        k = (str(lb.get("tenant", "default")),
             str(lb.get("slo_class", "standard")))
        keys.add(k)
        v = str(lb.get("verdict", ""))
        if v in VERDICTS:
            d = verd.setdefault(k, dict.fromkeys(VERDICTS, 0))
            d[v] += int(c.get("value", 0))

    e2e: dict[tuple, list] = {}
    stages: dict[tuple, dict[str, list]] = {}
    exact = True
    for h in snap.get("histograms", []):
        name, lb = h.get("name"), h.get("labels") or {}
        if name not in (E2E_SERIES, STAGE_SERIES):
            continue
        k = (str(lb.get("tenant", "default")),
             str(lb.get("slo_class", "standard")))
        if name == E2E_SERIES:
            if lb.get("stage") != "e2e":
                continue            # dispatch-only walls: not e2e
        if h.get("kind") != "log" or h.get("buckets") is None:
            exact = False           # reservoir data snuck in
            continue
        keys.add(k)
        if name == E2E_SERIES:
            e2e[k] = _metrics.merge_log_buckets(
                [e2e.get(k, []), h["buckets"]])
        else:
            st = str(lb.get("stage", "?"))
            sk = stages.setdefault(k, {})
            sk[st] = _metrics.merge_log_buckets(
                [sk.get(st, []), h["buckets"]])

    rows = []
    for k in sorted(keys):
        v = verd.get(k, dict.fromkeys(VERDICTS, 0))
        done = sum(v.values())
        sp = {st: _q(b, 0.99) for st, b in
              sorted(stages.get(k, {}).items())}
        cand = [(p, st) for st, p in sp.items() if p is not None]
        dominant = max(cand)[1] if cand else None
        rows.append({
            "tenant": k[0], "slo_class": k[1],
            "requests": done, **v,
            "goodput_frac": (v["in_slo"] / done) if done else 0.0,
            "p50_s": _q(e2e.get(k, []), 0.50),
            "p99_s": _q(e2e.get(k, []), 0.99),
            "p99_stage": dominant,
            "stage_p99_s": sp,
        })
    tot = dict.fromkeys(VERDICTS, 0)
    for r in rows:
        for v in VERDICTS:
            tot[v] += r[v]
    done = sum(tot.values())
    all_e2e = _metrics.merge_log_buckets(list(e2e.values()))
    return {"rows": rows,
            "total": {"requests": done, **tot,
                      "goodput_frac": (tot["in_slo"] / done)
                      if done else 0.0,
                      "p50_s": _q(all_e2e, 0.50),
                      "p99_s": _q(all_e2e, 0.99)},
            "exact": exact}


def _fmt_s(v) -> str:
    return "-" if v is None else f"{v * 1e3:9.3f}ms"


def format_table(report: dict) -> str:
    lines = ["slatepulse SLO attainment "
             f"({'exact log-bucket' if report.get('exact') else 'MIXED KINDS'})",
             f"{'tenant':<10} {'slo_class':<12} {'reqs':>6} "
             f"{'in_slo':>7} {'late':>5} {'shed':>5} {'goodput':>8} "
             f"{'p50':>11} {'p99':>11}  p99-dominant-stage"]
    for r in report["rows"]:
        dom = r["p99_stage"] or "-"
        if r["p99_stage"] and r["stage_p99_s"].get(r["p99_stage"]) \
                is not None:
            dom += f" ({_fmt_s(r['stage_p99_s'][r['p99_stage']]).strip()})"
        lines.append(
            f"{r['tenant']:<10} {r['slo_class']:<12} "
            f"{r['requests']:>6} {r['in_slo']:>7} {r['late']:>5} "
            f"{r['shed']:>5} {r['goodput_frac']:>8.3f} "
            f"{_fmt_s(r['p50_s']):>11} {_fmt_s(r['p99_s']):>11}  {dom}")
    t = report["total"]
    lines.append(
        f"{'TOTAL':<10} {'':<12} {t['requests']:>6} {t['in_slo']:>7} "
        f"{t['late']:>5} {t['shed']:>5} {t['goodput_frac']:>8.3f} "
        f"{_fmt_s(t['p50_s']):>11} {_fmt_s(t['p99_s']):>11}")
    return "\n".join(lines)


def add_cli(sub) -> None:
    p = sub.add_parser(
        "slo", help="per-(tenant, slo_class) SLO attainment table "
                    "with p99 tail attribution")
    p.add_argument("path", help="obs.dump metrics JSON, bench RESULT, "
                                "or flight bundle")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit the machine-readable report")


def cli_run(args) -> int:
    import sys
    try:
        with open(args.path) as f:
            doc = json.load(f)
        report = attainment(doc)
    except (OSError, json.JSONDecodeError, ValueError) as e:
        print(f"cannot read {args.path}: {e}", file=sys.stderr)
        return 1
    if args.as_json:
        print(json.dumps(report, indent=1, default=str))
    else:
        print(format_table(report))
    return 0
