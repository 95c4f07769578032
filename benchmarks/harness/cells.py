"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell is three small JSON files: its configuration (``file`` of the
``configs`` entry), its traffic mix (``benchmarks/traffic/<traffic>.json``)
and its own file (``benchmarks/workloads/<name>.json``: the limit of the
correctness check, why, who). Nothing here knows a cell by name.
"""

from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")


def read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def contract() -> dict:
    return read_json(os.path.join(ROOT, "BENCHMARK.json"))


def load_cell(name: str, n: int | None = None,
              nb: int | None = None) -> dict:
    """Everything about cell ``name``: ``{"name", "chips", "config":
    {...}, "traffic": {...}, "cell": {...}, "end_to_end": [...],
    "per_layer": [...]}``; the metric lists hold the contract's entries
    that this cell reports. ``n``/``nb`` shrink it for a rehearsal."""
    bench = contract()
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {[w['name'] for w in bench['workloads']]}")
    entry = entries[0]
    config = next(c for c in bench["configs"]
                  if c["name"] == entry["config"])

    def reports(metric):
        return name in metric.get("workloads", [name])

    sizes = read_json(os.path.join(ROOT, config["file"]))
    sizes.update({k: v for k, v in (("n", n), ("nb", nb)) if v})
    return {
        "name": name,
        "chips": entry["chips"],
        "config": sizes,
        "traffic": read_json(os.path.join(BENCH, "traffic",
                                          entry["traffic"] + ".json")),
        "cell": read_json(os.path.join(BENCH, "workloads",
                                       name + ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        "per_layer": [m for m in bench["per_layer"] if reports(m)],
    }
