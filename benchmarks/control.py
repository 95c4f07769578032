#!/usr/bin/env python3
"""Read the numbers a cell's correctness limit is set from, on the chip.

    python benchmarks/control.py --workload <cell> --seeds 12 \
        --tiers bf16_3x,mxu_bf16

One process. For the configuration's own precision tier and for each
lower tier named (the program's public ``Option.TrailingPrecision``:
``bf16_3x`` is ``lax.Precision.HIGH``, three passes; ``mxu_bf16`` is
one bf16 pass), and for each of ``--seeds`` seeds: the cell's operands
at the cell's own size, the cell's own public call, the plain backward
error of its X — the number ``run.py`` compares. Prints every reading
in units of eps = 2^-24, then the largest sound reading, each control's
smallest, and whether the cell's ``tol_eps`` separates them.

Not part of a benchmark run. The limit in the cell's file is set by
hand from this output (PERF.md section 2 holds the readings).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_400_000_000)
    ap.add_argument("--tiers", default="bf16_3x,mxu_bf16")
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument("--n", type=int)
    ap.add_argument("--nb", type=int)
    args = ap.parse_args(argv)

    from benchmarks.harness.cells import load_cell
    from benchmarks.run import find_devices
    spec = load_cell(args.workload, n=args.n, nb=args.nb)
    devices, device, _ = find_devices(spec, args.rehearse_on_cpu)

    import importlib
    import jax
    from slate_tpu.cache import place_jax_compile_cache
    from benchmarks.harness.check import EPS
    place_jax_compile_cache()
    traffic = importlib.import_module(
        f"benchmarks.traffic.{spec['traffic']['kind']}")
    sound = spec["config"]["tier"]
    readings = {}
    for tier in [sound] + [t for t in args.tiers.split(",") if t]:
        readings[tier] = []
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            session = traffic.open_session(spec, devices, seed)
            if tier == sound:       # the timed call itself
                session.warm_up()
                session.drive(calls=1)
                answers = {"warm_up": session.first_x,
                           "last": session.last[0]}
            else:
                answers = {"control": session.lower_precision(tier)}
            errors = session.errors_of(answers)
            worst = {norm: max(e[norm] for e in errors.values()) / EPS
                     for norm in ("inf", "fro")}
            readings[tier].append(worst)
            print(json.dumps({"tier": tier, "seed": seed,
                              "in_eps": worst, **device}), flush=True)
            del session, answers
    limits = {"inf": spec["cell"]["tol_eps"],
              "fro": spec["cell"]["tol_fro_eps"]}
    summary = {"cell": spec["name"], **device, "limits_eps": limits,
               "seeds": args.seeds}
    for tier, vals in readings.items():
        row = summary.setdefault(tier, {})
        for norm, limit in limits.items():
            got = [v[norm] for v in vals]
            row[norm] = {"min_eps": min(got), "max_eps": max(got),
                         "all_within": all(g <= limit for g in got),
                         "all_beyond": all(g > limit for g in got)}
        row["role"] = "sound" if tier == sound else "control"
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
