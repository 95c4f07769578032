"""CLI: ``python -m slate_tpu.tune`` — run a sweep and persist the
winners into the slatecache tuning table.

    python -m slate_tpu.tune --routine getrf,potrf --sizes 512 \
        --budget-s 60 --cache-dir /path/to/cache

Prints one greppable KEY=VALUE line per fact (the test/CI contract)
plus the winners as JSON.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m slate_tpu.tune",
        description="slatetune sweep: time candidate configs per "
                    "routine×shape and persist winners")
    ap.add_argument("--routine", default="potrf,getrf,geqrf",
                    help="comma-separated routines to sweep")
    ap.add_argument("--sizes", default="512",
                    help="comma-separated matrix sizes")
    ap.add_argument("--nb", default="",
                    help="comma-separated block sizes (default: "
                         "bucket-derived candidates)")
    ap.add_argument("--budget-s", type=float, default=60.0,
                    help="wall budget for the whole sweep")
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cache-dir", default=None,
                    help="table destination (default: the armed "
                         "SLATE_TPU_CACHE_DIR)")
    args = ap.parse_args(argv)

    from .. import obs
    from ..cache import store
    from .sweep import sweep

    obs.metrics.enable()
    if args.cache_dir:
        store.set_cache_dir(args.cache_dir)
    if store.cache_dir() is None:
        print("ERROR=no cache dir (pass --cache-dir or set "
              "SLATE_TPU_CACHE_DIR)", file=sys.stderr)
        return 2

    summary = sweep(
        routines=tuple(r for r in args.routine.split(",") if r),
        sizes=tuple(int(s) for s in args.sizes.split(",") if s),
        budget_s=args.budget_s,
        nbs=tuple(int(b) for b in args.nb.split(",") if b) or None,
        iters=args.iters, warmup=args.warmup, seed=args.seed)

    print(f"TABLE={summary['table']}")
    print(f"TIMED={summary['timed']}")
    print(f"SKIPPED={summary['skipped']}")
    print(f"WINNERS={len(summary['winners'])}")
    print(f"ELAPSED_S={summary['elapsed_s']}")
    print(f"SWEEP_COUNT={obs.metrics.counter_total('tune.sweep')}")
    print(f"WINNER_COUNT={obs.metrics.counter_total('tune.winner')}")
    print(json.dumps(summary["winners"], indent=1, sort_keys=True))
    return 0 if summary["table"] or not summary["winners"] else 1


if __name__ == "__main__":
    from ..cache import place_jax_compile_cache
    place_jax_compile_cache()
    raise SystemExit(main())
