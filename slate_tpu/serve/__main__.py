"""``python -m slate_tpu.serve`` — warmup + soak for the serving layer.

``warmup`` AOT-compiles one executable per (routine × bucket ×
batch-rung × tier) into the on-disk store — the serving sibling of
``python -m slate_tpu.cache warmup`` (which warms the single-matrix
bucketed drivers) and the step a deployment runs before opening the
request socket, so no live request ever pays a compile.  ``--dry-run``
lists the executable keys without compiling (deployment sizing).

``soak`` (slatepulse) runs the seeded open-loop load generator
against a live Scheduler — the CI ``soak-smoke`` job's entry point:
deterministic workload, goodput/stage accounting on the metrics
registry (scrapeable live via ``SLATE_TPU_METRICS_PORT``), an SLO
attainment report written as JSON (``--report``), and a nonzero exit
on queue collapse (invert with ``--expect-collapse`` for the overload
leg).

Store selection matches the cache CLI: ``--dir`` >
``SLATE_TPU_CACHE_DIR`` > the user default.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys

# shared store/operand plumbing with the cache CLI
from ..cache.__main__ import DEFAULT_DIR, _dtype, _operands, _resolve_dir


def _parse_ints(spec: str, what: str) -> tuple[int, ...]:
    try:
        vals = tuple(int(x) for x in spec.replace(";", ",").split(",")
                     if x.strip())
        if not vals or any(v <= 0 for v in vals):
            raise ValueError(spec)
        return vals
    except ValueError:
        raise SystemExit(f"bad --{what} list: {spec!r}") from None


def _rung_list(spec: str) -> tuple[int, ...]:
    from .ragged import batch_rungs
    vals = _parse_ints(spec, "batches")
    bad = [v for v in vals if batch_rungs(v) != [v]]
    if bad:
        raise SystemExit(
            f"--batches must be power-of-two ladder rungs, got {bad}")
    return vals


def cmd_warmup(args) -> int:
    from .. import obs
    from ..cache import buckets, store
    from ..obs import metrics
    from ..types import Option
    from . import batched
    import numpy as np

    routines = [r.strip() for r in args.routines.split(",") if r.strip()]
    for r in routines:
        if r not in ("posv", "gesv"):
            raise SystemExit(f"unknown routine {r!r} (posv, gesv)")
    table = (_parse_ints(args.buckets, "buckets") if args.buckets
             else buckets.bucket_table())
    rungs = _rung_list(args.batches)
    tier = args.tier
    keys = [(routine, N, b) for routine in routines for N in table
            for b in rungs]

    if args.nrhs <= 0:
        raise SystemExit(f"--nrhs must be positive, got {args.nrhs}")

    if args.dry_run:
        print(f"slateserve warmup (dry run): {len(keys)} executables")
        for routine, N, b in keys:
            nb = args.nb or buckets.default_nb(N)
            print(f"  serve.{routine} bucket={N:<7} batch={b:<4} "
                  f"nb={nb:<4} tier={tier or 'default'} "
                  f"dtype={args.dtype} nrhs={args.nrhs}")
        return 0

    store.set_cache_dir(_resolve_dir(args))
    metrics.enable()
    dtype = _dtype(args.dtype)
    opts = {Option.TrailingPrecision: tier} if tier else None
    print(f"slateserve warmup: dir={store.cache_dir()} "
          f"fingerprint={store.fp_digest()} dtype={args.dtype}")
    bad = 0
    for routine, N, b in keys:
        m0 = metrics.counter_total("cache.miss")
        h0 = metrics.counter_total("cache.hit")
        ops = [_operands(routine, N, dtype, seed=i) for i in range(b)]
        stack_a = np.stack([a for a, _ in ops])
        # executables are shape-keyed, values irrelevant: tile/crop the
        # canonical 2-column rhs to the serving traffic's nrhs so the
        # warmed program matches what live dispatch will request
        reps = (args.nrhs + 1) // 2
        stack_b = np.stack(
            [np.concatenate([rhs] * reps, axis=1)[:, :args.nrhs]
             for _, rhs in ops])
        with obs.span("serve.warmup", routine=routine, bucket=str(N),
                      b=b):
            if routine == "posv":
                _, _, info = batched.batched_posv(stack_a, stack_b,
                                                  opts, nb=args.nb)
            else:
                _, _, _, info = batched.batched_gesv(stack_a, stack_b,
                                                     opts, nb=args.nb)
        worst = int(max(abs(int(i)) for i in np.asarray(info)))
        compiled = int(metrics.counter_total("cache.miss") - m0)
        hits = int(metrics.counter_total("cache.hit") - h0)
        print(f"  {routine:>6} bucket={N:<7} batch={b:<4} "
              f"compiled={compiled:<3} hit={hits:<3} info={worst}")
        bad += worst != 0
    st = store.stats()
    print(f"store: {st['entries']} executables, "
          f"{st['bytes'] / 1e6:.1f} MB, "
          f"quarantined={st['quarantined']}")
    return 1 if bad else 0


def cmd_soak(args) -> int:
    import json

    from .. import obs
    from ..obs import metrics
    from ..obs import slo as _slo
    from . import loadgen
    from .sched import make_scheduler

    metrics.enable()
    table = _parse_ints(args.buckets, "buckets")
    mix = [dataclasses.replace(c, n_lo=args.n_lo,
                               n_hi=min(args.n_hi, max(table)))
           for c in loadgen.DEFAULT_MIX]
    mode = {"continuous": "flow"}.get(args.scheduler, args.scheduler)
    kwargs = dict(table=table, nb=args.nb, max_rung=args.max_rung,
                  max_depth=args.max_depth, slo_s=args.slo_s)
    if mode == "drain" and args.window_s is not None:
        kwargs["window_s"] = args.window_s
    s = make_scheduler(mode, **kwargs)
    work = loadgen.generate(args.requests, args.rate, mix=mix,
                            seed=args.seed)
    print(f"slatepulse soak: {args.requests} requests @ "
          f"{args.rate:g} req/s (seed={args.seed}, "
          f"table={table}, time_scale={args.time_scale:g}, "
          f"scheduler={mode})")
    try:
        rep = loadgen.run_soak(
            s, work, time_scale=args.time_scale,
            poll_every=args.poll_every, watch_every=args.watch_every,
            collapse_windows=args.collapse_windows,
            collapse_min_depth=args.collapse_min_depth)
    finally:
        if hasattr(s, "stop"):
            s.stop()
    d = rep.as_dict()
    d["scheduler"] = mode
    print(f"SOAK scheduler={mode}")
    for k in ("requests", "submitted", "served", "in_slo", "late",
              "shed", "unresolved", "wall_s", "goodput_frac"):
        v = d[k]
        print(f"SOAK {k}={v:.4f}" if isinstance(v, float)
              else f"SOAK {k}={v}")
    print(f"SOAK collapse={'yes' if rep.collapse else 'no'}")
    if rep.collapse:
        print(f"SOAK collapse_reason={rep.collapse.reason}")
    slo_report = _slo.attainment(obs.dump())
    print(_slo.format_table(slo_report))
    if args.report:
        with open(args.report, "w") as f:
            json.dump({"soak": d, "slo": slo_report,
                       "obs": obs.dump()}, f, indent=1, default=str)
        print(f"SOAK report={args.report}")
    collapsed = rep.collapse is not None
    if args.expect_collapse:
        return 0 if collapsed else 1
    return 1 if collapsed else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m slate_tpu.serve",
        description="slateserve: batched serving warmup")
    ap.add_argument("--dir", default=None,
                    help="store root (default: $SLATE_TPU_CACHE_DIR "
                         f"or {DEFAULT_DIR})")
    sub = ap.add_subparsers(dest="cmd", required=True)

    def add_dir(p):
        p.add_argument("--dir", default=argparse.SUPPRESS,
                       help=argparse.SUPPRESS)

    w = sub.add_parser(
        "warmup",
        help="AOT-compile the (routine x bucket x batch-rung) cross "
             "product")
    add_dir(w)
    w.add_argument("--routines", default="posv,gesv",
                   help="comma list: posv,gesv")
    w.add_argument("--buckets", default="",
                   help="comma list of bucket sizes (default: table / "
                        "$SLATE_TPU_CACHE_BUCKETS)")
    w.add_argument("--batches", default="1,2,4,8",
                   help="comma list of batch rungs (powers of two)")
    w.add_argument("--nb", type=int, default=None)
    w.add_argument("--dtype", default="f32",
                   choices=["f32", "f64", "c64", "c128"])
    w.add_argument("--nrhs", type=int, default=2,
                   help="RHS columns per instance (default 2; serving "
                        "traffic from the loadgen mix uses 1)")
    w.add_argument("--tier", default=None,
                   help="TrailingPrecision tier name, e.g. bf16_3x")
    w.add_argument("--dry-run", action="store_true",
                   help="list executable keys without compiling")
    w.set_defaults(fn=cmd_warmup)

    sk = sub.add_parser(
        "soak", help="seeded open-loop SLO soak (slatepulse)")
    sk.add_argument("--requests", type=int, default=2000)
    sk.add_argument("--rate", type=float, default=400.0,
                    help="mean arrival rate, req/s (default 400)")
    sk.add_argument("--seed", type=int, default=0)
    sk.add_argument("--buckets", default="8,16,32",
                    help="bucket table (default 8,16,32)")
    sk.add_argument("--nb", type=int, default=4)
    sk.add_argument("--n-lo", type=int, default=4, dest="n_lo")
    sk.add_argument("--n-hi", type=int, default=32, dest="n_hi")
    sk.add_argument("--max-rung", type=int, default=16)
    sk.add_argument("--max-depth", type=int, default=4096)
    sk.add_argument("--scheduler", default="drain",
                    choices=["drain", "flow", "continuous"],
                    help="drain = windowed microbatch queues; "
                         "flow/continuous = slateflow persistent "
                         "continuous-batching service")
    sk.add_argument("--window-s", type=float, default=None,
                    dest="window_s",
                    help="drain-mode microbatch window seconds "
                         "(default: scheduler default)")
    sk.add_argument("--slo-s", type=float, default=60.0,
                    help="per-bucket latency SLO seconds (default 60)")
    sk.add_argument("--time-scale", type=float, default=0.0,
                    help="0 = submit as fast as possible (CI mode); "
                         "1 = real-time schedule")
    sk.add_argument("--poll-every", type=int, default=16)
    sk.add_argument("--watch-every", type=int, default=64)
    sk.add_argument("--collapse-windows", type=int, default=4)
    sk.add_argument("--collapse-min-depth", type=int, default=64)
    sk.add_argument("--report", default="",
                    help="write soak + SLO attainment JSON here")
    sk.add_argument("--expect-collapse", action="store_true",
                    help="invert the exit gate (overload legs)")
    sk.set_defaults(fn=cmd_soak)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    from ..cache import place_jax_compile_cache
    place_jax_compile_cache()
    sys.exit(main())
