"""``python -m slate_tpu.cache`` — warmup / stats / check / clear.

The serving-side face of slatecache: ``warmup`` AOT-compiles the
bucket table into the on-disk store so a fresh serving process never
pays a cold compile; ``stats`` inspects the store; ``check`` proves
the hit path end-to-end in *this* process (first solve after a warmup
must record ``cache.hit ≥ 1`` and ``cache.miss = 0``, with numerics
verified against a host reference); ``clear`` prunes generations.

Store selection: ``--dir`` > ``SLATE_TPU_CACHE_DIR`` >
``~/.cache/slate_tpu/exec``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

DEFAULT_DIR = os.path.join(os.path.expanduser("~"), ".cache",
                           "slate_tpu", "exec")


def _resolve_dir(args) -> str:
    return (args.dir or os.environ.get("SLATE_TPU_CACHE_DIR")
            or DEFAULT_DIR)


def _parse_grid(spec: str):
    from ..grid import Grid, default_grid
    if not spec:
        return default_grid()
    p, q = (int(x) for x in spec.lower().split("x"))
    return Grid(p, q)


def _dtype(name: str):
    import jax.numpy as jnp
    return {"f32": jnp.float32, "f64": jnp.float64,
            "c64": jnp.complex64, "c128": jnp.complex128}[name]


def _operands(routine: str, N: int, dtype, seed: int = 0):
    """Deterministic host-side operands: SPD for posv, diagonally
    dominant for gesv (so warmup never trips an info != 0 path)."""
    import numpy as np
    rng = np.random.default_rng(seed + N)
    npdt = np.dtype(dtype)
    a = rng.standard_normal((N, N)).astype(npdt)
    if routine == "posv":
        a = (a @ a.T) / N + np.eye(N, dtype=npdt)
    else:
        a += N * np.eye(N, dtype=npdt)
    b = rng.standard_normal((N, 2)).astype(npdt)
    return a, b


def _warm_one(routine: str, N: int, nb, grid, dtype, tier):
    from . import buckets
    from .. import obs
    from ..types import Option
    opts = {Option.TrailingPrecision: tier} if tier else None
    with obs.span("cache.warmup", routine=routine, bucket=str(N)):
        if routine in ("posv", "gesv"):
            a, b = _operands(routine, N, dtype)
            fn = (buckets.bucketed_posv if routine == "posv"
                  else buckets.bucketed_gesv)
            _, info = fn(a, b, nb=nb, grid=grid, opts=opts,
                         table=(N,))
            return int(info)
        import slate_tpu as st
        if routine == "potrf":
            A = st.random_spd(N, nb or buckets.default_nb(N), grid,
                              dtype=dtype, seed=N)
            _, info = st.potrf(A, opts)
        elif routine == "getrf":
            A = st.random_matrix(N, N, nb or buckets.default_nb(N),
                                 grid, dtype, seed=N)
            _, _, info = st.getrf(A, opts)
        elif routine == "geqrf":
            A = st.random_matrix(N, N, nb or buckets.default_nb(N),
                                 grid, dtype, seed=N)
            st.geqrf(A, opts)
            info = 0
        else:
            raise SystemExit(f"unknown routine {routine!r}")
        return int(info) if info is not None else 0


def cmd_warmup(args) -> int:
    from . import buckets, store
    from ..obs import metrics
    store.set_cache_dir(_resolve_dir(args))
    metrics.enable()
    routines = [r.strip() for r in args.routines.split(",") if r.strip()]
    table = (tuple(int(x) for x in args.buckets.split(","))
             if args.buckets else buckets.bucket_table())
    grid = _parse_grid(args.grid)
    dtype = _dtype(args.dtype)
    print(f"slatecache warmup: dir={store.cache_dir()} "
          f"fingerprint={store.fp_digest()} grid={grid.p}x{grid.q} "
          f"dtype={args.dtype}")
    bad = 0
    for routine in routines:
        for N in table:
            m0 = metrics.counter_total("cache.miss")
            h0 = metrics.counter_total("cache.hit")
            info = _warm_one(routine, N, args.nb, grid, dtype,
                             args.tier)
            compiled = int(metrics.counter_total("cache.miss") - m0)
            hits = int(metrics.counter_total("cache.hit") - h0)
            print(f"  {routine:>6} n={N:<7} compiled={compiled:<3} "
                  f"hit={hits:<3} info={info}")
            bad += info != 0
    st = store.stats()
    print(f"store: {st['entries']} executables, "
          f"{st['bytes'] / 1e6:.1f} MB, "
          f"quarantined={st['quarantined']}")
    return 1 if bad else 0


def cmd_stats(args) -> int:
    from . import store
    store.set_cache_dir(_resolve_dir(args))
    st = store.stats()
    if args.json:
        json.dump(st, sys.stdout, indent=1)
        print()
        return 0
    print(f"store dir:    {st['dir']}")
    print(f"fingerprint:  {st['fingerprint']}")
    print(f"entries:      {st['entries']} "
          f"({st['bytes'] / 1e6:.1f} MB)")
    print(f"quarantined:  {st['quarantined']}")
    for g in st["generations"]:
        tag = "current" if g["current"] else "stale"
        print(f"  [{tag}] {g['fingerprint']}: {g['entries']} entries, "
              f"{g['bytes'] / 1e6:.1f} MB")
        for r, n in sorted(g["routines"].items()):
            print(f"      {r}: {n}")
    return 0


def cmd_check(args) -> int:
    """First solve of this process against a warmed store: must be
    all hits, no compiles, and numerically correct."""
    import numpy as np

    from . import buckets, store
    from ..obs import metrics
    store.set_cache_dir(_resolve_dir(args))
    metrics.enable()
    routine = args.routine
    n = args.n
    grid = _parse_grid(args.grid)
    dtype = _dtype(args.dtype)
    a, b = _operands(routine, n, dtype, seed=1)
    fn = (buckets.bucketed_posv if routine == "posv"
          else buckets.bucketed_gesv)
    x, info = fn(a, b, nb=args.nb, grid=grid)
    hits = metrics.counter_total("cache.hit")
    misses = metrics.counter_total("cache.miss")
    resid = float(np.linalg.norm(a @ x - b)
                  / (np.linalg.norm(a) * np.linalg.norm(x) + 1e-30))
    eps = float(np.finfo(np.dtype(dtype)).eps)
    ok = (info == 0 and hits >= 1 and misses == 0
          and resid < 200 * eps * n)
    print(f"slatecache check: routine={routine} n={n} "
          f"bucket={buckets.bucket_for(n)} hit={int(hits)} "
          f"miss={int(misses)} info={info} resid={resid:.2e} "
          f"-> {'OK' if ok else 'FAIL'}")
    if misses:
        print("  (misses mean the store was not warmed for this "
              "routine/bucket/grid/dtype/fingerprint combination)")
    return 0 if ok else 1


def cmd_clear(args) -> int:
    from . import store
    store.set_cache_dir(_resolve_dir(args))
    removed = store.clear(stale_only=args.stale)
    print(f"removed {removed} entries from {store.cache_dir()}"
          f"{' (stale generations only)' if args.stale else ''}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m slate_tpu.cache",
        description="slatecache: AOT executable cache warmup and "
                    "maintenance")
    ap.add_argument("--dir", default=None,
                    help="store root (default: $SLATE_TPU_CACHE_DIR "
                         f"or {DEFAULT_DIR})")
    sub = ap.add_subparsers(dest="cmd", required=True)

    # --dir is accepted on either side of the subcommand (CI writes
    # `warmup --dir ...`); SUPPRESS keeps the global value when the
    # per-subcommand flag is absent
    def add_dir(p):
        p.add_argument("--dir", default=argparse.SUPPRESS,
                       help=argparse.SUPPRESS)

    w = sub.add_parser("warmup", help="AOT-compile the bucket table")
    add_dir(w)
    w.add_argument("--routines", default="posv,gesv",
                   help="comma list: posv,gesv,potrf,getrf,geqrf")
    w.add_argument("--buckets", default="",
                   help="comma list of bucket sizes (default: table / "
                        "$SLATE_TPU_CACHE_BUCKETS)")
    w.add_argument("--nb", type=int, default=None)
    w.add_argument("--grid", default="", help="PxQ (default 1x1-ish)")
    w.add_argument("--dtype", default="f32",
                   choices=["f32", "f64", "c64", "c128"])
    w.add_argument("--tier", default=None,
                   help="TrailingPrecision tier name, e.g. bf16_3x")
    w.set_defaults(fn=cmd_warmup)

    s = sub.add_parser("stats", help="inspect the store")
    add_dir(s)
    s.add_argument("--json", action="store_true")
    s.set_defaults(fn=cmd_stats)

    c = sub.add_parser("check",
                       help="prove the hit path: first solve must be "
                            "hit>=1, miss==0, numerics verified")
    add_dir(c)
    c.add_argument("--routine", default="posv",
                   choices=["posv", "gesv"])
    c.add_argument("--n", type=int, default=97)
    c.add_argument("--nb", type=int, default=None)
    c.add_argument("--grid", default="")
    c.add_argument("--dtype", default="f32",
                   choices=["f32", "f64", "c64", "c128"])
    c.set_defaults(fn=cmd_check)

    cl = sub.add_parser("clear", help="prune the store")
    add_dir(cl)
    cl.add_argument("--stale", action="store_true",
                    help="keep the current fingerprint's generation")
    cl.set_defaults(fn=cmd_clear)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    from . import place_jax_compile_cache
    place_jax_compile_cache()
    sys.exit(main())
