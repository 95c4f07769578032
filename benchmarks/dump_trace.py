#!/usr/bin/env python3
"""Look at a profiler trace by hand: planes, lines, event counts, the
names that take most time and every stat key seen.

    python benchmarks/dump_trace.py <file.xplane.pb> [--events 15]

Needs nothing but jax. Use it before changing ``harness/trace_reduce.py``.
"""

from __future__ import annotations

import argparse
import collections
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--events", type=int, default=15)
    args = ap.parse_args(argv)
    import jax
    data = jax.profiler.ProfileData.from_file(args.path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            by_name = collections.Counter()
            count = collections.Counter()
            keys = collections.Counter()
            for ev in events:
                by_name[ev.name] += ev.duration_ns
                count[ev.name] += 1
                keys.update(k for k, _ in ev.stats)
            start = min(ev.start_ns for ev in events)
            end = max(ev.start_ns + ev.duration_ns for ev in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{len(by_name)} names, {start:.0f}..{end:.0f} ns")
            print(f"    stat keys: {dict(keys)}")
            for name, ns in by_name.most_common(args.events):
                print(f"    {ns / 1e6:12.3f} ms  x{count[name]:<5d} "
                      f"{name[:100]}")
            first = events[0]
            print(f"    first event: {first.name[:80]!r} "
                  f"{dict(first.stats)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
