"""Benchmark harness entrypoint: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows plus a claim summary block
and a per-bench timing-spread table (wall seconds this invocation, plus
the ``timing`` stability block each micro-timing artifact recorded —
spread > 0.5 is flagged UNSTABLE, matching ``scripts/
check_bench_schema.py``).

  PYTHONPATH=src python -m benchmarks.run [--only figNN] [--force]

Exits non-zero when any selected bench raises, with the failing bench
names (and their tracebacks on stderr) listed at the end — a partial
``results/bench/`` directory is a failure, not a quiet success.
"""
import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

#: artifacts whose ``timing.spread`` exceeds this are flagged UNSTABLE
#: (the same threshold ``scripts/check_bench_schema.py`` warns at)
SPREAD_WARN = 0.5


def timing_spread_table(walls):
    """Rows of the per-bench timing summary: wall seconds measured this
    invocation joined with the ``timing`` block (repeats + worst
    spread) the bench's cached artifact recorded, when it has one.
    ``walls`` is ``[(bench_name, wall_seconds), ...]``."""
    from benchmarks.common import RESULTS_DIR
    timing = {}
    if os.path.isdir(RESULTS_DIR):
        for fn in sorted(os.listdir(RESULTS_DIR)):
            if not fn.endswith(".json"):
                continue
            try:
                with open(os.path.join(RESULTS_DIR, fn)) as f:
                    doc = json.load(f)
            except Exception:
                continue
            if isinstance(doc, dict) and isinstance(doc.get("timing"),
                                                    dict):
                timing[fn[:-5]] = doc["timing"]
    rows = []
    for name, wall in walls:
        key = name.replace("bench_", "")
        t = timing.get(key, {})
        spread = t.get("spread")
        flag = ("UNSTABLE" if spread is not None
                and spread > SPREAD_WARN else "")
        rows.append((name, wall, t.get("repeats"), spread, flag))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on bench name")
    ap.add_argument("--force", action="store_true",
                    help="ignore cached results")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks.figures import ALL_BENCHES

    print("name,us_per_call,derived")
    claims = []
    failures = []
    walls = []
    for bench in ALL_BENCHES:
        name = bench.__name__
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        try:
            rows, derived = bench(force=args.force)
        except Exception as e:  # noqa: BLE001
            traceback.print_exc(file=sys.stderr)
            rows, derived = [f"{name},0.00,ERROR {type(e).__name__}: {e}"], \
                f"ERROR: {e}"
            failures.append(name)
        walls.append((name, time.time() - t0))
        for r in rows:
            print(r, flush=True)
        claims.append((name, derived))
    print("\n=== claim summary ===")
    for n, d in claims:
        print(f"{n:36s} {d}")
    print("\n=== timing spread ===")
    print(f"{'bench':36s} {'wall_s':>8s} {'repeats':>8s} "
          f"{'spread':>8s}")
    for n, wall, repeats, spread, flag in timing_spread_table(walls):
        rep = str(repeats) if repeats is not None else "-"
        spr = f"{spread:.3f}" if spread is not None else "-"
        print(f"{n:36s} {wall:8.1f} {rep:>8s} {spr:>8s} {flag}")
    if failures:
        print(f"\nFAILED benches ({len(failures)}): "
              + ", ".join(failures), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
