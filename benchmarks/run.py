"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines (see each module's docstring
for the paper claim it validates).

    PYTHONPATH=src python -m benchmarks.run [--only fig11,fig13] [--list]
    REPRO_BENCH_SCALE=full for the larger corpora.

``--only`` takes EXACT module names; append ``*`` for explicit prefix
matching (``--only 'fig1*'`` runs fig11..fig17 — a bare ``fig1`` used to,
silently). ``--list`` prints the registered modules and exits.

A module that raises prints a ``<module>/FAILED`` line and the run goes on
to the next one; the script then exits 1.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

MODULES = [
    "fig9_nand_tradeoff",
    "gap_compression",
    "fig11_recall_qps",
    "fig12_hw_comparison",
    "fig13_ablation",
    "fig14_traffic",
    "fig15_hotnodes",
    "fig16_queues",
    "fig17_biterror",
    "streaming_bench",
    "sharded_bench",
    "beam_bench",
    "filtered_bench",
    "planner_bench",
    "serving_bench",
    "continuous_bench",
    "kernels_bench",
    "roofline_bench",
    "build_bench",
]

# runs in its own subprocess (needs 512 host devices), not importable here
SUBPROCESS_MODULES = ["proxima_dryrun"]


def selected(modname: str, only: list[str]) -> bool:
    """Exact-name match, with ``pattern*`` as the explicit prefix opt-in."""
    for o in only:
        if o.endswith("*"):
            if modname.startswith(o[:-1]):
                return True
        elif modname == o:
            return True
    return False


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated module names (exact; 'prefix*' "
                         "for prefix matching)")
    ap.add_argument("--list", action="store_true",
                    help="print registered benchmark modules and exit")
    args = ap.parse_args()
    only = [s.strip() for s in args.only.split(",") if s.strip()]

    if args.list:
        for modname in MODULES + SUBPROCESS_MODULES:
            print(modname)
        return 0

    unknown = [o for o in only
               if not any(selected(m, [o]) for m in MODULES + SUBPROCESS_MODULES)]
    if unknown:
        print(f"# --only matched nothing for: {', '.join(unknown)} "
              f"(see --list; use 'prefix*' for prefix matching)",
              file=sys.stderr)

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    failed = []
    print("name,us_per_call,derived")
    for modname in MODULES:
        if only and not selected(modname, only):
            continue
        t0 = time.perf_counter()
        try:
            mod = __import__(f"benchmarks.{modname}", fromlist=["main"])
            mod.main(out=print)
            print(f"# {modname} done in {time.perf_counter()-t0:.1f}s", file=sys.stderr)
        except Exception:
            failed.append(modname)
            print(f"{modname}/FAILED,0.0,{traceback.format_exc().splitlines()[-1]}")
            traceback.print_exc(file=sys.stderr)

    # distributed-search dry-run needs 512 host devices -> own process. It
    # is a CPU compile by design, and this process may hold the chip.
    if not only or selected("proxima_dryrun", only):
        import os
        import subprocess

        t0 = time.perf_counter()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.proxima_dryrun"],
            capture_output=True, text=True, timeout=900, env=env,
        )
        for line in r.stdout.splitlines():
            if line.startswith("proxima-dist"):
                print(line)
        if r.returncode != 0:
            failed.append("proxima_dryrun")
            print(f"proxima_dryrun/FAILED,0.0,rc={r.returncode}")
            print(r.stderr[-1500:], file=sys.stderr)
        else:
            print(f"# proxima_dryrun done in {time.perf_counter()-t0:.1f}s",
                  file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
