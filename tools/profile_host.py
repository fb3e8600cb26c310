"""Host-path microprofiler: where does each pod's admission + commit
time go? Runs entirely on CPU with the C++ planes backend so the device
side is cheap and the HOST costs (round 5's notes: commit ~70µs/pod,
admission ~117µs/pod) dominate and are attributable.

Usage:  python tools/profile_host.py [--nodes 1000] [--pods 10000] [--cprofile]
"""

from __future__ import annotations

import argparse
import cProfile
import os
import pstats
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("KTPU_SOLVER", "cpp")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubernetes_tpu.harness import make_workload, run_workload  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=1000)
    ap.add_argument("--pods", type=int, default=10000)
    ap.add_argument("--cprofile", action="store_true")
    ap.add_argument("--sort", default="cumulative")
    ap.add_argument("--limit", type=int, default=45)
    args = ap.parse_args()

    ops = make_workload("SchedulingBasic", nodes=args.nodes, init_pods=0,
                        measure_pods=args.pods)

    def run():
        return run_workload("profile", ops, use_batch=True,
                            max_batch=8192, wait_timeout=600,
                            progress=lambda m: print(m, file=sys.stderr))

    if args.cprofile:
        prof = cProfile.Profile()
        t0 = time.time()
        result = prof.runcall(run)
        wall = time.time() - t0
        stats = pstats.Stats(prof)
        stats.sort_stats(args.sort).print_stats(args.limit)
    else:
        t0 = time.time()
        result = run()
        wall = time.time() - t0
    print(f"pods/s={result.pods_per_second:.0f} wall={wall:.1f}s "
          f"measured={result.measured_pods}", file=sys.stderr)


if __name__ == "__main__":
    main()
