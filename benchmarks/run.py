"""Benchmark driver — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [fig2 fig4 table1 ...]
    REPRO_BENCH_SCALE=small|full  (default small: 1-core CPU budget)

Prints CSV rows; JSON mirrors land in results/bench/.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from repro.hostdev import ensure_compile_cache
    ensure_compile_cache()                  # before the first jax import
    from . import paper_figs as pf
    wanted = [a for a in sys.argv[1:] if not a.startswith("-")]
    t0 = time.time()
    for fn in pf.ALL:
        if wanted and not any(w in fn.__name__ for w in wanted):
            continue
        print(f"# === {fn.__name__} ===", flush=True)
        t1 = time.time()
        fn()
        print(f"# {fn.__name__} took {time.time()-t1:.1f}s", flush=True)
    print(f"# total {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
