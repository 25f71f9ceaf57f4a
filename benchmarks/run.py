"""Benchmark orchestrator — one module per paper table/figure.

  tables          Tables 2-4 (per-service-provider metrics, 4 systems)
  fig9_11_params  Figs 9-11 (B/R parameter sweeps)
  fig12_14        Figs 12-14 (provider totals, peaks, adjustment overhead)
  tco             §4.5.5 TCO (DCS vs EC2-priced SSP)

``python -m benchmarks.run [name ...]`` runs all (or the named) benchmarks.
"""
from __future__ import annotations

import sys
import time

from benchmarks import fig9_11_params, fig12_14_provider, tables, tco

BENCHES = {
    "tables": tables.main,
    "fig9_11_params": fig9_11_params.main,
    "fig12_14": fig12_14_provider.main,
    "tco": tco.main,
}


def main() -> None:
    names = sys.argv[1:] or list(BENCHES)
    for name in names:
        t0 = time.perf_counter()
        print(f"\n{'=' * 72}\n# benchmark: {name}\n{'=' * 72}")
        BENCHES[name]()
        print(f"[{name}: {time.perf_counter() - t0:.1f}s]")


if __name__ == "__main__":
    main()
