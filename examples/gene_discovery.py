"""Gene-regulatory discovery with interventions (paper §4.1, Table 1).

    PYTHONPATH=src python examples/gene_discovery.py [--full]

Synthetic Perturb-seq-like data (the real Perturb-CITE-seq is not available
offline): single-gene interventions, 80/20 train/held-out split,
DirectLiNGAM + Stein-VI scoring of interventional NLL / MAE.
"""

import argparse

from benchmarks.bench_gene import run


def main():
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale d=961 (slow on CPU)")
    args = ap.parse_args()
    enable_compile_cache()
    results = run(quick=not args.full)
    print("\nSummary (lower is better):")
    for method, r in results.items():
        print(f"  {method:14s} I-NLL={r['inll']:.3f}  I-MAE={r['imae']:.3f}")


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    main()
