"""The paper's deployment sizes: (name, m samples, d variables).

``chip_smoke.py`` runs DirectLiNGAM at ``lingam-gene-964`` and the
serving engine and VarLiNGAM at ``varlingam-stocks-487``;
``benchmarks/bench_bootstrap.py`` reads the table as well.
"""

from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class LingamWorkload:
    name: str
    m: int           # samples
    d: int           # variables
    description: str


WORKLOADS: Dict[str, LingamWorkload] = {
    w.name: w
    for w in [
        LingamWorkload(
            "lingam-gene-964", 65_164, 964,
            "Perturb-CITE-seq co-culture dimensions (paper §4.1)",
        ),
        LingamWorkload(
            "lingam-1m-100", 1_000_000, 100,
            "paper Fig. 2 cell: '7 hours on a CPU' at 1M x 100",
        ),
        LingamWorkload(
            "lingam-1m-2048", 1_000_000, 2_048,
            "beyond-paper scale target (hillclimb cell C)",
        ),
        LingamWorkload(
            "varlingam-stocks-487", 4_000, 487,
            "S&P 500 VAR-residual ordering (paper §4.2)",
        ),
    ]
}
