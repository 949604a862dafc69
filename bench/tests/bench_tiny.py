"""Small sizes at which the benchmark's cells run on the CPU in tests.

Every path of a chip run is taken: set-up, window, trace reading,
collection, reference; the points' dimension and count are cut so that
the CPU's interpreted kernels finish in seconds.
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CLUSTER = {"config": {"cluster": {"points": 5000, "dim": 8,
                                  "components": 4, "k": 64,
                                  "fit_chunk_log2": 11}},
           "traffic": {"centers": [3, 5], "n_cand": 8, "rounds": 2,
                       "check_sets": 8}}

CELLS = ("metric_cluster.search",)


def run(cell: str, seed: int = 2**33 + 5, seconds: float = 1.0,
        trace: bool = False, control: bool = False, readings: bool = False,
        root: str = ROOT, extra=None):
    import run_cell
    return run_cell.run(cell, seed, seconds, trace, rehearse=True,
                        overrides=CLUSTER if extra is None else extra,
                        control=control, readings=readings, root=root)
