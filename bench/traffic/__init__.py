"""Traffic of the benchmark: the loop-nest trace generators
(:mod:`loopnests`, and one file each under ``nests/``), the one
generator that turns a mix file into campaigns (:mod:`generator`), and
the mix files ``<traffic>.json``."""
