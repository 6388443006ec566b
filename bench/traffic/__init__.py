"""Traffic of the benchmark: the loop-nest trace generators
(:mod:`loopnests`), the one generator that turns a mix file into
campaigns (:mod:`generator`), and the mix files ``<traffic>.json``."""
