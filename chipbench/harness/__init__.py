"""The benchmark's general code: the generator, the run, the reference,
the check and the trace reduction. Cells, mixes, modes and metrics are
data, drivers and readers in ``configs/``, ``traffic/``, ``modes/`` and
``metrics/``."""
