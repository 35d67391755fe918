"""The chip benchmark: one command runs one cell of ``BENCHMARK.json`` once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or metric sits in
a file of its own and is found by the name that ``BENCHMARK.json`` gives it
(``spec.py``):

- ``configs/<config>.json``: a deployment's sizes, its guarantees, the limits
  of its correctness check, the ``system`` that runs it and the plain
  ``reference`` beside it (``configs/<reference>.py``);
- ``traffic/<mix>.json``: one traffic mix, the parameters of a ``loop`` that
  the config's system module (``systems/<system>.py``) drives;
- ``end_to_end/<metric>.py`` and ``layer_metrics/<metric>.py``: one reader
  per metric, ``read(run) -> float | None``; a metric ``<base>.<split>``,
  split by the end-to-end metric it moves, may share ``<base>.py``.

``trace_reduce.py`` reduces a profiler trace; ``calibrate.py`` takes the
readings that a check's limits are set from.
"""
