"""The benchmark of the CADDeLaG write and read paths on TPU chips.

``python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``.  Everything that belongs
to one configuration, traffic mix or per-layer metric is a file of its own,
found by the name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the deployment (sizes, solver, mesh,
  the limits of the correctness comparison);
* ``bench/traffic/<traffic>.json``: the parameters that
  :mod:`bench.traffic` turns into snapshots or queries;
* ``bench/metrics/<metric>.py``: a ``read(rec)`` that reduces one traced
  run's record to a number, or ``None`` when it finds nothing to read.

The yardstick lives here too: traffic generation (:mod:`bench.traffic`),
the plain reference (:mod:`bench.reference`), the trace reduction
(:mod:`bench.trace_reduce`), the peaks and operation counts
(:mod:`bench.peaks`) and the comparison that decides ``correct``
(:mod:`bench.check`).
"""
