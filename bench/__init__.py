"""On-chip benchmark of CRAFT's checkpointed training loop.

``bench/run.py`` runs one cell of ``BENCHMARK.json``.  Everything that
belongs to one configuration, traffic mix, cell or per-layer metric is a
file of its own that the harness finds by name:

* ``bench/configs/<config>.json``  model sizes, optimizer and CRAFT settings
* ``bench/traffic/<traffic>.json``  the traffic mix (mode, lengths, steps)
* ``bench/workloads/<cell>.json``   cell parameters (batch, cadence, limits)
* ``bench/metrics/<metric>.py``     one reader per metric
* ``bench/peaks.py``               published peaks keyed by ``device_kind``
"""
