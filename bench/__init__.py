"""Chip benchmark of the served dynamic-graph path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on a TPU and prints one JSON result
line.  Everything a cell is made of is found by name:

  * a configuration (the graph): ``bench/configs/<name>.json``;
  * a traffic mix: ``bench/traffic/<name>.json``, read by the one general
    generator in ``bench/workload.py``;
  * a metric: ``bench/metrics/<name>.py``, a reader with ``read(run)``.

The yardstick lives here too, apart from the program: the generators, the
plain host reference (``bench/reference.py``), the comparison that decides
``correct`` (``bench/check.py``), the trace reduction (``bench/trace.py``)
and the table of chip peaks (``bench/peaks.py``).
"""
