"""One reader per per-layer metric, ``<metric name>.py``, found by name.

Each exports ``read(rec) -> float | None``.  ``rec`` holds the run's
``trace`` (``bench.trace_reduce.Trace``, traced runs only), the driver's
``counters``, the cell's ``config``, its ``chips`` and the device's
``peaks`` (``bench/peaks.json``).  A reader that finds nothing to read
returns None, and the metric is left out of the result line.
"""
