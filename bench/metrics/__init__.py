"""Per-layer metrics, one reader per file, found by the metric's name.

Each module ``<name>.py`` defines ``read(ctx) -> float | None``.  ``ctx``
is the harness's :class:`bench.harness.Reading` of a traced run: the
configuration's sizes, the traffic, the peaks of the device, the steps
completed in the window and its length, the trace and the compiled step's
HLO.  A reader that finds nothing to read returns None, and the metric is
left out of the run's line.
"""
