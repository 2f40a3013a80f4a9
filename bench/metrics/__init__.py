"""One reader per metric: ``<name>.py`` with ``read(run) -> float | None``.

``run`` is ``bench.run.Run``.  A reader that finds nothing to read
returns ``None`` and the metric is left out of the result line; a share
of a roofline or a peak is never reported as 0 for want of a reading.
"""
