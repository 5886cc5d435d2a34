"""Metric readers: ``<name>.py`` holds ``read(run) -> float | None`` for
every metric whose name up to its first ``.`` is ``<name>``.  A reader
that finds nothing to read returns None and the metric is left out."""
