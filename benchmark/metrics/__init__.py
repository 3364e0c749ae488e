"""One reader per metric: ``benchmark/metrics/<name>.py`` defines
``read(run) -> float | None`` (None: nothing to read in this run, and
the metric is left out of the result line).  ``_layers`` holds what
several readers share."""
