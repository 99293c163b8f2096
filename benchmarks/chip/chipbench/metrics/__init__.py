"""Per-layer metric readers, one module each, found by the metric's name.

Each module has ``read(run) -> float | None``. ``run`` is the finished
``chipbench.runner.Run``; a reader that finds nothing to read returns None
and the metric is left out of the result line.
"""
