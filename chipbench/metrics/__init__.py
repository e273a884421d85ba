"""One reader per per-layer metric, found by the metric's name.

Each module has ``read(view) -> float | None``, where ``view`` is a
:class:`chipbench.harness.LayerView`.  A reader that finds nothing to
read returns None, and the metric is left out of the result line.
"""
