"""The chip benchmark's harness: what every cell shares.

``run.py`` beside this package is the entry point.  Whatever belongs to one
configuration, one traffic mix, one way of driving a job or one per-layer
metric is a file of its own under ``configs/``, ``traffic/``, ``drivers/``
and ``layer_metrics/``, found by name (``catalog.py``); nothing in this
package names a cell.
"""
