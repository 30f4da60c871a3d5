"""Per-layer metric readers.  A metric's file under
``bench/layer_metrics/<metric>.json`` names its reader here and the
reader's arguments; ``read(readings, **args)`` returns the value, or
None when the run has nothing for it to read."""
