"""One file per metric, found by listing this directory. Each module
gives ``UNIT``, ``KIND`` (``end_to_end`` or ``per_layer``), ``SOURCE``,
``BETTER``, and for a per-layer metric ``LAYER`` and ``MOVES``; and
``read(obs)``: the value from a driver's observations, or ``None`` where
there is nothing to read (the harness then leaves the metric out of the
line)."""
