"""One driver per traffic kind; ``run.py`` imports ``drivers.<kind>``.

A driver exports ``setup(ctx) -> cell`` (inputs made from the seed, the
system built and every shape the window uses warmed up), ``window(cell)``
(the measured window), ``end_to_end(cell)``, ``counters(cell)``,
``release(cell)`` (frees the program's state) and ``check(cell)``.
"""
