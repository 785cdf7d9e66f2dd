"""Per-layer metric readers, one file per metric named as in
``BENCHMARK.json``.  Each defines ``read(run)`` and returns the metric,
or None when the run holds nothing to read it from.  ``run`` carries:

* ``trace``: the traced window reduced by ``bench/trace_reduce.py``;
* ``updates``: committed global updates in the window;
* ``spans``: seconds inside each benchmark host span in the window
  (``pop_strategy``, ``dispatch``, ``materialize``, ``eval``);
* ``wire_bytes``: the program's byte ledger (``bytes_up`` + ``bytes_down``)
  added in the window;
* ``train_flops``: training operations of the live rows the window's
  updates trained on (``bench/flops.py``);
* ``chips`` and ``peak_flops`` (bf16, ``bench/peaks.py``; None off-TPU).
"""
