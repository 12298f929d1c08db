"""The benchmark's yardstick: everything that turns a run into numbers.

Nothing here belongs to one cell.  A cell is found by name in
``BENCHMARK.json``; its configuration, traffic mix and per-layer metrics sit
in files of their own under ``bench/configs``, ``bench/traffic`` and
``bench/metrics``, and the generator for a traffic *kind* under
``bench/kinds``.
"""
