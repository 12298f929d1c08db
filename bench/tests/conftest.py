"""Tests of the benchmark's yardstick: ``pytest bench/tests``.

They run on the CPU (``JAX_PLATFORMS=cpu``): the trace reduction on
recorded and hand-made traces, the arithmetic against hand counts, and
every cell rehearsed at tiny sizes with its control and its faults.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
