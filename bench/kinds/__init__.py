"""Kinds of configuration: each module builds the system under test for
its kind (``build(cell, devices, seed) -> job``) and holds the plain
reference its correctness check compares against.

The system under test lives in ``<checkout>/src``; the references import
nothing from it.
"""
import os
import sys

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
