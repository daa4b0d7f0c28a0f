"""The benchmark's tests import its harness the way ``bench/run.py``
does: ``bench/`` and the tests' own directory on the path."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
