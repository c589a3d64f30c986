"""Suite-wide ``hypothesis`` settings, imported by the property tests.

Registers and loads one profile with no per-example deadline: the kernel
and paging properties run Pallas kernels in interpret mode, whose first
examples compile and whose later ones take about a second each under
several test workers — well past Hypothesis' 200 ms default, though the
math is right.  ``max_examples`` stays per test.
"""
from __future__ import annotations

from hypothesis import given, settings  # noqa: F401
from hypothesis import strategies as st  # noqa: F401

settings.register_profile("repro", deadline=None)
settings.load_profile("repro")
