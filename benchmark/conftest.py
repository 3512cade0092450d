"""What the process of a ``--trace 1`` run holds besides the context the
harness hands a reader: the program's histogram registry, observed into.

``tests/test_trace_reduce.py`` makes a traced run's context by hand and
asks every declared reader for a number; the context names the five
families ``systems.DeviceBroker.counters`` reports. The readers of the
program's later spans (``readers/program_span.py``) take a family the
context lacks from the registry, as they do on the chip, so the tests'
process gets a registry that a run has observed into: one observation a
family."""

import pytest


@pytest.fixture(autouse=True, scope="session")
def program_registry_observed():
    from vernemq_tpu.observability import histogram

    for family, _help in histogram.families():
        histogram.observe(family, 1.0)
