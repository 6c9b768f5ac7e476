"""Put the benchmark's modules and the program's sources on the path.

Run the benchmark's own tests explicitly: ``python -m pytest bench/tests``.
"""
import dataclasses
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (BENCH, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)

STAGE_FIELDS = ("input_channels", "stages")


@pytest.fixture(scope="session")
def stage_fields() -> bool:
    """Whether the program's ``SNNConfig`` takes a network of residual stages.

    Tests of a stage network assert what the program can do: run it where
    ``SNNConfig`` has the fields :data:`STAGE_FIELDS`, and be refused
    naming them where it has not.
    """
    from repro.models.snn import SNNConfig

    names = {f.name for f in dataclasses.fields(SNNConfig)}
    return set(STAGE_FIELDS) <= names
