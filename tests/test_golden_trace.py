"""Golden-trace regression fixtures: small deterministic runs (one static,
one churn) are serialized — cumulative counters, ``tier_stat``-level
summary metrics, and the decoded migration ring — into tests/golden/*.json
and diffed in tier-1, so *silent telemetry drift* (a counter that stops
incrementing, a ring record that changes meaning, a histogram that moves)
fails CI even when no behavioral test notices. The scenarios and the diff
live in ``repro.core.golden``, which ``chip_smoke.py`` replays on the TPU.

Regeneration (after an intentional behavior change):

    REPRO_REGEN_GOLDEN=1 PYTHONPATH=src python -m pytest tests/test_golden_trace.py

then commit the updated fixtures with a note on why the telemetry moved.
"""
import json
import os
import pathlib

import pytest

from repro.core.golden import SCENARIOS, collect, diff_all

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"
REGEN = os.environ.get("REPRO_REGEN_GOLDEN") == "1"


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_golden_trace(name):
    got = collect(SCENARIOS[name]())
    path = GOLDEN_DIR / f"{name}.json"
    if REGEN:
        GOLDEN_DIR.mkdir(exist_ok=True)
        path.write_text(json.dumps(got, indent=1) + "\n")
        return
    assert path.exists(), (
        f"missing golden fixture {path}; generate with "
        f"REPRO_REGEN_GOLDEN=1 python -m pytest {__file__}")
    diff_all(got, json.loads(path.read_text()))
