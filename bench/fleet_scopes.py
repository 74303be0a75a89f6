#!/usr/bin/env python3
"""Device time by scope in a fleet cell: ``bench/stages.py`` with the fleet
chunk's own scopes counted apart from ``outside_tick``.

  python3 bench/fleet_scopes.py --workload <fleet cell> --seed <n>
                               --seconds <s>

The fleet chunk program (``repro.obs.fleet.make_fleet_chunk``) gathers each
host's schedule row under ``fleet/schedule`` and sums its outputs under
``fleet/fold``; ``bench/stage_reduce.py`` counts every op outside
``tick/`` as ``outside_tick``. Here those two scopes are read as rows
``fleet/schedule`` and ``fleet/fold`` of the stage table; everything else,
the options, the table and the JSON line, is ``bench/stages.py``'s, and
``outside_tick`` keeps the scan machinery.
"""
from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import stage_reduce as SR  # noqa: E402
from bench import stages  # noqa: E402

FLEET_SCOPES = ("schedule", "fold")
_tick_path = SR.scope_path


def scope_path(op_name):
    """``stage_reduce.scope_path``, and ``("fleet", <scope>)`` for an op of
    the fleet chunk's own scopes."""
    path = _tick_path(op_name)
    parts = (op_name or "").split(";")[0].split("/")[:-1]
    if path == (SR.OUTSIDE,) and "fleet" in parts:
        inner = parts[parts.index("fleet") + 1:]
        for scope in FLEET_SCOPES:
            if scope in inner:
                return ("fleet", scope)
    return path


if __name__ == "__main__":
    SR.scope_path = scope_path
    sys.exit(stages.main())
