"""Shared benchmark utilities: CSV emission, smoke mode, JSON artifacts.

Smoke mode (``run.py --smoke``) is the CI-sized variant: every module
shrinks its shapes/steps/grids so the whole suite finishes in minutes on
a CPU runner while still executing the real code paths end-to-end.
``emit_json`` writes machine-readable ``BENCH_<name>.json`` artifacts
(uploaded by the ``bench-smoke`` CI job) next to the human CSV rows.
"""
from __future__ import annotations

import json
import os

SMOKE = False
OUT_DIR = "."
RESULTS = []          # every emitted CSV row, for the summary artifact


def set_smoke(value: bool) -> None:
    global SMOKE
    SMOKE = bool(value)


def is_smoke() -> bool:
    return SMOKE


def smoke_or(smoke_value, full_value):
    """Pick the reduced-size parameter in smoke mode."""
    return smoke_value if SMOKE else full_value


def set_out_dir(path: str) -> None:
    global OUT_DIR
    OUT_DIR = path
    os.makedirs(path, exist_ok=True)


def emit(name: str, us_per_call: float, derived: str):
    RESULTS.append({"name": name, "us_per_call": us_per_call,
                    "derived": derived})
    print(f"{name},{us_per_call:.1f},{derived}")


def emit_json(name: str, payload) -> str:
    """Write ``BENCH_<name>.json`` into the artifact dir; returns path."""
    path = os.path.join(OUT_DIR, f"BENCH_{name}.json")
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True, default=float)
    return path
