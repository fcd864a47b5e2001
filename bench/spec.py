"""Loading the benchmark's data files and the program under test.

Everything a cell needs is found by name: ``BENCHMARK.json`` at the root
of the checkout, ``bench/workloads/<cell>.json``, the configuration
``bench/configs/<config>.json`` and the traffic mix
``bench/traffic/<traffic>.json`` it names, and one reader per per-layer
metric under ``bench/metrics/``.  Nothing here branches on a cell's or a
configuration's name.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import zlib
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
CACHE_DIR = ROOT / ".jax_cache"
_PUBLISHED: dict = {}


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    """The cell's entry of BENCHMARK.json merged with its workload file,
    its configuration file and its traffic mix."""
    entry = next((w for w in benchmark()["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = dict(_json(BENCH / "workloads" / f"{name}.json"))
    for k in ("config", "traffic", "chips"):
        if cell[k] != entry[k]:
            raise SystemExit(f"workload file {name}: {k} {cell[k]!r} "
                             f"differs from BENCHMARK.json {entry[k]!r}")
    cell["name"] = name
    cell["config_file"] = _json(BENCH / "configs" / f"{entry['config']}.json")
    cell["mix"] = _json(BENCH / "traffic" / f"{entry['traffic']}.json")
    return cell


def metrics_for(name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    out = []
    for m in benchmark()[kind]:
        if "workloads" not in m or name in m["workloads"]:
            out.append(m)
    return out


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``: a job driver, a reference model or a
    per-layer metric's reader."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def use_program() -> None:
    """Put the program's ``src`` on the path (the checkout's own)."""
    src = str(SRC)
    if src not in sys.path:
        sys.path.insert(0, src)


def arch_config(config_file: dict):
    """The configuration as run: the registry's published config with
    the file's keys applied, registered in the program's registry under
    the file's own name (so ``RunSpec``/``ServeSpec`` take it by name).
    Refuses a file whose changed keys are not exactly its ``reduced``."""
    use_program()
    import repro.configs as rc
    reg = config_file["registry"]
    base = _PUBLISHED.setdefault(reg, rc.get_config(reg))
    fields = dict(config_file["config"])
    fields["pattern"] = tuple(fields["pattern"])
    cfg = dataclasses.replace(base, **fields, name=config_file["name"])
    changed = sorted(k for k in fields
                     if getattr(base, k) != getattr(cfg, k))
    if changed != sorted(config_file["reduced"]):
        raise SystemExit(f"config {config_file['name']}: keys changed from "
                         f"the published config {changed} != reduced "
                         f"{sorted(config_file['reduced'])}")
    rc.ARCHS[cfg.name] = (cfg, cfg)
    return cfg


def derive_seed(seed: int, *tags) -> int:
    """A 31-bit seed from the run's seed (any whole number) and tags."""
    words = [int(seed) % (1 << 64)] + [zlib.crc32(str(t).encode())
                                       for t in tags]
    return int(np.random.SeedSequence(words).generate_state(1)[0]
               & 0x7FFFFFFF)


def enable_compile_cache() -> str:
    """JAX's persistent cache at the checkout's fixed ``.jax_cache``,
    every program cached however quick its compile."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)
