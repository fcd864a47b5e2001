"""A tiny copy of the benchmark tree for CPU tests: the real bench/
files plus tiny configurations and cells, with ``spec`` pointed at it."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "reference"))

TINY = {
    "xlstm-tiny": ("xlstm-125m", "xlstm",
                   {"n_layers": 2, "d_model": 64, "n_heads": 4,
                    "n_kv_heads": 4, "vocab_size": 256}),
    "minicpm-tiny": ("minicpm-2b", "llama",
                     {"n_layers": 2, "d_model": 64, "n_heads": 4,
                      "n_kv_heads": 4, "d_ff": 128, "vocab_size": 256}),
}


def _config(name, registry, reference, changes):
    import dataclasses

    import spec
    spec.use_program()
    from repro.configs import get_config
    base = get_config(registry)
    d = dataclasses.asdict(dataclasses.replace(base, **changes))
    d.pop("name")
    d["pattern"] = list(d["pattern"])
    return {"name": name, "source": "test", "registry": registry,
            "reference": reference, "config": d,
            "reduced": sorted(k for k, v in changes.items()
                             if getattr(base, k) != v)}


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """(root, write_cell): a benchmark root holding tiny configurations;
    ``write_cell(name, config, traffic, **cell)`` adds a cell."""
    import spec
    root = tmp_path / "root"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, (reg, ref, ch) in TINY.items():
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(_config(name, reg, ref, ch)))
    bench = json.loads(open(os.path.join(BENCH, "..",
                                         "BENCHMARK.json")).read())
    bench["workloads"] = []

    def write_cell(name, config, traffic, mix=None, **cell):
        if mix is not None:
            (root / "bench" / "traffic" / f"{traffic}.json").write_text(
                json.dumps(mix))
        entry = {"name": name, "config": config, "traffic": traffic,
                 "chips": 1, "why": "test"}
        bench["workloads"].append(entry)
        (root / "BENCHMARK.json").write_text(json.dumps(bench))
        (root / "bench" / "workloads" / f"{name}.json").write_text(
            json.dumps({"config": config, "traffic": traffic, "chips": 1,
                        **cell}))

    monkeypatch.setattr(spec, "ROOT", root)
    monkeypatch.setattr(spec, "BENCH", root / "bench")
    monkeypatch.setattr(spec, "CACHE_DIR", tmp_path / "cache")
    return root, write_cell


TRAIN_MIX = {"job": "train", "seq_len": 32, "estimator": "wta_crs",
             "budget": 0.3, "lr": 1e-3, "zipf_exponent": 1.1,
             "zipf_offset": 10, "check_steps": 3, "trace_steps": 2,
             "controls": {"fp8": {"prec": "fp8"},
                          "half_batch": {"drop_half": True}}}
TRAIN_LIMITS = {"loss_gap": 0.01, "grad_norm_gap": 0.2,
                "update_norm_gap": 0.05}
