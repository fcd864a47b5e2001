"""Block-size autotuner for the sampled-backward Pallas kernels.

The fused sampled-dW kernel's grid is ``(d_in/bm, d_out/bn, B, k/bk)``;
the right ``(bm, bn, bk)`` depends on the problem shape and dtype (MXU
tile alignment vs VMEM pressure vs DMA batching).  This module owns
that decision:

* :func:`shape_key` — the tuning key ``(d_in, d_out, B, k, dtype)``
  rendered as a stable string.
* :class:`TuningTable` — a persisted JSON table mapping keys to block
  triples; loaded once per path (corrupt or missing tables degrade to
  the shape-derived defaults with a single warning, never an error).
* :func:`resolve_blocks` — the dispatch-time resolution every
  ``kernels.ops`` wrapper calls: explicit ``KernelConfig`` overrides
  beat the table, the table beats :func:`default_blocks`, and whatever
  wins is snapped to a tile-legal divisor of the actual shape, so the
  kernel's divisibility contract holds and the TPU compiler accepts it.
* :func:`autotune` — measure candidate grids for one shape and return
  the fastest (deterministic: candidates are enumerated in a fixed
  order and ties break toward the earliest candidate).
* ``python -m repro.kernels.autotune --out <path>`` — refresh a table
  over the default shape sweep (the nightly CI job runs this and
  uploads the result).

Table format (``version`` guards future migrations)::

    {"version": 1,
     "kernel": "fused_sampled_dw",
     "entries": {"di256-do256-b8-k77-float32":
                     {"bm": 128, "bn": 128, "bk": 77, "us": 41.2}}}
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import warnings
from typing import Callable, Dict, List, Optional, Sequence, Tuple

TABLE_VERSION = 1
PACKAGED_TABLE = os.path.join(os.path.dirname(__file__),
                              "tuning_table.json")

# Shapes the nightly refresh sweeps: (d_in, d_out, B, k, dtype).
DEFAULT_SWEEP: Tuple[Tuple[int, int, int, int, str], ...] = (
    (256, 256, 8, 77, "float32"),
    (256, 256, 8, 77, "bfloat16"),
    (64, 64, 2, 24, "float32"),
    (512, 512, 4, 154, "float32"),
)

_BLOCK_LADDER = (256, 128, 64, 32, 16, 8)


def shape_key(d_in: int, d_out: int, b: int, k: int, dtype) -> str:
    """Stable tuning-table key for one problem shape.  ``dtype`` may be
    a np/jnp dtype instance, a scalar-type class (``jnp.bfloat16``), or
    a plain name string — all normalize to the canonical dtype name."""
    import numpy as np
    try:
        name = np.dtype(dtype).name
    except TypeError:
        name = str(dtype)
    return f"di{d_in}-do{d_out}-b{b}-k{k}-{name}"


LANE = 128
# Per-step VMEM a block triple may take: the double-buffered H' and dZ
# blocks, the double-buffered output block and the f32 accumulator.
# 16 MiB is the default scoped-VMEM limit of a TPU v5e kernel.
VMEM_BUDGET = 16 * 2**20


def sublane(dtype) -> int:
    """Rows per (sublane, lane) tile: 8 for 32-bit dtypes, 16 for 16-bit
    ones (two rows pack into one sublane)."""
    import jax.numpy as jnp
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def lane_block(dim: int, want: int) -> int:
    """Tile-legal block along a minor (lane) dim that divides ``dim``:
    the largest multiple of 128 dividing ``dim`` that is <= ``want``,
    else the smallest one above it, else the full dim."""
    legal = [b for b in range(LANE, dim + 1, LANE) if dim % b == 0]
    if not legal:
        return dim
    fits = [b for b in legal if b <= want]
    return max(fits) if fits else legal[0]


def sublane_block(k: int, want: int, dtype) -> int:
    """Tile-legal k-block: the full k when it is <= ``want``, else the
    largest multiple of :func:`sublane` that is <= ``want`` (at least
    one tile).  The kernel's k-grid is ragged, so bk need not divide k."""
    sub = sublane(dtype)
    if k <= want:
        return k
    return min(k, max(sub, want // sub * sub))


def vmem_bytes(bm: int, bn: int, bk: int, dtype) -> int:
    """Per-step VMEM of the sampled-dW kernel for one block triple."""
    import jax.numpy as jnp
    item = jnp.dtype(dtype).itemsize
    return 2 * bk * (bm + bn) * item + 3 * bm * bn * 4


def default_blocks(d_in: int, d_out: int, k: int,
                   dtype) -> Tuple[int, int, int]:
    """Shape-derived fallback blocks: MXU-sized tiles, tile-legal and
    dividing the dims (the kernel never pads d_in/d_out)."""
    return (lane_block(d_in, 128), lane_block(d_out, 128),
            sublane_block(k, 128, dtype))


def candidate_blocks(d_in: int, d_out: int, k: int,
                     dtype) -> List[Tuple[int, int, int]]:
    """Deterministic candidate grid for one shape: the tile-legal blocks
    of the ladder per dim, crossed, largest-first (so ties resolve to the
    biggest tiles — fewest grid steps)."""
    bms = sorted({lane_block(d_in, w) for w in _BLOCK_LADDER},
                 reverse=True)
    bns = sorted({lane_block(d_out, w) for w in _BLOCK_LADDER},
                 reverse=True)
    bks = sorted({sublane_block(k, w, dtype) for w in _BLOCK_LADDER},
                 reverse=True)
    return [(bm, bn, bk) for bm in bms for bn in bns for bk in bks
            if vmem_bytes(bm, bn, bk, dtype) <= VMEM_BUDGET]


@dataclasses.dataclass
class TuningTable:
    """In-memory view of one persisted tuning table."""

    entries: Dict[str, Tuple[int, int, int]] = dataclasses.field(
        default_factory=dict)
    timings_us: Dict[str, float] = dataclasses.field(default_factory=dict)
    source: Optional[str] = None

    @classmethod
    def load(cls, path: str) -> "TuningTable":
        """Parse a table; corrupt/missing/mis-versioned files degrade to
        an EMPTY table (defaults take over) with one warning."""
        try:
            with open(path) as f:
                raw = json.load(f)
            if raw.get("version") != TABLE_VERSION:
                raise ValueError(f"tuning-table version "
                                 f"{raw.get('version')!r} != "
                                 f"{TABLE_VERSION}")
            entries, timings = {}, {}
            for key, rec in raw["entries"].items():
                bm, bn, bk = int(rec["bm"]), int(rec["bn"]), int(rec["bk"])
                if min(bm, bn, bk) < 1:
                    raise ValueError(f"non-positive block in {key!r}")
                entries[key] = (bm, bn, bk)
                if isinstance(rec.get("us"), (int, float)):
                    timings[key] = float(rec["us"])
            return cls(entries=entries, timings_us=timings, source=path)
        except FileNotFoundError:
            return cls(source=path)
        except Exception as exc:              # corrupt: degrade, don't die
            warnings.warn(f"ignoring corrupt kernel tuning table "
                          f"{path!r}: {exc}", RuntimeWarning)
            return cls(source=path)

    def lookup(self, key: str) -> Optional[Tuple[int, int, int]]:
        return self.entries.get(key)

    def put(self, key: str, blocks: Tuple[int, int, int],
            us: Optional[float] = None) -> None:
        self.entries[key] = tuple(int(x) for x in blocks)
        if us is not None:
            self.timings_us[key] = float(us)

    def save(self, path: str) -> str:
        payload = {"version": TABLE_VERSION, "kernel": "fused_sampled_dw",
                   "entries": {
                       key: {"bm": bm, "bn": bn, "bk": bk,
                             **({"us": self.timings_us[key]}
                                if key in self.timings_us else {})}
                       for key, (bm, bn, bk)
                       in sorted(self.entries.items())}}
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        return path


@functools.lru_cache(maxsize=8)
def load_table(path: Optional[str] = None) -> TuningTable:
    """Cached table load; ``None`` = the packaged default table."""
    return TuningTable.load(path or PACKAGED_TABLE)


def resolve_blocks(cfg, d_in: int, d_out: int, b: int, k: int,
                   dtype) -> Tuple[int, int, int]:
    """Dispatch-time block resolution for the sampled-dW kernel.

    Priority: explicit ``KernelConfig`` overrides > tuning table (when
    ``cfg.autotune``) > :func:`default_blocks`.  Whatever wins is snapped
    to a tile-legal block: bm/bn a multiple of 128 dividing the dim (or
    the full dim), bk a multiple of the dtype's sublane count (or the
    full k).  Raises ``ValueError`` when the legal blocks overflow
    :data:`VMEM_BUDGET` — a shape the kernel cannot run.
    """
    bm, bn, bk = default_blocks(d_in, d_out, k, dtype)
    if cfg is not None and cfg.autotune:
        hit = load_table(cfg.table_path).lookup(
            shape_key(d_in, d_out, b, k, dtype))
        if hit is not None:
            bm, bn, bk = hit
    if cfg is not None:
        over = cfg.block_overrides()
        bm = over.get("bm", bm)
        bn = over.get("bn", bn)
        bk = over.get("bk", bk)
    bm, bn = lane_block(d_in, bm), lane_block(d_out, bn)
    bk = sublane_block(k, bk, dtype)
    need = vmem_bytes(bm, bn, bk, dtype)
    if need > VMEM_BUDGET:
        raise ValueError(
            f"no tile-legal sampled-dW blocks for d_in={d_in}, "
            f"d_out={d_out}, k={k}: the legal blocks ({bm}, {bn}, {bk}) "
            f"need {need} bytes of VMEM, over the {VMEM_BUDGET}-byte "
            f"budget (a dim with no multiple-of-128 divisor must be "
            f"taken whole)")
    return bm, bn, bk


def _default_measure(interpret: Optional[bool]) -> Callable:
    """Median-of-N wall-clock timer for one candidate block triple."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.core.kernel_config import KernelConfig

    def measure(blocks: Tuple[int, int, int], d_in: int, d_out: int,
                b: int, k: int, dtype) -> float:
        from repro.kernels import ops
        bm, bn, bk = blocks
        cfg = KernelConfig(backend="pallas", bm=bm, bn=bn, bk=bk,
                           autotune=False, interpret=interpret)
        key = jax.random.PRNGKey(0)
        hs = jax.random.normal(key, (b, k, d_in), dtype=jnp.dtype(dtype))
        dz = jax.random.normal(jax.random.fold_in(key, 1),
                               (b, 4 * k, d_out), dtype=jnp.dtype(dtype))
        idx = jax.random.randint(jax.random.fold_in(key, 2), (b, k),
                                 0, 4 * k)
        sc = jax.random.uniform(jax.random.fold_in(key, 3), (b, k))
        fn = functools.partial(ops.fused_sampled_dw, hs, dz, idx, sc,
                               kernel=cfg)
        jax.block_until_ready(fn())                       # compile
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2] * 1e6

    return measure


def autotune(d_in: int, d_out: int, b: int, k: int, dtype, *,
             candidates: Optional[Sequence[Tuple[int, int, int]]] = None,
             measure: Optional[Callable] = None,
             interpret: Optional[bool] = None,
             max_candidates: Optional[int] = None
             ) -> Tuple[Tuple[int, int, int], float]:
    """Measure candidate grids for one shape; return (blocks, us).

    Deterministic by construction: the candidate order is fixed
    (:func:`candidate_blocks`), ties break toward the earliest
    candidate, and ``measure`` is injectable so tests can pin timings.
    ``max_candidates`` (optional) truncates the search to the first N
    candidates — the ladder is largest-blocks-first, so this skips the
    small-block tail whose grids are pathologically slow through the
    CPU interpreter (grid size grows as the product of the inverse
    block sizes) while keeping every plausible winner.
    """
    cands = list(candidates if candidates is not None
                 else candidate_blocks(d_in, d_out, k, dtype))
    if max_candidates is not None:
        cands = cands[:max_candidates]
    if not cands:
        raise ValueError("no candidate blocks to autotune over")
    fn = measure if measure is not None else _default_measure(interpret)
    best, best_us = cands[0], float("inf")
    for blocks in cands:
        us = float(fn(blocks, d_in, d_out, b, k, dtype))
        if us < best_us:
            best, best_us = blocks, us
    return best, best_us


def refresh_table(shapes: Sequence[Tuple[int, int, int, int, str]],
                  out_path: str, *,
                  measure: Optional[Callable] = None,
                  interpret: Optional[bool] = None,
                  max_candidates: Optional[int] = None,
                  base: Optional[TuningTable] = None) -> TuningTable:
    """Autotune every shape, merge over ``base``, persist to JSON."""
    table = base if base is not None else TuningTable()
    for (d_in, d_out, b, k, dtype) in shapes:
        blocks, us = autotune(d_in, d_out, b, k, dtype,
                              measure=measure, interpret=interpret,
                              max_candidates=max_candidates)
        table.put(shape_key(d_in, d_out, b, k, dtype), blocks, us)
    table.save(out_path)
    return table


def _parse_shapes(spec: str) -> List[Tuple[int, int, int, int, str]]:
    out = []
    for part in spec.split(";"):
        di, do, b, k, dt = part.split(",")
        out.append((int(di), int(do), int(b), int(k), dt.strip()))
    return out


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="refresh the fused sampled-dW kernel tuning table")
    ap.add_argument("--out", default=PACKAGED_TABLE,
                    help="output tuning-table JSON path")
    ap.add_argument("--shapes", default=None,
                    help="semicolon-separated 'd_in,d_out,B,k,dtype' "
                         "rows (default: the built-in sweep)")
    ap.add_argument("--merge", action="store_true",
                    help="merge over the existing table at --out "
                         "instead of replacing it")
    ap.add_argument("--max-candidates", type=int, default=8,
                    help="search only the first N (largest-block) "
                         "candidates per shape; 0 = the full ladder. "
                         "Small-block grids take minutes each through "
                         "the CPU interpreter, so the nightly refresh "
                         "keeps the default cap")
    args = ap.parse_args(argv)
    shapes = (_parse_shapes(args.shapes) if args.shapes
              else list(DEFAULT_SWEEP))
    base = TuningTable.load(args.out) if args.merge else None
    table = refresh_table(shapes, args.out, base=base,
                          max_candidates=args.max_candidates or None)
    for key in sorted(table.entries):
        bm, bn, bk = table.entries[key]
        us = table.timings_us.get(key)
        print(f"{key}: bm={bm} bn={bn} bk={bk}"
              + (f" ({us:.1f} us)" if us is not None else ""))
    print(f"wrote {len(table.entries)} entries -> {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
