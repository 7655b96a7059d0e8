"""Block-size autotuner with a persistent on-disk cache.

Picking Pallas tile sizes analytically (128 everywhere, MXU-shaped) is
right on average and wrong per shape: short sequences want smaller
``block_k`` so the causal skip fires more often, ragged row counts want
``block_rows`` near the remainder, and interpret mode (this container)
has per-grid-step overhead that favors the largest tile that fits.  The
autotuner benchmarks a small candidate grid once per
``(op, shape, dtype, chip)`` and remembers the winner on disk, so every
later process — tests, benchmarks, ``calibrate_kernels`` — reuses it
without re-timing.

Determinism: the cache key includes a fingerprint of the candidate grid,
so the same grid always resolves to the same stored winner; a fresh tune
breaks timing ties by candidate order (first-best wins), and candidates
whose benchmark raises (infeasible tiling) are skipped, not fatal.

Cache file schema (JSON, one file per chip by default)::

    { "<op>|<dtype>|<chip>|s<shape>|g<grid-fp>":
        {"config": {...}, "time_s": 1.2e-4, "tuned": [[{...}, t], ...]} }

``tuned`` keeps every candidate's time for later inspection (the bench
prints it); only ``config`` is consulted on the hot path.
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

Config = Dict[str, int]


def default_chip() -> str:
    """Cache identity of the device the kernels actually run on: its
    ``hw_specs.ACCELERATORS`` key, so calibrated tables are filed under
    the name the profiler asks for."""
    from repro.core.profiler.hw_specs import accelerator_for_kind
    return accelerator_for_kind(jax.devices()[0].device_kind)


def enabled() -> bool:
    """ops.py consults this for implicit (block size = None) autotuning."""
    return os.environ.get("REPRO_KERNEL_AUTOTUNE", "0") not in ("", "0")


def default_cache_path(chip: Optional[str] = None) -> Path:
    root = Path(os.environ.get("REPRO_KERNEL_CACHE_DIR",
                               Path.home() / ".cache" / "repro-kernels"))
    return root / f"autotune-{chip or default_chip()}.json"


def _grid_fingerprint(candidates: Sequence[Config]) -> str:
    blob = json.dumps([sorted(c.items()) for c in candidates])
    return hashlib.sha1(blob.encode()).hexdigest()[:12]


def cache_key(op: str, shape: Tuple[int, ...], dtype: str, chip: str,
              candidates: Sequence[Config]) -> str:
    sh = "x".join(str(int(s)) for s in shape)
    return f"{op}|{dtype}|{chip}|s{sh}|g{_grid_fingerprint(candidates)}"


class AutotuneCache:
    """Persistent winner store; loads eagerly, saves atomically."""

    def __init__(self, path: os.PathLike):
        self.path = Path(path)
        self._data: Dict[str, Dict[str, Any]] = {}
        if self.path.exists():
            try:
                self._data = json.loads(self.path.read_text())
            except (json.JSONDecodeError, OSError):
                self._data = {}            # corrupt cache: retune

    def get(self, key: str) -> Optional[Config]:
        ent = self._data.get(key)
        return dict(ent["config"]) if ent else None

    def put(self, key: str, config: Config, time_s: float,
            tuned: List[Tuple[Config, float]]) -> None:
        self._data[key] = {"config": dict(config), "time_s": time_s,
                           "tuned": [[dict(c), t] for c, t in tuned]}
        self.path.parent.mkdir(parents=True, exist_ok=True)
        tmp = self.path.with_suffix(f".tmp{os.getpid()}")
        tmp.write_text(json.dumps(self._data, indent=1, sort_keys=True))
        os.replace(tmp, self.path)


@functools.lru_cache(maxsize=8)
def _shared_cache(path: str) -> AutotuneCache:
    return AutotuneCache(Path(path))


def bench_time(fn: Callable[[], Any], *, warmup: int = 1,
               iters: int = 3) -> float:
    """Median wall-clock of ``fn()`` (blocks on the result)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def autotune(op: str, shape: Tuple[int, ...], dtype: str,
             candidates: Sequence[Config],
             bench: Callable[[Config], float], *,
             chip: Optional[str] = None,
             cache: Optional[AutotuneCache] = None) -> Config:
    """Return the fastest candidate config, consulting/updating the cache.

    ``bench(config) -> seconds``; raising marks the candidate infeasible.
    The winner is min by (time, candidate order) — deterministic given the
    measured times, and permanently deterministic once cached.
    """
    if not candidates:
        raise ValueError(f"autotune({op}): empty candidate grid")
    chip = chip or default_chip()
    if cache is None:
        cache = _shared_cache(str(default_cache_path(chip)))
    key = cache_key(op, shape, dtype, chip, candidates)
    hit = cache.get(key)
    if hit is not None:
        return hit
    tuned: List[Tuple[Config, float]] = []
    for cand in candidates:
        try:
            tuned.append((cand, bench(cand)))
        except Exception:
            continue                       # infeasible tiling
    if not tuned:
        raise RuntimeError(f"autotune({op}): no feasible candidate "
                           f"for shape={shape}")
    best_i = min(range(len(tuned)), key=lambda i: (tuned[i][1], i))
    best, t = tuned[best_i]
    cache.put(key, best, t, tuned)
    return dict(best)


# --- per-op candidate grids + tuners (used by ops.py and the bench) -----------

def flash_candidates(sq: int, sk: int) -> List[Config]:
    qs = sorted({min(b, sq) for b in (64, 128, 256)})
    ks = sorted({min(b, sk) for b in (64, 128, 256)})
    return [{"block_q": bq, "block_k": bk} for bq in qs for bk in ks]


def rows_candidates(rows: int) -> List[Config]:
    return [{"block_rows": b}
            for b in sorted({min(b, rows) for b in (64, 128, 256, 512)})]


def chunk_candidates(s: int) -> List[Config]:
    return [{"chunk": c} for c in sorted({min(c, s) for c in (64, 128, 256)})]


def tune_flash_attention(q, k, v, *, causal: bool,
                         cache: Optional[AutotuneCache] = None) -> Config:
    from repro.kernels import flash_attention as fa
    from repro.kernels.ops import on_platform
    bh, sq, d = q.shape
    sk = k.shape[1]

    def bench(c: Config) -> float:
        return bench_time(lambda: on_platform(
            fa.flash_attention, q, k, v, causal=causal, **c))

    return autotune("flash_attention", (bh, sq, sk, d, int(causal)),
                    str(q.dtype), flash_candidates(sq, sk), bench,
                    cache=cache)


def tune_rmsnorm(x, scale, *, eps: float,
                 cache: Optional[AutotuneCache] = None) -> Config:
    from repro.kernels import rmsnorm as rn
    from repro.kernels.ops import on_platform
    rows = 1
    for s in x.shape[:-1]:
        rows *= s

    def bench(c: Config) -> float:
        return bench_time(lambda: on_platform(
            rn.rmsnorm, x, scale, eps=eps, **c))

    return autotune("rmsnorm", (rows, x.shape[-1]), str(x.dtype),
                    rows_candidates(rows), bench, cache=cache)


def tune_fused_add_rmsnorm(x, res, scale, *, eps: float,
                           cache: Optional[AutotuneCache] = None) -> Config:
    from repro.kernels import fused
    from repro.kernels.ops import on_platform
    rows = 1
    for s in x.shape[:-1]:
        rows *= s

    def bench(c: Config) -> float:
        return bench_time(lambda: on_platform(
            fused.fused_add_rmsnorm, x, res, scale, eps=eps, **c))

    return autotune("fused_add_rmsnorm", (rows, x.shape[-1]), str(x.dtype),
                    rows_candidates(rows), bench, cache=cache)


def tune_ssd_scan(x, dt, a, b, c, *,
                  cache: Optional[AutotuneCache] = None) -> Config:
    from repro.kernels import ssd as ssd_mod
    from repro.kernels.ops import on_platform
    bs, s, h, p = x.shape

    def bench(cand: Config) -> float:
        return bench_time(lambda: on_platform(
            ssd_mod.ssd_scan, x, dt, a, b, c, **cand))

    return autotune("ssd_scan", (bs, s, h, p, b.shape[-1]), str(x.dtype),
                    chunk_candidates(s), bench, cache=cache)
