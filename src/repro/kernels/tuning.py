"""Persistent block-size autotuner for the Pallas kernel wrappers.

``pick_block_n`` (kernels/common.py) chooses block sizes by a VMEM-budget
heuristic: the largest ladder candidate whose stated working set fits.
That is safe but blind — on real hardware the best candidate depends on
how the block shape interacts with double buffering, grid residue and
the MXU/VPU mix, none of which the byte count sees.  This module adds
the empirical layer:

* ``tuned_block_n(kernel, precision, dims, vmem_bytes, ...)`` — the
  trace-time lookup every ops.py wrapper consults.  Cache hit → the
  measured winner (re-validated against the wrapper's CURRENT budget
  formula, so a stale entry can never oversubscribe VMEM); miss,
  corrupt file, stale schema version, or illegal entry → silent
  fall-through to ``pick_block_n``.  The lookup is pure host-side
  Python on static ints: consulting the cache never adds device work.
* ``autotune(kernel, precision, dims, run, vmem_bytes, ...)`` — the
  measurement pass; only its tests call it.  For each
  sublane-legal candidate that fits the budget it times ``run(block_n)``
  through the *public wrapper* — so the measured
  path includes padding and dispatch, the thing callers actually pay —
  and persists the winner.  A warm cache short-circuits before any
  measurement: the second invocation performs zero runs (asserted in
  tests via ``measurement_runs()``).

Cache file
----------
Versioned JSON at ``$REPRO_TUNING_CACHE``, one entry per device kind
(``jax.devices()[0].device_kind``) per key::

    {"version": 1,
     "entries": {"TPU v5 lite": {"filter_gains|bf16|dp=1024,kp=128,bp=128,m=8,g=1,nb=4096":
                                 {"block_n": 512, "us_per_call": 1234.5}}}}

Without ``$REPRO_TUNING_CACHE`` no file is read and every wrapper takes
the ``pick_block_n`` heuristic, so a file outside the checkout can never
decide what gets compiled; ``autotune`` then refuses to run, since it
would have nowhere to keep its winners.

Keys bucket shapes exactly like the compiled-launch buckets the
wrappers already produce — padded dims plus the candidate count rounded
to the largest ladder candidate (``nb`` must not depend on the chosen
block_n, or the key would be circular).  Writes are atomic
(tmp + replace) and loads are memoized on (path, mtime) so an external
edit or corruption is picked up on the next lookup.
"""

from __future__ import annotations

import json
import os
import tempfile
import time
from pathlib import Path
from typing import Callable, Mapping

from repro.kernels.common import (
    BLOCK_N_CANDIDATES,
    LANE,
    VMEM_BUDGET,
    pick_block_n,
    resolve_precision,
)

SCHEMA_VERSION = 1
ENV_VAR = "REPRO_TUNING_CACHE"
# Measurement ladder: the pick_block_n ladder plus larger/intermediate
# shapes worth trying when measurement (not a byte heuristic) decides.
DEFAULT_TUNE_CANDIDATES = (1024, 768, 512, 384, 256, 128)

# (path, mtime_ns) → parsed entries; invalidated automatically when the
# file is rewritten (or corrupted) because the mtime moves.
_LOAD_CACHE: dict[tuple[str, int], dict] = {}
# Total timed candidate runs this process — tests assert a warm cache
# performs zero of these.
_MEASUREMENT_RUNS = 0


def cache_path() -> Path | None:
    """The cache file named by ``$REPRO_TUNING_CACHE``, or None."""
    path = os.environ.get(ENV_VAR)
    return Path(path) if path else None


def device_kind() -> str:
    """The table a cache entry belongs to: winners measured on one chip
    generation say nothing about another."""
    import jax

    return jax.devices()[0].device_kind


def shape_key(kernel: str, precision: str | None, dims: Mapping[str, int]) -> str:
    """Bucket key for one tuned configuration.

    ``dims`` holds the wrapper's padded static dims (dp, kp, ...) plus
    ``nb`` — the candidate count rounded up to the largest ladder
    candidate, NOT to the chosen block_n (the key must not depend on
    the answer).  Sorted for stability.
    """
    body = ",".join(f"{k}={int(v)}" for k, v in sorted(dims.items()))
    return f"{kernel}|{resolve_precision(precision)}|{body}"


def bucket_n(n: int, candidates: tuple[int, ...] = DEFAULT_TUNE_CANDIDATES) -> int:
    """Round the candidate count to its launch bucket for the cache key."""
    m = max(candidates)
    return ((int(n) + m - 1) // m) * m


def _validate(payload) -> dict:
    """Return payload['entries'] iff the schema is the one we write."""
    if not isinstance(payload, dict) or payload.get("version") != SCHEMA_VERSION:
        raise ValueError("unknown tuning-cache schema")
    entries = payload.get("entries")
    if not isinstance(entries, dict):
        raise ValueError("malformed tuning-cache entries")
    for kind, table in entries.items():
        if not isinstance(kind, str) or not isinstance(table, dict):
            raise ValueError("malformed tuning-cache device table")
        for key, rec in table.items():
            if not isinstance(key, str) or not isinstance(rec, dict):
                raise ValueError("malformed tuning-cache record")
            if not isinstance(rec.get("block_n"), int):
                raise ValueError("malformed tuning-cache block_n")
    return entries


def _load_entries(path: Path | None = None) -> dict:
    """Parsed cache entries; {} on any miss/corruption (never raises)."""
    path = path or cache_path()
    if path is None:
        return {}
    try:
        mtime = path.stat().st_mtime_ns
    except OSError:
        return {}
    memo_key = (str(path), mtime)
    if memo_key in _LOAD_CACHE:
        return _LOAD_CACHE[memo_key]
    try:
        entries = _validate(json.loads(path.read_text()))
    except Exception:
        entries = {}
    _LOAD_CACHE.clear()  # one live file per process; drop stale mtimes
    _LOAD_CACHE[memo_key] = entries
    return entries


def _store_entry(key: str, block_n: int, us_per_call: float, path: Path | None = None) -> None:
    """Merge one winner into the cache file atomically."""
    path = path or cache_path()
    entries = dict(_load_entries(path))
    kind = device_kind()
    table = dict(entries.get(kind, {}))
    table[key] = {"block_n": int(block_n), "us_per_call": float(us_per_call)}
    entries[kind] = table
    payload = {"version": SCHEMA_VERSION, "entries": entries}
    path.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=str(path.parent), suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def cached_block_n(
    kernel: str,
    precision: str | None,
    dims: Mapping[str, int],
) -> int | None:
    """Raw cache lookup: the stored winner or None. No validation."""
    entries = _load_entries()
    if not entries:
        return None
    rec = entries.get(device_kind(), {}).get(shape_key(kernel, precision, dims))
    return None if rec is None else rec["block_n"]


def tuned_block_n(
    kernel: str,
    precision: str | None,
    dims: Mapping[str, int],
    vmem_bytes: Callable[[int], int],
    *,
    budget: int = VMEM_BUDGET,
    candidates: tuple[int, ...] = BLOCK_N_CANDIDATES,
) -> int:
    """Block size for one launch: tuned winner if cached and still
    legal under the wrapper's CURRENT budget formula, else
    ``pick_block_n``.  This is the single entry point the ops wrappers
    call; it must stay cheap (host-side dict lookups on static ints).
    """
    bn = cached_block_n(kernel, precision, dims)
    if (
        bn is not None
        and bn > 0
        and bn % LANE == 0
        and vmem_bytes(bn) <= budget
    ):
        return bn
    return pick_block_n(vmem_bytes, budget=budget, candidates=candidates)


def measurement_runs() -> int:
    """Timed candidate runs so far in this process (warm-cache tests
    assert this does not move across a second autotune call)."""
    return _MEASUREMENT_RUNS


def _time_once(run: Callable[[int], object], block_n: int, *, warmup: int, iters: int) -> float:
    """Median-free mean µs/call of ``run(block_n)``, post-warmup."""
    global _MEASUREMENT_RUNS
    import jax

    for _ in range(warmup):
        jax.block_until_ready(run(block_n))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(run(block_n))
    dt = (time.perf_counter() - t0) / max(iters, 1)
    _MEASUREMENT_RUNS += 1
    return dt * 1e6


def autotune(
    kernel: str,
    precision: str | None,
    dims: Mapping[str, int],
    run: Callable[[int], object],
    vmem_bytes: Callable[[int], int],
    *,
    budget: int = VMEM_BUDGET,
    candidates: tuple[int, ...] = DEFAULT_TUNE_CANDIDATES,
    warmup: int = 1,
    iters: int = 3,
    force: bool = False,
) -> int:
    """Measure the legal candidates for one configuration and persist
    the winner.  ``run(block_n)`` must execute the kernel end to end
    through its public wrapper (so padding/dispatch are inside the
    timed region).  Warm cache → returns the stored winner with ZERO
    measurement runs unless ``force``.  Needs ``$REPRO_TUNING_CACHE``.
    """
    if cache_path() is None:
        raise ValueError(
            f"autotune keeps its winners in ${ENV_VAR}; set it to a file"
        )
    key = shape_key(kernel, precision, dims)
    if not force:
        cached = cached_block_n(kernel, precision, dims)
        if cached is not None:
            return cached
    legal = [
        bn for bn in candidates if bn % LANE == 0 and vmem_bytes(bn) <= budget
    ]
    if not legal:
        legal = [pick_block_n(vmem_bytes, budget=budget)]
    timings = {bn: _time_once(run, bn, warmup=warmup, iters=iters) for bn in legal}
    winner = min(timings, key=timings.get)
    _store_entry(key, winner, timings[winner])
    return winner
