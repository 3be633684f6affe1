"""Streaming primal extraction: duals → decisions in source-block chunks.

The paper's production story is that the solver's *output* is the dual
vector λ — tiny, cheap to replicate — and the primal decisions are
recovered on demand as x*(λ) via the same blockwise projections
("communicates only dual variables").  This module is the batch half of
that story (DESIGN.md §8): walk every slab in fixed-size source-row
chunks, recover each chunk's x*(λ) through the objective's row-subset
primal op (`MatchingObjective.primal_rows` — the identical per-row sweep
as the solve loop, every formulation / shift hook / Pallas path
included), and either assemble the per-slab decision arrays or stream
them straight to `.npz` shards.

Memory contract: nothing larger than one (chunk_rows, w) block of a
single slab is ever materialized on device beyond λ itself — the full
edge space appears only shard-by-shard on disk (or per-slab on the host
when the caller asks for assembled arrays, which are O(E) decisions, not
O(E·m) gradients).

Chunking is shape-stable: every chunk of a slab runs at the same
(chunk_rows,) index-vector shape, so each (slab, chunk size) pair
compiles exactly one XLA program; the tail chunk clamps its index window
to the last row and the overhang is dropped host-side.  Per-row results
are independent of the batch split, so chunked extraction is BITWISE
equal to the all-at-once `obj.primal(λ)` recovery
(tests/test_primal_serving.py).
"""
from __future__ import annotations

import dataclasses
import os
import threading
import weakref
from typing import Iterator, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.maximizer import hoist_constants

# one jitted row-subset recovery fn per (objective, slab) — shared by the
# streaming extractor AND the allocation server (primal.server), so a query
# for rows the extractor already compiled at that batch shape reuses the
# very same XLA program.  Weak-keyed: dropping the objective drops its fns.
_JIT_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def primal_rows_fn(obj, slab_index: int):
    """The cached jitted `(λ, γ, rows) -> x` row-subset recovery for one
    slab of `obj` (compiled once per distinct `rows` length).

    The function holds only a *weakref* to the objective — a strong
    reference would chain value→key inside the WeakKeyDictionary and make
    every entry immortal (a replaced objective's slabs, plan, and compiled
    executables would leak across `warm_resolve` instance updates).
    """
    per_obj = _JIT_CACHE.get(obj)
    if per_obj is None:
        per_obj = {}
        _JIT_CACHE[obj] = per_obj
    fn = per_obj.get(slab_index)
    if fn is None:
        fn = _RowsFn(weakref.ref(obj), slab_index)
        per_obj[slab_index] = fn
    return fn


class _RowsFn:
    """`(λ, γ, rows) -> obj.primal_rows(λ, γ, slab_index, rows)`, jitted
    per `rows` length with the slab data passed in as an argument rather
    than compiled into the program (`core.maximizer.hoist_constants`)."""

    def __init__(self, obj_ref, slab_index: int):
        self._obj_ref = obj_ref
        self._slab_index = slab_index
        self._by_shape = {}
        self._lock = threading.Lock()   # server threads share this fn

    def __call__(self, lam, gamma, rows):
        lam = jnp.asarray(lam)
        gamma = jnp.asarray(gamma, jnp.float32)
        rows = jnp.asarray(rows)
        key = (lam.shape, lam.dtype, rows.shape, rows.dtype)
        with self._lock:
            entry = self._by_shape.get(key)
            if entry is None:
                obj, si = self._obj_ref(), self._slab_index
                hoisted, consts = hoist_constants(
                    lambda l, g, r: obj.primal_rows(l, g, si, r),
                    lam, gamma, rows)
                entry = self._by_shape[key] = (jax.jit(hoisted), consts)
        fn, consts = entry
        return fn(consts, lam, gamma, rows)


@dataclasses.dataclass(frozen=True)
class PrimalChunk:
    """One extracted source-block: the decisions of `rows` of one slab.

    Arrays are host numpy, already trimmed to the real rows of the chunk
    (the clamped tail overhang is gone).  `x` is (n_chunk, w) with zeros
    on padded edge positions; `dest_idx`/`mask` are the matching slab
    rows, so `(source_ids[r], dest_idx[r, q], x[r, q])` for mask[r, q]
    enumerates the chunk's real allocations.
    """

    slab_index: int
    start: int
    source_ids: np.ndarray     # (n_chunk,)
    dest_idx: np.ndarray       # (n_chunk, w)
    mask: np.ndarray           # (n_chunk, w)
    x: np.ndarray              # (n_chunk, w)


def iter_primal_chunks(obj, lam, gamma, chunk_rows: int = 4096,
                       slab_indices: Optional[Sequence[int]] = None,
                       sampler=None) -> Iterator[PrimalChunk]:
    """Yield x*(λ) chunk by chunk over source-row blocks (module doc).

    `sampler` (a `repro.obs.MemorySampler`) records peak host bytes
    across the streaming loop — the measurement seam ROADMAP item 3's
    out-of-core gate relies on.  None (the default) reads nothing.
    """
    lam = jnp.asarray(lam)
    gamma = jnp.asarray(gamma, jnp.float32)
    sel = range(len(obj.lp.slabs)) if slab_indices is None else slab_indices
    for si in sel:
        slab = obj.lp.slabs[si]
        n = slab.n
        c = min(int(chunk_rows), n)
        chunk_fn = primal_rows_fn(obj, si)
        ids = np.asarray(slab.source_ids)
        dest = np.asarray(slab.dest_idx)
        mask = np.asarray(slab.mask)
        for start in range(0, n, c):
            take = min(c, n - start)
            # fixed-shape window, clamped at the slab end; the duplicate
            # tail rows compute real (row n−1) values and are dropped here
            idx = np.minimum(np.arange(start, start + c), n - 1).astype(
                np.int32)
            x = np.asarray(chunk_fn(lam, gamma, jnp.asarray(idx)))[:take]
            real = idx[:take]
            if sampler is not None:
                sampler.sample(where="extract", it=start)
            yield PrimalChunk(slab_index=si, start=start,
                              source_ids=ids[real], dest_idx=dest[real],
                              mask=mask[real], x=x)


def extract_primal(obj, lam, gamma, chunk_rows: int = 4096,
                   sampler=None) -> List[np.ndarray]:
    """Assembled per-slab decision arrays from the chunked recovery.

    Same return shape as `obj.primal(λ)` (list of (n, w) arrays, host
    numpy) but computed without ever holding more than one chunk on
    device — and bitwise equal to it (sampled or not: the sampler only
    reads procfs/allocator stats between chunks).
    """
    out = [np.zeros(np.asarray(s.c_vals).shape, np.asarray(s.c_vals).dtype)
           for s in obj.lp.slabs]
    for ch in iter_primal_chunks(obj, lam, gamma, chunk_rows,
                                 sampler=sampler):
        out[ch.slab_index][ch.start:ch.start + len(ch.x)] = ch.x
    return out


def _shard_name(slab_index: int, start: int) -> str:
    return f"primal_s{slab_index:03d}_r{start:09d}.npz"


def write_shards(obj, lam, gamma, out_dir: str, chunk_rows: int = 4096,
                 rounder=None, sampler=None) -> List[str]:
    """Stream-extract to `.npz` shards, one per chunk (the export path).

    Each shard holds `slab_index`, `start`, `source_ids`, `dest_idx`,
    `mask`, `x` — and `x_round` when a `rounder(chunk) -> (n, w) array`
    is supplied (chunk-local rounding only; capacity-respecting repair is
    a global pass and lives in `primal.rounding`/`primal.certify`).
    Returns the shard paths in write order.
    """
    os.makedirs(out_dir, exist_ok=True)
    paths = []
    for ch in iter_primal_chunks(obj, lam, gamma, chunk_rows,
                                 sampler=sampler):
        payload = dict(slab_index=np.int64(ch.slab_index),
                       start=np.int64(ch.start),
                       source_ids=ch.source_ids, dest_idx=ch.dest_idx,
                       mask=ch.mask, x=ch.x)
        if rounder is not None:
            payload["x_round"] = np.asarray(rounder(ch))
        path = os.path.join(out_dir, _shard_name(ch.slab_index, ch.start))
        np.savez(path, **payload)
        paths.append(path)
    return paths


def read_shards(paths: Sequence[str], num_slabs: int,
                key: str = "x") -> List[np.ndarray]:
    """Reassemble per-slab decision arrays from `write_shards` output.

    `key` selects which decision array to read ("x" or "x_round").
    Slabs with no shards come back as None (partial exports are legal).

    Defensive against a damaged export (DESIGN.md §12 hardening): a
    missing file, an unreadable/truncated `.npz`, a shard without the
    requested key or the `slab_index`/`start` metadata, an out-of-range
    slab index, or a width mismatch between shards of the same slab all
    raise ValueError NAMING THE OFFENDING SHARD PATH — never a bare
    KeyError/zipfile error from deep inside numpy, and never a silently
    mis-assembled result.
    """
    parts: dict = {}
    for path in paths:
        if not os.path.exists(path):
            raise ValueError(f"shard missing: {path}")
        try:
            z = np.load(path)
        except Exception as e:
            raise ValueError(
                f"shard unreadable (corrupt or truncated): {path} "
                f"({type(e).__name__}: {e})") from e
        with z:
            for field in ("slab_index", "start", key):
                if field not in z.files:
                    raise ValueError(
                        f"shard missing array {field!r}: {path} "
                        f"(has {sorted(z.files)})")
            try:
                si, start = int(z["slab_index"]), int(z["start"])
                arr = z[key]
            except Exception as e:   # a torn member inside a valid zip
                raise ValueError(
                    f"shard unreadable (corrupt or truncated): {path} "
                    f"({type(e).__name__}: {e})") from e
            if not 0 <= si < num_slabs:
                raise ValueError(
                    f"shard slab_index {si} out of range "
                    f"[0, {num_slabs}): {path}")
            if arr.ndim != 2:
                raise ValueError(
                    f"shard {key!r} has shape {arr.shape}, expected "
                    f"(rows, w): {path}")
            parts.setdefault(si, []).append((start, arr, path))
    out: List[Optional[np.ndarray]] = [None] * num_slabs
    for si, chunks in parts.items():
        chunks.sort(key=lambda t: t[0])
        w = chunks[0][1].shape[1]
        for start, arr, path in chunks[1:]:
            if arr.shape[1] != w:
                raise ValueError(
                    f"shard width mismatch in slab {si}: {path} has "
                    f"w={arr.shape[1]}, expected w={w} (from "
                    f"{chunks[0][2]})")
        out[si] = np.concatenate([c for _, c, _ in chunks], axis=0)
    return out
