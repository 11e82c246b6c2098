"""Shape-keyed kernel autotuner for the direct-access kernels.

The SplitK kernels pick their tiles without a cost model — the GEMM
derives its blocks from the call's shapes and the DMA chunk size
(``splitk_gemm.gemm_blocks``), the attention kernels ship one
``DEFAULT_BLOCK_S`` — whatever the arch, offload ratio, or link profile;
but link-bound decode is exactly the regime where tile shape
matters: every remote tile pays a fixed DMA-issue cost that only the
in-flight window amortizes, and the padded-block waste of an oversized
tile is charged at full link bandwidth.  This module sweeps the candidate
block/stage shapes for each kernel under a deterministic extension of the
paper's EB cost model (per-transfer issue latency on top of the
bandwidth terms, pipeline fill for the windowed stream) and caches the
winner per

    (op, operand shape, dtype, offload-ratio bucket, hardware profile)

so a PCIe-class host link (``tpu_v5e``, 32 GB/s) and the 450 GB/s GH200
link can — and do — pick different winners for the same operand.

Every candidate is validated against the kernel's own
``vmem_footprint_bytes`` and the DAK101-103 lints
(`repro.analysis.kernel_lints`) before it may win, so a tuned shape can
never violate the VMEM/alignment invariants the static verifier checks.
Winners are cached in-process and persistable to a JSON table
(:meth:`Autotuner.save` / :meth:`Autotuner.load`) consumed by
``launch/serve.py --autotune-cache`` and ``benchmarks/kernel_micro.py``;
the sweep is pure arithmetic (no kernel launches), so reloading the table
reproduces the winners bit-for-bit.

Note on numerics: a different ``block_k`` / ``block_s`` regroups the
split-K accumulation / online-softmax chunking, so tuned outputs are
bitwise-identical *per table* (eager and jitted paths share the tuner),
not across tables tuned for different hardware.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
from typing import Any

import numpy as np

from repro.core.hardware import SYSTEMS, TPU_V5E, HardwareSpec

# Candidate tile extents.  All lane-aligned multiples of the kernels'
# minimum block (128); the sweep filters by the operand's divisibility and
# by the DAK101-103 lints before scoring.
BLOCK_CANDIDATES = (128, 256, 512)
# Candidate in-flight DMA slot counts for the paged attention stream (the
# page size itself is the chunk shape, fixed by the cache layout).
SLOT_CANDIDATES = (1, 2, 4, 8)

# Fixed per-transfer issue cost of one async copy (descriptor setup + DMA
# engine turnaround).  These are the EB-model extension that makes tile
# shape matter at all: pure bandwidth terms are tile-size-invariant.
HOST_ISSUE_S = 2e-6
HBM_ISSUE_S = 0.5e-6

TABLE_VERSION = 1

Key = tuple  # (op, shape-tuple, dtype, ratio-bucket, hw-name)


def _ratio_bucket(n_loc: int, n_rem: int) -> float:
    """Offload ratio bucketed to one decimal (the key granularity)."""
    total = n_loc + n_rem
    return round(n_rem / total, 1) if total else 0.0


def _dtype_bytes(dtype: str) -> int:
    return int(np.dtype(dtype).itemsize)


def _pad(v: int, mult: int) -> int:
    return -(-v // mult) * mult


@dataclasses.dataclass(frozen=True)
class Entry:
    """One tuned winner: the config that won the sweep plus its modeled
    latency (microseconds) under the key's hardware profile."""
    op: str
    shape: tuple[int, ...]
    dtype: str
    ratio: float
    hw: str
    config: dict[str, int] | None      # None: no candidate survived the lints
    modeled_us: float

    def key(self) -> Key:
        return (self.op, tuple(self.shape), self.dtype, self.ratio, self.hw)

    def to_json(self) -> dict[str, Any]:
        return {"op": self.op, "shape": list(self.shape), "dtype": self.dtype,
                "ratio": self.ratio, "hw": self.hw, "config": self.config,
                "modeled_us": self.modeled_us}

    @classmethod
    def from_json(cls, d: dict[str, Any]) -> "Entry":
        return cls(op=d["op"], shape=tuple(int(s) for s in d["shape"]),
                   dtype=d["dtype"], ratio=float(d["ratio"]), hw=d["hw"],
                   config=(None if d.get("config") is None
                           else {k: int(v) for k, v in d["config"].items()}),
                   modeled_us=float(d["modeled_us"]))


class Autotuner:
    """Sweeps kernel tile shapes under the EB cost model, lint-validated.

    ``sweep=False`` makes the tuner lookup-only: misses return ``None``
    (callers fall back to the kernels' own choice) instead of running a
    sweep — the mode ``--autotune-cache`` without ``--autotune`` uses to
    reproduce a checked-in table without growing it.
    """

    def __init__(self, hw: HardwareSpec = TPU_V5E, *, window: int = 2,
                 sweep: bool = True):
        self.hw = hw
        self.window = max(1, int(window))
        self.sweep = sweep
        self.table: dict[Key, Entry] = {}
        self.hits = 0
        self.misses = 0
        self.sweeps = 0

    # -- cache plumbing ----------------------------------------------------
    def _get(self, key: Key, sweep_fn) -> dict[str, int] | None:
        ent = self.table.get(key)
        if ent is not None:
            self.hits += 1
            return ent.config
        self.misses += 1
        if not self.sweep:
            return None
        self.sweeps += 1
        config, us = sweep_fn()
        self.table[key] = Entry(op=key[0], shape=key[1], dtype=key[2],
                                ratio=key[3], hw=key[4], config=config,
                                modeled_us=us)
        return config

    # -- lint guards (lazy import: analysis imports kernels, not vice versa)
    def _gemm_ok(self, m, k, n_loc, n_rem, bm, bn, bk, db) -> bool:
        from repro.analysis import kernel_lints as KL

        launch = KL.GemmLaunch(
            name="autotune", m=_pad(m, bm), k=_pad(k, bk),
            n_loc=n_loc, n_rem=n_rem, block_m=bm, block_n=bn, block_k=bk,
            window=self.window, dtype_bytes=db)
        return not KL.check_gemm_launch(launch, self.hw, where="autotune")

    def _attn_ok(self, kind, h, kh, hd, chunk, n_chunks, window, db) -> bool:
        from repro.analysis import kernel_lints as KL

        launch = KL.AttnLaunch(
            name="autotune", kind=kind, h=h, kh=kh, hd=hd, chunk=chunk,
            n_chunks=n_chunks, window=window, dtype_bytes=db)
        return not KL.check_attn_launch(launch, self.hw, where="autotune")

    def _prefill_ok(self, hd, tq, tk, bq, bk, db) -> bool:
        from repro.analysis import kernel_lints as KL

        launch = KL.PrefillLaunch(
            name="autotune", hd=hd, tq=_pad(tq, bq), tk=_pad(tk, bk),
            block_q=bq, block_k=bk, dtype_bytes=db)
        return not KL.check_prefill_launch(launch, self.hw, where="autotune")

    # -- cost models (deterministic EB extensions) -------------------------
    def _gemm_cost(self, m, k, n_loc, n_rem, bm, bn, bk, db) -> float:
        """max(host stream, HBM stream, compute) + pipeline fill, with a
        per-transfer issue cost amortized by the in-flight window.  Each
        M-row tile re-streams its weight columns chunk by chunk, so a
        larger ``block_m`` cuts re-streaming while padded extents charge
        the wasted lanes at full bandwidth."""
        hw, w = self.hw, self.window
        mp, kp = _pad(m, bm), _pad(k, bk)
        m_tiles = mp // bm
        rem_xfers = m_tiles * (n_rem // bn) * (kp // bk)
        loc_xfers = m_tiles * (n_loc // bn) * (kp // bk)
        t_host = (m_tiles * kp * n_rem * db) / hw.host.bandwidth \
            + rem_xfers * HOST_ISSUE_S / w
        t_hbm = (m_tiles * kp * n_loc * db + mp * kp * db) / hw.hbm.bandwidth \
            + loc_xfers * HBM_ISSUE_S / w
        t_compute = 2.0 * mp * kp * (n_loc + n_rem) / hw.peak_flops
        fill = min(w, max(1, kp // bk)) * HOST_ISSUE_S
        return max(t_host, t_hbm, t_compute) + fill

    def _attn_cost(self, h, kh, hd, chunk, n_chunks, b_rem_frac, db,
                   window) -> float:
        """Streamed K/V chunks, split across tiers by the remote fraction."""
        hw = self.hw
        kv_bytes = 2.0 * n_chunks * chunk * kh * hd * db
        rem = kv_bytes * b_rem_frac
        loc = kv_bytes - rem
        rem_xfers = max(1, round(n_chunks * b_rem_frac)) * 2
        t_host = rem / hw.host.bandwidth + rem_xfers * HOST_ISSUE_S / window
        t_hbm = loc / hw.hbm.bandwidth \
            + 2 * n_chunks * HBM_ISSUE_S / window
        t_compute = 4.0 * n_chunks * chunk * h * hd / hw.peak_flops
        fill = min(window, n_chunks) * HOST_ISSUE_S
        return max(t_host, t_hbm, t_compute) + fill

    def _prefill_cost(self, hd, tq, tk, bq, bk, db) -> float:
        hw = self.hw
        tqp, tkp = _pad(tq, bq), _pad(tk, bk)
        q_tiles, k_tiles = tqp // bq, tkp // bk
        bytes_streamed = (tqp * hd + q_tiles * 2 * tkp * hd + tqp * hd) * db
        t_hbm = bytes_streamed / hw.hbm.bandwidth \
            + q_tiles * k_tiles * HBM_ISSUE_S
        t_compute = 4.0 * tqp * tkp * hd / hw.peak_flops
        return max(t_hbm, t_compute)

    # -- per-op sweeps -----------------------------------------------------
    def best_gemm(self, m: int, k: int, n_loc: int, n_rem: int,
                  dtype: str = "float32") -> dict[str, int] | None:
        """Winning (block_m, block_n, block_k) for one splitk_gemm shape,
        or None when no candidate divides the tiers / passes the lints
        (callers keep the kernel's derived blocks and the wrapper's own
        fallback)."""
        if n_loc <= 0 or n_rem <= 0:
            return None
        key = ("splitk_gemm", (m, k, n_loc, n_rem), dtype,
               _ratio_bucket(n_loc, n_rem), self.hw.name)

        def sweep():
            db = _dtype_bytes(dtype)
            best, best_t = None, float("inf")
            for bm, bn, bk in itertools.product(
                    BLOCK_CANDIDATES, BLOCK_CANDIDATES, BLOCK_CANDIDATES):
                if n_loc % bn or n_rem % bn:
                    continue
                if not self._gemm_ok(m, k, n_loc, n_rem, bm, bn, bk, db):
                    continue
                t = self._gemm_cost(m, k, n_loc, n_rem, bm, bn, bk, db)
                if t < best_t:        # strict <: ties go to the first
                    best, best_t = {"block_m": bm, "block_n": bn,
                                    "block_k": bk}, t
            return best, (best_t * 1e6 if best is not None else 0.0)

        return self._get(key, sweep)

    def best_attn(self, h: int, kh: int, hd: int, s: int,
                  b_rem_frac: float = 0.5,
                  dtype: str = "float32") -> dict[str, int] | None:
        """Winning block_s for one batch-split splitk_flashattn shape."""
        key = ("splitk_flashattn", (h, kh, hd, s), dtype,
               round(b_rem_frac, 1), self.hw.name)

        def sweep():
            db = _dtype_bytes(dtype)
            best, best_t = None, float("inf")
            for bs in BLOCK_CANDIDATES:
                if s % bs:
                    continue
                if not self._attn_ok("batch", h, kh, hd, bs, s // bs,
                                     self.window, db):
                    continue
                t = self._attn_cost(h, kh, hd, bs, s // bs, b_rem_frac, db,
                                    self.window)
                if t < best_t:
                    best, best_t = {"block_s": bs}, t
            return best, (best_t * 1e6 if best is not None else 0.0)

        return self._get(key, sweep)

    def best_paged(self, h: int, kh: int, hd: int, page_size: int,
                   max_pages: int, rem_frac: float = 0.5,
                   dtype: str = "float32") -> dict[str, int] | None:
        """Winning in-flight slot count for paged_splitk_flashattn (the
        chunk shape is the page size; only the DMA stage depth is free)."""
        key = ("paged_splitk_flashattn", (h, kh, hd, page_size, max_pages),
               dtype, round(rem_frac, 1), self.hw.name)

        def sweep():
            db = _dtype_bytes(dtype)
            best, best_t = None, float("inf")
            for slots in SLOT_CANDIDATES:
                if not self._attn_ok("paged", h, kh, hd, page_size, max_pages,
                                     slots, db):
                    continue
                t = self._attn_cost(h, kh, hd, page_size, max_pages, rem_frac,
                                    db, slots)
                if t < best_t:
                    best, best_t = {"slots": slots}, t
            return best, (best_t * 1e6 if best is not None else 0.0)

        return self._get(key, sweep)

    def best_prefill(self, hd: int, tq: int, tk: int,
                     dtype: str = "float32") -> dict[str, int] | None:
        """Winning (block_q, block_k) for one flash_prefill shape."""
        key = ("flash_prefill", (hd, tq, tk), dtype, 0.0, self.hw.name)

        def sweep():
            db = _dtype_bytes(dtype)
            best, best_t = None, float("inf")
            for bq, bk in itertools.product(BLOCK_CANDIDATES, BLOCK_CANDIDATES):
                if tq % bq or tk % bk:
                    continue
                if not self._prefill_ok(hd, tq, tk, bq, bk, db):
                    continue
                t = self._prefill_cost(hd, tq, tk, bq, bk, db)
                if t < best_t:
                    best, best_t = {"block_q": bq, "block_k": bk}, t
            return best, (best_t * 1e6 if best is not None else 0.0)

        return self._get(key, sweep)

    # -- persistence -------------------------------------------------------
    def save(self, path: str) -> None:
        """Write the in-process table as a JSON cache (sorted keys so the
        file is byte-stable across runs with the same winners)."""
        entries = sorted((e.to_json() for e in self.table.values()),
                         key=lambda d: (d["op"], d["shape"], d["dtype"],
                                        d["ratio"], d["hw"]))
        with open(path, "w") as fh:
            json.dump({"version": TABLE_VERSION, "entries": entries}, fh,
                      indent=2)
            fh.write("\n")

    def load_table(self, path: str) -> int:
        """Merge a JSON cache into the in-process table; returns the number
        of entries loaded.  Loaded winners are served as cache hits — the
        sweep never reruns for a keyed shape, which is what makes a
        checked-in table reproducible."""
        with open(path) as fh:
            data = json.load(fh)
        if data.get("version") != TABLE_VERSION:
            raise ValueError(
                f"autotune table version {data.get('version')!r} "
                f"(want {TABLE_VERSION}) in {path}")
        n = 0
        for d in data["entries"]:
            ent = Entry.from_json(d)
            self.table[ent.key()] = ent
            n += 1
        return n

    @classmethod
    def load(cls, path: str, hw: HardwareSpec | None = None, *,
             window: int = 2, sweep: bool = True) -> "Autotuner":
        """Build a tuner seeded from a JSON cache.  ``hw`` defaults to the
        profile named by the table's entries (all tables written by
        :meth:`save` are single-profile unless merged by hand)."""
        tuner = cls(hw or TPU_V5E, window=window, sweep=sweep)
        tuner.load_table(path)
        if hw is None:
            names = {e.hw for e in tuner.table.values()}
            if len(names) == 1:
                name = next(iter(names))
                if name in SYSTEMS:
                    tuner.hw = SYSTEMS[name]
        return tuner

    # -- validation --------------------------------------------------------
    def validate(self, hw: HardwareSpec | None = None) -> list:
        """Re-lint every cached winner (DAK101-103) against ``hw`` (default:
        each entry's own profile).  Returns findings — empty means every
        tuned shape respects the VMEM/alignment invariants."""
        from repro.analysis.kernel_lints import check_autotune_table

        return check_autotune_table(
            [e.to_json() for e in self.table.values()], hw,
            where="autotune", default_window=self.window)

    def counters(self) -> dict[str, int]:
        return {"entries": len(self.table), "hits": self.hits,
                "misses": self.misses, "sweeps": self.sweeps}
