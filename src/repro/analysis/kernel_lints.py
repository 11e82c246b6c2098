"""Kernel lints (DAK101-103): static checks on the Pallas launch geometry.

The direct-access kernels stream remote tiles straight into VMEM scratch,
so three things must hold *statically* for every (family, offload ratio,
mesh) the engine can serve:

- DAK101 — the per-grid-step VMEM working set (operand blocks + windowed
  DMA scratch + accumulators) fits the hardware profile's ``vmem_bytes``.
  The footprint formulas live next to each kernel
  (``kernels.*.vmem_footprint_bytes``) so this lint checks the kernel's own
  arithmetic, not a stale copy.
- DAK102 — TMA-style alignment/divisibility: realized remote extents are
  multiples of the effective alignment (including the ``lcm(align, P)``
  mesh rounding), tiers conserve the full dimension, and every launch that
  takes the kernel path satisfies the kernel's block-divisibility
  preconditions (the async-copy descriptors slice ``block`` -sized windows;
  a ragged edge would read out of bounds).
- DAK103 — grid coverage: the grid tiles the padded operand exactly (no
  out-of-bounds tiles, no dead blocks) and the host-first schedule arrays
  are permutations (a duplicated entry computes one tile twice and leaves
  another unwritten).

Checks take plain launch descriptors, so seeded-violation fixtures can
feed broken geometry without building real kernels.
"""
from __future__ import annotations

import dataclasses
import importlib
import math
from typing import Any

import numpy as np

from repro.analysis.findings import Finding
from repro.core import tiering
from repro.core.engine import TieringPlan
from repro.core.hardware import HardwareSpec
from repro.kernels import splitk_flashattn, splitk_gemm

# `repro.kernels.__init__` re-exports the jitted `flash_prefill` *function*,
# which shadows the submodule on attribute import; resolve the module itself
# (the footprint helper lives there).
flash_prefill = importlib.import_module("repro.kernels.flash_prefill")


@dataclasses.dataclass(frozen=True)
class GemmLaunch:
    """Geometry of one ``splitk_gemm`` dispatch (already padded to blocks).
    A block left None is the kernel's own choice for these shapes
    (`splitk_gemm.gemm_blocks`)."""
    name: str
    m: int
    k: int
    n_loc: int
    n_rem: int
    block_m: int | None = None
    block_n: int | None = None
    block_k: int | None = None
    window: int = splitk_gemm.DEFAULT_WINDOW
    dtype_bytes: int = 4

    def __post_init__(self):
        auto = splitk_gemm.gemm_blocks(self.m, self.k, self.n_loc, self.n_rem,
                                       self.dtype_bytes)
        for field, v in zip(("block_m", "block_n", "block_k"), auto):
            if getattr(self, field) is None:
                object.__setattr__(self, field, v)


@dataclasses.dataclass(frozen=True)
class AttnLaunch:
    """Geometry of one decode-attention dispatch.

    ``kind`` is "paged" (page-table-indexed pools; ``chunk`` = page size,
    ``n_chunks`` = max pages per slot) or "batch" (batch-split caches;
    ``chunk`` = block_s, ``n_chunks`` = S / block_s)."""
    name: str
    kind: str
    h: int
    kh: int
    hd: int
    chunk: int
    n_chunks: int
    window: int
    dtype_bytes: int = 4


@dataclasses.dataclass(frozen=True)
class PrefillLaunch:
    """Geometry of one ``flash_prefill`` dispatch."""
    name: str
    hd: int
    tq: int
    tk: int
    block_q: int = flash_prefill.DEFAULT_BLOCK_Q
    block_k: int = flash_prefill.DEFAULT_BLOCK_K
    dtype_bytes: int = 4


def check_gemm_launch(launch: GemmLaunch, hw: HardwareSpec, *,
                      where: str = "kernel") -> list[Finding]:
    site = f"{where}.gemm[{launch.name}]"
    out: list[Finding] = []
    bm, bn, bk = launch.block_m, launch.block_n, launch.block_k
    if min(bm, bn, bk) < 1 or launch.window < 1:
        out.append(Finding("DAK102", site,
                           f"degenerate blocks ({bm},{bn},{bk}) or window "
                           f"{launch.window}"))
        return out
    # DAK102: the kernel's own alignment precondition (its ValueError).
    misaligned = [
        f"{lbl}={v}%{blk}" for lbl, v, blk in (
            ("M", launch.m, bm), ("K", launch.k, bk),
            ("N_loc", launch.n_loc, bn), ("N_rem", launch.n_rem, bn))
        if v % blk
    ]
    if misaligned:
        out.append(Finding(
            "DAK102", site,
            f"block-misaligned extents ({', '.join(misaligned)}): the DMA "
            "descriptors slice block-sized windows, a ragged edge reads OOB"))
        return out
    # DAK101: windowed VMEM working set vs the hardware profile.
    fp = splitk_gemm.vmem_footprint_bytes(
        launch.m, launch.k, launch.n_loc + launch.n_rem,
        block_m=bm, block_n=bn, block_k=bk,
        window=launch.window, dtype_bytes=launch.dtype_bytes)
    if fp > hw.vmem_bytes:
        out.append(Finding(
            "DAK101", site,
            f"per-block VMEM footprint {fp / 1e6:.2f} MB exceeds "
            f"{hw.name} budget {hw.vmem_bytes / 1e6:.2f} MB",
            context={"footprint_bytes": fp, "vmem_bytes": hw.vmem_bytes}))
    # DAK103: the grid tiles M x (N_loc + N_rem) exactly and the host-first
    # schedule is a permutation of the tile ids.
    n_tiles = launch.n_loc // bn + launch.n_rem // bn
    grid_cells = (launch.m // bm) * n_tiles
    want_cells = (launch.m * (launch.n_loc + launch.n_rem)) // (bm * bn)
    if grid_cells != want_cells:
        out.append(Finding(
            "DAK103", site,
            f"grid covers {grid_cells} tiles but the output has "
            f"{want_cells} (OOB or dead blocks)"))
    order = splitk_gemm.host_first_order(launch.n_loc // bn, launch.n_rem // bn)
    out.extend(check_order_permutation(order, n_tiles, where=site))
    return out


def check_attn_launch(launch: AttnLaunch, hw: HardwareSpec, *,
                      where: str = "kernel") -> list[Finding]:
    site = f"{where}.attn[{launch.name}]"
    out: list[Finding] = []
    if launch.chunk < 1 or launch.window < 1 or launch.n_chunks < 1:
        out.append(Finding("DAK102", site,
                           f"degenerate launch (chunk={launch.chunk}, "
                           f"window={launch.window}, n_chunks={launch.n_chunks})"))
        return out
    if launch.h % launch.kh:
        out.append(Finding("DAK102", site,
                           f"q heads {launch.h} not divisible by kv heads "
                           f"{launch.kh} (group-major GQA reshape)"))
        return out
    if launch.kind == "paged":
        fp = splitk_flashattn.paged_vmem_footprint_bytes(
            launch.h, launch.kh, launch.hd, launch.chunk, launch.n_chunks,
            window=launch.window, dtype_bytes=launch.dtype_bytes)
    else:
        fp = splitk_flashattn.vmem_footprint_bytes(
            launch.h, launch.kh, launch.hd, launch.chunk * launch.n_chunks,
            block_s=launch.chunk, window=launch.window,
            dtype_bytes=launch.dtype_bytes)
    if fp > hw.vmem_bytes:
        out.append(Finding(
            "DAK101", site,
            f"per-block VMEM footprint {fp / 1e6:.2f} MB exceeds "
            f"{hw.name} budget {hw.vmem_bytes / 1e6:.2f} MB",
            context={"footprint_bytes": fp, "vmem_bytes": hw.vmem_bytes}))
    return out


def check_prefill_launch(launch: PrefillLaunch, hw: HardwareSpec, *,
                         where: str = "kernel") -> list[Finding]:
    site = f"{where}.prefill[{launch.name}]"
    out: list[Finding] = []
    if launch.tq % launch.block_q or launch.tk % launch.block_k:
        out.append(Finding(
            "DAK102", site,
            f"T={launch.tq}/{launch.tk} not multiples of blocks "
            f"{launch.block_q}/{launch.block_k}"))
        return out
    fp = flash_prefill.vmem_footprint_bytes(
        launch.hd, block_q=launch.block_q, block_k=launch.block_k,
        dtype_bytes=launch.dtype_bytes)
    if fp > hw.vmem_bytes:
        out.append(Finding(
            "DAK101", site,
            f"per-block VMEM footprint {fp / 1e6:.2f} MB exceeds "
            f"{hw.name} budget {hw.vmem_bytes / 1e6:.2f} MB"))
    # DAK103: causal block-skip must still visit every k-block at or below
    # the diagonal — coverage is exact iff the grid is the full cross
    # product, which the wrapper builds from the checked divisibility.
    return out


def check_order_permutation(order: np.ndarray, n: int, *,
                            where: str = "kernel") -> list[Finding]:
    """DAK103 core: a schedule array must be a permutation of range(n) —
    the out-spec routes each grid step's write through it, so a duplicate
    writes one tile twice and leaves another dead."""
    order = np.asarray(order)
    if order.shape != (n,) or sorted(order.tolist()) != list(range(n)):
        return [Finding(
            "DAK103", f"{where}.order",
            f"schedule {order.tolist()} is not a permutation of 0..{n - 1} "
            "(dead or doubly-written tiles)")]
    return []


def check_paged_slot_order(tier: np.ndarray, lens: np.ndarray,
                           page_size: int, *, where: str = "kernel") -> list[Finding]:
    """DAK103 for the paged attention schedule: ``host_first_slot_order``
    must permute the slot ids for any tier/lens state."""
    import jax.numpy as jnp

    order = np.asarray(splitk_flashattn.host_first_slot_order(
        jnp.asarray(tier, jnp.int32), jnp.asarray(lens, jnp.int32), page_size))
    return check_order_permutation(order, tier.shape[0],
                                   where=f"{where}.paged_slot_order")


def _dtype_name(dtype_bytes: int) -> str:
    return {2: "bfloat16", 4: "float32", 8: "float64"}.get(dtype_bytes,
                                                           "float32")


def check_autotune_table(
        entries: list[dict[str, Any]], hw: HardwareSpec | None = None, *,
        where: str = "autotune", default_window: int = 2) -> list[Finding]:
    """DAK101-103 over a persisted autotune table (the JSON cache written
    by `kernels.autotune.Autotuner.save`): rebuild each tuned winner's
    launch descriptor from its (op, shape, config) entry and run the same
    lints the verifier applies to the module defaults — so a hand-edited
    or stale cache can never smuggle an over-VMEM or misaligned tile past
    the static checks.

    ``hw`` overrides the per-entry hardware profile (cross-check a table
    against a different budget); by default each entry is linted against
    the profile it was tuned for.  Entries with ``config: null`` are
    negative-cache markers (no candidate survived the sweep) — nothing is
    dispatched for them, so they are skipped.  ``default_window`` supplies
    the in-flight window for ops whose config does not carry one (the
    paged attention entries tune the window itself as ``slots``)."""
    from repro.core.hardware import SYSTEMS

    out: list[Finding] = []
    for i, d in enumerate(entries):
        op = d.get("op")
        config = d.get("config")
        if config is None:
            continue
        site = f"{where}.table[{i}:{op}]"
        ehw = hw if hw is not None else SYSTEMS.get(str(d.get("hw")))
        if ehw is None:
            out.append(Finding("DAK102", site,
                               f"unknown hardware profile {d.get('hw')!r}"))
            continue
        try:
            shape = [int(s) for s in d["shape"]]
            db = int(np.dtype(d.get("dtype", "float32")).itemsize)
            if op == "splitk_gemm":
                m, k, n_loc, n_rem = shape
                bm, bn, bk = (int(config["block_m"]), int(config["block_n"]),
                              int(config["block_k"]))
                out.extend(check_gemm_launch(GemmLaunch(
                    name=str(op), m=-(-m // bm) * bm, k=-(-k // bk) * bk,
                    n_loc=n_loc, n_rem=n_rem, block_m=bm, block_n=bn,
                    block_k=bk, window=default_window, dtype_bytes=db),
                    ehw, where=site))
            elif op == "splitk_flashattn":
                h, kh, hd, s = shape
                bs = int(config["block_s"])
                if bs < 1 or s % bs:
                    out.append(Finding(
                        "DAK102", site,
                        f"S={s} not a multiple of tuned block_s={bs}"))
                    continue
                out.extend(check_attn_launch(AttnLaunch(
                    name=str(op), kind="batch", h=h, kh=kh, hd=hd, chunk=bs,
                    n_chunks=s // bs, window=default_window, dtype_bytes=db),
                    ehw, where=site))
            elif op == "paged_splitk_flashattn":
                h, kh, hd, page_size, max_pages = shape
                out.extend(check_attn_launch(AttnLaunch(
                    name=str(op), kind="paged", h=h, kh=kh, hd=hd,
                    chunk=page_size, n_chunks=max_pages,
                    window=int(config["slots"]), dtype_bytes=db),
                    ehw, where=site))
            elif op == "flash_prefill":
                hd, tq, tk = shape
                bq, bk = int(config["block_q"]), int(config["block_k"])
                out.extend(check_prefill_launch(PrefillLaunch(
                    name=str(op), hd=hd, tq=-(-tq // bq) * bq,
                    tk=-(-tk // bk) * bk, block_q=bq, block_k=bk,
                    dtype_bytes=db), ehw, where=site))
            else:
                out.append(Finding("DAK102", site, f"unknown op {op!r}"))
        except (KeyError, ValueError, TypeError) as exc:
            out.append(Finding("DAK102", site, f"malformed entry: {exc}"))
    return out


# --------------------------------------------------------------------------
# Building launch descriptors from a plan + abstract operand shapes
# --------------------------------------------------------------------------
def check_alignment_invariants(
        plan: TieringPlan, shapes: dict[str, tuple[int, ...]], *,
        align: int, where: str = "plan") -> list[Finding]:
    """DAK102 over the partitioner's postconditions: every realized remote
    extent is a multiple of ``lcm(align, P)`` ("execution-wave alignment",
    paper §4.1) and the tiers conserve the dimension exactly."""
    out: list[Finding] = []
    mesh_div = (plan.mesh.n_devices
                if plan.mesh is not None and plan.mesh.n_devices > 1 else 1)
    for od in plan.registry:
        ratio = plan.op_ratios.get(od.op, 0.0)
        if ratio <= 0.0 or od.path_str not in shapes:
            continue
        dim = shapes[od.path_str][od.axis]
        align_eff = od.align if od.align is not None else align
        align_eff = math.lcm(align_eff, mesh_div)
        n_local, n_remote = tiering.split_sizes(dim, ratio, align_eff)
        site = f"{where}.split[{od.path_str}]"
        if n_local + n_remote != dim:
            out.append(Finding("DAK102", site,
                               f"tiers leak the dimension: {n_local} + "
                               f"{n_remote} != {dim}"))
        if n_remote % align_eff:
            out.append(Finding(
                "DAK102", site,
                f"remote extent {n_remote} not a multiple of the effective "
                f"alignment {align_eff} (align={od.align or align}, "
                f"P={mesh_div})"))
        if not 0 <= n_remote <= dim:
            out.append(Finding("DAK102", site,
                               f"remote extent {n_remote} outside [0, {dim}]"))
    return out


def describe_launches(
        cfg, plan: TieringPlan, shapes: dict[str, tuple[int, ...]], *,
        align: int, batch: int, max_len: int,
        dtype_bytes: int = 4, tuner: Any = None,
) -> tuple[list[GemmLaunch], list[AttnLaunch], list[PrefillLaunch]]:
    """Replay the serving engine's kernel dispatch decisions statically:
    which registered operands reach ``splitk_gemm`` (block-aligned tiers on
    the last axis — everything else takes the per-tier oracle), plus the
    decode-attention and prefill launches implied by the KV page plan.

    With a ``tuner`` (`kernels.autotune.Autotuner`) the descriptors carry
    the *autotuned* block shapes — the exact geometry the engine would
    dispatch with that tuner attached — so the DAK101-103 checks run over
    tuned launches, not just the kernels' own choices."""
    window = max(1, plan.window.n_inflight)
    dt = _dtype_name(dtype_bytes)
    gemms: list[GemmLaunch] = []
    mesh_div = (plan.mesh.n_devices
                if plan.mesh is not None and plan.mesh.n_devices > 1 else 1)
    for od in plan.registry:
        ratio = plan.op_ratios.get(od.op, 0.0)
        if ratio <= 0.0 or od.path_str not in shapes:
            continue
        shape = shapes[od.path_str]
        axis = od.axis % len(shape)
        if axis != len(shape) - 1:
            continue  # expert-stack splits run per-tier einsum, not splitk_gemm
        dim = shape[-1]
        k = shape[-2]
        align_eff = math.lcm(od.align if od.align is not None else align, mesh_div)
        n_loc, n_rem = tiering.split_sizes(dim, ratio, align_eff)
        bm, bn, bk = splitk_gemm.gemm_blocks(batch, k, n_loc, n_rem,
                                             dtype_bytes)
        if tuner is not None and n_loc and n_rem:
            tuned = tuner.best_gemm(batch, k, n_loc, n_rem, dt)
            if tuned is not None:
                bm, bn, bk = (tuned["block_m"], tuned["block_n"],
                              tuned["block_k"])
        if n_rem == 0 or n_loc == 0 or n_loc % bn or n_rem % bn:
            continue  # oracle fallback (per-tier, direct-access-clean)
        gemms.append(GemmLaunch(
            name=od.path_str,
            m=-(-batch // bm) * bm,          # tiered_matmul pads M and K
            k=-(-k // bk) * bk,
            n_loc=n_loc, n_rem=n_rem,
            block_m=bm, block_n=bn, block_k=bk,
            window=window, dtype_bytes=dtype_bytes))

    attns: list[AttnLaunch] = []
    prefills: list[PrefillLaunch] = []
    kp = plan.kv_pages
    if kp is not None and getattr(cfg, "has_decoder", True):
        if getattr(cfg, "use_mla", False):
            kh, hd = 1, cfg.kv_lora_rank + cfg.rope_head_dim
        else:
            kh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        max_pages = -(-max_len // kp.page_size)
        paged_window = window
        if tuner is not None:
            tuned = tuner.best_paged(cfg.n_heads, kh, hd, kp.page_size,
                                     max_pages, 0.5, dt)
            if tuned is not None:
                paged_window = max(1, min(window, tuned["slots"]))
        attns.append(AttnLaunch(
            name="paged_decode", kind="paged", h=cfg.n_heads, kh=kh, hd=hd,
            chunk=kp.page_size, n_chunks=max_pages, window=paged_window,
            dtype_bytes=dtype_bytes))
        bs = splitk_flashattn.DEFAULT_BLOCK_S
        s = -(-max_len // bs) * bs
        if tuner is not None:
            tuned = tuner.best_attn(cfg.n_heads, kh, hd, s, 0.5, dt)
            if tuned is not None:
                bs = tuned["block_s"]
        attns.append(AttnLaunch(
            name="batch_decode", kind="batch", h=cfg.n_heads, kh=kh, hd=hd,
            chunk=bs, n_chunks=s // bs, window=window,
            dtype_bytes=dtype_bytes))
        bq = flash_prefill.DEFAULT_BLOCK_Q
        bkp = flash_prefill.DEFAULT_BLOCK_K
        t = -(-max_len // bq) * bq
        if tuner is not None:
            tuned = tuner.best_prefill(cfg.resolved_head_dim, t, t, dt)
            if tuned is not None:
                bq, bkp = tuned["block_q"], tuned["block_k"]
        prefills.append(PrefillLaunch(
            name="flash_prefill", hd=cfg.resolved_head_dim, tq=t, tk=t,
            block_q=bq, block_k=bkp,
            dtype_bytes=dtype_bytes))
    return gemms, attns, prefills


def check_kernels(cfg, plan: TieringPlan, hw: HardwareSpec,
                  shapes: dict[str, tuple[int, ...]], *,
                  align: int, batch: int = 4, max_len: int = 256,
                  where: str = "kernel", tuner: Any = None) -> list[Finding]:
    """All kernel lints for one (cfg, plan) point of the matrix.  With a
    ``tuner`` the launch descriptors carry its autotuned block shapes."""
    out = check_alignment_invariants(plan, shapes, align=align, where=where)
    gemms, attns, prefills = describe_launches(
        cfg, plan, shapes, align=align, batch=batch, max_len=max_len,
        tuner=tuner)
    for g in gemms:
        out.extend(check_gemm_launch(g, hw, where=where))
    for a in attns:
        out.extend(check_attn_launch(a, hw, where=where))
    for p in prefills:
        out.extend(check_prefill_launch(p, hw, where=where))
    if plan.kv_pages is not None:
        # Representative ragged page-table states: all-local, all-remote,
        # mixed — the schedule must permute the slots in every one.
        ps = plan.kv_pages.page_size
        mp = max(1, -(-max_len // ps))
        lens = np.arange(1, batch + 1) * ps // 2
        for tag, tier in (("local", np.zeros((batch, mp), np.int32)),
                          ("remote", np.ones((batch, mp), np.int32)),
                          ("mixed", np.arange(batch * mp).reshape(batch, mp) % 2)):
            out.extend(check_paged_slot_order(
                tier, lens, ps, where=f"{where}[{tag}]"))
    return out
