"""The search space: tunable-kernel registry, candidate generation, and
analytic per-candidate censuses.

One :class:`Tunable` per tunable Pallas kernel (``repro.kernels``):

  * ``flash_attention`` — block_q x block_k x accumulator dtype
  * ``ssm_scan``        — channel tile (block_d)
  * ``wkv6``            — heads-per-grid-cell (block_h, a grid factorization)
  * ``mxu_probe``       — output tile (block_m, block_n)

``candidates`` enumerates MXU-aligned configurations and prunes them
against the hardware constraints carried by the loaded calibration (the
VMEM budget; tile alignment comes from the enumeration itself), always
keeping the default config so a ranking can never be empty.  ``census``
builds the census-shaped dict :meth:`CostModel.predict` prices — pure
arithmetic, no jax, no device — in which the launch config shows up as
issue-overhead (grid cells x inner-loop ops) and as the MXU tile shape,
while FLOPs and HBM bytes stay config-invariant: exactly the trade the
paper's tables let a model arbitrate (bigger tiles amortize issue cost
until the VMEM ladder cuts them off).

Everything here is deterministic: same shapes + same calibration ->
same candidate list in the same order.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

# MXU-aligned block-size ladder (the v5e systolic array is 128x128; 8 is
# the VPU sublane quantum, kept so tiny test shapes still get >1 candidate)
_BLOCK_LADDER = (8, 16, 32, 64, 128, 256, 512)

# fraction of VMEM a kernel instance may claim (scratch/double-buffer slack)
VMEM_FILL = 0.9
DEFAULT_VMEM_BYTES = 128 * 2**20


def vmem_budget_bytes(cal=None, hw=None) -> float:
    """The VMEM capacity candidates are pruned against: the calibration's
    measured 'vmem' rung if present, else the hardware spec, else 128 MiB."""
    if cal is not None:
        for lvl in getattr(cal, "memory_levels", ()):
            if lvl.name == "vmem":
                return float(lvl.capacity_bytes) * VMEM_FILL
    if hw is not None and getattr(hw, "vmem_bytes", 0.0):
        return float(hw.vmem_bytes) * VMEM_FILL
    return DEFAULT_VMEM_BYTES * VMEM_FILL


def _dtype_bytes(dtype: str) -> int:
    return {"f32": 4, "float32": 4, "bf16": 2, "bfloat16": 2, "f16": 2,
            "float16": 2, "s8": 1, "int8": 1}.get(dtype, 4)


def divisor_clamp(value: int, n: int) -> int:
    """Largest launchable block for a divisor-constrained axis: min-clamp
    to the problem size, then fall back to a common divisor when it does
    not divide.  THE one implementation — the kernels (ssm_scan, wkv6, the
    mxu_probe dispatch wrapper) and the candidate clamping both call it,
    so pricing always describes the block that actually launches."""
    v = max(min(int(value), n), 1)
    return v if n % v == 0 else math.gcd(v, n)


def _blocks_upto(limit: int) -> List[int]:
    """Ladder values clamped to the problem size, deduped, ascending."""
    out = sorted({min(b, limit) for b in _BLOCK_LADDER})
    return out or [limit]


def _divisors_from_ladder(n: int) -> List[int]:
    out = sorted({math.gcd(min(b, n), n) for b in _BLOCK_LADDER})
    return [d for d in out if d >= 1]


@dataclass(frozen=True)
class Tunable:
    """One tunable kernel: its default problem/launch shapes, the candidate
    enumerator, the analytic census, and the VMEM footprint model."""
    name: str
    shape_keys: Tuple[str, ...]
    default_shapes: Dict[str, int]
    default_config: Dict[str, Any]
    enumerate_fn: Callable[[Dict[str, int], str], List[Dict[str, Any]]]
    census_fn: Callable[[Dict[str, int], Dict[str, Any], str],
                        Dict[str, Any]]
    vmem_fn: Callable[[Dict[str, int], Dict[str, Any], str], float]

    def normalize_shapes(self, shapes: Optional[Mapping[str, int]]
                         ) -> Dict[str, int]:
        out = dict(self.default_shapes)
        for k, v in (shapes or {}).items():
            if k not in self.shape_keys:
                raise KeyError(
                    f"{self.name}: unknown shape key {k!r} "
                    f"(expected {', '.join(self.shape_keys)})")
            out[k] = int(v)
        return out

    def candidates(self, shapes: Mapping[str, int], dtype: str = "bf16",
                   budget_bytes: Optional[float] = None,
                   allow_low_precision: bool = False
                   ) -> List[Dict[str, Any]]:
        """Enumerate aligned configs, prune over-budget ones, dedupe on the
        effective (clamped) values, and guarantee the default survives.
        ``allow_low_precision`` opens reduced-precision axes (the bf16
        flash-attention accumulator) — off by default so tuning never
        trades numerics for speed without an explicit opt-in."""
        shapes = self.normalize_shapes(shapes)
        budget = budget_bytes if budget_bytes is not None \
            else DEFAULT_VMEM_BYTES * VMEM_FILL
        seen, out = set(), []
        for cand in self.enumerate_fn(shapes, dtype, allow_low_precision):
            # clamp BEFORE deduping: enumeration is shape-agnostic, so two
            # distinct raw candidates (e.g. block_size 256 and 512 at
            # ctx=128) can clamp to the same launched config — deduping on
            # the raw values used to let those duplicates through
            cand = _clamp_config(self.name, shapes,
                                 {**self.default_config, **cand})
            key = tuple(sorted(cand.items()))
            if key in seen:
                continue
            seen.add(key)
            if self.vmem_fn(shapes, cand, dtype) > budget:
                continue
            out.append(cand)
        default = self.effective_default(shapes)
        if not any(c == default for c in out):
            # the default must always be rankable (it is what launches
            # when no tuning entry exists), even past the budget
            out.insert(0, default)
        return out

    def effective_default(self, shapes: Mapping[str, int]) -> Dict[str, Any]:
        """The default config with the same clamping the kernel applies, so
        default-vs-tuned comparisons price what actually launches."""
        shapes = self.normalize_shapes(shapes)
        return _clamp_config(self.name, shapes, self.default_config)

    def census(self, shapes: Mapping[str, int], config: Mapping[str, Any],
               dtype: str = "bf16") -> Dict[str, Any]:
        shapes = self.normalize_shapes(shapes)
        cfg = _clamp_config(self.name, shapes,
                            {**self.default_config, **dict(config)})
        return self.census_fn(shapes, cfg, dtype)

    def vmem_bytes(self, shapes: Mapping[str, int],
                   config: Mapping[str, Any], dtype: str = "bf16") -> float:
        shapes = self.normalize_shapes(shapes)
        cfg = _clamp_config(self.name, shapes,
                            {**self.default_config, **dict(config)})
        return self.vmem_fn(shapes, cfg, dtype)


def _clamp_config(kernel: str, shapes: Mapping[str, int],
                  config: Dict[str, Any]) -> Dict[str, Any]:
    """Mirror the kernels' own clamping (min-with-problem, divisor fallback)
    so candidate dedup and pricing see the launched values."""
    c = dict(config)
    if kernel == "flash_attention":
        # pads ragged tails, so a plain min-clamp matches the kernel
        c["block_q"] = max(min(int(c["block_q"]), shapes["seq_q"]), 1)
        c["block_k"] = max(min(int(c["block_k"]), shapes["seq_kv"]), 1)
    elif kernel == "paged_attention":
        # pages pad the context tail; any size up to the context launches
        c["block_size"] = max(min(int(c["block_size"]), shapes["ctx"]), 1)
        # a split must cover >= 1 page (ops.paged_attention clamps to the
        # table width, which is ceil(ctx / block_size) here)
        nb = -(-shapes["ctx"] // c["block_size"])
        c["num_splits"] = max(min(int(c.get("num_splits", 1)), nb), 1)
    elif kernel == "ssm_scan":
        c["block_d"] = divisor_clamp(c["block_d"], shapes["d_inner"])
    elif kernel == "wkv6":
        c["block_h"] = divisor_clamp(c["block_h"], shapes["heads"])
    elif kernel == "mxu_probe":
        c["block_m"] = divisor_clamp(c["block_m"], shapes["m"])
        c["block_n"] = divisor_clamp(c["block_n"], shapes["n"])
    return c


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

def _fa_enumerate(shapes, dtype, allow_low_precision=False):
    acc_dtypes = ("f32", "bf16") if allow_low_precision else ("f32",)
    out = []
    for bq in _blocks_upto(shapes["seq_q"]):
        for bk in _blocks_upto(shapes["seq_kv"]):
            for acc in acc_dtypes:
                out.append({"block_q": bq, "block_k": bk, "acc_dtype": acc})
    return out


def _fa_vmem(shapes, cfg, dtype):
    it = _dtype_bytes(dtype)
    acc_it = _dtype_bytes(cfg.get("acc_dtype", "f32"))
    D = shapes["head_dim"]
    skv = -(-shapes["seq_kv"] // cfg["block_k"]) * cfg["block_k"]
    bq = cfg["block_q"]
    kv = 2 * skv * D * it                  # whole K/V panel resident
    q_o = bq * D * (4 + it)                # q in f32 + output block
    state = bq * (D + 2) * acc_it          # acc + (m, l)
    scores = bq * cfg["block_k"] * 4       # s/p transient
    return kv + q_o + state + scores


def _fa_census(shapes, cfg, dtype):
    B, H, KH = shapes["batch"], shapes["heads"], shapes["kv_heads"]
    Sq, Skv, D = shapes["seq_q"], shapes["seq_kv"], shapes["head_dim"]
    bq, bk = cfg["block_q"], cfg["block_k"]
    it = _dtype_bytes(dtype)
    nq = -(-Sq // bq)
    nk = -(-Skv // bk)
    cells = B * H * nq
    flops = 4.0 * B * H * Sq * Skv * D
    hbm = 2.0 * B * Sq * H * D * it + 2.0 * B * KH * Skv * D * it
    per_cell = {"dot": 2.0 * nk, "exponential": 2.0 * nk,
                "maximum": 2.0 * nk, "multiply": 3.0 * nk,
                "add": 2.0 * nk, "select": 1.0 * nk, "fusion": 1.0}
    hist = {k: v * cells for k, v in per_cell.items()}
    return {"flops": flops, "hbm_bytes": hbm, "op_histogram": hist,
            "mxu_shape": (bq, bk, D)}


# ---------------------------------------------------------------------------
# paged_attention (decode through a block table; the tunable axis is the
# KV page size — a cache-LAYOUT parameter the paged serving engine reads
# from the tuning cache when it sizes its block pool)
# ---------------------------------------------------------------------------

# split-KV flash-decoding factors; pruned so every split covers >= 1 page
_SPLIT_LADDER = (1, 2, 4, 8, 16)


def _pa_enumerate(shapes, dtype, allow_low_precision=False):
    out = []
    for bs in _blocks_upto(shapes["ctx"]):
        nb = -(-shapes["ctx"] // bs)
        for s in _SPLIT_LADDER:
            if s > nb:
                continue
            out.append({"block_size": bs, "num_splits": s})
    return out


def _pa_vmem(shapes, cfg, dtype):
    it = _dtype_bytes(dtype)
    D, bs = shapes["head_dim"], cfg["block_size"]
    ctx = shapes["ctx"]
    ns = int(cfg.get("num_splits", 1))
    # the HBM-resident lowering's working set: K and V pages land in a
    # TWO-slot VMEM scratch each (double buffering — page j+1's DMA is
    # in flight while page j is consumed), never the staged pool.  The
    # split form keeps the same two-slot scratch PER CELL; what grows
    # with num_splits is the partial-row buffer the merge pass reads.
    # A slot holds the page's KV heads (the DMA moves whole pages).
    kv = 2 * 2 * bs * shapes["kv_heads"] * D * it   # 2 K + 2 V page slots
    q_o = D * (4 + it)                     # q in f32 + output row
    state = (D + 2) * 4                    # acc + (m, l), f32
    scores = bs * 4                        # s/p transient
    table = -(-ctx // bs) * 4              # the block-table row
    partials = (ns * (D + 2) * 4) if ns > 1 else 0  # merge working set
    return kv + q_o + state + scores + table + partials


def _pa_census(shapes, cfg, dtype):
    """The two trades the cost model arbitrates.  Block size: small pages
    read fewer padded tail bytes (less fragmentation amplification) but
    pay more per-page issue/gather overhead; large pages amortize issue
    cost but round every context up to a coarser multiple.  Split factor:
    more splits multiply the grid's independent cells (``grid_cells`` —
    the utilization term ``CostModel.predict`` scales bandwidth by) at
    the price of re-reading q per split and writing + re-reading the
    f32 partial (m, l, acc) rows in the merge pass."""
    B, H, KH = shapes["batch"], shapes["heads"], shapes["kv_heads"]
    D, ctx, bs = shapes["head_dim"], shapes["ctx"], cfg["block_size"]
    it = _dtype_bytes(dtype)
    ns = int(cfg.get("num_splits", 1))
    nb = -(-ctx // bs)
    pps = -(-nb // ns)                     # pages per split
    cells = B * H * ns
    flops = 4.0 * B * H * ctx * D
    # K/V reads are page-granular (the padded tail is read, not the exact
    # ctx) and partitioned across splits, so total page bytes don't grow;
    # q is re-read once per split; one table read per page
    hbm = 2.0 * B * KH * nb * bs * D * it + (ns + 1.0) * B * H * D * it \
        + B * nb * 4.0
    if ns > 1:
        # partial (m, l, acc) rows: written by pass 1, read by the merge
        hbm += 2.0 * B * H * ns * (D + 2) * 4.0
    per_cell = {"dot": 2.0 * pps, "exponential": 2.0 * pps,
                "maximum": 2.0 * pps, "multiply": 3.0 * pps,
                "add": 2.0 * pps, "dynamic-slice": 2.0 * pps, "fusion": 1.0}
    hist = {k: v * cells for k, v in per_cell.items()}
    if ns > 1:
        # the log-sum-exp merge pass (one fused rescale over [B,H,ns])
        merge = B * H * ns
        for k, v in (("exponential", 1.0), ("maximum", 1.0),
                     ("multiply", 2.0), ("add", 2.0)):
            hist[k] = hist.get(k, 0.0) + v * merge
        hist["fusion"] = hist.get("fusion", 0.0) + 1.0
    return {"flops": flops, "hbm_bytes": hbm, "op_histogram": hist,
            "grid_cells": float(cells)}


# ---------------------------------------------------------------------------
# ssm_scan
# ---------------------------------------------------------------------------

def _ssm_enumerate(shapes, dtype, allow_low_precision=False):
    return [{"block_d": bd} for bd in _divisors_from_ladder(
        shapes["d_inner"])]


def _ssm_vmem(shapes, cfg, dtype):
    it = _dtype_bytes(dtype)
    S, N, bd = shapes["seq"], shapes["state_dim"], cfg["block_d"]
    streams = S * (2 * bd + 2 * N) * it    # x, dt, B, C panels
    out = S * bd * it
    state = bd * N * (4 + 4)               # h carry + dA transient (f32)
    return streams + out + state


def _ssm_census(shapes, cfg, dtype):
    B, S = shapes["batch"], shapes["seq"]
    Di, N, bd = shapes["d_inner"], shapes["state_dim"], cfg["block_d"]
    it = _dtype_bytes(dtype)
    cells = B * (-(-Di // bd))
    flops = 6.0 * B * S * Di * N
    hbm = (3.0 * B * S * Di + 2.0 * B * S * N) * it + 4.0 * Di * N
    per_cell_step = {"exponential": 1.0, "multiply": 4.0, "add": 2.0,
                     "dot": 1.0}
    hist = {k: v * cells * S for k, v in per_cell_step.items()}
    hist["fusion"] = float(cells)
    return {"flops": flops, "hbm_bytes": hbm, "op_histogram": hist}


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------

def _wkv_enumerate(shapes, dtype, allow_low_precision=False):
    return [{"block_h": bh} for bh in _divisors_from_ladder(shapes["heads"])]


def _wkv_vmem(shapes, cfg, dtype):
    it = _dtype_bytes(dtype)
    S, N, bh = shapes["seq"], shapes["head_dim"], cfg["block_h"]
    streams = 4 * S * bh * N * it          # r, k, v, w panels
    out = S * bh * N * it
    state = bh * N * N * (4 + 4)           # S carry + kv transient (f32)
    return streams + out + state


def _wkv_census(shapes, cfg, dtype):
    B, S = shapes["batch"], shapes["seq"]
    H, N, bh = shapes["heads"], shapes["head_dim"], cfg["block_h"]
    it = _dtype_bytes(dtype)
    cells = B * (-(-H // bh))
    flops = 6.0 * B * S * H * N * N
    hbm = 5.0 * B * S * H * N * it + H * N * it
    per_cell_step = {"multiply": 4.0, "add": 2.0, "dot": 1.0}
    hist = {k: v * cells * S for k, v in per_cell_step.items()}
    hist["fusion"] = float(cells)
    return {"flops": flops, "hbm_bytes": hbm, "op_histogram": hist}


# ---------------------------------------------------------------------------
# mxu_probe
# ---------------------------------------------------------------------------

def _mxu_enumerate(shapes, dtype, allow_low_precision=False):
    out = []
    for bm in _divisors_from_ladder(shapes["m"]):
        for bn in _divisors_from_ladder(shapes["n"]):
            out.append({"block_m": bm, "block_n": bn})
    return out


def _mxu_vmem(shapes, cfg, dtype):
    it = _dtype_bytes(dtype)
    K = shapes["k"]
    bm, bn = cfg["block_m"], cfg["block_n"]
    return (bm * K + K * bn) * it + bm * bn * (it + 4)


def _mxu_census(shapes, cfg, dtype):
    M, K, N = shapes["m"], shapes["k"], shapes["n"]
    bm, bn = cfg["block_m"], cfg["block_n"]
    it = _dtype_bytes(dtype)
    cells = (-(-M // bm)) * (-(-N // bn))
    flops = 2.0 * M * K * N
    # each grid cell re-reads its A-row and B-column panels
    hbm = (cells * (bm * K + K * bn) + M * N) * it
    hist = {"dot": float(cells), "multiply": float(cells),
            "fusion": float(cells)}
    return {"flops": flops, "hbm_bytes": hbm, "op_histogram": hist,
            "mxu_shape": (bm, bn, K)}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

TUNABLES: Dict[str, Tunable] = {
    t.name: t for t in (
        Tunable(
            name="flash_attention",
            shape_keys=("batch", "seq_q", "seq_kv", "heads", "kv_heads",
                        "head_dim"),
            default_shapes={"batch": 4, "seq_q": 1024, "seq_kv": 1024,
                            "heads": 8, "kv_heads": 2, "head_dim": 128},
            default_config={"block_q": 128, "block_k": 128,
                            "acc_dtype": "f32"},
            enumerate_fn=_fa_enumerate,
            census_fn=_fa_census,
            vmem_fn=_fa_vmem,
        ),
        Tunable(
            name="paged_attention",
            shape_keys=("batch", "heads", "kv_heads", "head_dim", "ctx"),
            default_shapes={"batch": 8, "heads": 8, "kv_heads": 2,
                            "head_dim": 128, "ctx": 2048},
            default_config={"block_size": 16, "num_splits": 1},
            enumerate_fn=_pa_enumerate,
            census_fn=_pa_census,
            vmem_fn=_pa_vmem,
        ),
        Tunable(
            name="ssm_scan",
            shape_keys=("batch", "seq", "d_inner", "state_dim"),
            default_shapes={"batch": 4, "seq": 512, "d_inner": 2048,
                            "state_dim": 16},
            default_config={"block_d": 256},
            enumerate_fn=_ssm_enumerate,
            census_fn=_ssm_census,
            vmem_fn=_ssm_vmem,
        ),
        Tunable(
            name="wkv6",
            shape_keys=("batch", "seq", "heads", "head_dim"),
            default_shapes={"batch": 4, "seq": 512, "heads": 32,
                            "head_dim": 64},
            default_config={"block_h": 1},
            enumerate_fn=_wkv_enumerate,
            census_fn=_wkv_census,
            vmem_fn=_wkv_vmem,
        ),
        Tunable(
            name="mxu_probe",
            shape_keys=("m", "k", "n"),
            default_shapes={"m": 512, "k": 512, "n": 512},
            default_config={"block_m": 128, "block_n": 128},
            enumerate_fn=_mxu_enumerate,
            census_fn=_mxu_census,
            vmem_fn=_mxu_vmem,
        ),
    )
}


def get_tunable(kernel: str) -> Tunable:
    try:
        return TUNABLES[kernel]
    except KeyError:
        raise KeyError(f"unknown tunable kernel {kernel!r}; available: "
                       f"{', '.join(sorted(TUNABLES))}") from None


def tunable_names() -> List[str]:
    return sorted(TUNABLES)


def shape_bucket(shapes: Mapping[str, int]) -> str:
    """Canonical shape-bucket key: every axis rounded UP to a power of two
    (nearby problem sizes share one tuning entry), axes sorted by name."""
    parts = []
    for k in sorted(shapes):
        v = max(int(shapes[k]), 1)
        parts.append(f"{k}{1 << (v - 1).bit_length()}")
    return "_".join(parts)
