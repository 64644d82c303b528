"""Shape-aware kernel autotuner: block-size search with a persistent cache.

The Pallas kernels used to answer the paper's §4 question ("how close to
hand-tuned hardware GEMM can a managed runtime get?") with hand-picked
`bm/bn/bk` constants that are only right for one shape regime.  This module
replaces those magic numbers with a three-stage, shape-aware search:

  1. **Candidate generation** — enumerate tile configs that satisfy the TPU
     layout rules (last dim a multiple of 128 lanes; second-to-last a
     multiple of the dtype sublane count: 8 for f32, 16 for bf16, 32 for
     int8/fp8) and whose VMEM working set — every pipelined block counted
     twice (Pallas double-buffers inputs AND outputs), (1, n) rows counted
     at their 8-sublane padding — fits `VMEM_BUDGET`, a headroom fraction
     of the scoped limit `VMEM_LIMIT` that every kernel requests from the
     compiler through `compiler_params()`.

  2. **Analytical roofline pre-ranking** — order candidates by a cost model:
     max(MXU time at the tile's utilization, HBM bytes / bandwidth) computed
     on the *padded* shape (so padding waste for the actual shape is priced
     in), plus a per-grid-step overhead that breaks ties toward larger
     tiles.  On CPU / interpret mode this ranking is the **sole selector**
     — no timing, fully deterministic, cheap enough to run at trace time.

  3. **On-device timing sweep** — `sweep()` times the top-N ranked
     candidates (median of k reps) on real hardware; winners are persisted
     via `record()`.  The sweep never runs implicitly inside an op dispatch
     (dispatch may happen mid-trace where timing is impossible); it is
     driven offline by `benchmarks/bench_autotune.py`.

Selected configs are memoized per (kernel, backend, dtype, shape-bucket)
and backed by a persistent JSON cache: the user cache (``$REPRO_AUTOTUNE_CACHE``
or ``~/.cache/repro/autotune.json``, written by the sweep CLI) takes
priority over the pre-swept v5e defaults shipped in ``autotune_v5e.json``.
A second lookup with the same shape bucket is a dict hit — no re-ranking,
no re-timing.

Shape buckets round every dimension up to the next power of two, so e.g.
(1000, 1000, 1000) and (1024, 1024, 1024) GEMMs share one cache entry.
"""
from __future__ import annotations

import json
import math
import os
import statistics
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import jax.experimental.pallas.tpu as pltpu

from repro.launch import machine as _machine
from repro.launch.machine import LANE, SUBLANE_BY_ITEMSIZE, CostTerms

# -- TPU layout constants -----------------------------------------------------
# Machine constants (HBM bandwidth, peak MXU FLOP/s, step overhead) live in
# launch/machine.py — the per-kernel functions below describe WORK
# (CostTerms: flops, bytes, steps, utilization) and the MachineModel turns
# work into seconds, with calibrated efficiencies when a sweep has recorded
# them.

VMEM_BYTES = _machine.V5E.vmem_bytes
# The compiler's default scoped VMEM limit for a kernel (16 MiB on v5e) is
# far below the physical VMEM; every kernel asks for VMEM_LIMIT explicitly
# and the working-set estimates below budget against it, leaving the rest
# of the core's VMEM to the compiler's own scratch.
VMEM_LIMIT = VMEM_BYTES * 3 // 4
VMEM_BUDGET = int(VMEM_LIMIT * 0.9)       # headroom for semaphores/spills
# f32 words of in-kernel temporaries per element of a streamed A block: an
# f32-precision contraction splits its operands into bf16 parts, and a
# row-vector product transposes the block.  Fitted to the v5e compiler's
# own accounting (fused_grad uses ~6 block-sizes of VMEM: 2 buffers + 4).
F32_TEMPS = 4


# Every contraction of this package runs at full f32 precision on the MXU
# (XLA's TPU default multiplies f32 matrices in one bf16 pass); the api
# sets the same precision as the default around every job it runs.
HIGHEST = jax.lax.Precision.HIGHEST


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    """The Mosaic compiler params every kernel of this package uses."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def sublane(dtype) -> int:
    """Minimum second-to-last-dim multiple for this dtype's tiled layout."""
    return SUBLANE_BY_ITEMSIZE[jnp.dtype(dtype).itemsize]


def _rup(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _itemsize(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def _rows(r: int) -> int:
    """VMEM rows a (r, ·) f32 block occupies: rounded up to 8 sublanes."""
    return _rup(max(r, 1), 8)


def _util(b: int) -> float:
    """MXU utilization factor for a tile dim feeding the 128-wide array."""
    return min(b, LANE) / LANE


def _steps(dim: int, mult: int, choices: Sequence[int]) -> list[int]:
    """Candidate block sizes for one dim: the given choices (multiples of
    `mult` only), each clamped to the dim rounded up to `mult`."""
    cap = _rup(max(dim, 1), mult)
    return sorted({min(c, cap) for c in choices if c % mult == 0})


# -- per-kernel candidate generation / VMEM / cost terms ----------------------
#
# Each kernel declares: the tunable knobs, the ordered logical dims that form
# the shape bucket, the legacy hand-picked constants (kept as a ranked
# candidate so the tuner can never regress past them), a generator of
# layout-legal + VMEM-feasible candidates, the double-buffered VMEM
# working-set estimate, and a declarative cost description — a CostTerms of
# (flops, hbm_bytes, steps, mxu_util) that the MachineModel prices.

@dataclass(frozen=True)
class KernelSpec:
    knobs: tuple[str, ...]
    dims: tuple[str, ...]
    legacy: Mapping[str, int]
    gen: Callable
    vmem: Callable
    terms: Callable                     # (blocks, dims, dtype) -> CostTerms


def _gemm_vmem(b, d, dtype):
    db = _itemsize(dtype)
    return (2 * (b["bm"] * b["bk"] + b["bk"] * b["bn"]) * db   # A, B streams
            + b["bm"] * b["bn"] * 4                            # f32 acc
            + 2 * b["bm"] * b["bn"] * db)                      # out tile


def _gemm_gen(d, dtype):
    sub = sublane(dtype)
    out = []
    for bm in _steps(d["m"], sub, (sub, 64, 128, 256, 512)):
        for bn in _steps(d["n"], LANE, (128, 256, 512)):
            for bk in _steps(d["k"], LANE, (128, 256, 512, 1024)):
                b = {"bm": bm, "bn": bn, "bk": bk}
                if _gemm_vmem(b, d, dtype) <= VMEM_BUDGET:
                    out.append(b)
    return out


def _gemm_terms(b, d, dtype):
    db = _itemsize(dtype)
    mp, kp = _rup(d["m"], b["bm"]), _rup(d["k"], b["bk"])
    np_ = _rup(d["n"], b["bn"])
    hbm = (mp * kp * db * (np_ // b["bn"])      # A re-read per output column
           + kp * np_ * db * (mp // b["bm"])    # B re-read per output row
           + mp * np_ * db)                     # C written once
    steps = (mp // b["bm"]) * (np_ // b["bn"]) * (kp // b["bk"])
    return CostTerms(flops=2.0 * mp * np_ * kp, hbm_bytes=hbm, steps=steps,
                     mxu_util=_util(b["bm"]))


def _tsgram_bn(b, d) -> int:
    """The output-tile width the kernel runs with: the knob clamped to the
    lane-padded n and rounded down to a divisor of it (so A's columns
    never need padding)."""
    np_ = _rup(d["n"], LANE)
    full = np_ if np_ <= TSGRAM_FULL_MAX else TSGRAM_MAX_TILE
    bn = min(_rup(b.get("bn", full), LANE), np_)
    while np_ % bn:
        bn -= LANE
    return bn


def _tsgram_vmem(b, d, dtype):
    db = _itemsize(dtype)
    bn = _tsgram_bn(b, d)
    ops = 1 if bn == _rup(d["n"], LANE) else 2      # one or two A operands
    return (ops * b["bm"] * bn * (2 * db + F32_TEMPS * 4)   # A blocks
            + 4 * bn * bn * 4)      # f32 accumulator, product, output x2


# Grams up to TSGRAM_FULL_MAX wide are one output tile (A read once);
# wider ones run in tiles of at most TSGRAM_MAX_TILE (bigger f32 tiles
# compile slowly — several seconds per doubling — and gain nothing on a
# compute-bound kernel).
TSGRAM_FULL_MAX = 1024
TSGRAM_MAX_TILE = 512


def _tsgram_gen(d, dtype):
    sub = sublane(dtype)
    np_ = _rup(d["n"], LANE)
    full = [np_] if np_ <= TSGRAM_FULL_MAX else []
    tiles = sorted({_tsgram_bn({"bn": c}, d)
                    for c in [128, 256, TSGRAM_MAX_TILE] + full})
    out = []
    for bm in _steps(d["m"], sub, (sub, 64, 128, 256, 512, 1024)):
        for bn in tiles:
            b = {"bm": bm, "bn": bn}
            if _tsgram_vmem(b, d, dtype) <= VMEM_BUDGET:
                out.append(b)
    return out


def _tsgram_terms(b, d, dtype):
    """(bn × bn) output tiles: tile (i, j) streams column blocks i and j
    of every row block, so A is read once when bn covers n and 2·n/bn
    times otherwise — narrow tiles trade HBM traffic for VMEM."""
    db = _itemsize(dtype)
    mp, np_ = _rup(d["m"], b["bm"]), _rup(d["n"], LANE)
    t = np_ // _tsgram_bn(b, d)
    a_reads = 1 if t == 1 else 2 * t
    hbm = mp * np_ * db * a_reads + np_ * np_ * db
    return CostTerms(flops=2.0 * mp * np_ * np_, hbm_bytes=hbm,
                     steps=t * t * (mp // b["bm"]), mxu_util=_util(b["bm"]))


def _randsketch_vmem(b, d, dtype):
    db = _itemsize(dtype)
    rp = _rup(d["r"], LANE)
    return (2 * (b["bm"] * b["bn"] + b["bm"] * rp) * db
            + b["bn"] * rp * 4 + 2 * b["bn"] * rp * 4)


def _randsketch_gen(d, dtype):
    sub = sublane(dtype)
    out = []
    for bm in _steps(d["m"], sub, (sub, 64, 128, 256, 512, 1024)):
        for bn in _steps(d["n"], LANE, (128, 256, 512, 1024)):
            b = {"bm": bm, "bn": bn}
            if _randsketch_vmem(b, d, dtype) <= VMEM_BUDGET:
                out.append(b)
    return out


def _randsketch_terms(b, d, dtype):
    db = _itemsize(dtype)
    mp, np_ = _rup(d["m"], b["bm"]), _rup(d["n"], b["bn"])
    rp = _rup(d["r"], LANE)
    hbm = (mp * np_ * db                        # one pass over A
           + mp * rp * db * (np_ // b["bn"])    # Q re-streamed per n-strip
           + np_ * rp * db)
    steps = (np_ // b["bn"]) * (mp // b["bm"])
    return CostTerms(flops=2.0 * mp * np_ * rp, hbm_bytes=hbm, steps=steps,
                     mxu_util=_util(b["bm"]))


def _fusedgrad_vmem(b, d, dtype):
    """Single-RHS kernel at k=1; the multi-RHS kernel at k = kp requests
    (d["k"]): x, g and the t/w/z strips are (k, ·) blocks padded to 8
    sublanes, every pipelined block double-buffered."""
    db = _itemsize(dtype)
    np_ = _rup(d["n"], LANE)
    k = _rows(d.get("k", 1))
    return (b["bm"] * np_ * (2 * db + F32_TEMPS * 4)    # A row blocks
            + 2 * k * np_ * 4            # x rows
            + 3 * 2 * k * b["bm"] * 4    # t, w, z strips
            + 3 * k * np_ * 4            # g accumulator + g out
            + 3 * k * LANE * 4)          # f accumulator + f out


def _fusedgrad_gen(d, dtype):
    # bm is both the A-block sublane count and the lane width of the t/w/z
    # vector strips, so candidates stay lane-aligned (multiples of 128).
    out = []
    for bm in _steps(d["m"], LANE, (128, 256, 512, 1024)):
        b = {"bm": bm}
        if _fusedgrad_vmem(b, d, dtype) <= VMEM_BUDGET:
            out.append(b)
    return out


def _fusedgrad_terms(b, d, dtype):
    """One streaming pass over A feeding two MXU contractions (z = x Aᵀ,
    g += r A) — the whole point vs apply+adjoint is the single A-read, so
    HBM traffic is m·n·db once plus vector noise."""
    db = _itemsize(dtype)
    mp, np_ = _rup(d["m"], b["bm"]), _rup(d["n"], LANE)
    hbm = mp * np_ * db + (2 * np_ + 3 * mp) * db   # ONE A pass + x,t,w,z,g
    return CostTerms(flops=4.0 * mp * np_, hbm_bytes=hbm,
                     steps=mp // b["bm"], mxu_util=_util(b["bm"]))


def _flash_vmem(b, d, dtype):
    db = _itemsize(dtype)
    dp = _rup(d["d"], LANE)
    return (2 * b["bq"] * dp * db + 4 * b["bk"] * dp * db     # Q + K,V streams
            + b["bq"] * dp * 4 + 2 * b["bq"] * LANE * 4       # acc + (m, l)
            + 2 * b["bq"] * dp * db)                          # out tile


def _flash_gen(d, dtype):
    sub = sublane(dtype)
    out = []
    for bq in _steps(d["sq"], sub, (sub, 64, 128, 256, 512)):
        for bk in _steps(d["sk"], LANE, (128, 256, 512)):
            b = {"bq": bq, "bk": bk}
            if _flash_vmem(b, d, dtype) <= VMEM_BUDGET:
                out.append(b)
    return out


def _flash_terms(b, d, dtype):
    db = _itemsize(dtype)
    sqp, skp = _rup(d["sq"], b["bq"]), _rup(d["sk"], b["bk"])
    dp = _rup(d["d"], LANE)
    frac = 0.5 if d.get("causal", 1) else 1.0   # live fraction of KV blocks
    hbm = (2 * sqp * dp * db                              # Q in + O out
           + 2 * skp * dp * db * (sqp // b["bq"]) * frac)  # K, V per q-row
    steps = (sqp // b["bq"]) * (skp // b["bk"])
    return CostTerms(flops=4.0 * sqp * skp * dp * frac, hbm_bytes=hbm,
                     steps=steps, mxu_util=_util(b["bq"]))


def _scan_vmem(b, d, dtype):
    db = _itemsize(dtype)
    bd = min(LANE, _rup(d["d"], LANE))
    np_ = _rup(d["n"], 8)
    return (6 * b["q"] * bd * db                # x, dt, y double-buffered
            + 4 * b["q"] * np_ * db             # B, C double-buffered
            + np_ * bd * (db + 4))              # A block + h scratch


def _scan_gen(d, dtype):
    sub = sublane(dtype)
    out = []
    for q in _steps(d["s"], sub, (sub, 64, 128, 256, 512)):
        b = {"q": q}
        if _scan_vmem(b, d, dtype) <= VMEM_BUDGET:
            out.append(b)
    return out


def _scan_terms(b, d, dtype):
    # VPU/memory-bound: one HBM pass over x/dt/y/B/C per d-block; the model
    # only has to order q choices (padding waste + grid-step overhead).
    # flops=0 — the max() roofline then reduces to the memory term.
    db = _itemsize(dtype)
    sp = _rup(d["s"], b["q"])
    bd = min(LANE, _rup(d["d"], LANE))
    dblocks = max(1, _rup(d["d"], bd) // bd)
    hbm = sp * (3 * bd + 2 * d["n"]) * db * dblocks
    steps = (sp // b["q"]) * dblocks
    return CostTerms(flops=0.0, hbm_bytes=hbm, steps=steps)


def _bsr_ell(bs: int, d) -> int:
    """Expected stored blocks per block-row.  When the caller knows the
    actual ELL width (an already-built BlockELL) it passes `ell` in the dims
    and we use it verbatim; otherwise estimate it from the entry count under
    a uniform-scatter model: P(block nonzero) = 1 - (1 - nnz/mn)^(bs²)."""
    if d.get("ell"):
        return int(d["ell"])
    m, n = max(int(d["m"]), 1), max(int(d["n"]), 1)
    nbc = max(n // bs, 1)
    density = min(1.0, float(d.get("nnz", m * n)) / (m * n))
    p_block = 1.0 - (1.0 - density) ** (bs * bs)
    return max(1, int(math.ceil(nbc * p_block)))


def _bsr_vmem(b, d, dtype):
    """Per-slot SpMM working set (A block + gathered X block in, output
    block-row out, f32 accumulator)."""
    db = _itemsize(dtype)
    bs = b["bs"]
    bsl, bsp = _rup(bs, sublane(dtype)), _rup(bs, LANE)
    nxp = _rup(max(d.get("nx", 1), 1), LANE)
    return (2 * bsl * bsp * db + 2 * bsl * nxp * db   # A block + X block
            + bsl * nxp * 4                           # f32 acc scratch
            + 2 * bsl * nxp * db)                     # out tile


def _bsr_gen(d, dtype):
    sub = sublane(dtype)
    out = []
    for bs in _steps(min(d["m"], d["n"]), sub, (8, 16, 32, 64, 128)):
        b = {"bs": bs}
        if _bsr_vmem(b, d, dtype) <= VMEM_BUDGET:
            out.append(b)
    return out


def _bsr_terms(b, d, dtype):
    """BSR SpMM roofline terms: MXU work on *layout-padded* blocks (a
    bs < 128 block still occupies full 128-lane tiles, so small blocks pay
    up to a 16× flop inflation) vs HBM traffic ∝ stored blocks, plus the
    per-block grid-step overhead that punishes very small blocks at high
    density."""
    db = _itemsize(dtype)
    bs = b["bs"]
    nxp = _rup(max(d.get("nx", 1), 1), LANE)
    mp = _rup(max(d["m"], 1), bs)
    nbr = mp // bs
    ell = _bsr_ell(bs, d)
    bsl, bll = _rup(bs, sublane(dtype)), _rup(bs, LANE)
    hbm = (nbr * ell * (bs * bs + bs * nxp) * db    # A blocks + gathered X
           + mp * nxp * db)                         # out written once
    return CostTerms(flops=2.0 * nbr * ell * bsl * bll * nxp, hbm_bytes=hbm,
                     steps=nbr * ell)


KERNELS: dict[str, KernelSpec] = {
    "gemm": KernelSpec(("bm", "bn", "bk"), ("m", "k", "n"),
                       {"bm": 256, "bn": 256, "bk": 512},
                       _gemm_gen, _gemm_vmem, _gemm_terms),
    "tsgram": KernelSpec(("bm", "bn"), ("m", "n"), {"bm": 512, "bn": 512},
                         _tsgram_gen, _tsgram_vmem, _tsgram_terms),
    "randsketch": KernelSpec(("bm", "bn"), ("m", "n", "r"),
                             {"bm": 512, "bn": 512},
                             _randsketch_gen, _randsketch_vmem,
                             _randsketch_terms),
    "fusedgrad": KernelSpec(("bm",), ("m", "n", "k"), {"bm": 512},
                            _fusedgrad_gen, _fusedgrad_vmem,
                            _fusedgrad_terms),
    "flash_attention": KernelSpec(("bq", "bk"), ("sq", "sk", "d", "causal"),
                                  {"bq": 256, "bk": 256},
                                  _flash_gen, _flash_vmem, _flash_terms),
    "selective_scan": KernelSpec(("q",), ("s", "d", "n"), {"q": 256},
                                 _scan_gen, _scan_vmem, _scan_terms),
    "bsr": KernelSpec(("bs",), ("m", "n", "nnz", "nx"), {"bs": 8},
                      _bsr_gen, _bsr_vmem, _bsr_terms),
}


# -- candidate enumeration + ranking -----------------------------------------

def candidates(kernel: str, dims: Mapping[str, int], dtype) -> list[dict]:
    """Layout-legal candidates whose VMEM working set fits the budget."""
    return KERNELS[kernel].gen(dims, dtype)


def estimate_vmem(kernel: str, blocks: Mapping[str, int],
                  dims: Mapping[str, int], dtype) -> int:
    """Double-buffered VMEM working-set estimate in bytes."""
    return KERNELS[kernel].vmem(blocks, dims, dtype)


def cost_terms(kernel: str, blocks: Mapping[str, int],
               dims: Mapping[str, int], dtype) -> CostTerms:
    """Machine-independent work description (flops/bytes/steps/util)."""
    return KERNELS[kernel].terms(blocks, dims, dtype)


def model_time(kernel: str, blocks: Mapping[str, int],
               dims: Mapping[str, int], dtype, *,
               machine: "_machine.MachineModel | None" = None) -> float:
    """Modeled seconds (lower is better) on `machine` — the calibrated
    model for the current backend by default."""
    machine = machine or _machine.for_backend()
    return machine.time(cost_terms(kernel, blocks, dims, dtype), dtype)


def rank(kernel: str, dims: Mapping[str, int], dtype, *,
         machine: "_machine.MachineModel | None" = None
         ) -> list[tuple[float, dict]]:
    """(score, blocks) ascending by model time; deterministic tie-break.

    The legacy hand-picked config joins the pool whenever its VMEM working
    set fits the budget, so the selected config never scores worse than
    the old constants where those could run at all.
    """
    machine = machine or _machine.for_backend()
    pool = candidates(kernel, dims, dtype)
    legacy = dict(KERNELS[kernel].legacy)
    if legacy not in pool \
            and estimate_vmem(kernel, legacy, dims, dtype) <= VMEM_BUDGET:
        pool = pool + [legacy]
    scored = [(model_time(kernel, b, dims, dtype, machine=machine), b)
              for b in pool]
    scored.sort(key=lambda t: (t[0], sorted(t[1].items())))
    return scored


# -- shape buckets + persistent cache ----------------------------------------

def bucket(x: int) -> int:
    """Next power of two (0 stays 0) — the shape-bucket granularity."""
    return 0 if x <= 0 else 1 << (x - 1).bit_length()


def cache_key(kernel: str, backend: str, dtype,
              dims: Mapping[str, int]) -> str:
    spec = KERNELS[kernel]
    # A dim the caller leaves out takes its unit value (fusedgrad's request
    # count k is 1 for the single-RHS kernel).
    shape = "x".join(str(bucket(int(dims.get(k, 1)))) for k in spec.dims)
    return f"{kernel}|{backend}|{jnp.dtype(dtype).name}|{shape}"


DEFAULTS_PATH = Path(__file__).with_name("autotune_v5e.json")


def user_cache_path() -> Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro" / "autotune.json"


class ConfigCache:
    """One JSON file of {key: {"blocks": ..., "source": ..., "us": ...}}."""

    def __init__(self, path: Path):
        self.path = Path(path)
        self.entries: dict[str, dict] = {}
        self._loaded = False

    def load(self) -> "ConfigCache":
        if not self._loaded:
            self._loaded = True
            try:
                data = json.loads(self.path.read_text())
                self.entries = dict(data.get("entries", {}))
            except (OSError, ValueError):
                self.entries = {}
        return self

    def lookup(self, key: str) -> dict | None:
        return self.load().entries.get(key)

    def put(self, key: str, blocks: Mapping[str, int], *,
            source: str = "swept", us: float | None = None) -> None:
        entry = {"blocks": dict(blocks), "source": source}
        if us is not None:
            entry["us"] = round(float(us), 3)
        self.load().entries[key] = entry

    def save(self) -> None:
        self.path.parent.mkdir(parents=True, exist_ok=True)
        tmp = self.path.with_suffix(".tmp")
        tmp.write_text(json.dumps(
            {"version": 1, "entries": self.entries}, indent=1, sort_keys=True))
        tmp.replace(self.path)


_memo: dict[str, dict] = {}
_caches: dict[Path, ConfigCache] = {}
stats = {"memo_hits": 0, "cache_hits": 0, "ranked": 0, "swept": 0}


def _cache_at(path: Path) -> ConfigCache:
    if path not in _caches:
        _caches[path] = ConfigCache(path)
    return _caches[path]


def reset() -> None:
    """Forget memoized configs, cache handles, and counters (tests) — and
    the planner/machine caches layered on top, so a recalibration or a
    cache-path change is picked up everywhere at once."""
    _memo.clear()
    _caches.clear()
    for k in stats:
        stats[k] = 0
    _machine.invalidate_cache()
    from repro.launch import planner as _planner
    _planner.invalidate_cache()


def get_config(kernel: str, dims: Mapping[str, int], dtype, *,
               backend: str | None = None) -> dict:
    """Resolve the block config for a shape: memo → user cache → shipped
    v5e defaults → roofline ranking.  Never times anything."""
    backend = backend or jax.default_backend()
    key = cache_key(kernel, backend, dtype, dims)
    if key in _memo:
        stats["memo_hits"] += 1
        return dict(_memo[key])
    entry = (_cache_at(user_cache_path()).lookup(key)
             or _cache_at(DEFAULTS_PATH).lookup(key))
    if entry is not None:
        stats["cache_hits"] += 1
        blocks = dict(entry["blocks"])
    else:
        stats["ranked"] += 1
        # Rank on the bucket's representative shape (each dim rounded up to
        # its power-of-two bucket), not the exact dims: the result is cached
        # under the bucket key, so it must not depend on which bucket member
        # arrived first.  Dispatch clamps blocks to the exact shape anyway.
        bdims = {k: bucket(int(v)) for k, v in dims.items()}
        blocks = rank(kernel, bdims, dtype,
                      machine=_machine.for_backend(backend))[0][1]
    _memo[key] = dict(blocks)
    return dict(blocks)


def resolve(kernel: str, dims: Mapping[str, int], dtype,
            overrides: Mapping[str, int | None] | None = None, *,
            tune: str = "auto", backend: str | None = None) -> dict:
    """Config the ops wrappers dispatch with: explicit block kwargs always
    win; missing knobs come from the execution planner (`tune="auto"` —
    launch/planner.plan, memoized/cached selection against the calibrated
    machine model) or the legacy constants (`tune="off"`)."""
    spec = KERNELS[kernel]
    ov = {k: v for k, v in (overrides or {}).items() if v is not None}
    if len(ov) == len(spec.knobs):
        return ov
    if tune == "auto":
        from repro.launch import planner as _planner
        base = dict(_planner.plan(kernel, dims, dtype,
                                  backend=backend).blocks)
    elif tune == "off":
        base = dict(spec.legacy)
    else:
        raise ValueError(f"tune must be 'auto' or 'off', got {tune!r}")
    base.update(ov)
    return base


# -- on-device timing sweep ---------------------------------------------------

def sweep(kernel: str, dims: Mapping[str, int], dtype,
          run_fn: Callable[[Mapping[str, int]], None], *,
          top_n: int = 3, reps: int = 5,
          include_legacy: bool = True) -> list[tuple[float, dict]]:
    """Time the top-N model-ranked candidates (plus the legacy constants)
    with `run_fn(blocks)` — which must block until the device is done —
    and return (median_seconds, blocks) ascending.  Offline use only
    (`benchmarks/bench_autotune.py`); dispatch never calls this."""
    ranked = rank(kernel, dims, dtype)
    pool = [blocks for _, blocks in ranked[:top_n]]
    legacy = dict(KERNELS[kernel].legacy)
    if include_legacy and legacy not in pool \
            and legacy in [b for _, b in ranked]:
        pool.append(legacy)
    stats["swept"] += 1
    timed = []
    for blocks in pool:
        run_fn(blocks)                       # warm-up eats compile time
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            run_fn(blocks)
            times.append(time.perf_counter() - t0)
        timed.append((statistics.median(times), blocks))
    timed.sort(key=lambda t: (t[0], sorted(t[1].items())))
    return timed


def record(kernel: str, dims: Mapping[str, int], dtype,
           blocks: Mapping[str, int], *, backend: str | None = None,
           source: str = "swept", us: float | None = None) -> str:
    """Persist a winner into the user cache (and the in-memory memo)."""
    backend = backend or jax.default_backend()
    key = cache_key(kernel, backend, dtype, dims)
    cache = _cache_at(user_cache_path())
    cache.put(key, blocks, source=source, us=us)
    cache.save()
    _memo[key] = dict(blocks)
    return key
