"""The machine model — the single home of every hardware constant.

Before this module existed the repo priced execution three different ways
with three copies of the same numbers: the autotuner's per-kernel ``_cost``
functions (kernels/autotune.py), the sparse/fused dispatch arithmetic
(launch/costmodel.py), and the dry-run roofline (launch/roofline.py).
Dünner et al. ("Understanding and Optimizing the Performance of Distributed
ML Applications on Apache Spark", 2016) make the case that one *calibrated*
analytical model of compute and bandwidth predicts the winning configuration
across a whole workload family; this module is that model, and
launch/planner.py is the one code path that consults it.

Two layers:

  * ``CostTerms`` — a declarative, machine-independent description of what
    an op does: FLOPs issued, HBM bytes moved, grid steps launched, and the
    MXU utilization fraction its tiling achieves.  The per-kernel terms
    functions in kernels/autotune.py produce these; nothing in them knows a
    bandwidth or a peak-FLOPs number.

  * ``MachineModel`` — turns terms into seconds:

        time = max(flops / (peak·util·mxu_eff), bytes / (bw·hbm_eff))
               + steps · step_overhead

    The built-in instances (``V5E``, ``CPU``) carry datasheet constants;
    ``calibrate()`` regresses the *effective* efficiencies ``mxu_eff`` /
    ``hbm_eff`` per dtype from recorded sweep timings (least squares on the
    roofline terms — eating our own optimizer), and ``save_calibration()``
    persists them next to the autotune config cache so every later
    ``planner.plan()`` prefers the calibrated constants.

Until a backend has been calibrated, every backend plans against the v5e
reference instance — deliberately: the CPU container ranks configs "as if
v5e" (deterministically, matching the shipped defaults), and dispatch
decisions are byte-ratio comparisons that a reference machine prices
correctly.  Calibrating a backend switches its plans to measured reality.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Mapping, Sequence

import numpy as np

# Layout constants (TPU tiled-memory geometry, not per-generation numbers).
LANE = 128
SUBLANE_BY_ITEMSIZE = {8: 8, 4: 8, 2: 16, 1: 32}


def _itemsize(dtype) -> int:
    import jax.numpy as jnp
    return jnp.dtype(dtype).itemsize


def _dtype_name(dtype) -> str:
    import jax.numpy as jnp
    return jnp.dtype(dtype).name


@dataclass(frozen=True)
class CostTerms:
    """What an op does, independent of the machine that runs it."""
    flops: float = 0.0           # MXU/VPU flops issued (padded shapes)
    hbm_bytes: float = 0.0       # bytes moved through HBM
    steps: float = 0.0           # grid steps launched
    mxu_util: float = 1.0        # utilization fraction of the tiling
    comm_bytes: float = 0.0      # bytes on the busiest ICI link (collectives)
    comm_steps: float = 0.0      # serial collective hops (latency term)


def collective_cost(n_devices: int, payload_bytes: float,
                    algorithm: str) -> tuple[float, float]:
    """(bytes on the busiest link, serial hops) for one all-reduce over a
    single torus axis of ``n_devices``.  Ring moves 2·P·(N−1)/N bytes in
    2·(N−1) hops (bandwidth-optimal); a binary reduce+broadcast tree moves
    2·P·⌈log₂N⌉ bytes in 2·⌈log₂N⌉ hops (latency-optimal for small P)."""
    n = int(n_devices)
    if n <= 1:
        return 0.0, 0.0
    if algorithm == "ring":
        return 2.0 * payload_bytes * (n - 1) / n, 2.0 * (n - 1)
    if algorithm == "tree":
        depth = math.ceil(math.log2(n))
        return 2.0 * payload_bytes * depth, 2.0 * depth
    raise ValueError(f"algorithm must be 'ring' or 'tree', got {algorithm!r}")


@dataclass(frozen=True)
class MachineModel:
    """Per-backend machine constants + calibrated effective efficiencies."""
    name: str
    mxu_flops: Mapping[int, float]      # peak FLOP/s by operand itemsize
    hbm_bw: float                       # bytes/s per chip
    step_overhead_s: float              # per-grid-step issue cost
    link_bw: float                      # bytes/s per ICI link
    vmem_bytes: int                     # fast scratch per core
    mxu_eff: Mapping[str, float] = field(default_factory=dict)  # dtype name
    hbm_eff: Mapping[str, float] = field(default_factory=dict)  # dtype name
    link_eff: Mapping[str, float] = field(default_factory=dict)  # dtype name
    link_latency_s: float = 1e-6        # per-hop collective latency
    source: str = "builtin"             # "builtin" | "calibrated"

    # -- constants, efficiency-adjusted --------------------------------------
    def peak_flops(self, dtype) -> float:
        base = self.mxu_flops.get(_itemsize(dtype),
                                  self.mxu_flops[max(self.mxu_flops)])
        return base * self.mxu_eff.get(_dtype_name(dtype), 1.0)

    def bandwidth(self, dtype) -> float:
        return self.hbm_bw * self.hbm_eff.get(_dtype_name(dtype), 1.0)

    def link_bandwidth(self, dtype) -> float:
        return self.link_bw * self.link_eff.get(_dtype_name(dtype), 1.0)

    # -- terms → seconds -----------------------------------------------------
    def breakdown(self, terms: CostTerms, dtype) -> dict:
        """The roofline decomposition plan().explain() prints."""
        compute_s = terms.flops / (self.peak_flops(dtype)
                                   * max(terms.mxu_util, 1e-9))
        memory_s = terms.hbm_bytes / self.bandwidth(dtype)
        step_s = terms.steps * self.step_overhead_s
        comm_s = 0.0
        if terms.comm_bytes or terms.comm_steps:
            comm_s = (terms.comm_bytes / self.link_bandwidth(dtype)
                      + terms.comm_steps * self.link_latency_s)
        bound = "compute" if compute_s >= memory_s else "memory"
        if comm_s > max(compute_s, memory_s):
            bound = "comm"
        total = max(compute_s, memory_s) + step_s
        if comm_s:     # keep the comm-free total bit-identical to the seed
            total += comm_s
        return {"compute_s": compute_s, "memory_s": memory_s,
                "step_s": step_s, "comm_s": comm_s, "bound": bound,
                "total_s": total}

    def time(self, terms: CostTerms, dtype) -> float:
        return self.breakdown(terms, dtype)["total_s"]

    # -- collectives ---------------------------------------------------------
    def collective(self, payload_bytes: float, axis_sizes: Sequence[int],
                   dtype="float32", algorithm: str = "auto") -> dict:
        """Price one all-reduce (psum) of ``payload_bytes`` over the mesh
        axes it reduces across — a sequential per-axis reduction, the way
        XLA lowers multi-axis psums on a torus.  ``algorithm`` picks ring
        vs tree per the link model; "auto" takes whichever is cheaper for
        this payload and topology (ring past the bandwidth break-even,
        tree under it)."""
        algos = ("ring", "tree") if algorithm == "auto" else (algorithm,)
        best = None
        for algo in algos:
            cb = cs = 0.0
            for nax in axis_sizes:
                b, s = collective_cost(nax, payload_bytes, algo)
                cb += b
                cs += s
            t = (cb / self.link_bandwidth(dtype)
                 + cs * self.link_latency_s)
            if best is None or t < best["comm_s"]:
                best = {"algorithm": algo, "comm_bytes": cb,
                        "comm_steps": cs, "comm_s": t}
        return best

    # -- calibration ---------------------------------------------------------
    def calibrate(self, records: Sequence[Mapping]) -> "MachineModel":
        """Fit effective MXU/HBM efficiencies per dtype from measured
        timings.  Each record carries its raw roofline terms (priced with
        efficiency 1 — ``planner.calibration_record`` builds them) plus the
        measured seconds:

            {"dtype": "float32", "flops": …, "hbm_bytes": …, "steps": …,
             "mxu_util": …, "measured_s": …}

        Least squares on the additive roofline relaxation
            measured − steps·overhead − comm_steps·latency
                ≈ a·compute_raw + b·hbm_raw [+ c·comm_raw]
        gives inverse efficiencies a = 1/mxu_eff, b = 1/hbm_eff, and —
        when any record carries collective terms (``comm_bytes`` from a
        distributed plan-vs-actual span or bench_collectives sweep) —
        c = 1/link_eff (the max() roofline is not linear; the sum is its
        standard regression surrogate and upper-bounds it within 2×).  The
        comm column joins the parameter vector only when the records
        exercise it, so compute-only sweeps reproduce the seed's two-term
        fit exactly.  Rows are weighted by 1/measured so the fit minimizes
        *relative* error — the metric ``error()`` scores and plan()
        decisions care about — instead of letting the largest shape
        dominate.  Coefficients are clamped positive; a dtype needs ≥ 2
        records to be fit."""
        by_dtype: dict[str, list[Mapping]] = {}
        for r in records:
            by_dtype.setdefault(str(r["dtype"]), []).append(r)
        mxu_eff = dict(self.mxu_eff)
        hbm_eff = dict(self.hbm_eff)
        link_eff = dict(self.link_eff)
        for dname, recs in by_dtype.items():
            if len(recs) < 2:
                continue
            has_comm = any(float(r.get("comm_bytes", 0.0)) > 0 for r in recs)
            A, y = [], []
            for r in recs:
                compute_raw = (float(r["flops"])
                               / (self.peak_flops_raw(dname)
                                  * max(float(r.get("mxu_util", 1.0)), 1e-9)))
                hbm_raw = float(r["hbm_bytes"]) / self.hbm_bw
                resid = (float(r["measured_s"])
                         - float(r.get("steps", 0.0)) * self.step_overhead_s
                         - (float(r.get("comm_steps", 0.0))
                            * self.link_latency_s))
                scale = 1.0 / max(float(r["measured_s"]), 1e-12)
                row = [compute_raw * scale, hbm_raw * scale]
                if has_comm:
                    row.append(float(r.get("comm_bytes", 0.0))
                               / self.link_bw * scale)
                A.append(row)
                y.append(max(resid, 0.0) * scale)
            A = np.asarray(A, np.float64)
            y = np.asarray(y, np.float64)
            ncol = A.shape[1]
            coef, *_ = np.linalg.lstsq(A, y, rcond=None)
            coef = [float(v) for v in coef]
            if coef[0] <= 0 or coef[1] <= 0:
                # Degenerate fit (one term dominates every record, or the
                # terms are collinear): projected NNLS — take whichever
                # single-slope fit leaves the smaller residual.
                fits = []
                for col in range(ncol):
                    s = float(A[:, col] @ y
                              / max(A[:, col] @ A[:, col], 1e-30))
                    s = max(s, 0.0)
                    sse = float(((A[:, col] * s - y) ** 2).sum())
                    fits.append((sse, col, s))
                _, col, s = min(fits)
                coef = [0.0] * ncol
                coef[col] = s
            a, b = coef[0], coef[1]
            c = coef[2] if ncol > 2 else 0.0
            if a > 0:
                mxu_eff[dname] = float(np.clip(1.0 / a, 1e-4, 16.0))
            if b > 0:
                hbm_eff[dname] = float(np.clip(1.0 / b, 1e-4, 16.0))
            if c > 0:
                link_eff[dname] = float(np.clip(1.0 / c, 1e-4, 16.0))
        return dataclasses.replace(self, mxu_eff=mxu_eff, hbm_eff=hbm_eff,
                                   link_eff=link_eff, source="calibrated")

    def peak_flops_raw(self, dname: str) -> float:
        import jax.numpy as jnp
        it = jnp.dtype(dname).itemsize
        return self.mxu_flops.get(it, self.mxu_flops[max(self.mxu_flops)])

    def error(self, records: Sequence[Mapping]) -> float:
        """Mean relative |modeled − measured| / measured over records —
        the number calibration must tighten."""
        errs = []
        for r in records:
            t = self.time(
                CostTerms(flops=float(r["flops"]),
                          hbm_bytes=float(r["hbm_bytes"]),
                          steps=float(r.get("steps", 0.0)),
                          mxu_util=float(r.get("mxu_util", 1.0)),
                          comm_bytes=float(r.get("comm_bytes", 0.0)),
                          comm_steps=float(r.get("comm_steps", 0.0))),
                str(r["dtype"]))
            meas = float(r["measured_s"])
            if meas > 0:
                errs.append(abs(t - meas) / meas)
        return float(np.mean(errs)) if errs else float("nan")

    # -- persistence ---------------------------------------------------------
    def as_dict(self) -> dict:
        return {"name": self.name,
                "mxu_flops": {str(k): v for k, v in self.mxu_flops.items()},
                "hbm_bw": self.hbm_bw,
                "step_overhead_s": self.step_overhead_s,
                "link_bw": self.link_bw, "vmem_bytes": self.vmem_bytes,
                "mxu_eff": dict(self.mxu_eff), "hbm_eff": dict(self.hbm_eff),
                "link_eff": dict(self.link_eff),
                "link_latency_s": self.link_latency_s,
                "source": self.source}

    @staticmethod
    def from_dict(d: Mapping) -> "MachineModel":
        return MachineModel(
            name=d["name"],
            mxu_flops={int(k): float(v) for k, v in d["mxu_flops"].items()},
            hbm_bw=float(d["hbm_bw"]),
            step_overhead_s=float(d["step_overhead_s"]),
            link_bw=float(d["link_bw"]), vmem_bytes=int(d["vmem_bytes"]),
            mxu_eff=dict(d.get("mxu_eff", {})),
            hbm_eff=dict(d.get("hbm_eff", {})),
            link_eff=dict(d.get("link_eff", {})),
            link_latency_s=float(d.get("link_latency_s", 1e-6)),
            source=d.get("source", "builtin"))


# -- built-in instances -------------------------------------------------------
# The ONLY place these numbers appear in src/: every roofline, every
# dispatch, every ranking imports them from here.

V5E = MachineModel(
    name="tpu-v5e",
    mxu_flops={1: 394e12, 2: 197e12, 4: 98.5e12},  # int8 / bf16 / f32 peak
    hbm_bw=819e9,                        # bytes/s per chip
    step_overhead_s=2e-7,                # per-grid-step issue cost
    link_bw=50e9,                        # bytes/s per ICI link
    vmem_bytes=128 * 2**20,              # physical VMEM per TensorCore
    link_latency_s=1e-6)                 # per-ICI-hop collective latency

CPU = MachineModel(
    name="cpu-host",
    mxu_flops={1: 1e11, 2: 1e11, 4: 1e11},  # a few vector cores' worth
    hbm_bw=3e10,                         # one socket's DRAM stream
    step_overhead_s=1e-6,                # dispatch/loop overhead per tile
    link_bw=1e10,
    vmem_bytes=16 * 2**20,               # keeps tilings TPU-shaped
    link_latency_s=2e-6)                 # shared-memory "hop" (host psum)

# TPU models by the device_kind JAX reports: a chip with no entry is an
# error, never a default.
TPU_BY_KIND = {"TPU v5 lite": V5E}


def tpu_model(device_kind: str) -> MachineModel:
    """The built-in model of the TPU JAX names `device_kind`."""
    try:
        return TPU_BY_KIND[device_kind]
    except KeyError:
        raise ValueError(f"no machine model for TPU device_kind "
                         f"{device_kind!r} (known: {sorted(TPU_BY_KIND)})"
                         ) from None


def _attached_tpu() -> MachineModel | None:
    """The model of the TPU this process runs on, None off TPU."""
    import jax
    if jax.default_backend() != "tpu":
        return None
    return tpu_model(jax.devices()[0].device_kind)


def builtin(backend: str) -> MachineModel:
    if backend == "tpu":
        return _attached_tpu() or V5E
    return CPU


# -- calibration cache (next to the autotune config cache) --------------------

def calibration_path() -> Path:
    """machine.json in the same directory as the autotune config cache
    ($REPRO_AUTOTUNE_CACHE redirects both)."""
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    base = Path(env) if env else Path.home() / ".cache" / "repro" / "autotune.json"
    return base.with_name("machine.json")


_loaded: dict[Path, dict] = {}


def invalidate_cache() -> None:
    """Forget loaded calibrations (tests; after save_calibration)."""
    _loaded.clear()


def _calibrations(path: Path) -> dict:
    if path not in _loaded:
        try:
            data = json.loads(Path(path).read_text())
            _loaded[path] = dict(data.get("backends", {}))
        except (OSError, ValueError):
            _loaded[path] = {}
    return _loaded[path]


def save_calibration(backend: str, model: MachineModel,
                     path: Path | None = None) -> Path:
    """Persist a calibrated model for `backend`; later for_backend() calls
    prefer it over the builtin reference."""
    path = Path(path) if path else calibration_path()
    try:
        data = json.loads(path.read_text())
    except (OSError, ValueError):
        data = {"version": 1, "backends": {}}
    data.setdefault("backends", {})[backend] = model.as_dict()
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(data, indent=1, sort_keys=True))
    tmp.replace(path)
    invalidate_cache()
    return path


def for_backend(backend: str | None = None, *,
                prefer_calibrated: bool = True) -> MachineModel:
    """The machine model every dispatch decision prices against: the
    calibrated model for this backend when one has been recorded; on a TPU
    the built-in model of its device_kind (an unknown chip raises); else
    the v5e reference instance (see module docstring for why the reference
    — not the CPU instance — is the uncalibrated default off TPU)."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    attached = _attached_tpu() if backend == "tpu" else None
    if prefer_calibrated:
        entry = _calibrations(calibration_path()).get(backend)
        if entry is not None:
            return MachineModel.from_dict(entry)
    return attached or V5E
