"""The unified execution planner — one code path for every "should we?".

Every dispatch decision the repo makes — which block config a Pallas kernel
runs with, BSR-vs-dense for a sparse shard, fused-vs-unfused composite
gradients, the BSR block size, the SVD mode — used to live in a different
module with its own copy of the machine constants.  ``plan()`` is now the
single entry point: it prices the alternatives against ONE
``MachineModel`` (launch/machine.py — calibrated per backend when sweep
timings have been recorded) and returns an ``ExecutionPlan`` that names the
chosen path, the block config, the modeled cost, and an ``explain()``
breakdown of why.

    >>> from repro.launch import planner
    >>> p = planner.plan("sparse_matmul",
    ...                  {"m": 4096, "n": 2048, "nx": 1, "ell": 2, "bs": 128})
    >>> p.choice
    'bsr'
    >>> print(p.explain())          # roofline terms + alternatives

Supported ops:

  kernel block selection   "gemm" | "tsgram" | "randsketch" | "fusedgrad" |
                           "flash_attention" | "selective_scan" | "bsr"
                           (dims = the kernel's logical dims; choice is the
                           kernel name, blocks the selected config — memo /
                           persistent sweep cache / model ranking, exactly
                           the ops-wrapper ``tune="auto"`` path)
  "sparse_matmul"          {m, n, nx, ell, bs} per-shard BSR-vs-dense
  "grad"                   {m, n} per-shard fused-vs-unfused composite
                           gradient (one A read vs two); with context
                           {"axes": mesh axis sizes} the psum of (f, g) is
                           priced end-to-end and an overlapped chunk count
                           is chosen (blocks["chunks"], 1 = eager).  A
                           BlockELL shard passes {m, n, bs, ell} + context
                           {"kind": "sparse"} (dtype int8 = quantized)
  "bsr_bs"                 {m, n, nx} + context {"ell_by_bs": {bs: ell}}
                           block-size selection on actual ELL widths
  "svd"                    {m, n, k} + context {"kind": "row"|"sparse"|
                           "other", thresholds} → gram | randomized | lanczos
  "gram"                   {m, n} per-shard AᵀA + context {"axes": …}:
                           eager tsgram+psum vs column-chunked cross-grams
                           whose partial psums pipeline behind the next
                           chunk's compute (choice "eager"|"overlap",
                           blocks["chunks"])
  "matvec"                 {m, n} one streaming shard pass + context
                           {"axes": …} reduction of the n-vector result;
                           choice names the reduction (ring|tree|local)

Precision is a planner axis too: pass a solver tolerance via
``context={"tol": ...}`` and grad/gram/matvec/sparse_matmul plans sweep
{f32, bf16 storage, int8 BlockELL, int8 error-feedback compressed psum}
against the PRECISION_GUARDS accuracy ceilings, picking the fastest
candidate the tolerance admits that also clears a savings floor (tiny
shapes stay f32).  The chosen plan's ``precision`` field names the pick,
``explain()`` prints it plus the modeled byte savings, and the solvers'
``precision="auto"`` (core/optim/first_order.py) defers to this decision.

Distributed ops price their collectives with ``MachineModel.collective``
(ring vs tree by mesh shape and payload — pass mesh axis sizes via
``launch.mesh.axis_sizes``), and ``explain()`` reports the comm fraction.
Decision functions are memoized (the shard_map bodies consult them at trace
time); ``kernels.autotune.reset()`` clears every layer at once.
"""
from __future__ import annotations

import functools
import math
import json
from dataclasses import dataclass, field
from typing import Mapping

from repro.kernels import autotune as at
from repro.launch import machine as _machine
from repro.launch.machine import LANE, CostTerms, MachineModel

KERNEL_OPS = tuple(at.KERNELS)
DECISION_OPS = ("sparse_matmul", "grad", "bsr_bs", "svd", "gram", "matvec")

# Overlap chunk counts the distributed deciders sweep (1 = eager
# compute-then-reduce); segments narrower than a lane never win.
CHUNK_CANDIDATES = (1, 2, 4, 8)

# BSR block-size candidates — the one definition (SparseRowMatrix's
# bs="auto" constructors and plan("bsr_bs") both sweep this list).
BS_CANDIDATES = (8, 16, 32, 64, 128)

# Precision as a planner axis.  When the caller passes a solver tolerance
# (context={"tol": ...}) and the operand is float32, grad/gram/matvec/
# sparse_matmul plans sweep lower-precision executions and pick the fastest
# candidate whose accuracy guard the tolerance clears:
#
#   "bf16"   A stored bfloat16, tiles upcast on-chip, f32 accumulation
#            (halves the HBM stream of every A pass)
#   "int8"   BlockELL data int8 + per-block f32 scale (sparse_matmul only)
#   "psum8"  error-feedback int8 compressed all-reduce for the distributed
#            (f, g) / gram reductions (train/compression.psum_int8) — the
#            wire payload drops 4×, a 4-byte shared-scale pmax rides along
#
# The guard values are worst-case relative-error ceilings per candidate
# (bf16 has ~3 decimal digits; int8 block quantization ~2; psum8 is tighter
# than its per-step quantization error because error feedback re-injects
# the residual, keeping the *converged* solution at tolerance).  A
# candidate is admissible iff tol >= guard.  On top of the guard, a
# savings floor keeps tiny shapes at f32: low precision must win by
# max(PRECISION_MIN_SAVINGS_FRAC of the f32 time, PRECISION_MIN_SAVINGS_S)
# or the plan stays exact — flipping precision for nanoseconds is all risk.
PRECISION_OPS = ("grad", "gram", "matvec", "sparse_matmul")
PRECISION_GUARDS = {"f32": 0.0, "psum8": 1e-6, "bf16": 1e-5, "int8": 1e-3}
PRECISION_MIN_SAVINGS_FRAC = 0.20
PRECISION_MIN_SAVINGS_S = 2e-6

# SVD auto-mode gates (paper §3.1 dispatch; see core/linalg/svd.py for the
# derivations of the two numbers).
GRAM_THRESHOLD = 8192
RANDOMIZED_K_THRESHOLD = 128


def _us(s: float) -> str:
    return f"{s * 1e6:.2f} us"


@dataclass(frozen=True)
class ExecutionPlan:
    """What to run and why — the planner's answer for one op instance."""
    op: str
    choice: str                       # chosen kernel/path/mode
    blocks: Mapping[str, int]         # block config ({} for path decisions)
    cost_s: float                     # modeled seconds of the choice
    dims: Mapping[str, int]
    dtype: str
    backend: str
    machine: str                      # MachineModel.name
    calibrated: bool                  # modeled with calibrated efficiencies?
    breakdown: Mapping[str, float] = field(default_factory=dict)
    alternatives: tuple = ()          # ((label, modeled_s), ...) ascending
    notes: tuple = ()
    terms: Mapping[str, float] = field(default_factory=dict)
    # ^ raw (efficiency-1) cost terms of the chosen path for decision ops
    #   that price collectives — lets actual_record() feed calibrate()
    #   with the comm column (kernel ops rebuild terms from blocks instead).
    precision: str = ""
    # ^ "" when the plan was not precision-swept (no context["tol"]);
    #   otherwise the chosen storage/wire precision: "f32" | "bf16" |
    #   "int8" | "psum8".  `dtype` stays the caller's logical operand
    #   dtype — precision names how the bytes move, not what x means.

    def explain(self) -> str:
        """Human-readable roofline breakdown of the decision."""
        dims = " ".join(f"{k}={v}" for k, v in self.dims.items())
        lines = [
            f"plan({self.op}) -> {self.choice}"
            + (f" {dict(self.blocks)}" if self.blocks else ""),
            f"  dims: {dims}  dtype={self.dtype}  backend={self.backend}",
            f"  machine: {self.machine}"
            f" ({'calibrated' if self.calibrated else 'builtin constants'})",
            f"  modeled: {_us(self.cost_s)}",
        ]
        if self.precision:
            lines.insert(2, f"  precision: {self.precision}")
        b = self.breakdown
        if b:
            lines.append(
                f"  roofline: compute {_us(b['compute_s'])}"
                f" | memory {_us(b['memory_s'])}"
                f" | steps {_us(b['step_s'])}  -> {b['bound']}-bound")
            comm_s = b.get("comm_s", 0.0)
            if comm_s:
                frac = comm_s / b["total_s"] if b["total_s"] > 0 else 0.0
                lines.append(f"  comm: {_us(comm_s)}"
                             f" ({frac:.0%} of modeled serial time)")
        if self.alternatives:
            selected = {self.choice,
                        json.dumps(dict(self.blocks), sort_keys=True)}
            lines.append("  alternatives:")
            for label, s in self.alternatives:
                marker = "*" if label in selected else " "
                lines.append(f"   {marker} {label}: {_us(s)}")
        for n in self.notes:
            lines.append(f"  note: {n}")
        return "\n".join(lines)


def invalidate_cache() -> None:
    """Forget memoized decisions (recalibration / tests)."""
    _decide_cached.cache_clear()


def plan(op: str, dims: Mapping[str, int], dtype="float32", *,
         backend: str | None = None, machine: MachineModel | None = None,
         context: Mapping | None = None, top: int = 0) -> ExecutionPlan:
    """Price the alternatives for `op` and return the chosen ExecutionPlan.

    `backend` defaults to the jax default backend; `machine` overrides the
    calibrated-model lookup (and bypasses the decision memo).  `top` > 0
    attaches the top-N ranked block configs as alternatives for kernel ops.
    `context` carries op-specific non-shape inputs (see module docstring).
    """
    import jax
    import jax.numpy as jnp
    backend = backend or jax.default_backend()
    dtype_name = jnp.dtype(dtype).name
    if op in KERNEL_OPS:
        return _plan_kernel(op, dict(dims), dtype_name, backend,
                            machine, top)
    if op not in DECISION_OPS:
        raise ValueError(f"unknown op {op!r}; expected one of "
                         f"{KERNEL_OPS + DECISION_OPS}")
    dims_key = tuple(sorted((k, int(v)) for k, v in dims.items()))
    ctx_key = _freeze(context or {})
    if machine is not None:
        return _decide(op, dims_key, dtype_name, backend, ctx_key, machine)
    return _decide_cached(op, dims_key, dtype_name, backend, ctx_key)


def _freeze(obj):
    if isinstance(obj, Mapping):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    if isinstance(obj, (list, tuple)):
        return tuple(_freeze(v) for v in obj)
    return obj


def _thaw_ctx(ctx_key) -> dict:
    out = {}
    for k, v in ctx_key:
        out[k] = dict(v) if isinstance(v, tuple) and v \
            and isinstance(v[0], tuple) else v
    return out


# -- kernel block selection ----------------------------------------------------

def _plan_kernel(op: str, dims: dict, dtype_name: str, backend: str,
                 machine: MachineModel | None, top: int) -> ExecutionPlan:
    explicit = machine is not None
    machine = machine or _machine.for_backend(backend)
    if explicit:
        blocks = at.rank(op, {k: at.bucket(int(v)) for k, v in dims.items()},
                         dtype_name, machine=machine)[0][1]
    else:
        # The memo → persistent sweep cache → ranking path the ops wrappers
        # have always dispatched through (kernels/autotune.get_config).
        blocks = at.get_config(op, dims, dtype_name, backend=backend)
    terms = at.cost_terms(op, blocks, dims, dtype_name)
    br = machine.breakdown(terms, dtype_name)
    alts = ()
    if top > 0:
        ranked = at.rank(op, dims, dtype_name, machine=machine)[:top]
        alts = tuple((json.dumps(b, sort_keys=True), s) for s, b in ranked)
    return ExecutionPlan(
        op=op, choice=op, blocks=dict(blocks), cost_s=br["total_s"],
        dims=dims, dtype=dtype_name, backend=backend, machine=machine.name,
        calibrated=machine.source == "calibrated", breakdown=br,
        alternatives=alts)


# -- path decisions ------------------------------------------------------------

@functools.lru_cache(maxsize=4096)
def _decide_cached(op, dims_key, dtype_name, backend, ctx_key):
    return _decide(op, dims_key, dtype_name, backend, ctx_key,
                   _machine.for_backend(backend))


def _decide(op, dims_key, dtype_name, backend, ctx_key,
            machine: MachineModel) -> ExecutionPlan:
    d = dict(dims_key)
    ctx = _thaw_ctx(ctx_key)
    kw = dict(dims=d, dtype=dtype_name, backend=backend,
              machine=machine.name,
              calibrated=machine.source == "calibrated")
    if op in PRECISION_OPS and "tol" in ctx and dtype_name == "float32":
        return _decide_precision(op, d, dtype_name, machine, ctx, kw)
    if op == "sparse_matmul":
        return _decide_sparse(d, dtype_name, machine, ctx, kw)
    if op == "grad":
        return _decide_grad(d, dtype_name, machine, ctx, kw)
    if op == "bsr_bs":
        return _decide_bsr_bs(d, dtype_name, machine, ctx, kw)
    if op == "gram":
        return _decide_gram(d, dtype_name, machine, ctx, kw)
    if op == "matvec":
        return _decide_matvec(d, dtype_name, machine, ctx, kw)
    return _decide_svd(d, dtype_name, machine, ctx, kw)


# -- collective helpers --------------------------------------------------------

def _axes(ctx) -> tuple[int, ...]:
    """Mesh axis sizes the op reduces across (context["axes"]); () when the
    caller runs single-device / undistributed."""
    return tuple(int(a) for a in ctx.get("axes", ()) or ())


def _terms_dict(t: CostTerms) -> dict:
    return {"flops": t.flops, "hbm_bytes": t.hbm_bytes, "steps": t.steps,
            "mxu_util": t.mxu_util, "comm_bytes": t.comm_bytes,
            "comm_steps": t.comm_steps}


def _with_comm(t: CostTerms, coll: Mapping) -> CostTerms:
    import dataclasses
    return dataclasses.replace(
        t, comm_bytes=t.comm_bytes + coll["comm_bytes"],
        comm_steps=t.comm_steps + coll["comm_steps"])


def _pipeline_s(t_chunk: float, comm_chunk: float, chunks: int,
                pre: float = 0.0) -> float:
    """Modeled wall time of `chunks` compute→psum stages where chunk k's
    psum overlaps chunk k+1's compute: the first compute and the last psum
    are exposed, every middle stage costs max(compute, comm)."""
    if chunks <= 1:
        return pre + t_chunk + comm_chunk
    return (pre + t_chunk
            + (chunks - 1) * max(t_chunk, comm_chunk) + comm_chunk)


def _chunk_counts(n: int) -> tuple[int, ...]:
    """Chunk counts worth sweeping for an n-column segment split."""
    return tuple(c for c in CHUNK_CANDIDATES if c == 1 or n // c >= LANE)


def _psum_cost(machine, elems: float, axes, dtype_name, wire=None) -> dict:
    """Price the all-reduce of an `elems`-element f32 accumulator.

    Default wire format is the f32 payload itself.  wire="int8" prices the
    error-feedback compressed collective (train/compression.psum_int8):
    the payload ships as int8 (4× fewer wire bytes) plus one 4-byte
    shared-scale pmax per reduction — cheap on fat payloads, pure latency
    overhead on small ones, which is exactly what the sweep should see."""
    if wire == "int8":
        body = machine.collective(elems * 1.0, axes, "int8")
        scale = machine.collective(4.0, axes, dtype_name)
        return {"algorithm": f"{body['algorithm']}+int8",
                "comm_bytes": body["comm_bytes"] + scale["comm_bytes"],
                "comm_steps": body["comm_steps"] + scale["comm_steps"],
                "comm_s": body["comm_s"] + scale["comm_s"]}
    return machine.collective(elems * 4.0, axes, dtype_name)


def _decide_precision(op, d, dtype_name, machine, ctx, kw) -> ExecutionPlan:
    """Sweep storage/wire precision for one decision op against the solver
    tolerance in context["tol"] (see PRECISION_GUARDS above).  Each
    candidate re-prices the op's full decision at the candidate's byte
    widths — bf16 swaps the storage dtype, psum8 swaps the collective wire
    format, int8 swaps the BlockELL data dtype — so precision composes
    with the existing fused/chunked/BSR choices rather than bypassing
    them.  The returned plan keeps the caller's logical dtype and reports
    the pick + modeled byte savings in `precision` / notes."""
    import dataclasses
    tol = float(ctx["tol"])
    sub = {k: v for k, v in ctx.items() if k != "tol"}

    def run(dname, wire=None):
        c = dict(sub)
        if wire:
            c["wire"] = wire
        kw2 = dict(kw, dtype=dname)
        if op == "sparse_matmul":
            return _decide_sparse(d, dname, machine, c, kw2)
        if op == "grad":
            return _decide_grad(d, dname, machine, c, kw2)
        if op == "gram":
            return _decide_gram(d, dname, machine, c, kw2)
        return _decide_matvec(d, dname, machine, c, kw2)

    base = run(dtype_name)
    cands = [("f32", base)]
    if tol >= PRECISION_GUARDS["psum8"] and op in ("grad", "gram") \
            and _axes(ctx):
        cands.append(("psum8", run(dtype_name, wire="int8")))
    if tol >= PRECISION_GUARDS["bf16"]:
        cands.append(("bf16", run("bfloat16")))
    if tol >= PRECISION_GUARDS["int8"] and op == "sparse_matmul":
        p8 = run("int8")
        if p8.choice == "bsr":     # only BlockELL data quantizes to int8
            cands.append(("int8", p8))

    floor = max(PRECISION_MIN_SAVINGS_S,
                PRECISION_MIN_SAVINGS_FRAC * base.cost_s)
    label, best = "f32", base
    for lb, p in cands[1:]:
        if base.cost_s - p.cost_s >= floor and p.cost_s < best.cost_s:
            label, best = lb, p

    def _moved(p):
        t = p.terms or {}
        return float(t.get("hbm_bytes", 0.0)) + float(t.get("comm_bytes", 0.0))

    b0, b1 = _moved(base), _moved(best)
    if label == "f32":
        note = (f"precision: f32 — no admissible candidate cleared the "
                f"savings floor max({PRECISION_MIN_SAVINGS_FRAC:.0%}, "
                f"{_us(PRECISION_MIN_SAVINGS_S)}) at tol={tol:g}")
    else:
        saved = 1.0 - b1 / b0 if b0 > 0 else 0.0
        note = (f"precision: {label} — modeled bytes {b0:.4g} -> {b1:.4g} "
                f"({saved:.0%} saved); tol={tol:g} clears guard "
                f"{PRECISION_GUARDS[label]:g}")
    return dataclasses.replace(
        best, precision=label, dtype=dtype_name,
        alternatives=best.alternatives + tuple(
            sorted(((f"precision:{lb}", p.cost_s) for lb, p in cands),
                   key=lambda t: t[1])),
        notes=best.notes + (note,))


def _decide_sparse(d, dtype_name, machine, ctx, kw) -> ExecutionPlan:
    """Per-shard BSR-vs-dense for an (m × n) BlockELL shard with `ell`
    stored blocks per block-row of size `bs`, times an (n × nx) operand
    (nx=1 for SpMV).  The BSR side pays lane/sublane padding on every
    stored block plus a per-block grid step; the dense side streams the
    full m·n at the best-ranked GEMM tiling.  At dtype int8 (the
    quantized-BlockELL candidate of the precision sweep) the BSR side
    also streams one f32 scale per stored block."""
    import dataclasses
    m, n, nx = d["m"], d["n"], max(d.get("nx", 1), 1)
    bsr_dims = {"m": m, "n": n, "nx": nx, "ell": d["ell"]}
    bsr_terms = at.cost_terms("bsr", {"bs": d["bs"]}, bsr_dims, dtype_name)
    if dtype_name == "int8":
        nbr = at._rup(m, d["bs"]) // d["bs"]
        bsr_terms = dataclasses.replace(
            bsr_terms,
            hbm_bytes=bsr_terms.hbm_bytes + nbr * d["ell"] * 4.0)
    bsr_s = machine.time(bsr_terms, dtype_name)
    gemm_dims = {"m": m, "k": n, "n": nx}
    dense_s, dense_blocks = at.rank("gemm", gemm_dims, dtype_name,
                                    machine=machine)[0]
    use_bsr = bsr_s <= dense_s
    chosen_terms = bsr_terms if use_bsr else at.cost_terms(
        "gemm", dense_blocks, gemm_dims, dtype_name)
    return ExecutionPlan(
        op="sparse_matmul", choice="bsr" if use_bsr else "dense",
        blocks={"bs": d["bs"]} if use_bsr else dict(dense_blocks),
        cost_s=min(bsr_s, dense_s),
        breakdown=machine.breakdown(chosen_terms, dtype_name),
        alternatives=tuple(sorted((("bsr", bsr_s), ("dense", dense_s)),
                                  key=lambda t: t[1])),
        notes=(f"stored-block fraction ell/nbc = "
               f"{d['ell'] / max(n // d['bs'], 1):.3f}",),
        terms=_terms_dict(chosen_terms), **kw)


def _decide_grad(d, dtype_name, machine, ctx, kw) -> ExecutionPlan:
    """Fused single-pass gradient vs apply + adjoint for an (m × n) shard.

    The fused side is the best-ranked fusedgrad config (ONE A read, but its
    t/w/z vector strips force lane-aligned row blocks).  The unfused side is
    two independent streaming passes, each priced on its OWN sublane-aligned
    layout — that asymmetry is the real trade: one read vs two, against
    lane-padding waste, so tiny row shards (m ≪ 128) pick unfused.

    With context {"axes": mesh axis sizes} the (f, g) psum is priced too,
    and a column-chunked overlapped schedule competes with the eager body:
    one full pass produces z and the residual, then the gradient is built
    per column segment with each segment's partial psum pipelined behind
    the next segment's compute (an extra read of A buys comm hiding —
    RowMatrix.fused_grad implements blocks["chunks"])."""
    import jax.numpy as jnp
    if ctx.get("kind") == "sparse":
        return _decide_grad_sparse(d, dtype_name, machine, ctx, kw)
    m, n = d["m"], d["n"]
    db = jnp.dtype(dtype_name).itemsize
    ranked = at.rank("fusedgrad", {"m": m, "n": n}, dtype_name,
                     machine=machine)
    # No row block of the dense kernel fits VMEM at this width: the fused
    # kernel is out, the two streaming passes are not.
    fused_s, fused_blocks = ranked[0] if ranked else (math.inf, {})
    mp = at._rup(m, at.sublane(dtype_name))
    np_ = at._rup(n, LANE)
    bm = min(512, mp)
    pass_terms = CostTerms(flops=2.0 * mp * np_,
                           hbm_bytes=(mp * np_ + mp + np_) * db,
                           steps=-(-mp // bm))
    unfused_s = 2.0 * machine.time(pass_terms, dtype_name)
    axes = _axes(ctx)
    if not axes:
        use_fused = fused_s <= unfused_s
        # Breakdown of the CHOSEN side: the fused kernel's terms, or both
        # unfused passes together (2× one pass — max and steps scale alike).
        chosen_terms = at.cost_terms(
            "fusedgrad", fused_blocks, {"m": m, "n": n}, dtype_name) \
            if use_fused else CostTerms(flops=2 * pass_terms.flops,
                                        hbm_bytes=2 * pass_terms.hbm_bytes,
                                        steps=2 * pass_terms.steps)
        return ExecutionPlan(
            op="grad", choice="fused" if use_fused else "unfused",
            blocks=dict(fused_blocks) if use_fused else {},
            cost_s=min(fused_s, unfused_s),
            breakdown=machine.breakdown(chosen_terms, dtype_name),
            alternatives=tuple(sorted((("fused", fused_s),
                                       ("unfused", unfused_s)),
                                      key=lambda t: t[1])),
            notes=("unfused = 2 sublane-padded streaming passes; "
                   "fused = 1 lane-padded pass",),
            terms=_terms_dict(chosen_terms), **kw)

    # Distributed: every alternative ends in a psum of the f32 (g, f)
    # accumulator — (n+1) elements whatever the storage dtype; context
    # {"wire": "int8"} prices the compressed-collective wire format.
    wire = ctx.get("wire")
    coll = _psum_cost(machine, n + 1.0, axes, dtype_name, wire)
    cands = [("fused", 1, fused_s + coll["comm_s"], _with_comm(
        at.cost_terms("fusedgrad", fused_blocks, {"m": m, "n": n},
                      dtype_name), coll))] if ranked else []
    pre = machine.time(pass_terms, dtype_name)
    for c in _chunk_counts(n):
        if c == 1:
            continue
        seg = -(-n // c)
        segp = at._rup(seg, LANE)
        chunk_terms = CostTerms(flops=2.0 * mp * segp,
                                hbm_bytes=(mp * segp + mp + segp) * db,
                                steps=-(-mp // bm))
        cc = _psum_cost(machine, float(seg), axes, dtype_name, wire)
        total = _pipeline_s(machine.time(chunk_terms, dtype_name),
                            cc["comm_s"], c, pre=pre)
        agg = CostTerms(
            flops=pass_terms.flops + c * chunk_terms.flops,
            hbm_bytes=pass_terms.hbm_bytes + c * chunk_terms.hbm_bytes,
            steps=pass_terms.steps + c * chunk_terms.steps,
            comm_bytes=c * cc["comm_bytes"], comm_steps=c * cc["comm_steps"])
        cands.append((f"fused-overlap{c}", c, total, agg))
    unfused_terms = _with_comm(
        CostTerms(flops=2 * pass_terms.flops,
                  hbm_bytes=2 * pass_terms.hbm_bytes,
                  steps=2 * pass_terms.steps), coll)
    cands.append(("unfused", 1, unfused_s + coll["comm_s"], unfused_terms))
    label, chunks, best_s, chosen_terms = min(cands, key=lambda t: t[2])
    use_fused = label != "unfused"
    notes = [f"psum({n}·4B) over axes={axes}: {coll['algorithm']} "
             f"all-reduce, {_us(coll['comm_s'])}"]
    if chunks > 1:
        notes.append(f"overlap: {chunks} column chunks pipeline each "
                     "partial psum behind the next chunk's compute "
                     "(one extra A read)")
    return ExecutionPlan(
        op="grad", choice="fused" if use_fused else "unfused",
        blocks={**dict(fused_blocks), "chunks": chunks} if use_fused else {},
        cost_s=best_s,
        breakdown=machine.breakdown(chosen_terms, dtype_name),
        alternatives=tuple(sorted(((lb, s) for lb, _, s, _ in cands),
                                  key=lambda t: t[1])),
        notes=tuple(notes), terms=_terms_dict(chosen_terms), **kw)


def _decide_grad_sparse(d, dtype_name, machine, ctx, kw) -> ExecutionPlan:
    """Fused single-read BSR gradient vs SpMV + transpose-multiply for an
    (m × n) BlockELL shard of `ell` (bs × bs) blocks per block-row.  Both
    sides stream the same padded blocks, so the fused kernel (one read
    against two) wins wherever it runs.  It does not run on int8-quantized
    blocks (no scale-aware fused kernel) or once its n-wide resident x and
    gradient accumulator outgrow VMEM; ops.fused_grad_bsr composes the two
    passes there, and the plan says so."""
    from repro.kernels import fusedgrad as _fg
    m, n, bs, ell = d["m"], d["n"], d["bs"], d["ell"]
    one = at.cost_terms("bsr", {"bs": bs},
                        {"m": m, "n": n, "nx": 1, "ell": ell}, dtype_name)
    pass_s = machine.time(one, dtype_name)
    fits = dtype_name != "int8" and _fg.fused_grad_bsr_vmem(
        bs, ell, n, dtype_name) <= at.VMEM_BUDGET
    cands = [("fused", 1, pass_s)] if fits else []
    cands.append(("unfused", 2, 2.0 * pass_s))
    label, reads, cost_s = min(cands, key=lambda t: t[2])
    terms = CostTerms(flops=reads * one.flops, hbm_bytes=reads * one.hbm_bytes,
                      steps=reads * one.steps, mxu_util=one.mxu_util)
    return ExecutionPlan(
        op="grad", choice=label,
        blocks={"bs": bs} if label == "fused" else {},
        cost_s=cost_s, breakdown=machine.breakdown(terms, dtype_name),
        alternatives=tuple(sorted(((lb, s) for lb, _, s in cands),
                                  key=lambda t: t[1])),
        notes=("fused = 1 read of the stored blocks; unfused = 2" + (
            "" if fits else "; the fused BSR kernel cannot run here "
            "(int8 blocks or VMEM)"),),
        terms=_terms_dict(terms), **kw)


def _decide_gram(d, dtype_name, machine, ctx, kw) -> ExecutionPlan:
    """Distributed AᵀA for an (m × n) row shard: eager tsgram + one n×n
    psum, vs C column-segment cross-grams Aᵀ·A[:, seg] whose n×(n/C)
    partial psums pipeline behind the next segment's compute.  Chunking
    re-reads A once per segment — it wins only when the modeled collective
    time dominates that extra memory traffic (pod-scale meshes), so eager
    stays the dispatch default on small meshes."""
    m, n = d["m"], d["n"]
    gram_s, gram_blocks = at.rank("tsgram", {"m": m, "n": n},
                                  dtype_name, machine=machine)[0]
    axes = _axes(ctx)
    # The psum payload is the f32 accumulator, whatever the operand dtype;
    # context {"wire": "int8"} prices the compressed-collective format.
    wire = ctx.get("wire")
    coll = _psum_cost(machine, float(n) * n, axes, dtype_name, wire)
    gram_terms = at.cost_terms("tsgram", gram_blocks,
                               {"m": m, "n": n}, dtype_name)
    cands = [("eager", 1, gram_s + coll["comm_s"],
              _with_comm(gram_terms, coll))]
    for c in _chunk_counts(n):
        if c == 1:
            continue
        seg = -(-n // c)
        sk_s, sk_blocks = at.rank("randsketch", {"m": m, "n": n, "r": seg},
                                  dtype_name, machine=machine)[0]
        cc = _psum_cost(machine, float(n) * seg, axes, dtype_name, wire)
        total = _pipeline_s(sk_s, cc["comm_s"], c)
        sk_terms = at.cost_terms("randsketch", sk_blocks,
                                 {"m": m, "n": n, "r": seg}, dtype_name)
        agg = CostTerms(flops=c * sk_terms.flops,
                        hbm_bytes=c * sk_terms.hbm_bytes,
                        steps=c * sk_terms.steps, mxu_util=sk_terms.mxu_util,
                        comm_bytes=c * cc["comm_bytes"],
                        comm_steps=c * cc["comm_steps"])
        cands.append((f"overlap{c}", c, total, agg))
    label, chunks, best_s, chosen_terms = min(cands, key=lambda t: t[2])
    notes = [f"psum({n}x{n} f32) over axes={axes}: {coll['algorithm']} "
             f"all-reduce, {_us(coll['comm_s'])}"]
    if chunks > 1:
        notes.append(f"overlap: {chunks} column-segment cross-grams, each "
                     "partial psum hidden behind the next segment's "
                     "compute (A re-read per segment)")
    return ExecutionPlan(
        op="gram", choice="eager" if chunks == 1 else "overlap",
        blocks={"chunks": chunks}, cost_s=best_s,
        breakdown=machine.breakdown(chosen_terms, dtype_name),
        alternatives=tuple(sorted(((lb, s) for lb, _, s, _ in cands),
                                  key=lambda t: t[1])),
        notes=tuple(notes), terms=_terms_dict(chosen_terms), **kw)


def _decide_matvec(d, dtype_name, machine, ctx, kw) -> ExecutionPlan:
    """One streaming pass over an (m × n) row shard plus the reduction of
    its n-vector result (the rmatvec/adjoint psum; context
    {"reduce": False} prices the psum-free row-space matvec).  The choice
    names the reduction the link model picks for this mesh shape and
    payload — ring past the bandwidth break-even, tree under it."""
    import jax.numpy as jnp
    m, n = d["m"], d["n"]
    db = jnp.dtype(dtype_name).itemsize
    mp = at._rup(m, at.sublane(dtype_name))
    np_ = at._rup(n, LANE)
    bm = min(512, mp)
    pass_terms = CostTerms(flops=2.0 * mp * np_,
                           hbm_bytes=(mp * np_ + mp + np_) * db,
                           steps=-(-mp // bm))
    t_pass = machine.time(pass_terms, dtype_name)
    axes = _axes(ctx)
    # The reduced rmatvec result is the f32 accumulator, whatever the
    # storage dtype — n·4 wire bytes.
    payload = n * 4.0 if ctx.get("reduce", True) else 0.0
    if not axes or not payload:
        return ExecutionPlan(
            op="matvec", choice="local", blocks={}, cost_s=t_pass,
            breakdown=machine.breakdown(pass_terms, dtype_name),
            alternatives=(("local", t_pass),),
            notes=("no reduction: result stays shard-resident",),
            terms=_terms_dict(pass_terms), **kw)
    priced = {algo: machine.collective(payload, axes, dtype_name,
                                       algorithm=algo)
              for algo in ("ring", "tree")}
    choice = min(priced, key=lambda a: priced[a]["comm_s"])
    chosen_terms = _with_comm(pass_terms, priced[choice])
    return ExecutionPlan(
        op="matvec", choice=choice, blocks={},
        cost_s=t_pass + priced[choice]["comm_s"],
        breakdown=machine.breakdown(chosen_terms, dtype_name),
        alternatives=tuple(sorted(
            ((a, t_pass + priced[a]["comm_s"]) for a in priced),
            key=lambda t: t[1])),
        notes=(f"psum({n}·4B) over axes={axes}",),
        terms=_terms_dict(chosen_terms), **kw)


def _decide_bsr_bs(d, dtype_name, machine, ctx, kw) -> ExecutionPlan:
    """Block-size selection on the *actual* per-candidate ELL widths
    (context["ell_by_bs"]) — the nnz-only estimate of the "bsr" kernel op
    assumes uniform scatter, which is pessimistic for block-structured
    sparsity.  Used by SparseRowMatrix's bs="auto" constructors."""
    ell_by_bs = {int(k): int(v) for k, v in ctx["ell_by_bs"].items()}
    nx = max(d.get("nx", 1), 1)
    sub = at.sublane(dtype_name)
    scored = []
    for bs in ctx.get("bs_candidates", BS_CANDIDATES):
        if bs % sub or bs not in ell_by_bs:
            continue
        bdims = {"m": at._rup(d["m"], bs), "n": at._rup(d["n"], bs),
                 "nx": nx, "ell": ell_by_bs[bs]}
        scored.append((at.model_time("bsr", {"bs": bs}, bdims, dtype_name,
                                     machine=machine), bs))
    scored.sort()
    best_s, best_bs = scored[0]
    bdims = {"m": at._rup(d["m"], best_bs), "n": at._rup(d["n"], best_bs),
             "nx": nx, "ell": ell_by_bs[best_bs]}
    terms = at.cost_terms("bsr", {"bs": best_bs}, bdims, dtype_name)
    return ExecutionPlan(
        op="bsr_bs", choice=f"bs={best_bs}", blocks={"bs": best_bs},
        cost_s=best_s, breakdown=machine.breakdown(terms, dtype_name),
        alternatives=tuple((f"bs={bs}", s) for s, bs in scored),
        notes=("priced on actual ELL widths, not the uniform-scatter "
               "estimate",), **kw)


def _decide_svd(d, dtype_name, machine, ctx, kw) -> ExecutionPlan:
    """compute_svd mode auto-dispatch (paper §3.1): gram while the n×n Gram
    is a comfortable replicated object, the randomized sketch when A is too
    wide for Gram but k is small, matrix-free Lanczos for everything else
    (and always for sparse operators — matvec cost ∝ nnz, no dense Gram).

    The structural gates decide; the modeled A-pass costs of all three
    modes are attached so explain() shows what each gate saved."""
    import jax.numpy as jnp
    m, n, k = d["m"], d["n"], d["k"]
    kind = ctx.get("kind", "row")
    gram_threshold = int(ctx.get("gram_threshold", GRAM_THRESHOLD))
    rand_k = int(ctx.get("randomized_k_threshold", RANDOMIZED_K_THRESHOLD))
    q = int(ctx.get("power_iters", 2))
    p = int(ctx.get("oversampling", 8))
    db = jnp.dtype(dtype_name).itemsize
    nnz = int(ctx.get("nnz", m * n))
    a_bytes = (nnz if kind == "sparse" else m * n) * db

    # Modeled pass structure per mode (informational; iteration counts are
    # a-priori estimates, not convergence guarantees).
    gram = CostTerms(flops=2.0 * m * n * n, hbm_bytes=a_bytes + n * n * db)
    sketch_passes = 2 + 2 * q
    rand = CostTerms(flops=2.0 * m * n * (k + p) * sketch_passes,
                     hbm_bytes=a_bytes * sketch_passes)
    lanczos_iters = min(max(2 * k + 10, 20), min(m, n))
    lz = CostTerms(flops=4.0 * (nnz if kind == "sparse" else m * n)
                   * lanczos_iters,
                   hbm_bytes=2.0 * a_bytes * lanczos_iters)
    costs = {"gram": machine.time(gram, dtype_name),
             "randomized": machine.time(rand, dtype_name),
             "lanczos": machine.time(lz, dtype_name)}

    notes = []
    if kind == "sparse":
        choice = "lanczos"
        notes.append("sparse operator: matrix-free iteration, no dense Gram")
    elif kind == "row" and n <= gram_threshold:
        choice = "gram"
        notes.append(f"n={n} <= gram_threshold={gram_threshold}: "
                     "one all-reduce + local eigh")
    elif kind == "row" and k <= rand_k:
        choice = "randomized"
        notes.append(f"k={k} <= randomized_k_threshold={rand_k}: "
                     f"{sketch_passes}-pass sketch beats k sequential "
                     "Lanczos directions")
    else:
        choice = "lanczos"
        notes.append("wide + large-k (or no sketch primitives): "
                     "matrix-free Lanczos")
    terms = {"gram": gram, "randomized": rand, "lanczos": lz}[choice]
    return ExecutionPlan(
        op="svd", choice=choice, blocks={}, cost_s=costs[choice],
        breakdown=machine.breakdown(terms, dtype_name),
        alternatives=tuple(sorted(costs.items(), key=lambda t: t[1])),
        notes=tuple(notes), **kw)


# -- calibration plumbing ------------------------------------------------------

def calibration_record(kernel: str, dims: Mapping[str, int],
                       blocks: Mapping[str, int], dtype,
                       measured_s: float) -> dict:
    """One MachineModel.calibrate() record from a measured kernel run:
    the raw roofline terms (efficiency-1 work description) + the wall
    time.  bench_autotune/bench_planner build these from their sweeps."""
    import jax.numpy as jnp
    t = at.cost_terms(kernel, blocks, dims, jnp.dtype(dtype))
    return {"kernel": kernel, "dims": dict(dims), "blocks": dict(blocks),
            "dtype": jnp.dtype(dtype).name, "flops": t.flops,
            "hbm_bytes": t.hbm_bytes, "steps": t.steps,
            "mxu_util": t.mxu_util, "measured_s": float(measured_s)}


def actual_record(plan: ExecutionPlan, measured_s: float) -> dict:
    """One plan-vs-actual record: an ExecutionPlan's modeled cost next to
    a measured wall time.  For kernel ops with block configs the record is
    merged with ``calibration_record()``'s raw roofline terms, so the same
    record that shows drift in ``Result.info["trace"]`` feeds
    ``calibrate()`` unchanged (launch/telemetry.py collects them)."""
    rec = {"op": plan.op, "choice": plan.choice, "dims": dict(plan.dims),
           "dtype": plan.dtype, "backend": plan.backend,
           "modeled_s": float(plan.cost_s),
           "measured_s": float(measured_s),
           "ratio": (float(measured_s) / plan.cost_s
                     if plan.cost_s > 0 else None)}
    if plan.op in KERNEL_OPS and plan.blocks:
        rec.update(calibration_record(plan.op, plan.dims, plan.blocks,
                                      plan.dtype, measured_s))
    elif plan.terms:
        # Distributed decision ops carry their raw terms (including the
        # comm column) on the plan itself — same calibrate() contract.
        rec.update(dict(plan.terms))
    return rec


def calibrate(records, backend: str | None = None, *,
              write: bool = True) -> tuple[MachineModel, float, float]:
    """Fit the backend's machine model to measured records; returns
    (calibrated model, mean relative error before, after).  With
    write=True the fit is persisted next to the autotune cache and every
    subsequent plan() on this backend prefers it."""
    import jax
    backend = backend or jax.default_backend()
    # "before" = the model plan() was actually using for this backend (the
    # v5e reference until a calibration exists); the fit itself starts from
    # the backend's builtin instance so the efficiencies stay interpretable.
    reference = _machine.for_backend(backend, prefer_calibrated=False)
    fitted = _machine.builtin(backend).calibrate(records)
    err_before, err_after = reference.error(records), fitted.error(records)
    if write:
        _machine.save_calibration(backend, fitted)
        # Every memo layer must drop pre-calibration selections — including
        # autotune's get_config memo, whose ranked block configs were priced
        # on the old efficiencies (at.reset clears this cache too).
        at.reset()
    return fitted, err_before, err_after
