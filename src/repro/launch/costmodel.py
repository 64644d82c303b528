"""Trip-count-correct cost extraction for scanned programs.

XLA's HloCostAnalysis counts a while-loop body ONCE regardless of trip
count, so the production (scan-over-layers) module under-reports FLOPs,
bytes, and collective bytes by ~the layer count.  The dry-run therefore
measures costs structurally:

  1. the FULL config is lowered+compiled with scan (the runnability proof
     and the *memory* analysis — buffer accounting is trip-count-exact);
  2. two/three REDUCED-DEPTH variants with `scan_unroll=True` (straight-line
     HLO, every op counted) are lowered+compiled; per-stack slopes come
     from differencing, and totals extrapolate linearly to the full depth:

        cost(depths) = fixed + Σ_stack slope_stack · n_stack

Linear extrapolation is exact here: layers within a stack are structurally
identical (same shapes, same collectives) — the whole point of stacking
them for scan in the first place.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro import configs
from repro.launch import roofline as RL


def depth_variants(cfg) -> tuple[list[dict], list[dict], dict]:
    """Returns (override_list, stack_count_list, full_counts).

    Each override dict produces a reduced config; stack_counts gives the
    per-stack layer counts of that variant; full_counts those of the real
    config.  Variant 0 must be the smallest (used for the fixed cost)."""
    fam = cfg.family
    if fam in ("dense", "vlm", "ssm"):
        full = {"blocks": cfg.num_layers}
        return ([{"num_layers": 1}, {"num_layers": 2}],
                [{"blocks": 1}, {"blocks": 2}], full)
    if fam == "moe":
        fk = cfg.moe.first_k_dense
        full = {"dense_prefix": fk, "moe_blocks": cfg.num_layers - fk}
        def mk(d, m):
            return {"num_layers": d + m,
                    "moe": dataclasses.replace(cfg.moe, first_k_dense=d)}
        return ([mk(1, 1), mk(2, 1), mk(1, 2)],
                [{"dense_prefix": 1, "moe_blocks": 1},
                 {"dense_prefix": 2, "moe_blocks": 1},
                 {"dense_prefix": 1, "moe_blocks": 2}], full)
    if fam == "hybrid":
        per = cfg.ssm.attn_every
        n_groups = cfg.num_layers // per
        rem = cfg.num_layers - n_groups * per
        full = {"groups": n_groups, "tail": rem}
        return ([{"num_layers": per}, {"num_layers": 2 * per},
                 {"num_layers": per + 1}],
                [{"groups": 1, "tail": 0}, {"groups": 2, "tail": 0},
                 {"groups": 1, "tail": 1}], full)
    if fam == "encdec":
        full = {"encoder": cfg.encoder_layers, "decoder": cfg.num_layers}
        return ([{"encoder_layers": 1, "num_layers": 1},
                 {"encoder_layers": 2, "num_layers": 1},
                 {"encoder_layers": 1, "num_layers": 2}],
                [{"encoder": 1, "decoder": 1}, {"encoder": 2, "decoder": 1},
                 {"encoder": 1, "decoder": 2}], full)
    raise ValueError(fam)


def _solve(stack_counts: list[dict], values: list[float],
           full: dict) -> float:
    """Least-squares fit cost = fixed + Σ slope_s·n_s, evaluate at full."""
    stacks = sorted(full.keys())
    A = np.array([[1.0] + [sc.get(s, 0) for s in stacks]
                  for sc in stack_counts])
    y = np.array(values, dtype=np.float64)
    coef, *_ = np.linalg.lstsq(A, y, rcond=None)
    est = coef[0] + sum(coef[1 + i] * full[s] for i, s in enumerate(stacks))
    return float(max(est, 0.0))


def measure(arch: str, shape: str, mesh, make_plan_fn, plan_kw: dict,
            verbose: bool = True) -> dict:
    """Lower/compile the reduced unrolled variants; extrapolate
    (flops, hbm_bytes, collective bytes by category) to the full depth."""
    cfg = configs.get(arch)
    overrides_list, counts_list, full = depth_variants(cfg)

    flops, hbm, coll = [], [], []
    base_ov = dict(plan_kw.get("overrides") or {})
    plan_kw = {k: v for k, v in plan_kw.items() if k != "overrides"}
    for ov in overrides_list:
        ov = dict(base_ov, **ov, scan_unroll=True)
        # microbatching is a while loop too — measure the step as a single
        # microbatch (identical totals: same tokens, one grad reduce)
        plan = make_plan_fn(arch, shape, mesh,
                            **{**plan_kw, "microbatches": 1,
                               "overrides": ov})
        compiled = plan.lower().compile()
        cost = compiled.cost_analysis()
        flops.append(float(cost.get("flops", 0.0)))
        hbm.append(float(cost.get("bytes accessed", 0.0)))
        coll.append(RL.parse_collectives(compiled.as_text()))
        if verbose:
            print(f"    [variant {ov}] flops={flops[-1]:.3e} "
                  f"bytes={hbm[-1]:.3e} coll={coll[-1]['total_bytes']:.3e}")

    out = {
        "flops": _solve(counts_list, flops, full),
        "hbm_bytes": _solve(counts_list, hbm, full),
        "collective_bytes": _solve(
            counts_list, [c["total_bytes"] for c in coll], full),
        "collectives": {},
        "variants": {"counts": counts_list, "flops": flops,
                     "hbm_bytes": hbm,
                     "collective_bytes": [c["total_bytes"] for c in coll],
                     "full": full},
    }
    for cat in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute"):
        out["collectives"][cat] = {
            "bytes": _solve(counts_list, [c[cat]["bytes"] for c in coll],
                            full),
            "count": _solve(counts_list,
                            [float(c[cat]["count"]) for c in coll], full),
        }
    return out


# The density-aware sparse dispatch and the fused-vs-unfused gradient
# dispatch that used to live here (with their own copies of the machine
# constants) are now ``launch/planner.plan("sparse_matmul", ...)`` and
# ``plan("grad", ...)`` — one calibrated MachineModel behind every
# decision (launch/machine.py).
