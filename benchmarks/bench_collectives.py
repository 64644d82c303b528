"""Collective benchmark: modeled vs measured psum time by payload & topology.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m benchmarks.bench_collectives

Two sections, each emitting ``BENCH {json}`` lines (run.py --only
collectives):

  1. **psum sweep** — for each device count in {1, 2, 4, 8} (capped by what
     exists) and payload size, the wall time of a jitted ``shard_map`` psum
     over a one-axis mesh next to ``MachineModel.collective()``'s link-model
     prediction (ring vs tree by payload, the algorithm the planner prices
     overlap decisions against).  On the CI host the "links" are shared
     memory, so the absolute ratio is expected to drift — the sweep's job is
     to expose that drift as data, per payload and device count.

  2. **link_eff fit demo** — the sweep's records (raw collective terms +
     measured seconds) run through ``MachineModel.calibrate()``, which fits
     the comm column (1/link_eff) alongside the roofline terms; the BENCH
     line reports modeled-vs-measured mean relative error before and after.
     NOT persisted by default (--write opts in): the host-CPU fit would
     poison kernel plans for anyone benchmarking on this machine, and the
     planner already prefers any real calibration recorded for the backend.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import machine, telemetry

# Payload sizes (f32 elements) spanning the latency- and bandwidth-bound
# regimes of the link model: 4 KiB, 256 KiB, 4 MiB.
PAYLOAD_ELEMS = (1024, 65536, 1048576)
DEVICE_COUNTS = (1, 2, 4, 8)


def _psum_fn(mesh, n_elems: int):
    """Jitted one-axis all-reduce: each shard contributes an (1, E) block,
    the psum leaves the replicated sum — the exact collective the distmat
    gram/fused_grad/rmatvec bodies issue."""
    P = jax.sharding.PartitionSpec

    def body(v):
        return jax.lax.psum(v, "data")

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=P("data", None),
                                 out_specs=P(None, None)))
    n = mesh.shape["data"]
    x = jnp.asarray(np.random.default_rng(0).normal(size=(n, n_elems)),
                    jnp.float32)
    return f, x


def sweep(model: machine.MachineModel, *, reps: int = 5) -> list[dict]:
    """One record per (device count, payload): measured psum wall time plus
    the link model's prediction and the raw collective terms calibrate()
    consumes."""
    devices = jax.devices()
    out = []
    for nd in DEVICE_COUNTS:
        if nd > len(devices):
            continue
        mesh = jax.sharding.Mesh(np.asarray(devices[:nd]).reshape(nd),
                                 ("data",))
        for elems in PAYLOAD_ELEMS:
            payload = float(elems) * 4.0
            f, x = _psum_fn(mesh, elems)
            measured = telemetry.timeit(
                lambda: jax.block_until_ready(f(x)),
                reps=reps, warmup=1).median_s
            coll = model.collective(payload, (nd,), "float32")
            out.append({
                "devices": nd, "payload_bytes": payload,
                "algorithm": coll["algorithm"],
                "comm_bytes": coll["comm_bytes"],
                "comm_steps": coll["comm_steps"],
                "modeled_s": coll["comm_s"],
                "measured_s": measured,
                # comm-only calibration records: the per-shard add is noise
                # next to the collective, and keeping the compute/memory
                # columns zero stops the (payload-collinear) roofline terms
                # from stealing the link coefficient in the lstsq
                "dtype": "float32", "flops": 0.0,
                "hbm_bytes": 0.0, "steps": 0.0, "mxu_util": 1.0,
            })
    return out


def run(*, write: bool = False, reps: int = 5) -> list[tuple[str, float, str]]:
    rows = []
    backend = jax.default_backend()
    model = machine.for_backend(backend)

    records = sweep(model, reps=reps)
    for r in records:
        ratio = r["measured_s"] / r["modeled_s"] if r["modeled_s"] > 0 \
            else None
        print("BENCH", json.dumps({
            "bench": "collective_psum", "backend": backend,
            "machine": model.name, "devices": r["devices"],
            "payload_bytes": r["payload_bytes"],
            "algorithm": r["algorithm"],
            "modeled_us": round(r["modeled_s"] * 1e6, 3),
            "measured_us": round(r["measured_s"] * 1e6, 3),
            "ratio": round(ratio, 4) if ratio is not None else None},
            sort_keys=True))
        rows.append((f"psum_d{r['devices']}_{int(r['payload_bytes'])}B",
                     r["measured_s"] * 1e6,
                     f"modeled_us={r['modeled_s'] * 1e6:.1f};"
                     f"algo={r['algorithm']}"))

    # -- link_eff fit demo: the comm column joins the lstsq ------------------
    comm_records = [r for r in records if r["devices"] > 1]
    if len(comm_records) >= 2:
        err_before = model.error(comm_records)
        fitted = model.calibrate(comm_records)
        err_after = fitted.error(comm_records)
        tightened = err_after <= err_before
        print("BENCH", json.dumps({
            "bench": "collective_link_fit", "backend": backend,
            "n_records": len(comm_records),
            "err_before": round(err_before, 4),
            "err_after": round(err_after, 4), "tightened": tightened,
            "link_eff": {k: round(v, 6) for k, v in fitted.link_eff.items()},
            "written": write}, sort_keys=True))
        rows.append(("collectives_link_fit", err_after * 100,
                     f"err_before={err_before:.3f};"
                     f"err_after={err_after:.3f};tightened={tightened}"))
        if write:
            machine.save_calibration(backend, fitted)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--write", action="store_true",
                    help="persist the link fit (off by default — see "
                         "module docstring)")
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()
    for name, us, derived in run(write=args.write, reps=args.reps):
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
