"""Unified execution planner: golden-shape dispatch parity, explain()
smoke, and machine-model calibration.

The golden tables pin the decisions the PR-3/PR-4 dispatch code made
(density break-even for BSR-vs-dense at 1/5/10% block density, the
fused-vs-unfused boundary including the tiny-m shard case, autotune rank
winners) so the refactor onto launch/planner + launch/machine is provably
behavior-preserving: plan() must reproduce every one of them with the
uncalibrated reference model.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune as at
from repro.launch import machine, planner


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Fresh persistent caches + memos: decisions must come from the
    builtin reference model, not a user calibration file."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    at.reset()
    yield
    at.reset()


# -- golden tables (decisions recorded from the pre-refactor dispatch) --------

# (m, n, nx, ell, bs) -> use_bsr.  Rows 3-5 are the bench_sparse break-even
# shard shapes (4096×2048, bs=128) at 1/5/10% block density (ell = 1/2/3 of
# nbc=16): BSR wins at 1% and 5%, and at 10%… the model still says BSR for
# matvec (nx=1) — the measured flip bench_sparse records is at nx-wide
# gram, covered by the 128-wide rows.
SPARSE_GOLD = [
    ((1024, 4096, 128, 2, 128), True),
    ((1024, 4096, 128, 32, 128), False),
    ((4096, 2048, 1, 1, 128), True),
    ((4096, 2048, 1, 2, 128), True),
    ((4096, 2048, 1, 3, 128), True),
    ((512, 1024, 1, 1, 64), True),
    ((512, 1024, 64, 8, 64), False),
    ((8192, 4096, 128, 4, 64), True),
    ((2048, 2048, 2048, 4, 128), True),
]

# (m, n) -> use_fused.  The first two rows are the tiny-m shard case: two
# sublane-padded streaming passes move fewer bytes than one lane-padded
# fused pass, so the boundary is real and below m ≈ 64.
FUSED_GOLD = [
    ((8, 512), False),
    ((16, 1024), False),
    ((64, 512), True),
    ((120, 24), True),
    ((128, 128), True),
    ((512, 512), True),
    ((1250, 64), True),
    ((10000, 1024), True),
    ((65536, 512), True),
    ((100, 4096), True),
    ((40, 256), True),
]

# Autotune rank winners on the reference machine (kernel, dims, blocks).
RANK_GOLD = [
    ("gemm", {"m": 1024, "k": 1024, "n": 1024},
     {"bm": 512, "bn": 512, "bk": 1024}),
    ("gemm", {"m": 10000, "k": 1000, "n": 1000},
     {"bm": 512, "bn": 512, "bk": 1024}),
    ("tsgram", {"m": 16384, "n": 256}, {"bm": 1024, "bn": 256}),
    ("fusedgrad", {"m": 10000, "n": 1024}, {"bm": 1024}),
    ("randsketch", {"m": 16384, "n": 2048, "r": 72},
     {"bm": 1024, "bn": 1024}),
    ("bsr", {"m": 4096, "n": 2048, "nnz": 4096 * 2048 // 20, "nx": 128},
     {"bs": 128}),
]

# SVD auto-mode golden decisions (the svd.py threshold logic, verbatim).
SVD_GOLD = [
    ({"m": 100000, "n": 512, "k": 8}, {"kind": "row"}, "gram"),
    ({"m": 100000, "n": 8192, "k": 8}, {"kind": "row"}, "gram"),
    ({"m": 100000, "n": 8193, "k": 8}, {"kind": "row"}, "randomized"),
    ({"m": 100000, "n": 8193, "k": 128}, {"kind": "row"}, "randomized"),
    ({"m": 100000, "n": 8193, "k": 129}, {"kind": "row"}, "lanczos"),
    ({"m": 4096, "n": 512, "k": 8}, {"kind": "sparse", "nnz": 40000},
     "lanczos"),
    ({"m": 100000, "n": 512, "k": 8}, {"kind": "other"}, "lanczos"),
]


class TestDispatchParity:
    @pytest.mark.parametrize("shape,want", SPARSE_GOLD)
    def test_sparse_matmul_golden(self, shape, want):
        m, n, nx, ell, bs = shape
        p = planner.plan("sparse_matmul",
                         {"m": m, "n": n, "nx": nx, "ell": ell, "bs": bs})
        assert (p.choice == "bsr") == want, p.explain()
        # the decision is the argmin of its own alternatives
        alt = dict(p.alternatives)
        assert p.choice == min(alt, key=alt.get)
        assert p.cost_s == min(alt.values())

    @pytest.mark.parametrize("shape,want", FUSED_GOLD)
    def test_grad_golden(self, shape, want):
        m, n = shape
        p = planner.plan("grad", {"m": m, "n": n})
        assert (p.choice == "fused") == want, p.explain()

    @pytest.mark.parametrize("kernel,dims,want", RANK_GOLD)
    def test_kernel_rank_golden(self, kernel, dims, want):
        p = planner.plan(kernel, dims, jnp.float32)
        assert dict(p.blocks) == want, p.explain()
        # and the planner's choice is exactly what the ops wrappers resolve
        knobs = {k: None for k in at.KERNELS[kernel].knobs}
        assert at.resolve(kernel, dims, jnp.float32, knobs) == want

    @pytest.mark.parametrize("dims,ctx,want", SVD_GOLD)
    def test_svd_mode_golden(self, dims, ctx, want):
        assert planner.plan("svd", dims, context=ctx).choice == want

    def test_sparse_break_even_moves_with_density(self):
        """Monotone in ell: once dense wins it keeps winning."""
        flips = [planner.plan("sparse_matmul",
                              {"m": 4096, "n": 2048, "nx": 128,
                               "ell": ell, "bs": 128}).choice
                 for ell in range(1, 17)]
        assert flips[0] == "bsr" and flips[-1] == "dense"
        first_dense = flips.index("dense")
        assert all(c == "dense" for c in flips[first_dense:])

    def test_fused_boundary_is_real(self):
        """Tiny-m shards pick unfused; the boundary sits below one lane."""
        choices = {m: planner.plan("grad", {"m": m, "n": 512}).choice
                   for m in (8, 16, 32, 64, 128, 512)}
        assert choices[8] == "unfused" and choices[512] == "fused"

    def test_bs_auto_matches_direct_argmin(self):
        """plan("bsr_bs") = argmin of the same model over the candidates."""
        ell_by_bs = {8: 80, 16: 44, 32: 24, 64: 14, 128: 8}
        p = planner.plan("bsr_bs", {"m": 4096, "n": 2048, "nx": 128},
                         context={"ell_by_bs": ell_by_bs})
        direct = min(
            ell_by_bs,
            key=lambda bs: at.model_time(
                "bsr", {"bs": bs},
                {"m": 4096, "n": 2048, "nx": 128, "ell": ell_by_bs[bs]},
                jnp.float32))
        assert p.blocks["bs"] == direct
        assert len(p.alternatives) == len(ell_by_bs)

    def test_dispatch_sites_consult_planner(self):
        """The real call sites produce the planner's decision."""
        from repro.core.distmat import SparseRowMatrix
        rng = np.random.default_rng(0)
        mask = rng.random((8, 16)) < 0.1
        dense = (np.kron(mask, np.ones((64, 64)))
                 * rng.normal(size=(512, 1024))).astype(np.float32)
        srm = SparseRowMatrix.from_dense(dense, bs=64)
        want = planner.plan(
            "sparse_matmul",
            {"m": srm._local_rows(), "n": srm.n_pad, "nx": 1,
             "ell": srm.ell, "bs": srm.bs}, "float32").choice
        assert srm._use_bsr(1, "auto") == (want == "bsr")


# Precision-sweep goldens on the reference V5E model: (op, dims, context)
# -> chosen precision.  Recorded from the tentpole's decision sweep; the
# guards are PRECISION_GUARDS (bf16 needs tol ≥ 1e-5, psum8 ≥ 1e-6, int8 ≥
# 1e-3) and every pick must also clear the modeled-savings floor, which is
# why the tiny grad row stays f32 at a loose tolerance.  The psum8 row is a
# comm-dominated shape (small per-shard m, wide n, 64-way reduction) where
# bf16 is inadmissible (tol below its guard) and the int8 wire still pays.
PRECISION_GOLD = [
    ("grad", {"m": 8192, "n": 2048}, {"tol": 1e-4, "axes": (8,)}, "bf16"),
    ("grad", {"m": 8192, "n": 2048}, {"tol": 1e-9, "axes": (8,)}, "f32"),
    ("grad", {"m": 4096, "n": 128}, {"tol": 1e-4, "axes": (8,)}, "f32"),
    ("gram", {"m": 65536, "n": 4096}, {"tol": 1e-4, "axes": (16, 16)},
     "bf16"),
    ("gram", {"m": 512, "n": 8192}, {"tol": 5e-6, "axes": (64,)}, "psum8"),
    ("sparse_matmul", {"m": 4096, "n": 2048, "nx": 1, "ell": 2, "bs": 128},
     {"tol": 1e-3}, "int8"),
    ("sparse_matmul", {"m": 4096, "n": 2048, "nx": 1, "ell": 2, "bs": 128},
     {"tol": 1e-8}, "f32"),
    ("matvec", {"m": 65536, "n": 4096}, {"tol": 1e-4}, "bf16"),
]


class TestPrecisionDecisions:
    @pytest.mark.parametrize("op,dims,ctx,want", PRECISION_GOLD)
    def test_precision_golden(self, op, dims, ctx, want):
        p = planner.plan(op, dims, machine=machine.V5E, context=ctx)
        assert p.precision == want, p.explain()
        # The sweep keeps the caller's logical dtype: precision names how
        # the bytes move, not what x means.
        assert p.dtype == "float32"

    def test_no_tol_means_no_sweep(self):
        """Legacy call sites (no context["tol"]) are untouched: the plan
        is not precision-swept and prices exactly as before."""
        p = planner.plan("grad", {"m": 8192, "n": 2048},
                         machine=machine.V5E, context={"axes": (8,)})
        assert p.precision == ""
        q = planner.plan("grad", {"m": 8192, "n": 2048},
                         machine=machine.V5E,
                         context={"axes": (8,), "tol": 1e-9})
        assert q.choice == p.choice and q.blocks == p.blocks

    def test_explain_reports_precision_and_savings(self):
        """Acceptance: explain() must name the chosen precision and the
        modeled byte savings for grad/gram/sparse_matmul picks."""
        picked = [
            planner.plan("grad", {"m": 8192, "n": 2048}, machine=machine.V5E,
                         context={"tol": 1e-4, "axes": (8,)}),
            planner.plan("gram", {"m": 512, "n": 8192}, machine=machine.V5E,
                         context={"tol": 5e-6, "axes": (64,)}),
            planner.plan("sparse_matmul",
                         {"m": 4096, "n": 2048, "nx": 1, "ell": 2,
                          "bs": 128}, machine=machine.V5E,
                         context={"tol": 1e-3}),
        ]
        for p in picked:
            text = p.explain()
            assert p.precision in ("bf16", "psum8", "int8"), text
            assert f"precision: {p.precision}" in text
            assert "saved" in text and "modeled bytes" in text
            # Lower precision must actually model fewer seconds than the
            # f32 alternative it displaced.
            alt = dict(p.alternatives)
            assert p.cost_s <= alt["precision:f32"]

    def test_precision_is_argmin_of_alternatives(self):
        p = planner.plan("grad", {"m": 8192, "n": 2048},
                         machine=machine.V5E,
                         context={"tol": 1e-4, "axes": (8,)})
        prec_alts = {k: v for k, v in p.alternatives
                     if k.startswith("precision:")}
        assert f"precision:{p.precision}" == min(prec_alts,
                                                 key=prec_alts.get)

    def test_bf16_grad_models_big_savings(self):
        """Acceptance floor: on the bandwidth-bound fused-grad shape the
        bf16 pick must model ≥ 1.5× over f32."""
        p = planner.plan("grad", {"m": 8192, "n": 2048},
                         machine=machine.V5E,
                         context={"tol": 1e-4, "axes": (8,)})
        alt = dict(p.alternatives)
        assert alt["precision:f32"] / p.cost_s >= 1.5, p.explain()


class TestExplain:
    def test_explain_smoke_all_ops(self):
        plans = [
            planner.plan("gemm", {"m": 1024, "k": 1024, "n": 1024}, top=3),
            planner.plan("sparse_matmul", {"m": 4096, "n": 2048, "nx": 1,
                                           "ell": 2, "bs": 128}),
            planner.plan("grad", {"m": 10000, "n": 1024}),
            planner.plan("bsr_bs", {"m": 512, "n": 512, "nx": 128},
                         context={"ell_by_bs": {8: 20, 64: 4}}),
            planner.plan("svd", {"m": 100000, "n": 4096, "k": 32},
                         context={"kind": "row"}),
        ]
        for p in plans:
            text = p.explain()
            assert f"plan({p.op})" in text
            assert p.choice in text
            assert "roofline:" in text and "-bound" in text
            assert "us" in text

    def test_explain_shows_alternatives_and_machine(self):
        p = planner.plan("sparse_matmul", {"m": 4096, "n": 2048, "nx": 1,
                                           "ell": 2, "bs": 128})
        text = p.explain()
        assert "bsr" in text and "dense" in text
        assert machine.V5E.name in text and "builtin constants" in text

    def test_unknown_op_raises(self):
        with pytest.raises(ValueError, match="unknown op"):
            planner.plan("nonsense", {"m": 1})


class TestMachineModel:
    def test_constants_single_home(self):
        """roofline + autotune constants are the MachineModel's."""
        from repro.launch import roofline as RL
        assert RL.PEAK_FLOPS == machine.V5E.mxu_flops[2]
        assert RL.HBM_BW == machine.V5E.hbm_bw
        assert RL.LINK_BW == machine.V5E.link_bw
        assert at.VMEM_BYTES == machine.V5E.vmem_bytes

    def test_terms_pricing_matches_legacy_formula(self):
        """machine.time(terms) reproduces the old max(compute, hbm/bw) +
        steps·overhead arithmetic bit-for-bit."""
        dims = {"m": 1024, "k": 1024, "n": 1024}
        blocks = {"bm": 256, "bn": 256, "bk": 512}
        t = at.cost_terms("gemm", blocks, dims, jnp.float32)
        mm = machine.V5E
        want = max(t.flops / (mm.mxu_flops[4] * t.mxu_util),
                   t.hbm_bytes / mm.hbm_bw) + t.steps * mm.step_overhead_s
        assert at.model_time("gemm", blocks, dims, jnp.float32,
                             machine=mm) == pytest.approx(want, rel=1e-12)

    def test_calibration_tightens_error_and_flips_plans(self, tmp_path):
        """Synthetic 'measured' timings from a machine 4× slower on HBM:
        calibrate() must cut the modeled-vs-measured error and subsequent
        plan() calls must pick the calibrated model up."""
        slow = machine.MachineModel(
            name="slow", mxu_flops=machine.V5E.mxu_flops,
            hbm_bw=machine.V5E.hbm_bw / 4.0,
            step_overhead_s=machine.V5E.step_overhead_s,
            link_bw=machine.V5E.link_bw,
            vmem_bytes=machine.V5E.vmem_bytes)
        records = []
        for kernel, dims, blocks in [
            ("gemm", {"m": 2048, "k": 2048, "n": 2048},
             {"bm": 256, "bn": 256, "bk": 512}),
            ("gemm", {"m": 512, "k": 4096, "n": 512},
             {"bm": 128, "bn": 128, "bk": 512}),
            ("tsgram", {"m": 65536, "n": 512}, {"bm": 512}),
            ("fusedgrad", {"m": 65536, "n": 512}, {"bm": 512}),
        ]:
            records.append(planner.calibration_record(
                kernel, dims, blocks, jnp.float32,
                at.model_time(kernel, blocks, dims, jnp.float32,
                              machine=slow)))
        fitted = machine.V5E.calibrate(records)
        before, after = (machine.V5E.error(records), fitted.error(records))
        assert after < before
        assert after < 0.35                      # additive-relaxation slack
        assert fitted.hbm_eff["float32"] == pytest.approx(0.25, rel=0.3)

        # persistence: for_backend prefers the saved calibration
        machine.save_calibration("cpu", fitted,
                                 path=tmp_path / "machine.json")
        loaded = json.loads((tmp_path / "machine.json").read_text())
        assert "cpu" in loaded["backends"]
        got = machine.MachineModel.from_dict(loaded["backends"]["cpu"])
        assert got.source == "calibrated"
        assert got.hbm_eff == fitted.hbm_eff

class TestCommPlanning:
    """Collective-aware pricing: psum terms in the plan, topology-keyed
    ring/tree selection, overlap break-even, and the link_eff fit."""

    def test_collective_cost_formulas(self):
        assert machine.collective_cost(1, 4096.0, "ring") == (0.0, 0.0)
        b, s = machine.collective_cost(8, 1024.0, "ring")
        assert b == 2.0 * 1024.0 * 7 / 8 and s == 14.0
        b, s = machine.collective_cost(8, 1024.0, "tree")
        assert b == 2.0 * 1024.0 * 3 and s == 6.0
        with pytest.raises(ValueError):
            machine.collective_cost(4, 1.0, "butterfly")

    def test_ring_tree_selection_by_payload(self):
        """Ring past the bandwidth break-even, tree under it (latency)."""
        big = machine.V5E.collective(4 * 2**20, (8,), "float32")
        small = machine.V5E.collective(256.0, (256,), "float32")
        assert big["algorithm"] == "ring"
        assert small["algorithm"] == "tree"
        # multi-axis reduction sums per-axis costs
        two = machine.V5E.collective(4 * 2**20, (16, 16), "float32")
        one = machine.V5E.collective(4 * 2**20, (16,), "float32")
        assert two["comm_s"] > one["comm_s"]

    def test_comm_fraction_grows_with_device_count(self):
        """Fixed global shape spread over more devices: the shard shrinks,
        the psum payload does not — the comm share of the modeled serial
        time must rise monotonically (and be absent on one device)."""
        fracs = []
        for dev in (1, 4, 16, 64):
            p = planner.plan("gram", {"m": 1_000_000 // dev, "n": 1024},
                             machine=machine.V5E,
                             context={"axes": (dev,)})
            comm = p.breakdown.get("comm_s", 0.0)
            serial = (max(p.breakdown["compute_s"], p.breakdown["memory_s"])
                      + p.breakdown["step_s"] + comm)
            fracs.append(comm / serial)
        assert fracs[0] == 0.0
        assert all(b > a for a, b in zip(fracs, fracs[1:])), fracs
        assert fracs[-1] > 0.1

    def test_gram_overlap_past_break_even(self):
        """Chunked overlap engages only once the modeled psum is worth
        hiding: eager on few devices, overlapped on many."""
        few = planner.plan("gram", {"m": 1_000_000 // 2, "n": 1024},
                           machine=machine.V5E, context={"axes": (2,)})
        many = planner.plan("gram", {"m": 1_000_000 // 64, "n": 1024},
                            machine=machine.V5E, context={"axes": (64,)})
        assert few.choice == "eager" and few.blocks["chunks"] == 1
        assert many.choice == "overlap" and many.blocks["chunks"] > 1
        # the decision is the argmin of its own alternatives
        alt = dict(many.alternatives)
        assert many.cost_s == min(alt.values())

    def test_grad_plan_without_axes_is_unchanged(self):
        """No topology context → the seed's compute-only fused/unfused
        decision, bit-identical: no comm terms, no chunks knob."""
        p = planner.plan("grad", {"m": 10000, "n": 1024})
        assert p.choice == "fused"
        assert "chunks" not in p.blocks
        assert p.breakdown.get("comm_s", 0.0) == 0.0

    def test_grad_plan_with_axes_prices_psum(self):
        p = planner.plan("grad", {"m": 4096, "n": 1024},
                         machine=machine.V5E,
                         context={"axes": (16, 16)})
        assert p.choice in ("fused", "unfused")
        assert p.breakdown["comm_s"] > 0.0
        assert p.terms["comm_bytes"] > 0.0
        assert "chunks" in p.blocks
        text = p.explain()
        assert "comm:" in text and "% of modeled serial time" in text

    def test_matvec_plan_topology(self):
        p = planner.plan("matvec", {"m": 65536, "n": 1024},
                         machine=machine.V5E, context={"axes": (16, 16)})
        assert p.choice in ("ring", "tree")
        assert p.breakdown["comm_s"] > 0.0
        assert dict(p.alternatives).keys() == {"ring", "tree"}
        local = planner.plan("matvec", {"m": 65536, "n": 1024},
                             machine=machine.V5E,
                             context={"axes": (16, 16), "reduce": False})
        assert local.choice == "local"
        assert local.breakdown.get("comm_s", 0.0) == 0.0

    def test_calibrate_fits_link_eff_and_persists(self, tmp_path):
        """Synthetic timings from a machine with 4× slower links: the comm
        column joins the fit, link_eff lands near 0.25, and the value
        survives the machine.json round-trip."""
        base = machine.V5E
        records = []
        for payload, axes in [(4 * 2**20, (8,)), (2**20, (16,)),
                              (16 * 2**20, (4,)), (8 * 2**20, (32,))]:
            coll = base.collective(float(payload), axes, "float32")
            slow_s = (coll["comm_bytes"] / (base.link_bw / 4.0)
                      + coll["comm_steps"] * base.link_latency_s)
            records.append({"dtype": "float32", "flops": 0.0,
                            "hbm_bytes": 0.0, "steps": 0.0, "mxu_util": 1.0,
                            "comm_bytes": coll["comm_bytes"],
                            "comm_steps": coll["comm_steps"],
                            "measured_s": slow_s})
        fitted = base.calibrate(records)
        assert fitted.link_eff["float32"] == pytest.approx(0.25, rel=0.05)
        assert fitted.error(records) < base.error(records)

        machine.save_calibration("cpu", fitted,
                                 path=tmp_path / "machine.json")
        loaded = json.loads((tmp_path / "machine.json").read_text())
        got = machine.MachineModel.from_dict(loaded["backends"]["cpu"])
        assert got.link_eff == fitted.link_eff
        assert got.link_latency_s == fitted.link_latency_s

    def test_comm_free_records_reproduce_two_term_fit(self):
        """A compute-only sweep must fit exactly as before the comm column
        existed (the column only joins when records exercise it)."""
        records = []
        for kernel, dims, blocks in [
            ("gemm", {"m": 2048, "k": 2048, "n": 2048},
             {"bm": 256, "bn": 256, "bk": 512}),
            ("tsgram", {"m": 65536, "n": 512}, {"bm": 512}),
        ]:
            records.append(planner.calibration_record(
                kernel, dims, blocks, jnp.float32,
                at.model_time(kernel, blocks, dims, jnp.float32,
                              machine=machine.V5E) * 2.0))
        fitted = machine.V5E.calibrate(records)
        assert fitted.link_eff == {}


class TestMachineModelCalibrated:
    def test_plan_prefers_calibrated_constants(self, tmp_path, monkeypatch):
        """After a calibration is persisted next to the autotune cache,
        plan() on that backend reports calibrated=True and prices with the
        fitted efficiencies."""
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                           str(tmp_path / "autotune.json"))
        at.reset()
        before = planner.plan("grad", {"m": 10000, "n": 1024})
        assert not before.calibrated
        fitted = machine.builtin("cpu").calibrate([])  # no-op fit, flagged
        machine.save_calibration("cpu", fitted)
        at.reset()
        after = planner.plan("grad", {"m": 10000, "n": 1024})
        assert after.calibrated and after.machine == "cpu-host"
        # CPU instance: same ratio structure, decision unchanged here
        assert after.choice == "fused"
        at.reset()
