"""Share of the roofline of the dense fused-gradient kernel.

One evaluation reads the stored A once (4mn bytes) with its vectors —
x and g (n each), the target, the row weights and z = Ax (m each) — and
does 4mn flops (Ax and Aᵀr).  At these shapes it is bound by memory:
8.6 GB against a few GFLOP.  The device time is that of the
`repro_fused_grad` events in the trace, one per evaluation."""
from metrics._common import least_s, roofline

KERNELS = r"\brepro_fused_grad\b"


def work(w):
    m, n = w["m"], w["n"]
    return {"flops": 4 * m * n, "bytes": 4 * m * n + 4 * (2 * n + 3 * m)}


def read(run):
    w = work(run.work)
    return roofline(run, KERNELS, least_s(run, w["flops"], w["bytes"]))
