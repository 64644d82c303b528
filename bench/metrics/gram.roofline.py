"""Share of the roofline of the Gram AᵀA (`repro_tsgram`,
`repro_randsketch`).

One Gram reads A once (4mn bytes), writes the n × n result, and does
2mn² flops.  It is bound by compute; the peak is the published bf16 one,
which float32 contractions at precision highest (six bf16 passes) cannot
approach.  The kernels' device time is divided among the Grams the window
ran, one per SVD job."""
import trace_reduce
from metrics._common import least_s

KERNELS = r"\brepro_(tsgram|randsketch)\b"


def work(w):
    m, n = w["m"], w["n"]
    return {"flops": 2 * m * n * n, "bytes": 4 * m * n + 4 * n * n}


def read(run):
    if run.trace is None:
        return None
    spent = trace_reduce.kernel_s(run.trace, KERNELS)
    grams = run.counters.get("jobs")
    if spent <= 0 or not grams:
        return None
    w = work(run.work)
    return 100.0 * grams * least_s(run, w["flops"], w["bytes"]) / spent
