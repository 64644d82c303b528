"""Tiny sizes for running the cells on the CPU in tests, and a runner.

Each configuration's `.py` exports its tiny sizes as `TINY`."""
import run as R

# Stand-in peaks: CPU runs only check that the readers produce numbers.
PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def bench():
    return R.read_json(R.ROOT / "BENCHMARK.json")


def sizes(cell):
    """The configuration's `TINY`."""
    name = cell.spec["config"]
    return R.load_module(cell.config_py, f"config_{name}").TINY


def run(name, *, seed=2**33 + 5, seconds=1.0, trace=False, root=None,
        bench_json=None):
    cell = R.Cell(bench_json or bench(), name, root or R.ROOT)
    return R.run_cell(cell, seed, seconds, trace, peaks=PEAKS,
                      sizes=sizes(cell))
