"""Device milliseconds per training step in the program's Pallas kernels
(`repro.kernels.bsr_spmm`, `repro.kernels.fused_gcn`), from the trace."""
from bench.metrics_common import KERNELS


def read(run):
    s = run.trace.op_seconds(KERNELS)
    if s <= 0 or not run.counters.get("steps"):
        return None
    return 1e3 * s / run.counters["steps"]
