"""The fused ragged-BSR forward kernels' share of their roofline: the least
time the forward layers' own work needs on this chip (`bench.work.gcn_forward`:
the transform and the aggregation of every layer, their least bytes), over
the device time per step of the ops that compute it, the forward fused
layer kernels (`bench.metrics_common.FUSED_FORWARD`). Other kernels, such as
a backward moved into Pallas, count neither above nor below the line."""
from bench import work
from bench.metrics_common import FUSED_FORWARD


def read(run):
    s = run.trace.op_seconds(FUSED_FORWARD)
    c = run.counters
    if s <= 0 or run.peaks is None or not c.get("steps"):
        return None
    need = work.gcn_forward(c["n_nodes"], c["n_edges"] + c["n_nodes"],
                            run.cell.config["model"]["layer_dims"])
    return 100.0 * work.least_seconds(need, run.peaks) / (s / c["steps"])
