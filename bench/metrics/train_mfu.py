"""Model FLOP utilization of full-graph training: the model operations of a
step (`bench.work.gcn_train_step_flops`) times the steps of the traced window,
over its host-clock seconds and the chip's bf16 peak."""
from bench import work


def read(run):
    c, m = run.counters, run.cell.config["model"]
    if run.peaks is None or not c.get("steps"):
        return None
    flops = work.gcn_train_step_flops(c["n_nodes"], c["n_edges"] + c["n_nodes"], m["layer_dims"])
    return 100.0 * flops * c["steps"] / c["window_s"] / run.peaks["bf16_flops"] / run.cell.chips
