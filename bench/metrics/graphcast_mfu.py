"""Model FLOP utilization of GraphCast training: the matmul operations of a
step (`bench.work_graphcast.train_step_flops`, no recomputation) times the
steps of the traced window, over its host-clock seconds and the chip's
bf16 peak."""
from bench import work_graphcast


def read(run):
    c = run.counters
    if run.peaks is None or not c.get("steps") or "n_mesh_edges" not in c:
        return None
    flops = work_graphcast.train_step_flops(run.cell.config["model"], c)
    return 100.0 * flops * c["steps"] / c["window_s"] / run.peaks["bf16_flops"] / run.cell.chips
