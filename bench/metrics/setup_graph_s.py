"""Host seconds the program spends preparing the graph before its first
compile (`repro.graph.structure`: self-loops, normalization, the node order
and blocked tables its backend needs), by the host clock."""


def read(run):
    return run.counters["setup_graph_s"]
