"""Host milliseconds per serving step spent building neighbour graphs
(`ServeMetrics` counter ``graph_s`` over its steps): the k nearest
neighbours of every slot staged with new geometry.  None where the program
builds no graphs."""


def read(ctx):
    m = ctx.serve_metrics
    if m is None or not hasattr(m, "observe_graph") or not m.counters["steps"]:
        return None
    return 1e3 * m.counters["graph_s"] / m.counters["steps"]
