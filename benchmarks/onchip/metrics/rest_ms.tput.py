"""Device ms per serve flush outside the cluster_rank and merge scopes:
the slab and candidate gathers, exact scores and the ranking stage."""
from trace_reduce import ms_per_run


def read(ctx):
    return ms_per_run(ctx["trace"], ctx["module"],
                      ("cluster_rank", "merge_serve", "fused_gather_rank"),
                      outside=True)
