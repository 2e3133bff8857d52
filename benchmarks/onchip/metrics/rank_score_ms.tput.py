"""Device ms per serve flush inside the ``rank_score`` scope: the
ranking model, the validity mask, the argsort and the reorder of the
outputs (retriever.serve_stage_ranking)."""
from trace_reduce import ms_per_run


def read(ctx):
    return ms_per_run(ctx["trace"], ctx["module"], ("rank_score",))
