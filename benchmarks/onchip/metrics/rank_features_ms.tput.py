"""Device ms per serve flush inside the ``rank_features`` scope: the
ranking stage's item-feature lookups over the B x S candidates and the
user-item cross features (retriever.serve_stage_ranking)."""
from trace_reduce import ms_per_run


def read(ctx):
    return ms_per_run(ctx["trace"], ctx["module"], ("rank_features",))
