"""Device ms per serve flush inside the ``rank_attn`` scope: the target
attention of the ranking model, its query, K and V projections, logits,
softmax over the history and weighted sum (ranking.ranking_scores,
nested in ``rank_score``)."""
from trace_reduce import ms_per_run


def read(ctx):
    return ms_per_run(ctx["trace"], ctx["module"], ("rank_attn",))
