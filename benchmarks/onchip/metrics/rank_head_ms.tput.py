"""Device ms per serve flush inside the ``rank_head`` scope: the target
attention ranker's concatenation of user, item, attended and cross
features and its head MLP (ranking.ranking_scores, nested in
``rank_score``)."""
from trace_reduce import ms_per_run


def read(ctx):
    return ms_per_run(ctx["trace"], ctx["module"], ("rank_head",))
