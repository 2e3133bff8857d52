"""Device-idle ms per serve flush while the micro-batcher's worker
waited for a trigger, took, assembled or scattered a flush (any
``batcher.*`` program span), in the window from the first submit on."""
import span_reduce


def read(ctx):
    return span_reduce.idle_ms_per_run(ctx, span_reduce.BATCHER)
