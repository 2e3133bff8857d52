"""Device-idle ms per serve flush while the host put the batch on the
device or dispatched the serve jit (program spans ``serve.put`` and
``serve.dispatch``), in the window from the first submit on."""
import span_reduce


def read(ctx):
    return span_reduce.idle_ms_per_run(ctx, span_reduce.LAUNCH)
