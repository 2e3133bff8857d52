"""Device-idle ms per serve flush while the host copied the outputs
back (program span ``serve.fetch``: it waits for the device, then
copies), in the window from the first submit on."""
import span_reduce


def read(ctx):
    return span_reduce.idle_ms_per_run(ctx, span_reduce.FETCH)
