"""The whole serve's share of the chip's bf16 peak, %: model
operations per served user (work.serve_user_flops) times users served
per second in this run, over the peak."""
import work


def read(ctx):
    rate = ctx["e2e"]["serve_users_per_s"]
    if rate <= 0:
        return None
    flops = work.serve_user_flops(ctx["cfg"])
    return 100.0 * flops * rate / ctx["peaks"]["bf16_flops_per_s"]
