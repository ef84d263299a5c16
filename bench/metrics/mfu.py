"""mfu: the whole step's share of the chip's peak, in percent. The
estimator forward FLOPs one UE-period's decision needs (from the
configuration's shapes), times the UE-periods decided per second in the
traced window, over the bf16 peak of the chips used. The controller,
scheduler and pool do elementwise work and count as 0."""


def read(ctx):
    if ctx.peaks is None or not ctx.red.has_device:
        return None
    rate = ctx.window.units / ctx.red.window_s
    flops = ctx.ref.decision_flops(ctx.config) * rate
    return 100.0 * flops / (ctx.peaks["bf16_flops"] * ctx.chips)
