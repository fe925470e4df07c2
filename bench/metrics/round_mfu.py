"""Per-layer metric `round_mfu`: model FLOPs of the rounds completed in
the traced window (forward and backward, from shapes) over the window's
length times the chip's published bf16 peak, in %."""
from bench.harness.readers import flops_module, peaks


def read(ctx):
    window = ctx.traced_window_s()
    if window <= 0 or ctx.rounds <= 0:
        return None
    flops = flops_module(ctx).model_flops_per_round(ctx.spec.config,
                                                    ctx.spec.traffic)
    peak = peaks(ctx.devices[0].device_kind, ctx.spec.root)
    return (100.0 * flops * ctx.rounds
            / (window * len(ctx.devices) * peak['bf16_flops_per_s']))
