"""Per-layer metric `kernel_roofline`: the least time the chip could take
for the kernel layer's logical work in a round (the larger of bytes over
peak HBM bandwidth and FLOPs over peak bf16 throughput; counts in
bench/flops) over the measured kernel time per round, in %."""
from bench.harness.readers import flops_module, module_ms, peaks


def read(ctx):
    ms = module_ms(ctx, 'kernel_ms')
    if not ms:
        return None
    nbytes, flops = flops_module(ctx).kernel_work_per_round(
        ctx.spec.config, ctx.spec.traffic)
    peak = peaks(ctx.devices[0].device_kind, ctx.spec.root)
    least = max(nbytes / peak['hbm_bytes_per_s'],
                flops / peak['bf16_flops_per_s'])
    return 100.0 * least / (ms * 1e-3)
