"""Roofline share of the `delta_codec` Pallas kernel (the upload codec's
fused roundtrip), in %.

Work from shapes (`flops.delta_codec_work`): every round encodes and
decodes M deltas of D f32 per replica, reading and writing each element
once.  The least time is the larger of FLOPs over the bf16 peak and
bytes over the HBM bandwidth (the bytes bound it); the share is that
over the kernel's device time.  Leaves outside the kernel's width gate
are counted in the work: under 1% of D for the MLP."""
from bench import flops


def read(ctx):
    seconds = ctx.summary.kernel_s("delta_codec")
    if seconds <= 0 or ctx.rounds <= 0:
        return None
    f, b = flops.delta_codec_work(ctx.config,
                                  ctx.rounds * ctx.fl["m"])
    least = max(f / ctx.peaks.flops, b / ctx.peaks.hbm_bw)
    return 100.0 * least / seconds
