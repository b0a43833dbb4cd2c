"""Whole-step model FLOP/s utilization of training: the model operations
per trained frame (the configuration's ``flops_per_frame``: the actor's
forward, the learner's forward and backward, no recomputation) times the
run's ``train_frames_per_s``, over the cell's chips times the chip's peak
bf16 FLOP/s."""


def read(ctx):
    flops = ctx.cfg_module.flops_per_frame(ctx.cfg, ctx.traffic)
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops * ctx.train_frames_per_s / peak
