"""The Pallas V-trace kernel's share of its roofline on the learner chips:
the bytes V-trace must move at the call's shape (four (B, T) float32
inputs and the (B,) bootstrap read, two (B, T) outputs written; B the
rows one learner chip holds, no padding counted) over peak HBM bandwidth,
or its operations over peak FLOP/s, whichever is longer, over the
kernel's device time per call.  The bytes bound it at every shape here."""

KERNEL = r"vtrace"
FLOPS_PER_ELEMENT = 16  # exp, 2 clips, delta, the recursion, vs, advantage


def cost(B: int, T: int) -> tuple[float, float]:
    return float(FLOPS_PER_ELEMENT * B * T), float(4 * (6 * B * T + B))


def read(ctx):
    B = ctx.traffic["actor_batch_size"] // len(ctx.learner_ids)
    T = ctx.traffic["trajectory_length"]
    shares = []
    for dev in ctx.learner_ids:
        total, calls = ctx.trace.op_time(dev, KERNEL)
        if calls:
            flops, nbytes = cost(B, T)
            least = max(flops / ctx.peaks["bf16_flops_per_s"],
                        nbytes / ctx.peaks["hbm_bytes_per_s"])
            shares.append(100.0 * least / (total / calls / 1e9))
    return min(shares) if shares else None
