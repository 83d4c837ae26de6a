"""Model FLOP utilization, percent: the window's model FLOPs (forward and
backward, recomputation not counted) over window x chips x bf16 peak."""


def read(r):
    return (r.flops_per_step * r.window_steps
            / (r.window_s * r.chips * r.peak_flops) * 100)
