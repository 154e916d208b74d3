"""Model FLOPs of the samples trained in the traced window (forward and
backward, from the configuration's shapes) over the window's seconds
times the chips' peak."""


def read(run):
    if run.trace is None or run.samples == 0:
        return None
    flops = run.config.flops_per_sample(run.cfg, run.wl["seq_len"])
    peak = run.chips * run.peaks["bf16_flops_per_s"]
    return 100.0 * flops * run.samples / (run.trace.window_s * peak)
