"""The comm step's least HBM time, its algorithmic bytes
(``bench.counting``) over the chips' HBM bandwidth, as a share of its
device time."""

from bench import counting


def read(run):
    if run.trace is None or run.rounds == 0 or run.trace.cat_s["comm"] <= 0:
        return None
    wl = run.wl
    least = counting.comm_bytes(
        run.state_dims, n=wl["clients"], c=wl["cohort"], s=wl["sparsity"]
    ) / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (run.trace.cat_s["comm"] / run.rounds)
