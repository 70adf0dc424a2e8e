"""The algorithm's pair-samples per graph (``workcount.pair_samples``,
sequential DirectLiNGAM's work, not the padded work the schedule runs),
per second of moment-kernel device time, in billions."""


def read(reduced, work):
    if reduced["kernel_s"] <= 0:
        return None
    return work["pair_samples"] * reduced["graphs"] / reduced["kernel_s"] / 1e9
