"""Share of the HBM roofline that the masked moment kernel
(``pallas:pairwise_moments_masked``) reaches, in percent: the bytes its
grid moves per graph (``workcount_int.masked_kernel_bytes``: data and
validity blocks at every grid step, the per-pair and output blocks once
per output tile) over the chip's HBM bandwidth, divided by the kernel's
device time per graph.

This is a share of the HBM roofline only. The kernel is bound by its
vector and transcendental work, for which no peak is published, so the
share says how far the kernel is from being bound by memory, not how
close it runs to its own bound."""

#: HBM bandwidth per chip, bytes per second, by ``device_kind``. Source:
#: Google Cloud documentation, "TPU v5e" (16 GB of HBM at 819 GB/s).
HBM_BYTES_PER_S = {
    "TPU v5 lite": 819e9,
}

KERNEL = "pallas:pairwise_moments_masked"


def read(reduced, work):
    kernel_s = dict(reduced["device_ops"]).get(KERNEL, 0.0)
    if kernel_s <= 0 or not reduced["graphs"]:
        return None
    kind = work["device_kind"]
    if kind not in HBM_BYTES_PER_S:
        raise KeyError(f"no HBM bandwidth on record for device {kind!r}")
    at_peak_s = work["masked_moment_bytes"] / HBM_BYTES_PER_S[kind]
    return 100.0 * at_peak_s / (kernel_s / reduced["graphs"])
