"""Device milliseconds of the Pallas moment kernel per graph."""


def read(reduced, work):
    if reduced["kernel_s"] <= 0 or not reduced["graphs"]:
        return None
    return 1e3 * reduced["kernel_s"] / reduced["graphs"]
