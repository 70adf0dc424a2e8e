"""Device milliseconds per graph of the fit program outside the moment
kernel: standardisation, Gram matrices, scores, residual updates,
compaction gathers and the pruning solves.

The fit program is found as the program (``XLA Modules`` entry) that runs
the moment kernel, not by its name, so a renamed program still reads."""


def read(reduced, work):
    hosts = [name for name, s in reduced["module_kernel_s"].items() if s > 0]
    if not hosts or not reduced["graphs"]:
        return None
    other = sum(reduced["modules"][name] - reduced["module_kernel_s"][name]
                for name in hosts)
    return 1e3 * other / reduced["graphs"]
