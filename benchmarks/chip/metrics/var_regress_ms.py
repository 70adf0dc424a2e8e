"""Device milliseconds per fit outside the fit program: the VAR least
squares, its residuals and the lag-matrix transform, which the VarLiNGAM
facade runs as programs of their own.

The fit program is the one that runs the moment kernel (as in
``fit_other_ms.fit``); every other program of the window counts here."""


def read(reduced, work):
    kernel = reduced["module_kernel_s"]
    if not any(s > 0 for s in kernel.values()) or not reduced["graphs"]:
        return None
    other = sum(s for name, s in reduced["modules"].items()
                if kernel.get(name, 0.0) <= 0)
    return 1e3 * other / reduced["graphs"]
