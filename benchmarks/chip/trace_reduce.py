"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

What a TPU trace holds, as read with ``jax.profiler.ProfileData``:

* a plane ``/device:TPU:<n>`` per chip, with a line ``XLA Modules`` (one
  event per executed program, named ``jit_<function>(<id>)``) and a line
  ``XLA Ops`` (one event per HLO instruction, named by its HLO text,
  ``%<instruction> = ...``). Control-flow ops (``%while``) span the ops of
  their bodies, so op events nest.
* a plane ``/host:CPU`` whose lines are host threads; the Python thread's
  line holds the benchmark's ``jax.profiler.TraceAnnotation`` spans
  (``bench.window``, ``bench.graph``) and JAX's own dispatch events
  (``PjitFunction(<name>)``, ``np.asarray(jax.Array)``).

The reduction, over the traced window (the ``bench.window`` span):

* ``busy_s``: the union of the device's op intervals, averaged over chips;
* ``kernel_s``: the summed device time of the Pallas (Mosaic) kernels, the
  ops whose HLO is ``custom_call_target="tpu_custom_call"``;
* ``modules``: device seconds per program, and ``module_kernel_s`` the
  kernel seconds inside each;
* ``device_ops``: self time per op kind (the instruction name without its
  number; a custom call by its target), longest first;
* ``idle_gaps``: the device's idle time in the window, each gap charged to
  the innermost host event on the Python thread at its midpoint.

The device clock lags the host's by up to a millisecond; the window is
moved onto the device clock by the lag between the host's first dispatch
in it and the device's first op.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_INSTR = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s|=|$)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def op_kind(name: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion``; a custom call by its target,
    the Pallas kernels by their instruction name."""
    if KERNEL_MARK in name:
        m = _INSTR.match(name)
        return "pallas:" + (m.group(1) if m else "kernel")
    target = _TARGET.search(name)
    if target:
        return "custom-call:" + target.group(1)
    m = _INSTR.match(name)
    return m.group(1) if m else name[:64]


def module_name(name: str) -> str:
    return _MODULE.match(name).group(1)


def union(intervals):
    """Merged, sorted list of (start, end) covering ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def self_times(events):
    """{kind: self seconds}: each op's duration less the ops nested in it."""
    totals = {}
    stack = []  # (end, kind) of the enclosing ops
    for s, e, kind in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        totals[kind] = totals.get(kind, 0.0) + (e - s)
        if stack:
            p_end, parent = stack[-1]
            totals[parent] -= min(e, p_end) - s
        stack.append((e, kind))
    return {k: v * 1e-9 for k, v in totals.items()}


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce_profile(pd, devices: int = 1):
    """The reduced numbers of a loaded ``ProfileData``."""
    host_lines = []
    dev_planes = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev_planes.append((int(m.group(1)), plane))
        elif plane.name == "/host:CPU":
            host_lines = list(plane.lines)
    dev_planes = [p for _, p in sorted(dev_planes, key=lambda t: t[0])]
    dev_planes = dev_planes[:devices]

    window = None
    py_events = []
    for line in host_lines:
        evs = [(e.start_ns, e.end_ns, e.name) for e in line.events]
        marks = [ev for ev in evs if ev[2] == "bench.window"]
        if marks:
            window = (marks[0][0], marks[0][1])
            py_events = evs
            break

    per_dev = []
    for plane in dev_planes:
        lines = {ln.name: ln for ln in plane.lines}
        ops = [(e.start_ns, e.end_ns, e.name)
               for e in lines["XLA Ops"].events] if "XLA Ops" in lines else []
        mods = [(e.start_ns, e.end_ns, e.name)
                for e in lines["XLA Modules"].events] \
            if "XLA Modules" in lines else []
        per_dev.append((ops, mods))

    first_op = min((s for ops, _ in per_dev for s, _, _ in ops), default=None)
    if window is None:
        ends = [e for ops, _ in per_dev for _, e, _ in ops]
        window = (first_op, max(ends)) if ends else (0.0, 0.0)
    # The device clock runs behind the host's by up to a millisecond. No
    # device op starts before the host dispatched the first program of the
    # window, so shift the host window onto the device clock by that lag.
    dispatch = min((s for s, _, name in py_events
                    if name.startswith("PjitFunction(") and s >= window[0]),
                   default=None)
    skew = 0.0
    if dispatch is not None and first_op is not None:
        skew = max(0.0, dispatch - first_op)
    lo, hi = window[0] - skew, window[1] - skew

    busy_ns = []
    kernel_ns = 0.0
    modules = {}
    module_kernel = {}
    op_self = {}
    gaps = []
    for ops, mods in per_dev:
        clipped = []
        kernels = []
        for s, e, name in ops:
            s, e = _clip(s, e, lo, hi)
            if e <= s:
                continue
            kind = op_kind(name)
            clipped.append((s, e, kind))
            if kind.startswith("pallas:"):
                kernels.append((s, e))
        merged = union([(s, e) for s, e, _ in clipped])
        busy_ns.append(sum(e - s for s, e in merged))
        kernel_ns += sum(e - s for s, e in kernels)
        for kind, sec in self_times(clipped).items():
            op_self[kind] = op_self.get(kind, 0.0) + sec
        kernels.sort()
        k_starts = [s for s, _ in kernels]
        for s, e, name in mods:
            s, e = _clip(s, e, lo, hi)
            if e <= s:
                continue
            mod = module_name(name)
            modules[mod] = modules.get(mod, 0.0) + (e - s) * 1e-9
            i = bisect.bisect_left(k_starts, s)
            inside = 0.0
            while i < len(kernels) and kernels[i][0] < e:
                ks, ke = _clip(*kernels[i], s, e)
                inside += max(0.0, ke - ks)
                i += 1
            module_kernel[mod] = module_kernel.get(mod, 0.0) + inside * 1e-9
        prev = lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)

    n_dev = max(len(per_dev), 1)
    idle = {}
    py_sorted = sorted(ev for ev in py_events if ev[2] != "bench.window")
    py_starts = [ev[0] for ev in py_sorted]
    for s, e in gaps:
        mid = 0.5 * (s + e) + skew  # on the host clock
        owner = "outside"
        # Events on one thread nest: the innermost one holding the
        # midpoint is the latest-starting one that is still open there.
        i = bisect.bisect_right(py_starts, mid) - 1
        while i >= 0:
            if py_sorted[i][1] >= mid:
                owner = py_sorted[i][2]
                break
            i -= 1
        idle[owner] = idle.get(owner, 0.0) + (e - s) * 1e-9
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy_ns) * 1e-9 / n_dev
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "kernel_s": kernel_ns * 1e-9 / n_dev,
        "modules": {k: v / n_dev for k, v in modules.items()},
        "module_kernel_s": {k: v / n_dev for k, v in module_kernel.items()},
        "device_ops": sorted(([k, v / n_dev] for k, v in op_self.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(([k, v / n_dev] for k, v in idle.items()),
                            key=lambda kv: -kv[1]),
    }


def reduce_file(path: str, devices: int = 1):
    import jax

    return reduce_profile(jax.profiler.ProfileData.from_file(path), devices)


def reduce_dir(log_dir: str, devices: int = 1):
    return reduce_file(find_xplane(log_dir), devices)


def brief(reduced):
    """The reduction without its long lists, for a log line."""
    return {
        "window_s": reduced["window_s"],
        "busy_s": reduced["busy_s"],
        "kernel_s": reduced["kernel_s"],
        "modules": dict(sorted(reduced["modules"].items(),
                               key=lambda kv: -kv[1])[:8]),
        "module_kernel_s": {k: v for k, v in
                            reduced["module_kernel_s"].items() if v},
    }
