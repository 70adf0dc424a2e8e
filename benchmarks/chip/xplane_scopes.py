"""Per-stage device time and per-span idle time from a profiler trace.

The program names the stages of a fit. On the device each stage runs under
a ``jax.named_scope`` called ``lingam.<stage>``; the name lands in the
``op_name`` of the stage's HLO ops, and a TPU trace keeps it per op as the
``tf_op`` stat of the op's event metadata, for example
``jit(_fit_local)/while/body/closed_call/lingam.moments/jit(pairwise_moments)/
jit(pairwise_moments_pallas)/pairwise_moments_pallas/pallas_call``. On the
host the facades' spans ``lingam.fit`` and ``lingam.fetch`` are profiler
annotations on the Python thread.

``jax.profiler.ProfileData`` gives an event its name and times but not the
stats of its metadata, so the metadata maps of each device plane are read
here from the ``.xplane.pb`` itself, with a small protobuf wire-format
reader that skips the planes' ``lines`` (the events). Events are matched
to their metadata by name.

The reduction, over the window of ``trace_reduce.reduce_profile`` (the
``bench.window`` span moved onto the device clock):

* ``scope_s``: device self time per scope, by the nesting rule of
  ``trace_reduce.self_times``: each op goes to the innermost ``lingam.*``
  component of its path, or to ``(unscoped)``; summed over scopes it is
  the self-time total of ``trace_reduce``'s ``device_ops``;
* ``idle_in_span_s``: device idle seconds whose midpoint lies inside each
  ``lingam.*`` host span, at any depth;
* ``unscoped_paths``: the longest ``(unscoped)`` op paths, for a look by
  hand.

A trace of a program without the scopes reduces to ``(unscoped)`` alone
and no spans; :func:`stage_ms` then finds nothing and reads None.
"""

from __future__ import annotations

import trace_reduce

PREFIX = "lingam."
UNSCOPED = "(unscoped)"
AMBIGUOUS = "(ambiguous)"

# XSpace: planes = 1. XPlane: name = 2, lines = 3, event_metadata = 4,
# stat_metadata = 5 (maps: key = 1, value = 2). XEventMetadata: name = 2,
# stats = 5. XStatMetadata: name = 2. XStat: metadata_id = 1,
# str_value = 5, ref_value = 7.
_PLANES, _NAME, _EVENT_MD, _STAT_MD = 1, 2, 4, 5
_MD_STATS, _STAT_ID, _STR, _REF = 5, 1, 5, 7


def _varint(buf, i):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def _fields(buf, lo=0, hi=None):
    """(field number, value) of one message in ``buf[lo:hi]``; a
    length-delimited value is its (start, end) in ``buf``."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 1:
            value, i = None, i + 8
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, span):
    for f, v in _fields(buf, *span):
        if f == 2:
            return v
    return (span[0], span[0])


def _plane_paths(buf, lo, hi):
    """(plane name, {event name: tf_op path}) of one XPlane."""
    name = ""
    event_mds, stat_names = [], {}
    for f, v in _fields(buf, lo, hi):
        if f == _NAME:
            name = _text(buf, v)
        elif f == _EVENT_MD:
            event_mds.append(_map_values(buf, v))
        elif f == _STAT_MD:
            md_id, md_name = None, ""
            for g, w in _fields(buf, *_map_values(buf, v)):
                if g == 1:
                    md_id = w
                elif g == _NAME:
                    md_name = _text(buf, w)
            stat_names[md_id] = md_name
    if not trace_reduce._DEVICE.match(name):
        return name, None
    tf_op = next((i for i, n in stat_names.items() if n == "tf_op"), None)
    paths = {}
    for span in event_mds:
        ev_name, path = None, None
        for f, v in _fields(buf, *span):
            if f == _NAME:
                ev_name = _text(buf, v)
            elif f == _MD_STATS and tf_op is not None:
                stat_id, value = None, None
                for g, w in _fields(buf, *v):
                    if g == _STAT_ID:
                        stat_id = w
                    elif g == _STR:
                        value = _text(buf, w)
                    elif g == _REF:
                        value = stat_names.get(w)
                if stat_id == tf_op and value is not None:
                    path = value
        if ev_name is None or path is None:
            continue
        if ev_name in paths and scope_of(paths[ev_name]) != scope_of(path):
            path = AMBIGUOUS
        paths[ev_name] = path
    return name, paths


def op_paths(path: str):
    """{device plane name: {op event name: tf_op path}} of a trace file."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for f, v in _fields(buf):
        if f == _PLANES:
            name, paths = _plane_paths(buf, *v)
            if paths is not None:
                out[name] = paths
    return out


def scope_of(path) -> str:
    """The innermost ``lingam.*`` component of an op path."""
    if path is None:
        return UNSCOPED
    if path == AMBIGUOUS:
        return AMBIGUOUS
    for part in reversed(path.split("/")):
        if part.startswith(PREFIX):
            return part
    return UNSCOPED


def _window(host_lines, per_dev):
    """(lo, hi, skew) as ``trace_reduce.reduce_profile`` sets them."""
    window, py_events = None, []
    for line in host_lines:
        evs = [(e.start_ns, e.end_ns, e.name) for e in line.events]
        marks = [ev for ev in evs if ev[2] == "bench.window"]
        if marks:
            window = (marks[0][0], marks[0][1])
            py_events = evs
            break
    first_op = min((s for ops in per_dev for s, _, _ in ops), default=None)
    if window is None:
        ends = [e for ops in per_dev for _, e, _ in ops]
        window = (first_op, max(ends)) if ends else (0.0, 0.0)
    dispatch = min((s for s, _, name in py_events
                    if name.startswith("PjitFunction(") and s >= window[0]),
                   default=None)
    skew = 0.0
    if dispatch is not None and first_op is not None:
        skew = max(0.0, dispatch - first_op)
    return window[0] - skew, window[1] - skew, skew


def reduce_profile(pd, paths, devices: int = 1):
    """``scope_s``, ``idle_in_span_s`` and ``unscoped_paths`` of a loaded
    ``ProfileData`` whose device planes' op paths are ``paths``."""
    host_lines, dev_planes = [], []
    for plane in pd.planes:
        m = trace_reduce._DEVICE.match(plane.name)
        if m:
            dev_planes.append((int(m.group(1)), plane))
        elif plane.name == "/host:CPU":
            host_lines = list(plane.lines)
    dev_planes = [p for _, p in sorted(dev_planes, key=lambda t: t[0])]
    dev_planes = dev_planes[:devices]
    per_dev = []
    for plane in dev_planes:
        ops = [line for line in plane.lines if line.name == "XLA Ops"]
        names = paths.get(plane.name, {})
        per_dev.append([(e.start_ns, e.end_ns, names.get(e.name))
                        for line in ops for e in line.events])
    lo, hi, skew = _window(host_lines, per_dev)

    scope_s, path_s, gaps = {}, {}, []
    for ops in per_dev:
        clipped = []
        for s, e, path in ops:
            s, e = trace_reduce._clip(s, e, lo, hi)
            if e > s:
                clipped.append((s, e, path))
        by_path = trace_reduce.self_times(clipped)
        for path, sec in by_path.items():
            scope = scope_of(path)
            scope_s[scope] = scope_s.get(scope, 0.0) + sec
            if scope == UNSCOPED:
                path_s[path] = path_s.get(path, 0.0) + sec
        merged = trace_reduce.union([(s, e) for s, e, _ in clipped])
        prev = lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)

    n_dev = max(len(per_dev), 1)
    spans = [(e.start_ns, e.end_ns, e.name) for line in host_lines
             for e in line.events if e.name.startswith(PREFIX)]
    idle = {}
    for s, e in gaps:
        mid = 0.5 * (s + e) + skew  # on the host clock
        for name in {n for a, b, n in spans if a <= mid <= b}:
            idle[name] = idle.get(name, 0.0) + (e - s) * 1e-9
    return {
        "scope_s": {k: v / n_dev for k, v in scope_s.items()},
        "idle_in_span_s": {k: v / n_dev for k, v in idle.items()},
        "unscoped_paths": sorted(
            ([str(k), v / n_dev] for k, v in path_s.items()),
            key=lambda kv: -kv[1])[:10],
    }


def reduce_file(path: str, devices: int = 1):
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    return reduce_profile(pd, op_paths(path), devices)


# Stages of the ordering step outside the moment computation.
STEP_OTHER = ("lingam.standardize", "lingam.scores", "lingam.residual",
              "lingam.compact")


def stage_ms(scoped, graphs: int):
    """Milliseconds per graph of each stage group, None where the trace
    has none of it: ``prune``, ``step_other`` (standardise, scores,
    residual update, compaction), ``moment_stage`` (the kernel with its
    wrapper), ``diagnostics``, ``var_lstsq`` (the VAR regression),
    ``lag_transform``, ``unscoped``, and ``fetch_idle`` (device idle time
    inside the facades' host reads)."""
    scopes = scoped["scope_s"]
    idle = scoped["idle_in_span_s"]

    def total(names, source):
        found = [source[n] for n in names if n in source]
        return 1e3 * sum(found) / graphs if found and graphs else None

    return {
        "prune": total(["lingam.prune"], scopes),
        "step_other": total(STEP_OTHER, scopes),
        "moment_stage": total(["lingam.moments"], scopes),
        "diagnostics": total(["lingam.diagnostics"], scopes),
        "var_lstsq": total(["lingam.var_regress"], scopes),
        "lag_transform": total(["lingam.lag_transform"], scopes),
        "unscoped": total([UNSCOPED], scopes),
        "fetch_idle": total(["lingam.fetch"], idle),
    }
