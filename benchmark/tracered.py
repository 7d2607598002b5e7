"""From the profiler's ``.xplane.pb`` to numbers: the device's busy
union and idle share, the programs and the operations that took most
time, and the longest idle gaps by what the host was doing.

Read with ``jax.profiler.ProfileData`` and nothing else.  A device is a
plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event
for each operation that ran, its ``XLA Modules`` line one for each
program.  Busy time is the union of the operations' intervals (of the
programs', where a trace has no operations line).  An operation's name
in the trace is its whole HLO text and names no program, so it is
reported as ``<program>:<%instruction>``, the program being the one
whose interval holds it; an operation inside another (the body of a
``while`` or a ``conditional``) is left to the outer one, so that the
operations' times add up to the busy time.  The benchmark's own
host spans are ``jax.profiler.TraceAnnotation``s whose names start with
``bench.``; the first, ``bench.anchor``, also ties the host's monotonic
clock to the trace's, so that the program's own spans (recorded on the
host clock) can label a gap.
"""

import glob
import os

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def union(intervals) -> list:
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def read_planes(xplane_path: str) -> dict:
    """``{"devices": {plane: {"ops": [(name, start, dur)], "modules":
    [...]}}, "bench": [(name, start, dur, stats)]}``, times in ns on
    the trace's clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    devices, bench = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
            devices[plane.name] = {"ops": lines.get(OPS_LINE, []),
                                   "modules": lines.get(MODULES_LINE, [])}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        bench.append((e.name, float(e.start_ns),
                                      float(e.duration_ns),
                                      {k: v for k, v in e.stats}))
    return {"devices": devices, "bench": sorted(bench, key=lambda b: b[1])}


def short_op(text: str) -> str:
    """``%fusion.5`` of ``%fusion.5 = u32[16777216]{...} fusion(...)``."""
    return text.split(" = ", 1)[0][:80]


def short_module(text: str) -> str:
    """``jit__apply_impl`` of ``jit__apply_impl(16850901781861046291)``."""
    return text.split("(", 1)[0]


def top_level_ops(dev: dict) -> list:
    """``(name, start, dur)`` of the operations that lie in no other,
    each named with its program."""
    import bisect
    modules = sorted((s, s + d, short_module(n))
                     for n, s, d in dev["modules"])
    starts = [m[0] for m in modules]
    out, end = [], -1.0
    for n, s, d in sorted(dev["ops"], key=lambda e: (e[1], -e[2])):
        if s < end:
            continue
        end = s + d
        i = bisect.bisect_right(starts, s) - 1
        program = modules[i][2] if i >= 0 and s < modules[i][1] else "?"
        out.append((f"{program}:{short_op(n)}", s, d))
    return out


def _label(t: float, spans) -> str:
    """The shortest span that covers ``t``: the innermost thing the
    host was doing."""
    covering = [(d, n) for n, s, d in spans if s <= t <= s + d]
    return min(covering)[1] if covering else "outside any span"


def reduce(planes: dict, host_spans=(), anchor_host_ns=None,
           window_host_ns=None, top: int = 10):
    """``host_spans`` are ``(name, t0_ns, dur_ns)`` on the host's
    monotonic clock, ``anchor_host_ns`` that clock's reading inside
    ``bench.anchor``.  The traced window is ``window_host_ns`` (start
    and end on the host's clock) where the caller knows it; else it
    runs from the start of the first ``bench.query`` span to the end of
    the last (the whole trace where there is none).  Returns ``None``
    for a trace with no device plane: a CPU run has no device number."""
    devices = planes["devices"]
    if not devices:
        return None
    queries = [b for b in planes["bench"] if b[0] == "bench.query"]
    every = [(s, s + d) for dev in devices.values()
             for _, s, d in (dev["ops"] or dev["modules"])]
    anchor = next((b for b in planes["bench"] if b[0] == "bench.anchor"),
                  None)
    shift = None if anchor is None or anchor_host_ns is None \
        else anchor[1] - anchor_host_ns
    if window_host_ns is not None and shift is not None:
        w0, w1 = (t + shift for t in window_host_ns)
    elif queries:
        w0 = min(s for _, s, _, _ in queries)
        w1 = max(s + d for _, s, d, _ in queries)
    elif every:
        w0, w1 = min(s for s, _ in every), max(e for _, e in every)
    else:
        return None
    window = w1 - w0

    spans = [(n, s, d) for n, s, d, _ in planes["bench"]
             if n != "bench.anchor"]
    if shift is not None:
        spans += [(n, t0 + shift, d) for n, t0, d in host_spans]

    busy_ns, op_ns, gaps = [], {}, []
    for name in sorted(devices):
        dev = devices[name]
        events = dev["ops"] or dev["modules"]
        clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in events
                   if s + d > w0 and s < w1]
        merged = union(clipped)
        busy_ns.append(sum(e - s for s, e in merged))
        named = top_level_ops(dev) if dev["ops"] else \
            [(short_module(n), s, d) for n, s, d in dev["modules"]]
        for n, s, d in named:
            if s + d > w0 and s < w1:
                op_ns[n] = op_ns.get(n, 0.0) + min(s + d, w1) - max(s, w0)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) / 2))
    chips = len(devices)
    # a program's operations together, as ``<program>:*``: dozens of
    # equal operations of one program would else fill the list
    program_ns = {}
    for n, t in op_ns.items():
        if ":" in n:
            key = n.split(":", 1)[0] + ":*"
            program_ns[key] = program_ns.get(key, 0.0) + t
    by_label = {}
    for dur, mid in gaps:
        label = _label(mid, spans)
        by_label[label] = by_label.get(label, 0.0) + dur
    return {
        "chips": chips,
        "window_s": window / 1e9,
        "busy_s": sum(busy_ns) / chips / 1e9,
        "busy_s_per_chip": [b / 1e9 for b in busy_ns],
        "queries": len(queries),
        "ops_line": any(dev["ops"] for dev in devices.values()),
        "device_programs": [[n, t / chips / 1e9] for n, t in sorted(
            program_ns.items(), key=lambda kv: -kv[1])[:top]],
        "device_ops": [[n, t / chips / 1e9] for n, t in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, t / chips / 1e9] for n, t in
                      sorted(by_label.items(), key=lambda kv: -kv[1])[:top]],
        "longest_gap_s": max((g[0] for g in gaps), default=0.0) / 1e9,
    }


def describe(xplane_path: str, limit: int = 12) -> list:
    """Planes, lines, event counts and the first names of each line:
    what to look at by hand before trusting a reduction."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        for line in plane.lines:
            events = list(line.events)
            out.append({"plane": plane.name, "line": line.name,
                        "events": len(events),
                        "first": [e.name for e in events[:limit]]})
    return out


if __name__ == "__main__":
    import json
    import sys
    for row in describe(sys.argv[1]):
        print(json.dumps(row))
