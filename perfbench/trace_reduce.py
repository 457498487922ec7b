"""From a profiler trace to numbers: device busy time, the operations that
took most of it, and the longest idle gaps by what the host was doing.

Two steps, so that the arithmetic is checked without a profile file:
`read_xplane` turns an .xplane.pb into plain lists (the only part that needs
jax), and `reduce_events` turns those lists into the numbers. A recorded
example of the lists is kept in tests/trace_small.json.

Times are seconds on the trace's own clock. The traced window is the
harness's own host span SPAN_WINDOW; device events are clipped to it.
"""

import glob
import os
import re

SPAN_WINDOW = "perfbench:window"
SPAN_PREFIX = "perfbench:"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def short_name(name: str) -> str:
    """An XLA Ops event is named by its whole HLO instruction,
    `%fusion.62 = s32[8388608]{...} fusion(...), kind=kCustom, calls=...`:
    keep `fusion.62 s32[8388608] fusion kCustom`."""
    if " = " not in name:
        return name.lstrip("%")[:80]
    op, rest = name.split(" = ", 1)
    code = _OPCODE.search(" " + rest)
    shape = rest.split("{", 1)[0].split(" ", 1)[0].lstrip("(")
    kind = rest.split("kind=", 1)[1].split(",", 1)[0] if "kind=" in rest else ""
    return " ".join(w for w in (op.lstrip("%"), shape,
                                code.group(1) if code else "", kind) if w)[:80]


def category(name: str) -> str:
    """The group of an operation (by its short name) in the breakdown. On the
    TPU a gather or a scatter is a fusion of kind kCustom whose own name says
    nothing more; kLoop / kInput fusions are elementwise and reduce passes."""
    words = short_name(name).split(" ")
    base = words[0].split(".")[0]
    code = words[2] if len(words) > 2 else base
    if "sort" in (base, code):
        return "sort"
    if any(w in base or w in code for w in (
            "all-to-all", "all-reduce", "all-gather", "reduce-scatter",
            "collective", "permute")):
        return "collective"
    if code in ("while", "conditional") or base in ("while", "conditional"):
        return "while"
    if "custom-call" in (base, code):
        return "custom call"
    if code in ("copy", "transpose", "bitcast") or base in ("copy", "transpose"):
        return "copy"
    if "fusion" in base or code == "fusion":
        if "kCustom" in words or any(w in base for w in (
                "gather", "scatter", "dynamic-slice", "dynamic-update-slice")):
            return "gather-scatter fusion"
        return "other fusion"
    if code in ("gather", "scatter", "dynamic-slice", "dynamic-update-slice"):
        return "gather-scatter fusion"
    return "other"


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def read_xplane(path: str, device_plane: str = DEVICE_PLANE,
                ops_line: str = OPS_LINE) -> dict:
    """{"devices": {plane: [[name, start_s, dur_s], ...]},
        "host": [[name, start_s, dur_s], ...]} — device operations of each
    device plane's ops line, and the harness's own host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(device_plane):
            for line in plane.lines:
                if line.name.startswith(ops_line):
                    devices.setdefault(plane.name, []).extend(
                        [e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                        for e in line.events
                        if e.duration_ns > 0
                        and not e.name.startswith(SPAN_PREFIX))
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [[e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                          for e in line.events]
                if any(e[0] == SPAN_WINDOW for e in events):
                    # the harness's thread: its spans, and what jax was
                    # doing inside them (events of 0.1 ms or more)
                    host.extend(e for e in events
                                if e[0].startswith(SPAN_PREFIX) or e[2] >= 1e-4)
    return {"devices": devices, "host": host}


def _union(intervals: list) -> list:
    """Sorted, merged [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events: list) -> dict:
    """Seconds by op name, an op's time less that of the ops nested inside
    it on the same line (a `while` holds its body's ops)."""
    total = {}
    stack = []  # [name, end, self_s]
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        e = s + d
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            total[done[0]] = total.get(done[0], 0.0) + done[2]
        if stack:
            stack[-1][2] -= min(d, max(0.0, stack[-1][1] - s))
        stack.append([name, e, d])
    for name, _e, self_s in stack:
        total[name] = total.get(name, 0.0) + self_s
    return total


def reduce_events(events: dict, top: int = 10) -> dict:
    """busy_s and window_s (busy averaged over the device planes), the top
    operations by self time as [["<category>: <op>", seconds], ...], every
    operation as [[op, category, self seconds averaged over the planes,
    times it ran on all planes], ...] under "ops", and the first device's
    idle gaps summed by what the host was doing in them, as
    [["<harness span> > <jax call> (n gaps, the longest)", seconds], ...].
    None where the trace holds no window span or no device operation: a
    reader then reports nothing."""
    windows = [h for h in events["host"] if h[0] == SPAN_WINDOW]
    if not windows or not events["devices"]:
        return None
    w0 = min(h[1] for h in windows)
    w1 = max(h[1] + h[2] for h in windows)
    busy, ops, counts, by_what = [], {}, {}, {}
    for plane in sorted(events["devices"]):
        clipped = [[n, max(s, w0), min(s + d, w1) - max(s, w0)]
                   for n, s, d in events["devices"][plane]
                   if s + d > w0 and s < w1]
        merged = _union([[s, s + d] for _n, s, d in clipped])
        busy.append(sum(e - s for s, e in merged))
        for name, sec in _self_times(clipped).items():
            ops[name] = ops.get(name, 0.0) + sec / len(events["devices"])
        for name, _s, _d in clipped:
            counts[name] = counts.get(name, 0) + 1
        if not by_what:  # idle gaps of the first device plane
            edges = [w0] + [t for iv in merged for t in iv] + [w1]
            by_what = _gaps_by_host(
                [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2]) if g1 > g0],
                [h for h in events["host"] if h[0] != SPAN_WINDOW])
    if not any(busy):
        return None
    by_time = sorted(ops.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": w1 - w0,
        "device_ops": [[f"{category(n)}: {short_name(n)}", s] for n, s in
                       by_time[:top]],
        "ops": [[short_name(n), category(n), s, counts[n]] for n, s in by_time],
        "idle_gaps": [[f"{what} ({n} gaps, the longest {longest:.6f} s)", total]
                      for what, (total, longest, n) in
                      sorted(by_what.items(), key=lambda kv: -kv[1][0])[:top]
                      if total >= 1e-6],
    }


def _gaps_by_host(gaps: list, spans: list) -> dict:
    """{what the host was doing: [seconds in all, longest gap, gaps]} over
    the idle gaps (sorted, disjoint). What the host was doing in a gap is
    the harness's span that covers most of it, then the jax call of its
    thread that covers most of it (`python` where none covers half: the
    program's own Python). One sweep: a span stays a candidate from the
    first gap it overlaps to the last."""
    spans = sorted(spans, key=lambda h: h[1])
    by_what, live, nxt = {}, [], 0
    for g0, g1 in gaps:
        while nxt < len(spans) and spans[nxt][1] < g1:
            live.append(spans[nxt])
            nxt += 1
        live = [h for h in live if h[1] + h[2] > g0]
        best = {True: ("between spans", 0.0), False: ("python", 0.5 * (g1 - g0))}
        for name, s, d in live:
            own = name.startswith(SPAN_PREFIX)
            cover = min(g1, s + d) - max(g0, s)
            if cover > best[own][1]:
                best[own] = (name, cover)
        acc = by_what.setdefault(f"{best[True][0]} > {best[False][0]}", [0.0, 0.0, 0])
        acc[0] += g1 - g0
        acc[1] = max(acc[1], g1 - g0)
        acc[2] += 1
    return by_what
