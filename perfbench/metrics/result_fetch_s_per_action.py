"""Seconds per action that the host spent in the program's `vega:fetch`
spans (vega_tpu/tpu/spans.py: one blocking device->host round trip each)
while the device ran no operation: the copy and the sync latency that
nothing hides. A fetch span also holds the host's wait for the device's own
work; that part is `device_busy_s_per_action`'s, and only the profiler's
clock, which the span and the device planes share, can take it out. Read
from `obs["events"]`: the spans of the harness's thread inside
`perfbench:window`, less their overlap with the first device plane's
operations. Spans under 0.1 ms are not in `events` (trace_reduce.read_xplane
drops them) and are not counted. No such span, nothing to read."""

import bisect

WINDOW = "perfbench:window"
SPAN = "vega:fetch"


def read(obs: dict):
    host, devices = obs["events"]["host"], obs["events"]["devices"]
    windows = [h for h in host if h[0] == WINDOW]
    if not windows or not devices or not obs["actions"]:
        return None
    w0 = min(h[1] for h in windows)
    w1 = max(h[1] + h[2] for h in windows)
    spans = [(max(s, w0), min(s + d, w1)) for n, s, d in host
             if n == SPAN and s + d > w0 and s < w1]
    if not spans:
        return None
    starts, ends, before = [], [], []  # the plane's busy intervals, merged
    for _n, s, d in sorted(devices[min(devices)], key=lambda ev: ev[1]):
        if ends and s <= ends[-1]:
            ends[-1] = max(ends[-1], s + d)
        else:
            before.append(before[-1] + ends[-1] - starts[-1] if ends else 0.0)
            starts.append(s)
            ends.append(s + d)

    def busy_until(t: float) -> float:
        i = bisect.bisect_right(starts, t) - 1
        return before[i] + min(t, ends[i]) - starts[i] if i >= 0 else 0.0

    idle = sum((e - s) - (busy_until(e) - busy_until(s)) for s, e in spans)
    return idle / obs["actions"]
