"""The share of the traced window, in percent, during which the first
device plane runs no operation AND no `vega:` span of the program
(vega_tpu/tpu/spans.py) is open on the harness's thread. Beside
`device_idle_share` it says how much of the idleness the program's spans
explain: what is left is host time inside an action that has no name yet,
the harness's own work between actions, and spans shorter than 0.1 ms,
which are not in `events` (trace_reduce.read_xplane drops them) and so count
as unspanned. A program that opens no span gives nothing."""

WINDOW = "perfbench:window"
PREFIX = "vega:"


def read(obs: dict):
    host, devices = obs["events"]["host"], obs["events"]["devices"]
    windows = [h for h in host if h[0] == WINDOW]
    if not windows or not devices:
        return None
    w0 = min(h[1] for h in windows)
    w1 = max(h[1] + h[2] for h in windows)
    spans = [[s, s + d] for n, s, d in host if n.startswith(PREFIX)]
    if not spans:
        return None
    covered, end = 0.0, w0  # sweep the union of operations and spans
    for s, e in sorted(spans + [[s, s + d] for _n, s, d in
                                devices[min(devices)]]):
        s, e = max(s, end), min(e, w1)
        if e > s:
            covered += e - s
            end = e
    return 100.0 * (1.0 - covered / (w1 - w0))
