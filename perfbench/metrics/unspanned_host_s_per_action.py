"""Host seconds per action that still have no name: the client walls of the
window's actions less the seconds of every span the program tallied under
the traced window's profiler session (vega_tpu/tpu/spans.py), over the
actions. The spans are flat, so nothing is taken off twice, and every span
of the window opens inside an action (nothing else calls the program
there), so spans + this = the mean client wall. What is left is the lineage
walk, key building, capacity sizing, jax's own overhead outside the spans,
and the harness's `build lineage`. The tally adds spans from every thread:
where an action decodes shards on the scheduler's task threads (a cogroup's
`shard_rows`; no cell of today does), concurrent `fetch` and `decode` spans
sum past the wall and this reads low, even below 0. A program without the
tally gives nothing."""


def read(obs: dict):
    try:
        from vega_tpu.tpu import spans
    except ImportError:
        return None
    tally = spans.session()
    if not tally or not obs["actions"]:
        return None
    spanned = sum(acc["seconds"] for acc in tally.values())
    return (sum(obs["action_walls"]) - spanned) / obs["actions"]
