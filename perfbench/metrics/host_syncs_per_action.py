"""Blocking device->host round trips per action: the count of the program's
`fetch` spans (vega_tpu/tpu/spans.py: mesh.host_get on a tree that holds a
jax.Array, and shard_rows' sliced read) tallied under the traced window's
profiler session, over the window's actions. A program without that tally
gives nothing."""


def read(obs: dict):
    try:
        from vega_tpu.tpu import spans
    except ImportError:
        return None
    fetch = spans.session().get("fetch")
    if not fetch or not obs["actions"]:
        return None
    return fetch["count"] / obs["actions"]
