"""Shard-program launches per action: the count of the program's `launch`
spans (vega_tpu/tpu/spans.py, one around every call of a cached shard
program) tallied under the traced window's profiler session, over the
window's actions. A program without that tally gives nothing."""


def read(obs: dict):
    try:
        from vega_tpu.tpu import spans
    except ImportError:
        return None
    launch = spans.session().get("launch")
    if not launch or not obs["actions"]:
        return None
    return launch["count"] / obs["actions"]
