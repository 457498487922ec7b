"""Host seconds per action in the program's `pivot` spans
(vega_tpu/tpu/spans.py: collect() turning fetched columns into Python row
objects, take_ordered's host merge), tallied on time.perf_counter under the
traced window's profiler session. Nothing tallied gives nothing, never 0."""


def read(obs: dict):
    try:
        from vega_tpu.tpu import spans
    except ImportError:
        return None
    pivot = spans.session().get("pivot")
    if not pivot or not obs["actions"]:
        return None
    return pivot["seconds"] / obs["actions"]
