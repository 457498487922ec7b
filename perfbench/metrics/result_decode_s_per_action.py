"""Host seconds per action in the program's `decode` spans
(vega_tpu/tpu/spans.py: Block.to_numpy and shard_rows after their fetch —
shard slicing, np.concatenate, the int64 join of two key words, dictionary
decode), tallied on time.perf_counter under the traced window's profiler
session. Nothing tallied gives nothing, never 0."""


def read(obs: dict):
    try:
        from vega_tpu.tpu import spans
    except ImportError:
        return None
    decode = spans.session().get("decode")
    if not decode or not obs["actions"]:
        return None
    return decode["seconds"] / obs["actions"]
