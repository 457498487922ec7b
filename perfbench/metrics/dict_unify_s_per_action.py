"""Host seconds per action in merges of two string dictionaries: the
program's `dict_unify` span (dense_rdd._DictUnification.tables, around
dict_encoding.merge_dicts, which runs once per pair of dictionaries and is
a lookup after), tallied under the traced window's profiler session, over
the window's actions. Near 0 once the merge is memoized; the whole merge
where a frame's unification merges anew every action. A program without
the span gives nothing, never 0."""


def read(obs: dict):
    try:
        from vega_tpu.tpu import spans
    except ImportError:
        return None
    unify = spans.session().get("dict_unify")
    if not unify or not obs["actions"]:
        return None
    return unify["seconds"] / obs["actions"]
