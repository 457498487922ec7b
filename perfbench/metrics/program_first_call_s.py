"""Host seconds of the first call of every shard program minted in the
whole run (vega_tpu/tpu/spans.programs(): trace, lower, compile or
persistent-cache load, dispatch), summed: the part of set-up that minting
costs, by the program's own stopwatch. No table gives nothing."""


def read(obs: dict):
    try:
        from vega_tpu.tpu import spans
    except ImportError:
        return None
    programs = spans.programs()
    if not programs:
        return None
    return sum(p["first_call_s"] for p in programs.values())
