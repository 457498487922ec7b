"""Which collective program the exchanges of the traced window ran: the
rounds their plans held, from the program's count-only tally entry
`exchange_plan_rounds` (vega_tpu/tpu/spans.py `count`), under the traced
window's profiler session,

    exchange_plan_rounds / actions

Each launch of an exchange program across shards adds its resolved plan's
`rounds` (tpu/exchange_plan.py): 1 for the one-shot `all_to_all`, 2 for
`staged` in groups of two on four shards, `n - 1` for `ring`; one shard
plans nothing and adds nothing. An action of one exchange reads 1.0 where the
planner kept the one-shot program, more where `dense_hbm_budget` moved it to
`staged` or `ring` or where an overflow launched again. A program without
the counter gives nothing, never 0."""


def read(obs: dict):
    try:
        from vega_tpu.tpu import spans
    except ImportError:
        return None
    tally = spans.session()
    if "exchange_plan_rounds" not in tally or not obs["actions"]:
        return None
    return tally["exchange_plan_rounds"]["count"] / obs["actions"]
