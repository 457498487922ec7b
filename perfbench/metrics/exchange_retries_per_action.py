"""Exchange launches per action beyond the first of each exchange, and the
blocks rebuilt after a speculative launch overflowed: from the program's
count-only tally entries (vega_tpu/tpu/spans.py `count`), under the traced
window's profiler session,

    (exchange_round - exchange + exchange_repair) / actions

`exchange` counts calls of `_run_exchange`, `exchange_round` its launches
(an overflow launches again with grown capacities), `exchange_repair` the
blocks `_settle_pending` rebuilt. Histogram sizing and a warm capacity hint
mean 0.0; each retry is a second run of the shard program, and a recompile
where the grown capacity is a new shape. A program without the counters
gives nothing, never 0."""


def read(obs: dict):
    try:
        from vega_tpu.tpu import spans
    except ImportError:
        return None
    tally = spans.session()
    if "exchange" not in tally or not obs["actions"]:
        return None

    def count(name):
        return tally.get(name, {"count": 0})["count"]

    return (count("exchange_round") - count("exchange")
            + count("exchange_repair")) / obs["actions"]
