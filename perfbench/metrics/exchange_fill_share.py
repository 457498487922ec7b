"""How full the exchanges of the traced window ran: the rows their moving
sides put in over the receive slots the programs held for them, from the
program's count-only tally entries (vega_tpu/tpu/spans.py `count`), under the
traced window's profiler session,

    100 * exchange_rows / exchange_slots

`exchange_rows` adds, for each side of a succeeded exchange that crossed
shards and whose row count the host already held, its rows over all shards;
`exchange_slots` adds `n_shards x out_cap` for the same side. Every shard's
`out_cap` is sized to the fullest destination, and a join holds both sides at
one capacity, so a hot key's chip and a small dimension table both read as
empty slots here: a uniform one-sided exchange reads over 90%. A program
without the counters gives nothing, never 0."""


def read(obs: dict):
    try:
        from vega_tpu.tpu import spans
    except ImportError:
        return None
    tally = spans.session()
    slots = tally.get("exchange_slots", {"count": 0})["count"]
    if "exchange_rows" not in tally or not slots:
        return None
    return 100.0 * tally["exchange_rows"]["count"] / slots
