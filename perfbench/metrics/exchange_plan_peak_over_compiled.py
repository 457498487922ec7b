"""The exchange planner's model against the compiler's own plan: the
`est_peak_bytes` of the last exchange the run planned
(`vega_tpu.tpu.exchange_plan.last_plan()`: a chip's operand, grouped copy,
collective buffers and output, by the model) over the `temp_bytes +
output_bytes` the compiler gives for the program that ran it
(`spans.program_stages()`: the memory analysis of the newest minted program
of a kind; the kind is the last one minted whose table holds an operation of
the stage `exchange_wire`, which is the program the last plan was resolved
for: a run mints `rbk` before `join`, and an elided exchange plans nothing).
The planner picks `staged` or `ring` where this estimate passes
`dense_hbm_budget`; a ratio well over 1 says it would give up the one-round
program for memory the chip does not need. One chip plans nothing, a program
without the table or a backend without the analysis gives nothing."""


def read(obs: dict):
    try:
        from vega_tpu.tpu import exchange_plan, spans
    except ImportError:
        return None
    plan = exchange_plan.last_plan()
    if plan is None or not hasattr(spans, "program_stages"):
        return None
    compiled = None
    for table in spans.program_stages().values():  # in the order minted
        if "temp_bytes" in table and any(
                row["stage"] == "exchange_wire" for row in table["ops"]):
            compiled = table["temp_bytes"] + table["output_bytes"]
    return plan.est_peak_bytes / compiled if compiled else None
