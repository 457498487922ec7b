"""Host seconds per action the frame planner takes to turn a frame's logical
plan into its lineage: the program's `frame_plan` span (vega_tpu/frame/
planner.compile_plan; it yields to the spans inside it, so a dictionary
build or merge that planning asks for is not in it), tallied on
time.perf_counter under the traced window's profiler session, over the
window's actions. A program without the span gives nothing, never 0."""


def read(obs: dict):
    try:
        from vega_tpu.tpu import spans
    except ImportError:
        return None
    plan = spans.session().get("frame_plan")
    if not plan or not obs["actions"]:
        return None
    return plan["seconds"] / obs["actions"]
