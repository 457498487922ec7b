"""Exchange-stage launches (ctx.metrics_summary()["stages"]) in the window,
per completed action. Cells whose action launches none leave it out."""


def read(obs: dict):
    if not obs["actions"] or not obs["window"]["stages"]:
        return None
    return obs["window"]["stages"] / obs["actions"]
