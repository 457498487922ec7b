"""Median client wall of the traced window's actions: the steadier statistic
beside `action_p95_s` (read under the profiler, so a little above an untraced
run's)."""

import statistics


def read(obs: dict):
    if not obs["action_walls"]:
        return None
    return statistics.median(obs["action_walls"])
