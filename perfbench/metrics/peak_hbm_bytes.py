"""memory_stats()["peak_bytes_in_use"] on the fullest chip, read once the
window has closed."""


def read(obs: dict):
    return obs["peak_hbm_bytes"] or None
