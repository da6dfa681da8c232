"""``peak_bytes_in_use`` of the fullest chip after the window, in GB (1e9).
Layer: device programs.  Moves tokens_per_s."""


def read(run: dict):
    if run["rehearsal"]:
        return None
    return run["peak_bytes"] / 1e9
