"""The most the fullest chip held at once, in GB (1e9): ``run.py``'s
``peak_bytes_of`` over ``memory_stats()`` after the window, the larger of
the live buffers' own peak and what a step holds while it runs, its
temporaries (XLA's reservation) on top of the state and the code.  Layer:
device programs.  Moves tokens_per_s."""


def read(run: dict):
    if run["rehearsal"]:
        return None
    return run["peak_bytes"] / 1e9
