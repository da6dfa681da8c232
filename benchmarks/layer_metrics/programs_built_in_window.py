"""Backend-compile events after the window opened; 0 expected, and
``correct`` is false otherwise.  Layer: entry.  Moves tokens_per_s."""


def read(run: dict):
    return run["programs_built_in_window"]
