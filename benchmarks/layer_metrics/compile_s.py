"""Seconds of XLA backend compilation (or of loading from the persistent
cache) during set-up: the sum of JAX's backend-compile events before the
window opened.  Layer: entry.  Moves setup_s."""


def read(run: dict):
    return run["compile_s"]
