"""Bytes of the cut tensor up and its gradient down per client step, counted
by the benchmark's wrapper around the client's transport (the one that
times the reply).  Layer: transport.  Moves reply_ms_p50."""


def read(run: dict):
    if not run["wire_bytes"]:
        return None
    return sum(run["wire_bytes"]) / len(run["wire_bytes"])
