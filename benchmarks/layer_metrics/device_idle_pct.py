"""Share of the traced window in which no operation ran on the device, the
mean over the cell's chips: 1 - union of the device-operation intervals
over the window.  Layer: device programs.  Moves tokens_per_s."""


def read(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
