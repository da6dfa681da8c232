"""One driver per execution path.  ``run.py`` finds ``paths/<path>.py`` by
the ``path`` of the cell's traffic file and asks it for a ``Driver``:

    Driver(plan, cfg, key, job)   build the parties (weights come from
                                  ``plan.init``, so from the seed)
    .check_gate(on)               make the check steps' grouping fixed
    .step(batch) -> [loss, ...]   the window's own call; one loss per unit
    .warm_up(batch)               run the shapes the window may meet and the
                                  check steps did not
    .sync()                       wait for everything on the device
    .params() / .first_moments()  {"client<i>"|"server": tree}
    .counters() -> dict           the program's own counters, cumulative
    .reply_seconds / .wire_bytes  lists the transport wrapper fills (party)
    .unit                         what ``attempted`` counts
    .close()
"""


def first_moment(opt_state):
    """Adam's first moment inside an optax state: after one step it is
    (1 - b1) times the gradient the optimizer was given."""
    found = [s for s in _walk(opt_state) if hasattr(s, "mu")]
    if len(found) != 1:
        raise ValueError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def _walk(node):
    yield node
    if isinstance(node, tuple):
        for child in node:
            if isinstance(child, tuple):
                yield from _walk(child)
