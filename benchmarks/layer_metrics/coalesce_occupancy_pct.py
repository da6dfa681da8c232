"""How full the server's groups were over the window: requests coalesced
over groups flushed over ``coalesce_max``, from the deltas of
``ServerRuntime.health()["coalescing"]``.  Nothing to read where the server
has no coalescer.  Layer: runtime.  Moves tokens_per_s."""


def read(run: dict):
    before, after = run["counters_before"], run["counters_after"]
    if "coalesce_max" not in after:
        return None
    groups = after.get("groups_flushed", 0) - before.get("groups_flushed", 0)
    if groups <= 0:
        return None
    requests = after.get("requests_coalesced", 0) - before.get("requests_coalesced", 0)
    return 100.0 * requests / groups / after["coalesce_max"]
