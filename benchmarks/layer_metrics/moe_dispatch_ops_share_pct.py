"""Share of the device operations' summed time (containers left out: it
comes to about the busy time, ``trace_reduce.reduce``) in the operations
that move tokens to their experts and back: events whose opcode
(``trace_reduce.short_name``'s second word) is ``sort``, ``gather``, ``scatter`` or a top-k.  A lower
bound: a gather that XLA fused into a ``fusion`` is not seen.  And not the
routed layer's alone: the embedding's gather and its gradient's scatter are
in it.  Layer: device programs.  Moves tokens_per_s."""

OPCODES = ("sort", "gather", "scatter", "topk", "top-k")


def read(run: dict):
    trace = run["trace"]
    if trace is None:
        return None
    total = sum(trace["op_seconds"].values())
    moved = sum(seconds for name, seconds in trace["op_seconds"].items()
                if _opcode(name) in OPCODES or "TopK" in name)
    return 100.0 * moved / total if total else None


def _opcode(name: str) -> str:
    words = name.split(" ")
    return words[1] if len(words) > 1 else ""
