"""Roofline share of the flash kernels' calls of the latent-attention layers
(the program's device scope ``attn_latent``; a Pallas call inside a scope is
named after it in the trace, see ``_afmoe.py``), forward
(``tpu_custom_call/3``) and one-pass backward (``/6``) together.  Costed at
the true widths (``flops/joyai_llm_flash.py``: QK^T at 192, PV at 128, at
the keys a query sees), so what the kernel pads (192 to 256 lanes) and the
dead part of the tiles the mask cuts read as time lost.  Where the trace has
no such event, or the configuration's family has no such attention, there
is nothing to read: ``None``.  Layer: kernels.  Moves mfu_pct."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _afmoe

SCOPE = "attn_latent"


def read(run: dict):
    trace, job, flops = run["trace"], run["job"], run["flops"]
    if trace is None:
        return None
    forward = _afmoe.events(trace, SCOPE, "tpu_custom_call/3")
    backward = _afmoe.events(trace, SCOPE, "tpu_custom_call/6")
    if not forward[0] and not backward[0]:
        return None
    shape = flops.attention_shape(run["config"], job["rows_per_client"],
                                  job["tokens_per_row"])
    return _afmoe.share("mla_attn", run, [(forward, flops.attn_fwd(**shape)),
                                          (backward, flops.attn_bwd(**shape))])
