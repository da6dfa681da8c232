"""GPT-2's forward pass and loss as the program's ``transformer_lm`` states
it: token and learned position embeddings, pre-LN blocks with causal
attention and GELU-tanh, a final LayerNorm and an untied head with a bias.
The weights come in the program's tree layout, made by ``weights.py``."""

from __future__ import annotations

from . import common


def loss_fn(config: dict, precision: str):
    heads = config["n_head"]
    mm = common.matmul(precision)

    def loss(client, server, tokens, labels):
        c, s = client["params"], server["params"]
        x = c["tok"]["embedding"][tokens] + c["pos"][None, :tokens.shape[1]]
        x = common.blocks(c, x, heads, True, mm)
        x = common.blocks(s["trunk"], x, heads, True, mm)
        x = common.layer_norm(s["head"]["ln_f"], x)
        return common.cross_entropy(common.dense(s["head"]["lm_head"], x, mm), labels)

    return loss
