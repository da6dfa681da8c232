"""ViT's forward pass and loss as the program's ``vit`` states it: a
patch-by-patch linear stem, learned positions, pre-LN blocks with full
attention, then LayerNorm, the mean over the patch tokens (no CLS token) and
a linear classifier.  Weights in the program's tree layout."""

from __future__ import annotations

from . import common


def loss_fn(config: dict, precision: str):
    heads, p = config["num_attention_heads"], config["patch_size"]
    mm = common.matmul(precision)

    def loss(client, server, images, labels):
        c, s = client["params"], server["params"]
        b, h, w, ch = images.shape
        x = images.reshape(b, h // p, p, w // p, p, ch).transpose(0, 1, 3, 2, 4, 5)
        x = x.reshape(b, (h // p) * (w // p), p * p * ch)
        x = mm(x, c["patch"]["kernel"].reshape(p * p * ch, -1)) + c["patch"]["bias"]
        x = x + c["pos"][None, :x.shape[1]]
        x = common.blocks(c, x, heads, False, mm)
        x = common.blocks(s["trunk"], x, heads, False, mm)
        x = common.layer_norm(s["head"]["ln_f"], x).mean(axis=1)
        return common.cross_entropy(common.dense(s["head"]["fc"], x, mm), labels)

    return loss
