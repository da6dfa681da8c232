"""ViT as the program builds it: the patch stem, the blocks, and a
classifier that runs once an image (spread over its tokens here)."""

from . import common


def tokens_per_image(config: dict) -> int:
    return (config["image_size"] // config["patch_size"]) ** 2


def matmul_params(config: dict) -> float:
    d = config["hidden_size"]
    stem = config["patch_size"] ** 2 * config["num_channels"] * d
    head = d * config["num_labels"] / tokens_per_image(config)
    return config["num_hidden_layers"] * common.block_matmul_params(d) + stem + head


def train_flops_per_token(config: dict, t: int) -> float:
    return common.train_flops_per_token(
        matmul_params(config), config["num_hidden_layers"], t,
        config["hidden_size"], causal=False)


def attention_shape(config: dict, rows: int, t: int) -> dict:
    return dict(batch=rows, heads=config["num_attention_heads"], t=t,
                head_dim=config["hidden_size"] // config["num_attention_heads"],
                causal=False)
