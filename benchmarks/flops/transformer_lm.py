"""GPT-2 as the program builds it: blocks and the untied head are matrix
products; the embedding lookups are not."""

from . import common


def matmul_params(config: dict) -> int:
    d = config["n_embd"]
    return config["n_layer"] * common.block_matmul_params(d) + d * config["vocab_size"]


def train_flops_per_token(config: dict, t: int) -> float:
    return common.train_flops_per_token(
        matmul_params(config), config["n_layer"], t, config["n_embd"], causal=True)


def attention_shape(config: dict, rows: int, t: int) -> dict:
    return dict(batch=rows, heads=config["n_head"], t=t,
                head_dim=config["n_embd"] // config["n_head"], causal=True)
