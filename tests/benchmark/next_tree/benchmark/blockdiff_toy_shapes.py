"""The arithmetic of the dry run's second configuration (``tests/benchmark``):
a dense decoder that generates by passes over a block.  A forward pass is a
llama-like one; what differs is how many of them a served token costs."""

BF16 = 2


def _layer(hf):
    h, d = hf["hidden_size"], hf["head_dim"]
    attention = h * d * (2 * hf["num_attention_heads"] + 2 * hf["num_key_value_heads"])
    return attention + 3 * h * hf["intermediate_size"]


def total_params(hf):
    """Every matrix, the embedding and the untied head, the norms."""
    h = hf["hidden_size"]
    return hf["num_hidden_layers"] * _layer(hf) + 2 * hf["vocab_size"] * h + (2 * hf["num_hidden_layers"] + 1) * h


def matmul_params(hf):
    """What ONE row multiplies against in ONE forward pass (no embedding gather)."""
    return hf["num_hidden_layers"] * _layer(hf) + hf["vocab_size"] * hf["hidden_size"]


def rows_per_token(hf):
    """Forward rows a served token costs: its block goes through the model
    once a pass, ``block_length / tokens_per_pass`` passes, and once more to
    commit its keys and values."""
    return -(-hf["block_length"] // hf["tokens_per_pass"]) + 1


def weight_bytes(hf):
    """A pass streams every matrix and the head; the embedding is looked up."""
    return BF16 * (total_params(hf) - hf["vocab_size"] * hf["hidden_size"])


def kv_bytes_per_token(hf):
    return hf["num_hidden_layers"] * 2 * hf["num_key_value_heads"] * hf["head_dim"] * BF16


def flops_per_token(hf):
    """Of one row of one pass: a served token's are ``rows_per_token`` times this."""
    return 2 * matmul_params(hf)


def cache_bytes(hf, serving):
    """Blocks of keys and values; a block's are rewritten in place pass after
    pass until its committing pass, so nothing is held beside the pages."""
    args = serving["args"]
    block = args[args.index("--kv-block-size") + 1] if "--kv-block-size" in args else 16
    return int(args[args.index("--num-blocks") + 1]) * int(block) * kv_bytes_per_token(hf)
