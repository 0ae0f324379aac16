"""The arithmetic of the dry run's configuration (``tests/benchmark``): a
decoder whose layers are linear-attention layers with a recurrent state a
lane, every fourth one latent attention with a compressed cache a token, and
sparse experts of which this chip holds a share.  Invented, never
instantiated: it shows that such a configuration sizes itself by a module
of its own, and nothing in ``benchmark/`` knows its keys."""

BF16, F32 = 2, 4


def _layers(hf):
    lin = hf["linear_attn_config"]
    return len(lin["kda_layers"]), len(lin["full_attn_layers"])


def _linear_attn(hf):
    lin = hf["linear_attn_config"]
    wide = lin["num_heads"] * lin["head_dim"]
    taps = lin["short_conv_kernel_size"] * 3 * wide   # q, k, v each behind a short convolution
    return 4 * hf["hidden_size"] * wide + 2 * hf["hidden_size"] * lin["num_heads"] + taps


def _latent_attn(hf):
    h, heads = hf["hidden_size"], hf["num_attention_heads"]
    qk = hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]
    return (h * heads * qk + h * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"])
            + hf["kv_lora_rank"] * heads * (hf["qk_nope_head_dim"] + hf["v_head_dim"])
            + heads * hf["v_head_dim"] * h)


def _expert(hf):
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def _routed(hf):
    """The router's width: every expert of the deployment, ``num_experts``
    held on each of ``expert_parallel_size`` chips (the program's own key)."""
    return hf["num_experts"] * hf["expert_parallel_size"]


def _attention(hf):
    linear, latent = _layers(hf)
    return linear * _linear_attn(hf) + latent * _latent_attn(hf)


def _expert_layers(hf):
    return hf["num_hidden_layers"] - hf["first_k_dense_replace"]


def total_params(hf):
    """What this chip holds: every attention layer and shared expert whole,
    ``num_experts`` routed experts a layer, a router as wide as the
    published count, its slice of the vocabulary twice (untied)."""
    h = hf["hidden_size"]
    experts = _expert_layers(hf) * (
        (hf["num_experts"] + hf["num_shared_experts"]) * _expert(hf) + h * _routed(hf))
    dense = hf["first_k_dense_replace"] * 3 * h * hf["intermediate_size"]
    return _attention(hf) + experts + dense + 2 * hf["vocab_size"] * h + (2 * hf["num_hidden_layers"] + 1) * h


def matmul_params(hf):
    """What ONE token multiplies against here: of its ``num_experts_per_token``
    routed experts, the share that this chip holds."""
    h = hf["hidden_size"]
    here = hf["num_experts_per_token"] * hf["num_experts"] / _routed(hf)
    experts = _expert_layers(hf) * (
        (here + hf["num_shared_experts"]) * _expert(hf) + h * _routed(hf))
    dense = hf["first_k_dense_replace"] * 3 * h * hf["intermediate_size"]
    return int(_attention(hf) + experts + dense + hf["vocab_size"] * h)


def weight_bytes(hf):
    """A decode step of many lanes touches every expert held."""
    return BF16 * (total_params(hf) - hf["vocab_size"] * hf["hidden_size"])


def kv_bytes_per_token(hf):
    """Only the latent layers keep anything a token."""
    return _layers(hf)[1] * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * BF16


def state_bytes_per_lane(hf):
    """A linear layer's float32 state a head and the convolutions' last taps."""
    lin = hf["linear_attn_config"]
    state = lin["num_heads"] * lin["head_dim"] * lin["head_dim"] * F32
    taps = (lin["short_conv_kernel_size"] - 1) * 3 * lin["num_heads"] * lin["head_dim"] * BF16
    return _layers(hf)[0] * (state + taps)


def flops_per_token(hf):
    return 2 * matmul_params(hf)


def cache_bytes(hf, serving):
    """Pages of the latent cache PLUS ``--max-batch-size`` lanes of recurrent state."""
    args = serving["args"]
    block = args[args.index("--kv-block-size") + 1] if "--kv-block-size" in args else 16
    pages = int(args[args.index("--num-blocks") + 1]) * int(block) * kv_bytes_per_token(hf)
    return pages + int(args[args.index("--max-batch-size") + 1]) * state_bytes_per_lane(hf)
