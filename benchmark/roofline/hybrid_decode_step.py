"""The least a decode step of a hybrid (linear + full attention) model must
do, from sizes read off the program and the configuration file: every
weight the step reads once (all but the token table, of which a step reads
one row a lane), the live context's K and V of the full-attention layers
once, and each active lane's recurrent state read AND written once (bytes);
2 operations per matmul weight per active lane (operations). At 16 lanes the
bytes bound it by far; the function says which."""

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def linear_layers(cfg: dict) -> int:
    n = int(cfg["num_hidden_layers"])
    return sum(k == "linear_attention" for k in cfg["layer_types"][:n])


def state_bytes_per_lane(cfg: dict) -> int:
    """One lane's recurrent state over all linear-attention layers: the
    float32 matrix state and the convolution's carried rows."""
    heads, dk, dv = (int(cfg["linear_num_key_heads"]),
                     int(cfg["linear_key_head_dim"]),
                     int(cfg["linear_value_head_dim"]))
    rows = int(cfg["linear_conv_kernel_dim"]) - 1
    per_layer = heads * dv * dk * 4 \
        + rows * heads * (2 * dk + dv) * _ITEMSIZE[cfg["dtype"]]
    return linear_layers(cfg) * per_layer


def matmul_params(cfg: dict) -> int:
    """Weights of the layers' matrices and of the head (what a token is
    multiplied by; the token table is looked up, not multiplied)."""
    h, inter = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    heads, dk, dv = (int(cfg["linear_num_key_heads"]),
                     int(cfg["linear_key_head_dim"]),
                     int(cfg["linear_value_head_dim"]))
    n, n_lin = int(cfg["num_hidden_layers"]), linear_layers(cfg)
    lin = h * heads * (2 * dk + 2 * dv + 2) + heads * dv * h
    full = 4 * h * h
    return (n_lin * lin + (n - n_lin) * full + n * 3 * h * inter
            + h * int(cfg["vocab_size"]))


def least_seconds(cfg: dict, weight_bytes: float, kv_bytes_per_token: float,
                  live_tokens: float, active_lanes: float,
                  peaks: dict) -> dict:
    item = _ITEMSIZE[cfg["dtype"]]
    table = int(cfg["vocab_size"]) * int(cfg["hidden_size"]) * item
    b = (weight_bytes - table + active_lanes * int(cfg["hidden_size"]) * item
         + kv_bytes_per_token * live_tokens
         + 2.0 * active_lanes * state_bytes_per_lane(cfg))
    f = 2.0 * matmul_params(cfg) * active_lanes
    by_bytes = b / peaks["hbm_bytes_per_s"]
    by_flops = f / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
            "bytes": b, "flops": f}
