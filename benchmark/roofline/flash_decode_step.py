"""The least a decode step of the decoder-hybrid-decoder model
(Phi-4-mini-flash: Mamba layers, window attention, one paged full-attention
cache that the cross-attention layers read too) must do, from sizes read off
the program and the configuration file.

Bytes: every weight once (the head is tied to the token table, so the table
is read whole, as the head); the live context's K and V of the ONE paged
layer once for every layer that reads it (the full-attention layer and each
cross-attention layer: ``readers``); each active lane's window rings, as far
as they are filled, once for every window layer; each active lane's Mamba
state read AND written once. Operations: 2 per matmul weight per active
lane. At 32 lanes the bytes bound it by far; the function says which."""

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_counts(cfg: dict) -> dict:
    """How many layers of each kind ``num_hidden_layers`` gives (the table
    at the head of ``benchmark/reference/phi4flash.py``)."""
    n = int(cfg["num_hidden_layers"])
    half = n // 2
    return {"mamba": half // 2 + 1, "window": half // 2, "full": 1,
            "gmu": (n - half) // 2 - 1, "cross": (n - half) // 2 - 1}


def sizes(cfg: dict) -> dict:
    """The widths the counts below need; the Mamba sizes the configuration
    does not state are where the weights are made
    (``benchmark/weights/phi4flash.py``)."""
    from benchmark.weights.phi4flash import sizes as widths

    w = widths(cfg)
    return {"h": w["hidden_size"], "inter": w["intermediate_size"],
            "di": w["d_inner"], "n": w["d_state"], "k": w["d_conv"],
            "r": w["dt_rank"],
            "kv": w["num_key_value_heads"] * w["head_dim"],
            "vocab": w["vocab_size"], "window": w["sliding_window"]}


def mixer_params(cfg: dict) -> dict:
    """Matmul weights of one mixer of each kind, and of one MLP."""
    s = sizes(cfg)
    h, di = s["h"], s["di"]
    return {"mamba": h * 2 * di + di * (s["r"] + 2 * s["n"]) + s["r"] * di
            + di * h,
            "window": 2 * h * h + h * 2 * s["kv"],
            "full": 2 * h * h + h * 2 * s["kv"],
            "gmu": 2 * h * di, "cross": 2 * h * h,
            "mlp": 3 * h * s["inter"]}


def matmul_params(cfg: dict) -> int:
    """Weights a decoded token is multiplied by: every layer's mixer and
    MLP, and the head."""
    s, c, m = sizes(cfg), layer_counts(cfg), mixer_params(cfg)
    return (sum(c[k] * (m[k] + m["mlp"]) for k in c)
            + s["h"] * s["vocab"])


def kv_row_bytes(cfg: dict) -> int:
    """K and V of one token of one attention layer."""
    return 2 * sizes(cfg)["kv"] * _ITEMSIZE[cfg["dtype"]]


def ssm_bytes_per_lane(cfg: dict) -> int:
    s = sizes(cfg)
    return layer_counts(cfg)["mamba"] * (
        s["di"] * s["n"] * 4
        + (s["k"] - 1) * s["di"] * _ITEMSIZE[cfg["dtype"]])


def least_seconds(cfg: dict, weight_bytes: float, kv_bytes_per_token: float,
                  live_tokens: float, active_lanes: float,
                  peaks: dict) -> dict:
    c, s = layer_counts(cfg), sizes(cfg)
    readers = c["full"] + c["cross"]
    per_lane = live_tokens / active_lanes if active_lanes else 0.0
    windows = (active_lanes * c["window"] * min(per_lane, s["window"])
               * kv_row_bytes(cfg))
    b = (weight_bytes + readers * kv_bytes_per_token * live_tokens
         + windows + 2.0 * active_lanes * ssm_bytes_per_lane(cfg))
    f = 2.0 * matmul_params(cfg) * active_lanes
    by_bytes = b / peaks["hbm_bytes_per_s"]
    by_flops = f / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
            "bytes": b, "flops": f, "readers": readers}
