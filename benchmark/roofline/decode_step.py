"""The least a decode step must do, from sizes read off the program: every
weight once, the live context's K and V once (bytes), and 2 operations per
weight per active lane plus the attention over each lane's context
(operations). At 32 lanes the bytes bound it by far; the function says
which."""


def least_seconds(weight_bytes: float, kv_bytes_per_token: float,
                  live_tokens: float, active_lanes: float,
                  matmul_params: float, hidden: int, layers: int,
                  peaks: dict) -> dict:
    b = weight_bytes + kv_bytes_per_token * live_tokens
    f = 2.0 * matmul_params * active_lanes + 4.0 * layers * hidden * live_tokens
    by_bytes = b / peaks["hbm_bytes_per_s"]
    by_flops = f / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
            "bytes": b, "flops": f}
