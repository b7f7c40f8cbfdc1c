"""Operations a training step needs, from shapes.

Copied from ``bench.py:model_flops_per_token`` (sound arithmetic, see
PERF.md's inventory): 6 x the matmul parameters (forward 2, backward 4)
plus causal attention, 6 L s h per token (QK^T and PV, forward and backward,
halved by the causal mask). Recomputed operations are not counted; the
embedding lookup, norms and the optimizer are not matmuls and are left out.
"""


def matmul_params(cfg: dict) -> int:
    h, L, V = cfg["hidden_size"], cfg["num_layers"], cfg["vocab_size"]
    i = cfg["intermediate_size"]
    return L * (4 * h * h + 2 * h * i) + h * V   # qkv+proj, mlp, tied head


def flops_per_token(cfg: dict, seq_len: int) -> float:
    attn = 6 * cfg["num_layers"] * seq_len * cfg["hidden_size"]
    return 6.0 * matmul_params(cfg) + attn
