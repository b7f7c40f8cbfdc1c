"""Operations a prefill over ``positions`` positions NEEDS in the
decoder-hybrid-decoder model (Phi-4-mini-flash), from the configuration
file. The layers up to the full-attention one's K/V projection run on every
position: 2 per matmul weight per position, the scan's elementwise update of
every (channel, state) pair, and each query's window of keys in the window
layers. Everything after (the rest of the full-attention layer, the gated
memory units, the cross-attention layers, the head) is needed for the last
position alone: 2 per matmul weight once, and one query over ``positions``
keys in each layer that reads the paged cache. The masked half of an
attention matrix, the zero halves of the padded differential queries and
anything recomputed are not counted. A traced prefill counts at its bucket
(``hybrid_prefill.traced_prefills``: the chip computes the padded positions
like the real ones, and the trace says how many there were)."""
from benchmark.roofline import flash_decode_step as D


def attention_flops_per_key(cfg: dict) -> float:
    """One query row against one key, all heads, both softmaxes: scores
    over ``heads`` heads of width ``hd``, values ``2 hd`` wide."""
    heads = int(cfg["num_attention_heads"])
    hd = int(cfg["hidden_size"]) // heads
    return 2.0 * heads * hd + 2.0 * heads * 2 * hd


def scan_flops_per_token(cfg: dict) -> float:
    """One Mamba layer, one token: decay (multiply, exp), update (two
    multiplies, add) and read-out (multiply, add) of every (channel, state)
    pair, and the width-K convolution."""
    s = D.sizes(cfg)
    return 7.0 * s["di"] * s["n"] + 2.0 * s["k"] * s["di"]


def flops(cfg: dict, positions: float) -> dict:
    """``{"body", "tail"}``: one prefill over ``positions`` positions."""
    c, m, s = D.layer_counts(cfg), D.mixer_params(cfg), D.sizes(cfg)
    per_key = attention_flops_per_key(cfg)
    kv_proj = s["h"] * 2 * s["kv"]
    body_weights = (c["mamba"] * (m["mamba"] + m["mlp"])
                    + c["window"] * (m["window"] + m["mlp"]) + kv_proj)
    w = s["window"]
    full = min(positions, w)
    # sum over t of min(t + 1, window)
    windowed = full * (full + 1) / 2.0 + max(positions - w, 0) * w
    body = positions * (2.0 * body_weights
                        + c["mamba"] * scan_flops_per_token(cfg)) \
        + c["window"] * per_key * windowed
    tail_weights = (m["full"] - kv_proj + m["mlp"]
                    + c["gmu"] * (m["gmu"] + m["mlp"])
                    + c["cross"] * (m["cross"] + m["mlp"])
                    + s["h"] * s["vocab"])
    tail = 2.0 * tail_weights \
        + (c["full"] + c["cross"]) * per_key * positions
    return {"body": body, "tail": tail}
