"""What the shortcut-connected expert model (LongCat-Flash) NEEDS on this
chip, from THIS configuration's keys: parameters a token is multiplied by
(two latent attentions and two dense MLPs a layer, the router over routed
and zero-compute columns, the HELD experts a token was routed to), the
operations of a prefill, the least seconds of a decode step and of its two
kernels. The masked half of an attention matrix, an expert that is not held
here or that no token reached, a zero-compute pick's weight read (it has
none) and anything recomputed are not counted. The trace's readers that know
no configuration are ``roofline/latent_moe.py``'s, imported."""
from benchmark.roofline.latent_moe import (_ITEMSIZE, live,  # noqa: F401
                                           step_op_seconds, traced_prefills)


def sizes(cfg: dict) -> dict:
    g = lambda k: int(cfg[k])
    held = g("n_routed_experts")
    return {"h": g("hidden_size"), "heads": g("num_attention_heads"),
            "nope": g("qk_nope_head_dim"), "rope": g("qk_rope_head_dim"),
            "vd": g("v_head_dim"), "kvr": g("kv_lora_rank"),
            "qr": g("q_lora_rank"), "dense_w": g("ffn_hidden_size"),
            "expert_w": g("expert_ffn_hidden_size"), "held": held,
            "routed": int(cfg.get("published", {}).get("n_routed_experts",
                                                       held)),
            "zero": g("zero_expert_num"), "k": g("moe_topk"),
            "layers": g("num_layers"), "vocab": g("vocab_size")}


def params(cfg: dict) -> dict:
    """Matmul parameters: of one latent attention, one dense MLP, one
    expert, the router."""
    s = sizes(cfg)
    h, heads = s["h"], s["heads"]
    return {
        "attention": (h * s["qr"] + s["qr"] * heads * (s["nope"] + s["rope"])
                      + h * (s["kvr"] + s["rope"])
                      + s["kvr"] * heads * (s["nope"] + s["vd"])
                      + heads * s["vd"] * h),
        "dense_mlp": 3 * h * s["dense_w"],
        "expert": 3 * h * s["expert_w"],
        "router": h * (s["routed"] + s["zero"])}


def routed_here(run):
    """``(held experts' picks a token a layer, zero-compute picks a token
    a layer)`` as the window's decode steps were routed, by the program's
    own counters; None where it counts none."""
    c = run["counters"]
    total = c.get("moe.assignments", 0)
    if not total:
        return None
    k = sizes(run["cell"].config)["k"]
    return (k * c.get("moe.local_assignments", 0) / total,
            k * c.get("moe.zero_assignments", 0) / total)


def active_params_per_token(cfg: dict, local_picks: float) -> float:
    """What one token is multiplied by here, the head not counted: every
    layer's two attentions and two dense MLPs, the router, and the held
    experts it was routed to (``local_picks`` a layer)."""
    s, p = sizes(cfg), params(cfg)
    return s["layers"] * (2 * p["attention"] + 2 * p["dense_mlp"]
                          + p["router"] + local_picks * p["expert"])


def attention_flops_per_key(cfg: dict) -> float:
    """One query row against one key, all heads, expanded: scores over 192,
    values over 128."""
    s = sizes(cfg)
    return 2.0 * s["heads"] * (s["nope"] + s["rope"] + s["vd"])


def prefill_flops(cfg: dict, positions: float, local_picks: float,
                  zero_picks: float) -> float:
    """A prefill of ``positions``: every position through what
    :func:`active_params_per_token` counts and its zero-compute picks (a
    multiply-add a value each), the causal half of both attentions of
    every layer, the head once."""
    s = sizes(cfg)
    causal = positions * (positions + 1) / 2.0
    return (2.0 * (active_params_per_token(cfg, local_picks)
                   + s["layers"] * zero_picks * s["h"]) * positions
            + 2 * s["layers"] * attention_flops_per_key(cfg) * causal
            + 2.0 * s["h"] * s["vocab"])


def latent_decode_least(cfg: dict, kv_bytes_per_token: float,
                        live_tokens: float, peaks: dict) -> dict:
    """The latent decode kernel, both calls of every layer, one step: the
    live rows read once, or the absorbed scores (576 wide) and values (512
    wide) of every head over them at the bf16 peak if that is longer."""
    s = sizes(cfg)
    b = kv_bytes_per_token * live_tokens
    f = 2 * s["layers"] * 2.0 * s["heads"] * live_tokens \
        * (2 * s["kvr"] + s["rope"])
    by_bytes = b / peaks["hbm_bytes_per_s"]
    by_flops = f / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops), "bytes": b, "flops": f,
            "bound": "memory" if by_bytes >= by_flops else "compute"}


def experts_touched_per_step(run):
    """Held experts that got a token, summed over a step's layers: the
    window's mean (None where the program counts none)."""
    c = run["counters"]
    if not c.get("moe.layer_steps"):
        return None
    return sizes(run["cell"].config)["layers"] \
        * c.get("moe.experts_touched", 0) / c["moe.layer_steps"]


def expert_ffn_least(cfg: dict, experts_touched: float,
                     peaks: dict) -> dict:
    """The grouped matmuls of one decode step: the weights of the held
    experts a token reached (counted over all layers) read once."""
    b = experts_touched * params(cfg)["expert"] * _ITEMSIZE[cfg["dtype"]]
    return {"seconds": b / peaks["hbm_bytes_per_s"], "bytes": b,
            "bound": "memory"}


def decode_step_least(cfg: dict, weight_bytes: float,
                      kv_bytes_per_token: float, live_tokens: float,
                      active_lanes: float, experts_touched: float,
                      local_picks: float, peaks: dict) -> dict:
    """One decode step: every weight but the token table (a step gathers
    one row a lane of it) and the held experts no token reached, and the
    live rows, each once; or the active lanes' matmuls at the peak if that
    is longer."""
    s = sizes(cfg)
    item = _ITEMSIZE[cfg["dtype"]]
    idle = s["layers"] * s["held"] - experts_touched
    b = (weight_bytes - s["vocab"] * s["h"] * item
         - idle * params(cfg)["expert"] * item
         + kv_bytes_per_token * live_tokens)
    f = 2.0 * (active_params_per_token(cfg, local_picks)
               + s["h"] * s["vocab"]) * active_lanes
    by_bytes = b / peaks["hbm_bytes_per_s"]
    by_flops = f / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops), "bytes": b, "flops": f,
            "bound": "memory" if by_bytes >= by_flops else "compute"}
