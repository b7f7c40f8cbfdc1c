"""What the latent-attention expert model (Xing4.0) NEEDS, from the
configuration file: parameters a token is multiplied by, operations of a
prefill, the least seconds of a decode step and of its two kernels. The
masked half of an attention matrix, an expert a token was not routed to and
anything recomputed are not counted."""
import re

from benchmark.harness import trace as T

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def sizes(cfg: dict) -> dict:
    g = lambda k: int(cfg[k])
    layers, dense = g("num_hidden_layers"), g("first_k_dense_replace")
    return {"h": g("hidden_size"), "heads": g("num_attention_heads"),
            "nope": g("qk_nope_head_dim"), "rope": g("qk_rope_head_dim"),
            "vd": g("v_head_dim"), "kvr": g("kv_lora_rank"),
            "qr": g("q_lora_rank"), "dense_w": g("intermediate_size"),
            "expert_w": g("moe_intermediate_size"),
            "experts": g("n_routed_experts"), "k": g("num_experts_per_tok"),
            "layers": layers, "dense": dense, "expert": layers - dense,
            "n": g("hc_mult"), "vocab": g("vocab_size")}


def params(cfg: dict) -> dict:
    """Matmul parameters: of one attention sublayer, one mixer pair, one
    dense MLP, one expert, the router."""
    s = sizes(cfg)
    h, heads = s["h"], s["heads"]
    return {
        "attention": (h * s["qr"] + s["qr"] * heads * (s["nope"] + s["rope"])
                      + h * (s["kvr"] + s["rope"])
                      + s["kvr"] * heads * (s["nope"] + s["vd"])
                      + heads * s["vd"] * h),
        "mixers": 2 * s["n"] * h * (2 * s["n"] + s["n"] ** 2),
        "dense_mlp": 3 * h * s["dense_w"],
        "expert": 3 * h * s["expert_w"],
        "router": h * s["experts"]}


def active_params_per_token(cfg: dict) -> float:
    """What one token is multiplied by, the head not counted: every layer's
    attention and mixers, the dense MLP, and in an expert layer the router,
    its ``k`` chosen experts and the shared one."""
    s, p = sizes(cfg), params(cfg)
    return (s["layers"] * (p["attention"] + p["mixers"])
            + s["dense"] * p["dense_mlp"]
            + s["expert"] * (p["router"] + (s["k"] + 1) * p["expert"]))


def attention_flops_per_key(cfg: dict) -> float:
    """One query row against one key, all heads, expanded: scores over 192,
    values over 128."""
    s = sizes(cfg)
    return 2.0 * s["heads"] * (s["nope"] + s["rope"] + s["vd"])


def prefill_flops(cfg: dict, positions: float) -> float:
    s = sizes(cfg)
    causal = positions * (positions + 1) / 2.0
    return (2.0 * active_params_per_token(cfg) * positions
            + s["layers"] * attention_flops_per_key(cfg) * causal
            + 2.0 * s["h"] * s["vocab"])


def row_bytes(cfg: dict) -> int:
    """One token's latent row in one layer."""
    s = sizes(cfg)
    return (s["kvr"] + s["rope"]) * _ITEMSIZE[cfg["dtype"]]


def latent_decode_least(cfg: dict, kv_bytes_per_token: float,
                        live_tokens: float, peaks: dict) -> dict:
    """The latent decode kernel, all layers, one step: the live rows read
    once, or the absorbed scores (576 wide) and values (512 wide) of every
    head over them at the bf16 peak if that is longer."""
    s = sizes(cfg)
    b = kv_bytes_per_token * live_tokens
    f = s["layers"] * 2.0 * s["heads"] * live_tokens \
        * (2 * s["kvr"] + s["rope"])
    by_bytes = b / peaks["hbm_bytes_per_s"]
    by_flops = f / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops), "bytes": b, "flops": f,
            "bound": "memory" if by_bytes >= by_flops else "compute"}


def expert_ffn_least(cfg: dict, experts_touched: float,
                     peaks: dict) -> dict:
    """The grouped matmuls of one decode step: the weights of the experts
    a token reached (counted over all its expert layers) read once."""
    b = experts_touched * params(cfg)["expert"] * _ITEMSIZE[cfg["dtype"]]
    return {"seconds": b / peaks["hbm_bytes_per_s"], "bytes": b,
            "bound": "memory"}


def decode_step_least(cfg: dict, weight_bytes: float,
                      kv_bytes_per_token: float, live_tokens: float,
                      active_lanes: float, experts_touched: float,
                      peaks: dict) -> dict:
    """One decode step: every weight but the token table (a step gathers
    one row a lane of it) and the experts no token reached
    (``experts_touched``: the step's count over all its expert layers), and
    the live rows, each once; or the active lanes' matmuls at the peak if
    that is longer."""
    s = sizes(cfg)
    item = _ITEMSIZE[cfg["dtype"]]
    idle = s["expert"] * s["experts"] - experts_touched
    b = (weight_bytes - s["vocab"] * s["h"] * item
         - idle * params(cfg)["expert"] * item
         + kv_bytes_per_token * live_tokens)
    f = 2.0 * (active_params_per_token(cfg) + s["h"] * s["vocab"]) \
        * active_lanes
    by_bytes = b / peaks["hbm_bytes_per_s"]
    by_flops = f / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops), "bytes": b, "flops": f,
            "bound": "memory" if by_bytes >= by_flops else "compute"}


def experts_touched_per_step(run):
    """Experts that got a token, summed over a step's expert layers: the
    window's mean (None where the program counts none)."""
    c = run["counters"]
    if not c.get("moe.layer_steps"):
        return None
    return sizes(run["cell"].config)["expert"] \
        * c.get("moe.experts_touched", 0) / c["moe.layer_steps"]


# ------------------------------------------------------ reading the trace

_FLASH = re.compile(r"\[\d+,(\d+),\d+\]")


def _inside(tr, module: str):
    """``[(device, name, start, end)]`` of a program's executions that lie
    wholly inside the traced stretch."""
    lo, hi = tr.window
    return [(dev, n, s, s + d) for dev in tr.devices.values()
            for n, s, d in dev["modules"]
            if T.module_name(n) == module and s >= lo and s + d <= hi]


def bucket_of(ops, start: float, end: float):
    """The positions a traced prefill computed: its attention is the flash
    kernel the program names ``latent_prefill_flash``, whose result is
    ``[heads, positions, 128]``."""
    for name, s, d in ops:
        if s >= start and s + d <= end and \
                "latent_prefill_flash" in name.split(" = ", 1)[0]:
            m = _FLASH.search(name.split(" = ", 1)[1])
            if m:
                return int(m.group(1))
    return None


def traced_prefills(run):
    """``[(positions, device seconds)]`` of the traced stretch's
    ``jit_prefill`` executions, or None (one program a bucket, so the
    bucket is worked out once per fingerprint)."""
    tr = run.get("trace")
    if tr is None:
        return None
    buckets, out = {}, []
    for dev, name, start, end in _inside(tr, "jit_prefill"):
        if name not in buckets:
            buckets[name] = bucket_of(dev["ops"], start, end)
        if buckets[name]:
            out.append((buckets[name], end - start))
    return out or None


def step_op_seconds(run, match):
    """``(decode steps in the traced stretch, device seconds inside them of
    the operations whose HLO instruction name ``match`` accepts)``."""
    tr = run.get("trace")
    if tr is None:
        return 0, 0.0
    steps = _inside(tr, "jit_step")
    total = 0.0
    for dev in tr.devices.values():
        spans = sorted((s, e) for d, _, s, e in steps if d is dev)
        if not spans:
            continue
        i = 0
        for name, s, d in sorted(dev["ops"], key=lambda o: o[1]):
            while i < len(spans) and spans[i][1] < s:
                i += 1
            if i == len(spans):
                break
            if s >= spans[i][0] and s + d <= spans[i][1] \
                    and match(name.split(" = ", 1)[0]):
                total += d
    return len(steps), total


def live(run):
    """``(live tokens, active lanes)``: means of the once-a-second polls."""
    rows = [r for r in run["polls"] if r.get("arena.blocks_total")]
    if not rows:
        return None
    used = sum(r["arena.blocks_total"] - r["arena.blocks_free"]
               for r in rows) / len(rows)
    lanes = sum(r["slots.active"] or 0 for r in rows) / len(rows)
    return used * run["program"]["block_size"], lanes
