"""Operations a prefill over ``positions`` positions needs in a hybrid
(linear + full attention) model, from the configuration file: 2 per matmul
weight per position (the head once, for the last position); the chunkwise
gated delta rule of every linear-attention layer; the causal half of the
attention matrix of every full-attention layer. The masked half of the
attention matrix and anything recomputed are not counted. A traced prefill
counts at its bucket (:func:`traced_prefills`): the chip computes the padded
positions like the real ones, and the trace says how many there were."""
import re
from collections import Counter

from benchmark.harness import trace as T
from benchmark.roofline import hybrid_decode_step as D

CHUNK = 64  # paddle_tpu/ops/gated_delta.py


def gated_delta_flops_per_token(cfg: dict, chunk: int = CHUNK) -> float:
    """One linear-attention layer, per token, chunk ``C``: K K^T and Q K^T
    (2 C dk each), the unit-triangular solve with dk + dv right-hand sides
    (C (dk + dv)), W S, (G q) S and U^T (decay K) (2 dk dv each), (Q K^T) U
    (2 C dv), and the width-K convolution."""
    heads, dk, dv = (int(cfg["linear_num_key_heads"]),
                     int(cfg["linear_key_head_dim"]),
                     int(cfg["linear_value_head_dim"]))
    per_head = (4 * chunk * dk + chunk * (dk + dv) + 6 * dk * dv
                + 2 * chunk * dv)
    conv = 2 * int(cfg["linear_conv_kernel_dim"]) * heads * (2 * dk + dv)
    return heads * per_head + conv


def flops(cfg: dict, positions: float) -> float:
    """One prefill over ``positions`` positions."""
    h, vocab = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    n, n_lin = int(cfg["num_hidden_layers"]), D.linear_layers(cfg)
    body = D.matmul_params(cfg) - h * vocab
    per_position = 2.0 * body + n_lin * gated_delta_flops_per_token(cfg)
    causal = (n - n_lin) * 2.0 * h * positions * positions  # sum_t 4 h t
    return positions * per_position + causal + 2.0 * h * vocab


_SHAPE = re.compile(r"\[(\d+),(\d+)\]")


def bucket_of(ops, start: float, end: float, hidden: int):
    """The positions one traced ``jit_prefill`` computed: an operation's
    event is named by its HLO text, and the program's activations are
    ``[positions, hidden]``, so the commonest such shape among the
    operations inside the program's interval is its bucket."""
    seen = Counter()
    for name, s, d in ops:
        if s >= start and s + d <= end:
            seen.update(int(a) for a, b in _SHAPE.findall(name)
                        if int(b) == hidden)
    return seen.most_common(1)[0][0] if seen else None


def traced_prefills(run):
    """``[(positions, device seconds)]`` of the ``jit_prefill`` executions
    that lie wholly inside the traced stretch, or None. Positions are the
    program's bucket, read off the trace itself (one program a bucket, so
    once per fingerprint): what the chip computed, padding included. The
    trace holds no true prompt lengths."""
    tr = run.get("trace")
    if tr is None:
        return None
    hidden = int(run["cell"].config["hidden_size"])
    lo, hi = tr.window
    buckets, out = {}, []
    for dev in tr.devices.values():
        for name, s, d in dev["modules"]:
            if T.module_name(name) != "jit_prefill" or s < lo or s + d > hi:
                continue
            if name not in buckets:
                buckets[name] = bucket_of(dev["ops"], s, s + d, hidden)
            if buckets[name]:
                out.append((buckets[name], d))
    return out or None
