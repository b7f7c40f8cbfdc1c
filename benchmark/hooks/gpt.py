"""The program side of the ``gpt`` model hook: build
``paddle_tpu.models.gpt.GPTForCausalLM`` at a configuration file's sizes and
fill it with the benchmark's seeded weights (``benchmark/weights/gpt.py``).

A configuration file names its hook (``"hooks": {"model": ...}``); another
architecture brings a file like this one beside its weights and its
reference, and no edit to the harness.
"""
from __future__ import annotations

from benchmark.weights import gpt as W

#: program parameter name (under ``gpt.layers.<i>.``) -> weights leaf
_LAYER_NAMES = {
    "ln1.weight": "ln1_w", "ln1.bias": "ln1_b",
    "attn.qkv.weight": "qkv_w", "attn.qkv.bias": "qkv_b",
    "attn.proj.weight": "proj_w", "attn.proj.bias": "proj_b",
    "ln2.weight": "ln2_w", "ln2.bias": "ln2_b",
    "mlp.up.weight": "up_w", "mlp.up.bias": "up_b",
    "mlp.down.weight": "down_w", "mlp.down.bias": "down_b",
}
_TOP_NAMES = {"gpt.wte.weight": ("embed", "wte"),
              "gpt.wpe.weight": ("embed", "wpe"),
              "gpt.ln_f.weight": ("final", "lnf_w"),
              "gpt.ln_f.bias": ("final", "lnf_b")}


def leaf_of(name: str):
    """Program parameter name -> (group, layer index or None, leaf): the
    address of the same numbers in the weights module and the reference."""
    if name in _TOP_NAMES:
        group, leaf = _TOP_NAMES[name]
        return group, None, leaf
    prefix = "gpt.layers."
    if not name.startswith(prefix):
        raise KeyError(f"no seeded weight for parameter {name!r}")
    index, _, rest = name[len(prefix):].partition(".")
    return "layers", int(index), _LAYER_NAMES[rest]


def build_model(model_cfg: dict, seed: int, dtype: str, train: bool):
    """The program's model at ``model_cfg`` sizes, every parameter set from
    the seed in ``dtype``. Construction runs with constant initialisers
    (its values are overwritten), so no random initialiser is compiled."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.nn import initializer

    gcfg = GPTConfig(
        vocab_size=model_cfg["vocab_size"],
        hidden_size=model_cfg["hidden_size"],
        num_layers=model_cfg["num_layers"],
        num_heads=model_cfg["num_heads"],
        max_position_embeddings=model_cfg["max_position_embeddings"],
        intermediate_size=model_cfg["intermediate_size"],
        layer_norm_epsilon=model_cfg["layer_norm_epsilon"])
    zero = initializer.Constant(0.0)
    initializer.set_global_initializer(zero, zero)
    try:
        model = GPTForCausalLM(gcfg)
    finally:
        initializer.set_global_initializer(None, None)
    if train:
        model.train()
    else:
        model.eval()
    if dtype != "float32":
        model.to(dtype=dtype)
    groups = {"embed": W.embed(seed, model_cfg, dtype),
              "final": W.final(seed, model_cfg, dtype)}
    layers = {}
    for name, p in model.named_parameters():
        group, index, leaf = leaf_of(name)
        if group == "layers":
            if index not in layers:
                layers[index] = W.layer(seed, index, model_cfg, dtype)
            value = layers[index][leaf]
        else:
            value = groups[group][leaf]
        if tuple(value.shape) != tuple(p.shape):
            raise ValueError(f"{name}: program {tuple(p.shape)}, seeded "
                             f"weights {tuple(value.shape)}")
        p.set_value(value)
    return model
