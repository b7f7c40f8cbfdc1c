"""The program side of the ``phi4flash`` model hook: build
``paddle_tpu.models.phi4flash.Phi4FlashForCausalLM`` at a configuration
file's sizes and fill it with the benchmark's seeded weights
(``benchmark/weights/phi4flash.py``).
"""
from __future__ import annotations

from benchmark.weights import phi4flash as W

#: program parameter name (under ``model.layers.<i>.``) -> weights leaf
_LAYER_NAMES = {
    "input_layernorm.weight": "ln1_w", "input_layernorm.bias": "ln1_b",
    "post_attention_layernorm.weight": "ln2_w",
    "post_attention_layernorm.bias": "ln2_b",
    "mlp.gate_up_proj.weight": "up_w", "mlp.down_proj.weight": "down_w",
    # Mamba, and the gated memory unit's two matrices
    "mixer.in_proj.weight": "in_w", "mixer.out_proj.weight": "out_w",
    "mixer.conv_w": "conv_w", "mixer.conv_b": "conv_b",
    "mixer.x_proj.weight": "x_w", "mixer.dt_proj.weight": "dt_w",
    "mixer.dt_proj.bias": "dt_b", "mixer.A_log": "A_log", "mixer.D": "D",
    # attention
    "mixer.q_proj.weight": "q_w", "mixer.q_proj.bias": "q_b",
    "mixer.kv_proj.weight": "kv_w", "mixer.kv_proj.bias": "kv_b",
    "mixer.o_proj.weight": "o_w", "mixer.o_proj.bias": "o_b",
    "mixer.lambda_q1": "lq1", "mixer.lambda_k1": "lk1",
    "mixer.lambda_q2": "lq2", "mixer.lambda_k2": "lk2",
    "mixer.subln": "subln",
}
_TOP_NAMES = {"model.embed_tokens.weight": ("embed", "embed"),
              "model.final_layernorm.weight": ("final", "norm_w"),
              "model.final_layernorm.bias": ("final", "norm_b")}


def leaf_of(name: str):
    """Program parameter name -> (group, layer index or None, leaf): the
    address of the same numbers in the weights module and the reference."""
    if name in _TOP_NAMES:
        group, leaf = _TOP_NAMES[name]
        return group, None, leaf
    prefix = "model.layers."
    if not name.startswith(prefix):
        raise KeyError(f"no seeded weight for parameter {name!r}")
    index, _, rest = name[len(prefix):].partition(".")
    return "layers", int(index), _LAYER_NAMES[rest]


def model_config(model_cfg: dict):
    from paddle_tpu.models.phi4flash import Phi4FlashConfig

    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "max_position_embeddings",
            "layer_norm_eps", "sliding_window", "mb_per_layer")
    s = W.sizes(model_cfg)
    return Phi4FlashConfig(
        mamba_d_state=s["d_state"], mamba_d_conv=s["d_conv"],
        mamba_expand=s["d_inner"] // s["hidden_size"],
        mamba_dt_rank=s["dt_rank"], **{k: model_cfg[k] for k in keys})


def build_model(model_cfg: dict, seed: int, dtype: str, train: bool):
    """The program's model at ``model_cfg`` sizes, every parameter set from
    the seed in ``dtype``. Construction fills the matrices with zeros made
    IN ``dtype`` (their values are overwritten): at these widths a float32
    copy of the weights would not fit on the chip."""
    import jax.numpy as jnp

    from paddle_tpu.models.phi4flash import Phi4FlashForCausalLM
    from paddle_tpu.nn import initializer

    if train:
        raise ValueError("the phi4flash hook builds the served model only")

    class ZerosInDtype(initializer.Initializer):
        def __call__(self, shape, _dtype):
            return jnp.zeros(tuple(shape), dtype)

    initializer.set_global_initializer(ZerosInDtype(), ZerosInDtype())
    try:
        model = Phi4FlashForCausalLM(model_config(model_cfg))
    finally:
        initializer.set_global_initializer(None, None)
    model.eval()
    if dtype != "float32":
        model.to(dtype=dtype)  # what a layer initialises itself (norms)
    groups = {"embed": W.embed(seed, model_cfg, dtype),
              "final": W.final(seed, model_cfg, dtype)}
    index_now, layer_now = None, None
    for name, p in model.named_parameters():
        group, index, leaf = leaf_of(name)
        if group == "layers":
            if index != index_now:  # one layer's leaves at a time
                index_now = index
                layer_now = W.layer(seed, index, model_cfg, dtype)
            value = layer_now[leaf]
        else:
            value = groups[group][leaf]
        if tuple(value.shape) != tuple(p.shape) \
                or str(value.dtype) != str(p._data.dtype):
            raise ValueError(
                f"{name}: program {tuple(p.shape)} {p._data.dtype}, seeded "
                f"weights {tuple(value.shape)} {value.dtype}")
        p.set_value(value)
    return model
