"""The program side of the ``olmo_hybrid`` model hook: build
``paddle_tpu.models.olmo_hybrid.OlmoHybridForCausalLM`` at a configuration
file's sizes and fill it with the benchmark's seeded weights
(``benchmark/weights/olmo_hybrid.py``).
"""
from __future__ import annotations

from benchmark.weights import olmo_hybrid as W

#: program parameter name (under ``model.layers.<i>.``) -> weights leaf
_LAYER_NAMES = {
    "post_attention_layernorm.weight": "post_attn_norm",
    "post_feedforward_layernorm.weight": "post_ffn_norm",
    "mlp.gate_proj.weight": "gate_w", "mlp.up_proj.weight": "up_w",
    "mlp.down_proj.weight": "down_w",
    "mixer.q_proj.weight": "q_w", "mixer.k_proj.weight": "k_w",
    "mixer.v_proj.weight": "v_w", "mixer.o_proj.weight": "o_w",
    # full attention
    "mixer.q_norm.weight": "q_norm", "mixer.k_norm.weight": "k_norm",
    # linear attention
    "mixer.g_proj.weight": "g_w", "mixer.a_proj.weight": "a_w",
    "mixer.b_proj.weight": "b_w", "mixer.q_conv": "q_conv",
    "mixer.k_conv": "k_conv", "mixer.v_conv": "v_conv",
    "mixer.A_log": "A_log", "mixer.dt_bias": "dt_bias",
    "mixer.o_norm": "o_norm",
}
_TOP_NAMES = {"model.embed_tokens.weight": ("embed", "embed"),
              "model.norm.weight": ("final", "norm"),
              "lm_head.weight": ("final", "head")}


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
    from paddle_tpu.models.olmo_hybrid import OlmoHybridConfig

    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "max_position_embeddings", "rms_norm_eps",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "linear_allow_neg_eigval")
    return OlmoHybridConfig(layer_types=tuple(model_cfg["layer_types"]),
                            **{k: model_cfg[k] for k in keys})


def build_model(model_cfg: dict, seed: int, dtype: str, train: bool):
    """The program's model at ``model_cfg`` sizes, every parameter set from
    the seed in ``dtype``. Construction fills the matrices with zeros made
    IN ``dtype`` (their values are overwritten): at these widths a float32
    copy of the weights would not fit on the chip."""
    import jax.numpy as jnp

    from paddle_tpu.models.olmo_hybrid import OlmoHybridForCausalLM
    from paddle_tpu.nn import initializer

    if train:
        raise ValueError("the olmo_hybrid hook builds the served model only")

    class ZerosInDtype(initializer.Initializer):
        def __call__(self, shape, _dtype):
            return jnp.zeros(tuple(shape), dtype)

    initializer.set_global_initializer(ZerosInDtype(), ZerosInDtype())
    try:
        model = OlmoHybridForCausalLM(model_config(model_cfg))
    finally:
        initializer.set_global_initializer(None, None)
    model.eval()
    if dtype != "float32":
        model.to(dtype=dtype)  # what a layer initialises itself (norms, table)
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
