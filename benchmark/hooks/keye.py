"""The program side of the ``keye`` model hook: build
``paddle_tpu.models.keye.KeyeForCausalLM`` at a configuration file's sizes
and fill it with the benchmark's seeded weights
(``benchmark/weights/keye.py``).
"""
from __future__ import annotations

from benchmark.hooks.xing4 import _on_int8_grid
from benchmark.weights import keye as W

#: program parameter name (under a decoder layer) -> weights leaf
_LAYER_NAMES = {"mlp.router": "router", "mlp.e_up": "e_up",
                "mlp.e_down": "e_down", "attn.iw": "iw"}
_LAYER_NAMES.update({n: n for n in ("input_norm", "post_attn_norm")})
_LAYER_NAMES.update({f"attn.{n}": n for n in (
    "q_norm", "k_norm", "ik_norm", "ik_bias")})
_LAYER_NAMES.update({f"attn.{n}.weight": n for n in (
    "q_proj", "k_proj", "v_proj", "o_proj", "iq_proj", "ik_proj")})
_TOP_NAMES = {"model.embed_tokens.weight": ("embed", "embed"),
              "model.norm": ("final", "norm"),
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
    """The program's configuration: every ``KeyeConfig`` field the file
    has, with the share read as the weights module reads it: the file's
    ``num_experts`` is what this chip HOLDS (``expert_count``, from
    ``expert_first`` on) of ``published.num_experts``, which is what the
    router routes over."""
    from paddle_tpu.models.keye import KeyeConfig

    sz = W.sizes(model_cfg)
    keys = [k for k in KeyeConfig.__dataclass_fields__ if k in model_cfg]
    kw = {k: model_cfg[k] for k in keys}
    kw.update(num_experts=sz["routed"], expert_first=sz["first"],
              expert_count=sz["held"])
    return KeyeConfig(**kw)


def build_model(model_cfg: dict, seed: int, dtype: str, train: bool):
    """The program's model at ``model_cfg`` sizes, every parameter set from
    the seed in ``dtype``. Construction fills the matrices with zeros made
    IN ``dtype`` (their values are overwritten): at these widths a float32
    copy of the weights would not fit beside the arena.
    ``model_cfg["expert_weights"] == "int8_grid"`` (the control's) rounds
    the held experts as ``hooks/xing4.py:_on_int8_grid`` says (the product
    has no int8 grouped matmul, so the control rounds the experts' values
    here)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.keye import KeyeForCausalLM
    from paddle_tpu.nn import initializer

    if train:
        raise ValueError("the keye hook builds the served model only")

    class ZerosInDtype(initializer.Initializer):
        def __call__(self, shape, _dtype):
            return jnp.zeros(tuple(shape), dtype)

    cfg = model_config(model_cfg)
    how = model_cfg.get("expert_weights", "as_drawn")
    if how not in ("as_drawn", "int8_grid"):
        raise ValueError("expert_weights is 'as_drawn' or 'int8_grid'")
    initializer.set_global_initializer(ZerosInDtype(), ZerosInDtype())
    try:
        model = KeyeForCausalLM(cfg)
    finally:
        initializer.set_global_initializer(None, None)
    model.eval()
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
        if leaf in ("e_up", "e_down") and how == "int8_grid":
            value = jax.jit(_on_int8_grid)(value)
        if tuple(value.shape) != tuple(p.shape):
            raise ValueError(f"{name}: program {tuple(p.shape)}, seeded "
                             f"weights {tuple(value.shape)}")
        p._data = value
    return model
