"""The program side of the ``trinity`` model hook: build
``paddle_tpu.models.trinity.TrinityForCausalLM`` at a configuration file's
sizes and fill it with the benchmark's seeded weights
(``benchmark/weights/trinity.py``).
"""
from __future__ import annotations

from benchmark.hooks.xing4 import _on_int8_grid
from benchmark.weights import trinity as W

#: program parameter name (under a decoder layer) -> weights leaf
_LAYER_NAMES = {
    "mlp.up.weight": "up", "mlp.down.weight": "down",          # dense
    "mlp.router": "router", "mlp.e_bias": "e_bias",            # experts
    "mlp.e_up": "e_up", "mlp.e_down": "e_down",
    "mlp.shared.up.weight": "s_up", "mlp.shared.down.weight": "s_down",
}
_LAYER_NAMES.update({n: n for n in ("input_norm", "post_attn_norm",
                                    "pre_mlp_norm", "post_mlp_norm")})
_LAYER_NAMES.update({f"attn.{n}": n for n in ("q_norm", "k_norm")})
_LAYER_NAMES.update({f"attn.{n}.weight": n for n in (
    "q_proj", "k_proj", "v_proj", "gate_proj", "o_proj")})
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
    """The program's configuration: every ``TrinityConfig`` field the file
    has, with the share read as the weights module reads it: the file's
    ``num_experts`` is what this chip HOLDS (``expert_count``, from
    ``expert_first`` on) of ``published.num_experts``, which is what the
    router routes over."""
    from paddle_tpu.models.trinity import TrinityConfig

    sz = W.sizes(model_cfg)
    keys = [k for k in TrinityConfig.__dataclass_fields__ if k in model_cfg]
    kw = {k: model_cfg[k] for k in keys}
    kw.update(num_experts=sz["routed"], expert_first=sz["first"],
              expert_count=sz["held"])
    return TrinityConfig(**kw)


def build_model(model_cfg: dict, seed: int, dtype: str, train: bool):
    """The program's model at ``model_cfg`` sizes, every parameter set from
    the seed in ``dtype`` (the router's selection bias stays float32).
    Construction fills the matrices with zeros made IN ``dtype`` (their
    values are overwritten): at these widths a float32 copy of the weights
    would not fit on the chip. ``model_cfg["expert_weights"] ==
    "int8_grid"`` (the control's) rounds the held experts as
    ``hooks/xing4.py:_on_int8_grid`` says (the product has no int8 grouped
    matmul, so the control rounds the experts' values here)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.trinity import TrinityForCausalLM
    from paddle_tpu.nn import initializer

    if train:
        raise ValueError("the trinity hook builds the served model only")

    class ZerosInDtype(initializer.Initializer):
        def __call__(self, shape, _dtype):
            return jnp.zeros(tuple(shape), dtype)

    cfg = model_config(model_cfg)
    how = model_cfg.get("expert_weights", "as_drawn")
    if how not in ("as_drawn", "int8_grid"):
        raise ValueError("expert_weights is 'as_drawn' or 'int8_grid'")
    # the fit runs whole layers in float32: before the model takes the chip
    W.selection_biases(seed, model_cfg, dtype)
    initializer.set_global_initializer(ZerosInDtype(), ZerosInDtype())
    try:
        model = TrinityForCausalLM(cfg)
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
        p._data = value  # in the weights module's dtype (some are float32)
    return model
