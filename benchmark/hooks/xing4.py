"""The program side of the ``xing4`` model hook: build
``paddle_tpu.models.xing4.Xing4ForCausalLM`` at a configuration file's sizes
and fill it with the benchmark's seeded weights
(``benchmark/weights/xing4.py``).
"""
from __future__ import annotations

from benchmark.weights import xing4 as W

#: program parameter name (under a decoder layer) -> weights leaf
_LAYER_NAMES = {
    "attn_norm": "attn_norm", "mlp_norm": "mlp_norm",
    "attn.q_a.weight": "q_a", "attn.q_a_norm": "q_a_norm",
    "attn.q_b.weight": "q_b", "attn.kv_a.weight": "kv_a",
    "attn.kv_a_norm": "kv_a_norm", "attn.kv_b.weight": "kv_b",
    "attn.o.weight": "o",
    "mlp.up.weight": "up", "mlp.down.weight": "down",          # dense
    "mlp.router": "router", "mlp.e_bias": "e_bias",            # experts
    "mlp.e_up": "e_up", "mlp.e_down": "e_down",
    "mlp.shared.up.weight": "s_up", "mlp.shared.down.weight": "s_down",
}
for _hc, _leaf in (("hc_attn", "hca"), ("hc_mlp", "hcm")):
    _LAYER_NAMES.update({f"{_hc}.norm": f"{_leaf}_norm",
                         f"{_hc}.w": f"{_leaf}_w", f"{_hc}.b": f"{_leaf}_b",
                         f"{_hc}.a": f"{_leaf}_a"})
_TOP_NAMES = {"model.embed_tokens.weight": ("embed", "embed"),
              "model.norm": ("final", "norm"),
              "lm_head.weight": ("final", "head"),
              "mtp.hnorm": ("mtp", "hnorm"), "mtp.enorm": ("mtp", "enorm"),
              "mtp.proj.weight": ("mtp", "proj")}


def leaf_of(name: str):
    """Program parameter name -> (group, layer index or None, leaf): the
    address of the same numbers in the weights module and the reference."""
    if name in _TOP_NAMES:
        group, leaf = _TOP_NAMES[name]
        return group, None, leaf
    if name.startswith("mtp.block."):
        return "mtp", None, _LAYER_NAMES[name[len("mtp.block."):]]
    prefix = "model.layers."
    if not name.startswith(prefix):
        raise KeyError(f"no seeded weight for parameter {name!r}")
    index, _, rest = name[len(prefix):].partition(".")
    return "layers", int(index), _LAYER_NAMES[rest]


def model_config(model_cfg: dict):
    """The program's configuration: every ``Xing4Config`` field the file
    has (the experts this chip holds among them: ``expert_first``,
    ``expert_count``, ``shared_expert_here``; all of them where the file
    says nothing)."""
    from paddle_tpu.models.xing4 import Xing4Config

    keys = [k for k in Xing4Config.__dataclass_fields__ if k in model_cfg]
    return Xing4Config(**{k: model_cfg[k] for k in keys})


def _on_int8_grid(w):
    """``w`` ``[E, in, out]`` with every (expert, output channel) column
    rounded to the 255 levels int8 weight-only quantization would keep of
    it, in ``w``'s dtype: the VALUES an int8 expert would multiply by. The
    product has no int8 grouped matmul (``quant_weights`` converts the
    linears a model declares, ROADMAP A9), so the cell's control, which
    runs the whole model one precision lower, rounds the experts here."""
    import jax
    import jax.numpy as jnp

    def one(expert):  # [in, out]; one expert's float32 copy at a time
        w32 = expert.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w32), axis=0, keepdims=True) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        return (jnp.round(w32 / scale) * scale).astype(expert.dtype)

    return jax.lax.map(one, w)


def build_model(model_cfg: dict, seed: int, dtype: str, train: bool,
                with_mtp: bool = False):
    """The program's model at ``model_cfg`` sizes, every parameter set from
    the seed in ``dtype`` (the router's bias and the hyper-connections'
    mixers stay float32, as the weights module makes them). Construction
    fills the matrices with zeros made IN ``dtype`` (their values are
    overwritten): at these widths a float32 copy of the weights would not
    fit on the chip. ``model_cfg["expert_weights"] == "int8_grid"`` (the
    control's) rounds the stacked experts as :func:`_on_int8_grid` says."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.xing4 import Xing4ForCausalLM
    from paddle_tpu.nn import initializer

    if train:
        raise ValueError("the xing4 hook builds the served model only")

    class ZerosInDtype(initializer.Initializer):
        def __call__(self, shape, _dtype):
            return jnp.zeros(tuple(shape), dtype)

    cfg = model_config(model_cfg)
    # the fit runs whole layers in float32: before the model takes the chip
    W.selection_biases(seed, model_cfg, dtype)
    initializer.set_global_initializer(ZerosInDtype(), ZerosInDtype())
    try:
        model = Xing4ForCausalLM(cfg, with_mtp=with_mtp)
    finally:
        initializer.set_global_initializer(None, None)
    model.eval()
    groups = {"embed": W.embed(seed, model_cfg, dtype),
              "final": W.final(seed, model_cfg, dtype)}
    if with_mtp:
        groups["mtp"] = W.mtp(seed, model_cfg, dtype)
    held = slice(cfg.expert_first, cfg.expert_first + cfg.expert_count)
    how = model_cfg.get("expert_weights", "as_drawn")
    if how not in ("as_drawn", "int8_grid"):
        raise ValueError("expert_weights is 'as_drawn' or 'int8_grid'")
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
        if leaf in ("e_up", "e_down"):
            value = value[held]  # this chip's experts
            if how == "int8_grid":
                value = jax.jit(_on_int8_grid)(value)
        if tuple(value.shape) != tuple(p.shape):
            raise ValueError(f"{name}: program {tuple(p.shape)}, seeded "
                             f"weights {tuple(value.shape)}")
        p._data = value  # in the weights module's dtype (some are float32)
    return model
