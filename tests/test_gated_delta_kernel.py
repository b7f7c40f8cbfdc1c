"""The ``gdn_chunk`` Pallas kernel (``ops/gated_delta.py``) against the
token-serial recurrence AND against the ``jax.numpy`` chunked form it
replaces on a TPU. Its body runs in the Pallas interpreter here
(``interpret=True``); that Mosaic takes it at the cell's shapes is
``tests/test_tpu_compile.py -k gdn``."""
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import gated_delta as gd

F32, BF16 = jnp.float32, jnp.bfloat16


def _inputs(seed, b, t, h, dk, dv, g_min=-1.0, beta_max=2.0, keys=None):
    """Normalised ``q``, ``k`` as the model hands them over, a log decay
    in ``[g_min, 0]``, a write strength in ``[0, beta_max]`` and a
    non-zero state. ``keys``: every position's key is one of that many,
    and every write is at full strength."""
    ks = jax.random.split(jax.random.key(seed), 7)
    q = gd.l2norm(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = gd.l2norm(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = g_min * jax.random.uniform(ks[3], (b, t, h))
    beta = beta_max * jax.random.uniform(ks[4], (b, t, h))
    if keys:
        k = jnp.take(k, jax.random.randint(ks[6], (t,), 0, keys), axis=1)
        beta = jnp.maximum(beta, beta_max * (jnp.arange(t) % 3 > 0)[:, None])
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, h, dv, dk))


# name: (shape b t h dk dv, valid_len, mm_dtype, inputs' options,
#        limit against the serial form, limit against the jax.numpy form),
# both as a share of the largest value of what is compared
CASES = {
    "non-zero-state": ((2, 256, 3, 8, 16), None, F32, {}, 1e-5, 1e-5),
    "one-short-chunk": ((1, 40, 2, 8, 16), None, F32, {}, 1e-5, 1e-5),
    "t-not-whole-chunks": ((1, 200, 2, 24, 40), None, F32, {}, 1e-5, 1e-5),
    "t-65": ((1, 65, 2, 8, 16), None, F32, {}, 1e-5, 1e-5),
    "five-chunks-padded-to-eight": ((1, 320, 2, 8, 16), None, F32, {},
                                    1e-5, 1e-5),
    "valid-len-inside-a-chunk": ((2, 256, 2, 8, 16), 100, F32, {},
                                 1e-5, 1e-5),
    "valid-len-on-a-chunks-edge": ((1, 256, 2, 8, 16), 128, F32, {},
                                   1e-5, 1e-5),
    # a chunk's log decay reaches -700 here, and float32 sums it to 4e-5
    "strong-decay": ((1, 192, 2, 8, 16), None, F32, dict(g_min=-20.0),
                     5e-5, 5e-5),
    "no-decay": ((1, 192, 2, 8, 16), None, F32, dict(g_min=0.0),
                 2e-5, 2e-5),
    # the worst conditioning `I + A` meets: beta 2 on two thirds of the
    # positions, three keys in all, nothing forgotten
    "beta-2-keys-repeat": ((1, 256, 2, 8, 16), None, F32,
                           dict(g_min=0.0, keys=3), 2e-4, 2e-4),
    "beta-2-keys-repeat-some-decay": ((1, 256, 2, 8, 16), 200, F32,
                                      dict(g_min=-0.1, keys=3), 1e-4, 1e-4),
    "cells-widths-float32": ((1, 192, 2, 96, 192), 150, F32, {},
                             1e-5, 1e-5),
    "cells-widths-bfloat16": ((1, 256, 2, 96, 192), 200, BF16, {},
                              2e-2, 1e-2),
    "bfloat16-operands": ((2, 200, 3, 8, 16), None, BF16, {}, 3e-2, 2e-2),
    "bfloat16-beta-2-keys-repeat": ((1, 192, 2, 24, 40), None, BF16,
                                    dict(g_min=-0.05, keys=3), 5e-2, 3e-2),
}


def _share(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_matches_the_serial_and_the_chunked_form(name):
    shape, valid_len, mm_dtype, options, vs_serial, vs_chunked = CASES[name]
    args = _inputs(len(name), *shape, **options)
    n = shape[1] if valid_len is None else valid_len
    o, s = gd.gated_delta_chunked(*args, valid_len, mm_dtype=mm_dtype,
                                  interpret=True)
    assert o.shape == args[2].shape and o.dtype == F32
    assert s.shape == args[5].shape and s.dtype == F32
    o_ser, s_ser = gd.gated_delta_serial(*(a[:, :n] for a in args[:5]),
                                         args[5])
    assert _share(o[:, :n], o_ser) < vs_serial
    assert _share(s, s_ser) < vs_serial
    # the route off the chip: the same arithmetic in jax.numpy
    o_xla, s_xla = gd.gated_delta_chunked(*args, valid_len,
                                          mm_dtype=mm_dtype)
    assert _share(o[:, :n], o_xla[:, :n]) < vs_chunked
    assert _share(s, s_xla) < vs_chunked


def test_the_inverse_is_substitution_not_a_neumann_product():
    """``(I + a)^-1`` of the worst block the rule can meet (every entry
    under the diagonal 2: ``beta`` 2, one key, no decay) is ``I - 2 S + 2
    S^2 - ...`` with ``S`` the shift: entries of size 2, where the powers
    of ``a`` reach 1e6 and a Neumann product in float32 is off by 0.05."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c = gd.CHUNK
    idx = jnp.arange(c)
    a = jnp.where(idx[:, None] > idx[None, :], 2.0, 0.0)[None]

    def body(a_ref, t_ref, at_ref):
        row = jax.lax.broadcasted_iota(jnp.int32, (1, c, c), 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, c, c), 2)
        t_ref[...] = gd._unit_lower_inverse(
            a_ref[...], jnp.where(row < lane, 2.0, 0.0), at_ref, row, lane)

    t = pl.pallas_call(
        body, out_shape=jax.ShapeDtypeStruct((1, c, c), F32),
        scratch_shapes=[pltpu.VMEM((1, c, c), F32)], interpret=True)(a)[0]
    sign = jnp.where((idx[:, None] - idx[None, :]) % 2 == 0, 1.0, -1.0)
    want = jnp.where(idx[:, None] > idx[None, :], 2.0 * sign, 0.0) \
        + jnp.eye(c)
    assert float(jnp.max(jnp.abs(t - want))) < 1e-5


def test_engine_says_which_form_it_runs():
    """``kernel.gdn_chunk``: 1 on a TPU, 0 here, where a prefill's rule
    runs in ``jax.numpy``."""
    from paddle_tpu.models.olmo_hybrid import (OlmoHybridForCausalLM,
                                               olmo_hybrid_tiny)
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.serving import metrics as serving_metrics

    model = OlmoHybridForCausalLM(olmo_hybrid_tiny())
    assert model.serving_spec().kernels == ("gdn_chunk",)
    ServingEngine(model, config=ServingConfig(
        num_slots=2, kv_block_size=8, max_model_len=64))
    assert serving_metrics.gauges()["kernel.gdn_chunk"] == 0
