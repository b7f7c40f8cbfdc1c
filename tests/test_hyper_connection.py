"""The fused hyper-connection kernel against the ``jax.numpy`` form.

``paddle_tpu/ops/hyper_connection.py`` (one Pallas kernel a sublayer, its
body in the interpreter here) must give what ``models/xing4.py``
``HyperConnection.mixers`` / ``.pre`` / ``.post`` give, at float32
tolerance: the streams and the unrounded read-out within 1e-5 relative,
the mixers within 1e-6.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import xing4 as X
from paddle_tpu.ops import hyper_connection as hc

F32 = jnp.float32


def _connection(n, H, seed, res_shift=0.0):
    """A ``HyperConnection`` with drawn (not initial) weights; ``res_shift``
    moves every ``H_res`` logit, past a clamp where it is large."""
    cfg = X.Xing4Config(hidden_size=H, hc_mult=n)
    con = X.HyperConnection(cfg)
    rng = np.random.default_rng(seed)
    cols = 2 * n + n * n
    con.w._data = jnp.asarray(rng.normal(size=(n * H, cols))
                              * (n * H) ** -0.5, F32)
    con.norm._data = jnp.asarray(rng.uniform(0.5, 1.5, n * H), F32)
    con.a._data = jnp.asarray(rng.uniform(0.3, 0.9, 3), F32)
    bias = rng.normal(size=cols)
    bias[2 * n:] += res_shift
    con.b._data = jnp.asarray(bias, F32)
    return con


def _unpack(mix, n):
    """The kernel's ``[..., 128]`` mixers -> ``(pre, post, res)`` as the
    model lays them (``res[..., i, j]``)."""
    res = mix[..., 2 * n:2 * n + n * n].reshape(mix.shape[:-1] + (n, n))
    return mix[..., :n], mix[..., n:2 * n], jnp.swapaxes(res, -1, -2)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


CASES = {
    # (lanes, positions, n, hidden, a previous sublayer, shift of the res
    # logits); a grid step takes 128 positions, an inner step 512 lanes of
    # a stream (so 1024 is two chunks, 128 one)
    "prefill_tiles_do_not_divide": (1, 200, 4, 1024, True, 0.0),
    "prefill_two_lanes": (2, 72, 4, 128, True, 0.0),
    "decode_lanes_by_one": (24, 1, 4, 1024, True, 0.0),
    "first_sublayer": (1, 150, 4, 128, False, 0.0),
    "res_at_the_upper_clamp": (1, 20, 4, 128, True, 40.0),
    "res_at_the_lower_clamp": (1, 20, 4, 128, True, -40.0),
    "two_streams": (1, 150, 2, 1024, True, 0.0),
    "two_streams_first_sublayer": (3, 1, 2, 128, False, 0.0),
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_kernel_gives_what_the_jax_numpy_form_gives(case):
    b, s, n, H, has_prev, shift = CASES[case]
    rng = np.random.default_rng(len(case))
    before, con = _connection(n, H, 1), _connection(n, H, 2, shift)
    cfg = con.cfg
    Xs = jnp.asarray(rng.normal(size=(b, s, n, H)) * 3.0, F32)
    y = jnp.asarray(rng.normal(size=(b, s, H)), jnp.bfloat16)
    gain = jnp.asarray(rng.uniform(0.5, 1.5, H), F32)
    flat = Xs.reshape(b, s, n * H)
    kw = dict(n=n, iters=int(cfg.hc_sinkhorn_iters),
              rms_eps=float(cfg.rms_norm_eps), hc_eps=float(cfg.hc_eps),
              clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max),
              out_dtype=jnp.bfloat16, want_f32=True)

    want_X, prev = Xs, None
    if has_prev:  # the sublayer before: its mixers, by both routes
        _, mix0 = before.pre(Xs)
        want_X = before.post(Xs, y, mix0)
        _, _, _, kmix0 = hc.hyper_connection(
            flat, hc.pack_mixer_params(before.w._data, before.norm._data,
                                       before.a._data, before.b._data, n),
            gain, **kw)
        prev = (y, kmix0)
        for got, want in zip(_unpack(kmix0, n)[1:], mix0):
            assert float(jnp.max(jnp.abs(got - want))) < 1e-6
    pre, post, res = con.mixers(want_X)
    want_u, _ = con.pre(want_X)
    want_u = X._rms(want_u, gain, float(cfg.rms_norm_eps), F32)

    new_X, u, u32, mix = hc.hyper_connection(
        flat, hc.pack_mixer_params(con.w._data, con.norm._data, con.a._data,
                                   con.b._data, n), gain, prev=prev, **kw)
    assert new_X.shape == flat.shape and new_X.dtype == F32
    assert u.shape == u32.shape == (b, s, H) and u.dtype == jnp.bfloat16
    assert mix.shape == (b, s, 128)
    assert _rel(new_X.reshape(Xs.shape), want_X) < 1e-5
    assert _rel(u32, want_u) < 1e-5
    assert bool(jnp.all(u == u32.astype(jnp.bfloat16)))
    for got, want in zip(_unpack(mix, n), (pre, post, res)):
        assert float(jnp.max(jnp.abs(got - want))) < 1e-6
    if shift:  # the case means what it says: every logit sits at a clamp
        z = jnp.log(res / res[..., :1, :1])
        assert float(jnp.max(jnp.abs(z))) < 1e-3
    # and the update alone (behind a stack's last sublayer)
    y2 = jnp.asarray(rng.normal(size=(b, s, H)), jnp.bfloat16)
    got = hc.hyper_connection_update(new_X, y2, mix, n=n)
    want = con.post(want_X, y2, (post, res))
    assert _rel(got.reshape(Xs.shape), want) < 1e-5


def test_the_layout_of_the_mixers_and_what_does_not_fit():
    """Column ``2n + j n + i`` of the kernel's mixers is ``H_res[i, j]``
    (one COLUMN of the matrix in consecutive rows, positions in the lanes),
    and a stream count whose columns do not fit one weight tile is refused
    by name, not mis-computed."""
    cols, order = hc._columns(4)
    assert cols == 24 and order[:8] == list(range(8))
    assert order[8:12] == [8, 12, 16, 20]      # column 0 of H_res
    with pytest.raises(ValueError, match="do not fit"):
        hc.pack_mixer_params(jnp.zeros((6 * 128, 48)), jnp.ones(6 * 128),
                             jnp.ones(3), jnp.zeros(48), 6)
    hi, mid, lo = hc._split3(jnp.asarray([np.pi, -1e-3, 12345.678], F32))
    back = hi.astype(F32) + mid.astype(F32) + lo.astype(F32)
    assert bool(jnp.all(back == jnp.asarray([np.pi, -1e-3, 12345.678], F32)))


def test_a_stack_hands_the_last_update_on_under_a_step_carry(monkeypatch):
    """Layers of a stack leave their last update to the next layer's first
    kernel when a step carry is there, and the stack's last layer finishes:
    the same streams as each layer finishing its own."""
    cfg = X.xing4_tiny()
    model = X.Xing4ForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    for p in model.parameters():  # drawn, so that every mixer differs
        p._data = jnp.asarray(rng.normal(size=p.shape) * 0.05
                              + (1.0 if len(p.shape) == 1 else 0.0),
                              p._data.dtype)
    layers = list(model.model.layers)
    assert [l.hands_on for l in layers] == [True] * (len(layers) - 1) + [False]
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 12)), jnp.int32)
    from paddle_tpu.core.tensor import Tensor

    def run(carry):
        x = model.serving_embed(Tensor(ids), 0)
        for layer in layers:
            view = X._SequenceView(False, int(cfg.kv_lora_rank))
            x, _ = layer(x, cache=view, carry=carry)
        return x._data

    alone, carry = run(None), {}
    chained = run(carry)
    assert "hc.y" not in carry and "hc.mix" not in carry
    assert _rel(chained, alone) < 1e-6
    # what an engine does before it takes its snapshot: the mixers' weights
    # in the kernel's form are buffers then, and the programs pack nothing
    assert not model.functional_state()[1]
    model.serving_prepare()
    packed = model.functional_state()[1]
    assert len(packed) == 2 * 2 * len(layers)
    assert all(name.endswith(("packed_w", "packed_ab")) for name in packed)
    assert not any("packed" in name for name in model.state_dict())
    def packed_again(*_a, **_k):
        raise AssertionError("a prepared model packs its mixers in a call")
    monkeypatch.setattr(hc, "pack_mixer_params", packed_again)
    assert bool(jnp.all(run({}) == chained))
