"""``ops/ssm.py`` (the causal depthwise convolution and the chunked
state-space scan) against the per-step recurrence of the benchmark's plain
reference, and the ungated form of ``ops/moe.py`` ``expert_ffn`` against a
dense loop; tiny, on the CPU. The model these serve is tested in
``tests/test_ssm_moe_lm.py``.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from alphafold2_tpu.models.ssm_moe_lm import relu2  # noqa: E402
from alphafold2_tpu.ops import moe, ssm  # noqa: E402
from alphafold2_tpu.ops.pallas import conv, ssd  # noqa: E402
from benchmark.reference import ssm_lm_model as ref  # noqa: E402


@pytest.fixture(params=["xla", "pallas"])
def scan(request, monkeypatch):
    """``ssm.ssd_scan`` through each form of its chunks: the XLA products,
    and the kernels of ``ops/pallas/ssd.py``, interpreted (these shapes are
    off the predicate's tile grid, which interpretation does not need)."""
    monkeypatch.setattr(ssm, "scan_kernel_takes",
                        lambda *shapes: request.param == "pallas")
    return ssm.ssd_scan


def scan_inputs(length, groups, heads=4, width=8, n=16, batch=1, seed=0):
    keys = jax.random.split(jax.random.key(length + seed), 5)
    x = jax.random.normal(keys[0], (batch, length, heads, width))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, length, heads)))
    a = -jnp.exp(jax.random.normal(keys[2], (heads,)))
    b = jax.random.normal(keys[3], (batch, length, groups, n))
    c = jax.random.normal(keys[4], (batch, length, groups, n))
    return x, dt, a, b, c


def recurrence(x, dt, a, b, c, state=None):
    """The reference's per-step walk, groups repeated to heads."""
    rep = x.shape[2] // b.shape[2]
    return ref.recurrence(x, dt, a, jnp.repeat(b, rep, 2),
                          jnp.repeat(c, rep, 2), state)


def output_and_gradients(scan, weight):
    """One jitted call: (y, final state, gradients of a weighted sum of y
    towards x, dt, A, B, C)."""

    def run(*args):
        y, state = scan(*args)[:2]
        grads = jax.grad(lambda *a: (scan(*a)[0] * weight).sum(),
                         argnums=(0, 1, 2, 3, 4))(*args)
        return y, state, grads

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def reference_readings(length, groups):
    """What ``test_chunked_scan_is_the_per_step_recurrence`` compares with,
    once a (length, groups) and not once a case."""
    args = scan_inputs(length, groups)
    weight = jax.random.normal(jax.random.key(9), args[0].shape)
    return output_and_gradients(recurrence, weight)(*args)


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("chunk", [16, 128])
@pytest.mark.parametrize("length", [128, 200, 384])
def test_chunked_scan_is_the_per_step_recurrence(length, chunk, groups, scan):
    """Lengths on and off the chunk grid, a chunk as long as the sequence,
    heads sharing B and C in one group or two: output, final state and the
    gradients towards x, dt, A, B and C, through either form."""
    args = scan_inputs(length, groups)
    weight = jax.random.normal(jax.random.key(9), args[0].shape)
    y, state, grads = output_and_gradients(
        lambda *a: scan(*a, chunk), weight)(*args)
    want_y, want_state, want = reference_readings(length, groups)
    scale = float(jnp.abs(want_y).max())
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-5 * scale)
    np.testing.assert_allclose(state, want_state, rtol=1e-4, atol=1e-4)
    decays = scan(*args, chunk)[2]
    assert decays.shape == (1, -(-length // chunk), 4)
    padded = jnp.pad(args[1] * args[2], ((0, 0), (0, -length % chunk), (0, 0)))
    np.testing.assert_allclose(
        decays, jnp.exp(padded.reshape(1, -1, chunk, 4).sum(2)), rtol=1e-4)
    for name, got, ref_grad in zip(("x", "dt", "A", "B", "C"), grads, want):
        np.testing.assert_allclose(
            got, ref_grad, rtol=2e-4,
            atol=2e-5 * float(jnp.abs(ref_grad).max()), err_msg=f"d{name}")


def test_a_decay_that_underflows_inside_a_chunk_gives_no_nan(scan):
    """dt A = -500 a step: exp of a chunk's sum is 0 in float32 and a ratio
    of exponentials would be 0 / 0. The decay matrix is built from
    differences inside the mask, so the output (each step sees itself) and
    every gradient stay finite, and equal the recurrence's."""
    x, _, _, b, c = scan_inputs(256, 1)
    dt, a = jnp.full((1, 256, 4), 50.0), jnp.full((4,), -10.0)
    y, state, decays = scan(x, dt, a, b, c, 128)
    assert float(decays.max()) == 0.0
    want, _ = jax.jit(recurrence)(x, dt, a, b, c)
    assert bool(jnp.isfinite(y).all())
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-3)
    grads = jax.jit(jax.grad(
        lambda *args: scan(*args, 128)[0].sum(),
        argnums=(0, 1, 2, 3, 4)))(x, dt, a, b, c)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


def test_a_later_step_reaches_no_earlier_output(scan):
    """Causality at the ops: step 40 of the scan's input moves outputs 40..
    and none before; step 9 of the convolution's moves 9..12 (four taps)."""
    args = scan_inputs(64, 2)
    y, _, _ = scan(*args, 16)
    y2, _, _ = scan(args[0].at[:, 40].add(1.0), *args[1:], 16)
    gap = np.abs(np.asarray(y2 - y)).max((0, 2, 3))
    np.testing.assert_array_equal(gap[:40], 0.0)
    assert (gap[40:] > 0).all()
    w = jax.random.normal(jax.random.key(1), (6, 4))
    u = jax.random.normal(jax.random.key(2), (1, 20, 6))
    conv = ssm.causal_conv(u, w, jnp.zeros(6))
    conv2 = ssm.causal_conv(u.at[:, 9].add(1.0), w, jnp.zeros(6))
    gap = np.abs(np.asarray(conv2 - conv)).max((0, 2))
    assert (gap[:9] == 0).all() and (gap[9:13] > 0).all() \
        and (gap[13:] == 0).all()


def test_one_group_of_64_heads_in_chunks_of_256(scan):
    """The dense hybrid's scan: every one of 64 heads reads the one group's
    B and C, whose gradients sum over all 64, in chunks of 256 (two of
    them, so a state is carried). The kernels walk the group eight heads a
    grid step and XLA sums the eight parts of dB and dC
    (``ssd.heads_a_step``): output, final state and the five gradients
    against the per-step recurrence, through either form."""
    assert ssd.heads_a_step(64) == (8, 8) and ssd.heads_a_step(8) == (8, 1)
    assert ssd.heads_a_step(4) == (4, 1) and ssd.heads_a_step(12) == (12, 1)
    args = scan_inputs(512, 1, heads=64)
    weight = jax.random.normal(jax.random.key(9), args[0].shape)
    y, state, grads = output_and_gradients(
        lambda *a: scan(*a, 256), weight)(*args)
    want_y, want_state, want = output_and_gradients(recurrence, weight)(*args)
    scale = float(jnp.abs(want_y).max())
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-5 * scale)
    np.testing.assert_allclose(state, want_state, rtol=1e-4, atol=1e-4)
    for name, got, ref_grad in zip(("x", "dt", "A", "B", "C"), grads, want):
        np.testing.assert_allclose(
            got, ref_grad, rtol=2e-4,
            atol=2e-5 * float(jnp.abs(ref_grad).max()), err_msg=f"d{name}")


@pytest.mark.parametrize("heads,groups", [(4, 2), (16, 1)])
def test_the_kernels_in_bfloat16_are_the_xla_form_in_bfloat16(heads, groups):
    """bfloat16 operands, a state entering, two groups (or one group walked
    in two head blocks): the kernels against
    the XLA form at the same ``dtype``. The forward casts sit where the XLA
    form's do, so output and state differ by accumulation order alone; the
    backward products take their cotangents rounded to bfloat16 in both
    (the XLA form's score cotangent is bfloat16 by type), in another order."""
    x, dt, a, b, c = scan_inputs(64, groups, heads=heads, batch=2)
    x, b, c = (t.astype(jnp.bfloat16) for t in (x, b, c))
    state = jax.random.normal(jax.random.key(5), (2, heads, 16, 8))
    weight = jax.random.normal(jax.random.key(9), x.shape)

    def run(chunks):
        def loss(x, dt, a, b, c, state):
            y, final, _ = chunks(x, dt, a, b, c, 16, jnp.bfloat16, state)
            return (y * weight).sum() + final.sum(), (y, final)

        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4, 5), has_aux=True))(
            x, dt, a, b, c, state)

    (_, (y, final)), grads = run(ssd.ssd_chunks)
    (_, (want_y, want_final)), want = run(ssm.ssd_chunks_xla)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(final, want_final, rtol=1e-5, atol=1e-5)
    for name, got, ref_grad in zip(
            ("x", "dt", "A", "B", "C", "state"), grads, want):
        assert got.dtype == ref_grad.dtype and got.shape == ref_grad.shape
        got, ref_grad = (np.asarray(g, np.float32) for g in (got, ref_grad))
        assert np.abs(got - ref_grad).max() <= 2e-2 * np.abs(ref_grad).max(), \
            f"d{name}"


def test_the_kernels_take_tiled_shapes_on_a_tpu_and_nothing_elsewhere(
        monkeypatch):
    """The rule of ``scan_kernel_takes``: chunk and state rows multiples of
    128, a head's width a multiple of 16, a group's heads a multiple of 8 or
    one group; and no shape at all off the TPU."""
    cell = ((1, 8192, 64, 64), (1, 8192, 8, 128), 128)
    assert not ssm.scan_kernel_takes(*cell)  # the tests run on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssm.scan_kernel_takes(*cell)
    assert ssm.scan_kernel_takes((2, 512, 8, 128), (2, 512, 1, 256), 256)
    assert ssm.scan_kernel_takes((1, 256, 4, 32), (1, 256, 1, 128), 128)
    for x_shape, b_shape, chunk in (
            ((1, 8192, 64, 64), (1, 8192, 8, 128), 64),    # the chunk
            ((1, 8192, 64, 64), (1, 8192, 8, 64), 128),    # the state rows
            ((1, 8192, 64, 64), (1, 8192, 16, 128), 128),  # 4 heads a group
            ((1, 8192, 64, 24), (1, 8192, 8, 128), 128),   # a head's width
            ((1, 128, 4, 8), (1, 128, 2, 16), 16)):        # this file's sizes
        assert not ssm.scan_kernel_takes(x_shape, b_shape, chunk)


def test_the_convolutions_last_tap_meets_the_current_step():
    u = jnp.zeros((1, 8, 1)).at[0, 2, 0].set(1.0)
    w = jnp.asarray([[1.0, 2.0, 3.0, 4.0]])
    out = ssm.causal_conv(u, w, jnp.zeros(1))[0, :, 0]
    want = jax.nn.silu(jnp.asarray([0, 0, 4.0, 3.0, 2.0, 1.0, 0, 0]))
    np.testing.assert_allclose(out, want, rtol=1e-6)
    p = {"kernel": w, "bias": jnp.zeros(1)}
    np.testing.assert_allclose(ref.causal_conv(p, u, 4)[0, :, 0], want,
                               rtol=1e-6)
    backwards = ref.causal_conv(p, u, 4, reverse=True)[0, :, 0]
    np.testing.assert_allclose(
        backwards, jax.nn.silu(jnp.asarray([0, 0, 1.0, 2.0, 3.0, 4.0, 0, 0])),
        rtol=1e-6)


@pytest.mark.parametrize("cut", [16, 21])
def test_a_sequence_split_in_two_with_its_state_carried_is_the_whole(
        cut, scan):
    """The first half's final state and last three convolution inputs carried
    into the second half give the whole sequence's output, for a cut on and
    off the chunk grid."""
    args = scan_inputs(40, 2)
    whole, final, _ = scan(*args, 16)
    first = [t[:, :cut] if t.ndim > 1 else t for t in args]
    second = [t[:, cut:] if t.ndim > 1 else t for t in args]
    y1, state, _ = scan(*first, 16)
    y2, final2, _ = scan(*second, 16, state=state)
    np.testing.assert_allclose(
        jnp.concatenate([y1, y2], 1), whole, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(final2, final, rtol=1e-4, atol=1e-5)
    w = jax.random.normal(jax.random.key(1), (6, 4))
    bias = jax.random.normal(jax.random.key(3), (6,))
    u = jax.random.normal(jax.random.key(2), (1, 40, 6))
    conv = ssm.causal_conv(u, w, bias)
    tail = ssm.causal_conv(u[:, cut - 3:], w, bias)[:, 3:]
    np.testing.assert_allclose(tail, conv[:, cut:], rtol=1e-5, atol=1e-6)


def conv_inputs(batch, length, channels, dtype=jnp.float32, taps=4, seed=0):
    """(x, taps, bias, a weight of x's shape) under the layer's
    initialisation: taps and bias uniform on +-1/sqrt(taps)."""
    keys = jax.random.split(jax.random.key(seed), 4)
    bound = taps ** -0.5
    x = jax.random.normal(keys[0], (batch, length, channels)).astype(dtype)
    w = jax.random.uniform(keys[1], (channels, taps), minval=-bound,
                           maxval=bound)
    b = jax.random.uniform(keys[2], (channels,), minval=-bound, maxval=bound)
    return x, w, b, jax.random.normal(keys[3], x.shape)


def conv_and_gradients(form, weight):
    """One jitted call: (output, gradients of a weighted sum of it towards
    x, the taps and the bias)."""
    def loss(x, w, b):
        return (form(x, w, b).astype(jnp.float32) * weight).sum()

    return jax.jit(lambda *args: (
        form(*args), jax.grad(loss, argnums=(0, 1, 2))(*args)))


@pytest.mark.parametrize("batch,length,channels,dtype", [
    (1, 256, 32, "float32"), (2, 512, 64, "float32"),
    (1, 1024, 128, "bfloat16"), (2, 256, 96, "bfloat16")])
def test_the_conv_kernels_are_the_xla_form(batch, length, channels, dtype):
    """The kernels of ``ops/pallas/conv.py``, interpreted, against
    ``causal_conv_xla`` and autodiff through it, at shapes on the tile grid.
    The pre-activation is summed in the same order in float32 and cast
    once, so the outputs agree to bfloat16's last bit; dx differs by the
    order of SiLU's derivative's products (float32, then one rounding to
    x's type: a unit in its last place); dw and db sum the same float32
    terms over batch and time in another order."""
    x, w, b, weight = conv_inputs(batch, length, channels, jnp.dtype(dtype))
    y, grads = conv_and_gradients(conv.causal_conv, weight)(x, w, b)
    want_y, want = conv_and_gradients(ssm.causal_conv_xla, weight)(x, w, b)
    assert y.dtype == x.dtype and y.shape == x.shape
    ulp = 2.0 ** -7 if dtype == "bfloat16" else 1e-6
    got_y, want_y = (np.asarray(t, np.float32) for t in (y, want_y))
    np.testing.assert_allclose(got_y, want_y, rtol=ulp, atol=1e-6)
    for name, got, ref_grad in zip(("x", "w", "b"), grads, want):
        assert got.dtype == ref_grad.dtype and got.shape == ref_grad.shape
        got, ref_grad = (np.asarray(g, np.float32) for g in (got, ref_grad))
        tol = ulp if name == "x" else 1e-5
        assert np.abs(got - ref_grad).max() <= tol * np.abs(ref_grad).max(), \
            f"d{name}"


@pytest.mark.parametrize("start", [0, 32])
def test_the_conv_kernels_read_their_channels_where_they_lie(start):
    """The layer's projection holds the convolved channels among others: the
    kernels read channels ``start`` .. ``start + C`` of the whole array in
    place (``ssm.causal_conv``'s ``start``), and the gradient towards the
    other channels is zero. Against the XLA form on the slice."""
    x, w, b, _ = conv_inputs(2, 256, 80, jnp.bfloat16)
    w, b = w[:32], b[:32]
    weight = jax.random.normal(jax.random.key(7), (2, 256, 32))
    y, grads = conv_and_gradients(
        lambda x, w, b: conv.causal_conv(x, w, b, start), weight)(x, w, b)
    want_y, want = conv_and_gradients(
        lambda x, w, b: ssm.causal_conv_xla(x[..., start:start + 32], w, b),
        weight)(x, w, b)
    assert y.shape == (2, 256, 32)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want_y, np.float32), rtol=2.0 ** -7)
    dx = np.asarray(grads[0], np.float32)
    assert (dx[..., :start] == 0).all() and (dx[..., start + 32:] == 0).all()
    for got, ref_grad in zip(grads, want):
        got, ref_grad = (np.asarray(g, np.float32) for g in (got, ref_grad))
        assert np.abs(got - ref_grad).max() <= 2.0 ** -7 * np.abs(
            ref_grad).max()


def test_in_the_conv_kernels_a_step_reaches_no_earlier_output_and_no_later_dx():
    """Causality through the kernels: step 300 of x moves outputs 300..303
    (four taps) and none before or after; it moves dx at 297..303 only (the
    steps whose outputs read 300, and those 300's output reads)."""
    x, w, b, weight = conv_inputs(1, 512, 32)
    run = conv_and_gradients(conv.causal_conv, weight)
    y, (dx, _, _) = run(x, w, b)
    y2, (dx2, _, _) = run(x.at[:, 300].add(1.0), w, b)
    gap = np.abs(np.asarray(y2 - y)).max((0, 2))
    assert (gap[:300] == 0).all() and (gap[300:304] > 0).all() \
        and (gap[304:] == 0).all()
    gap = np.abs(np.asarray(dx2 - dx)).max((0, 2))
    assert (gap[:297] == 0).all() and (gap[297:304] > 0).all() \
        and (gap[304:] == 0).all()


def test_the_conv_kernels_see_zeros_before_the_start_and_after_the_end():
    """A shift is a roll along the block's lanes with the wrapped steps
    masked: x large in the LAST three steps reaches none of the first three
    outputs (each is silu(bias) exactly), and a cotangent on the FIRST three
    outputs alone reaches no dx of the last three."""
    x = jnp.zeros((1, 256, 32)).at[:, -3:].set(100.0)
    _, w, b, _ = conv_inputs(1, 256, 32)
    y = conv.causal_conv(x, w, b)
    np.testing.assert_array_equal(y[0, :3], jnp.broadcast_to(
        jax.nn.silu(b), (3, 32)))
    weight = jnp.zeros(x.shape).at[:, :3].set(1.0)
    x = jax.random.normal(jax.random.key(4), x.shape)
    _, (dx, _, _) = conv_and_gradients(conv.causal_conv, weight)(x, w, b)
    assert bool(jnp.all(dx[0, -3:] == 0)) and bool(jnp.all(dx[0, :3] != 0))


def test_the_conv_kernels_take_tiled_shapes_on_a_tpu_and_nothing_elsewhere(
        monkeypatch):
    """The rule of ``conv_kernel_takes``: channels and their first channel
    in the array multiples of a block's 32 rows, the length a multiple of
    128 and whole in a block, at most 128 taps; and no shape at all off the
    TPU."""
    cells = ((1, 8192, 6144), (1, 8192, 4352))
    assert not any(ssm.conv_kernel_takes(s, 4, 4096) for s in cells)  # CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert all(ssm.conv_kernel_takes(s, 4, 4096) for s in cells)
    assert ssm.conv_kernel_takes((2, 256, 96), 4)
    assert ssm.conv_kernel_takes((1, 8192, 32), 128, 64)
    for shape, taps, start in (((1, 8192, 6160), 4, 0),    # channels off 32
                               ((1, 8192, 6144), 4, 4112),  # start off 32
                               ((1, 8000, 6144), 4, 0),    # length off 128
                               ((1, 16384, 6144), 4, 0),   # a block's length
                               ((1, 8192, 6144), 129, 0),  # taps
                               ((1, 20, 6), 4, 0)):        # this file's
        assert not ssm.conv_kernel_takes(shape, taps, start), (shape, start)


def test_ungated_experts_run_two_products_against_a_dense_loop():
    """``w_gate=None``: ``W_down relu^2(W_up x)`` an expert over its own
    rows; rows past the groups come out zero and take a zero gradient."""
    sizes = jnp.asarray([3, 0, 5], jnp.int32)
    keys = jax.random.split(jax.random.key(0), 3)
    rows = jax.random.normal(keys[0], (12, 8))
    w_up = jax.random.normal(keys[1], (3, 8, 4))
    w_down = jax.random.normal(keys[2], (3, 4, 8))

    def ffn(r, activation=relu2):
        return moe.expert_ffn(r, sizes, None, w_up, w_down, jnp.float32,
                              activation)

    out = ffn(rows)
    want = jnp.stack([
        jnp.square(jax.nn.relu(rows[r] @ w_up[e])) @ w_down[e]
        for r, e in enumerate(np.repeat([0, 2], [3, 5]))])
    np.testing.assert_allclose(out[:8], want, rtol=1e-5, atol=1e-5)
    assert bool(jnp.all(out[8:] == 0))
    back = jax.grad(lambda r: ffn(r).sum())(rows)
    assert bool(jnp.all(back[8:] == 0)) and bool(jnp.all(back[:8] != 0))
    # one in-product of the expert's width, not twice it
    products = [e.outvars[0].aval.shape for e in jax.make_jaxpr(ffn)(rows).eqns
                if e.primitive.name.startswith("ragged_dot")]
    assert products == [(12, 4), (12, 8)]
    assert float(jnp.abs(ffn(rows, jax.nn.relu)[:8] - out[:8]).max()) > 1e-3
