"""The plain reference of the ``train_ssm_lm`` cells: a hybrid decoder whose
layer is one norm and one mixer (a Mamba-2 state-space mixer, sigmoid-routed
ungated relu^2 experts beside one shared expert, or positionless grouped-query
attention), next-token loss, gradients and Adam, for ONE chip's share of an
expert-parallel layer.

Straightforward ``jax.numpy`` written from the published configuration
(``model_type`` nemotron_h; the equations are in the configuration's file and
PERF.md section 4). It imports nothing of ``alphafold2_tpu``; ``Precision``,
``rms_norm``, ``dense``, the sigmoid router and the Adam update are
``reference/lm_model.py``'s, the grouped attention in query blocks is
``reference/swa_lm_model.py``'s, the learning-rate schedule and the per-leaf
norms ``reference/model.py``'s. It is handed nothing the program made:
weights come from :func:`init_params`, tokens from ``harness/traffic_lm.py``.

Layer ``l`` on the stream ``x``: ``x' = x + Mixer_l(RMSNorm_l(x))``, the mixer
named by character ``l`` of ``hybrid_override_pattern``. With ``u`` the normed
stream, no biases but the convolution's:

- ``M``: ``[z, c, d] = W_in u`` (inner + (inner + 2 G N) + heads);
  ``c_t <- silu(b + sum_{j=0..K-1} w[:, j] * c_{t-K+1+j})``, zeros before the
  start, as its K-term sum; ``c`` splits into ``x`` (heads x width), ``B``,
  ``C`` (G groups x N; head h reads group h // (heads / G)); ``dt =
  softplus(d + dt_bias)``, ``A = -exp(A_log)``. **The recurrence itself**, a
  ``lax.scan`` over time steps, every head in one step: ``H_t = exp(dt_t A)
  H_{t-1} + dt_t B_t (x) x_t``, ``y_t = C_t^T H_t + D x_t``, ``H_0 = 0``, all
  float32 (nothing of the program's chunked algebra: no chunk, no decay
  matrix). ``g = y * silu(z)``; ``g`` normed over each of G groups of
  channels (root mean square, eps, a scale); ``W_out``.
- ``E``: ``s = sigmoid(W_r u)`` over ALL experts, the ``top_k`` largest ``s +
  b`` (``b`` stays zero and takes no gradient), ``w_e = scaling * s_e / sum
  of the selected s``; ``W_down,s relu^2(W_up,s u) + sum over the experts
  HELD HERE of w_e W_down,e relu^2(W_up,e u)``: a plain loop over the held
  experts with a mask, every token through every held expert. What the absent
  experts would add is left out, as in the program: the same share.
- ``*``: ``q = W_q u`` (H heads), ``k = W_k u``, ``v = W_v u`` (G heads); no
  positional encoding; query head h reads key/value head h // (H / G); query
  i sees keys j <= i; ``softmax(q k^T / sqrt(width))``; ``W_o``. Dense, in
  blocks of queries.
- embedding, final RMSNorm, an untied head over the vocabulary rows held
  here, the mean next-token cross-entropy over positions 0..S-2.

Departures from the published model: none in the equations. The time steps
are walked in segments of 128 under ``jax.checkpoint`` so that the backward
pass holds one segment's states and not 8,192 (a matter of memory; the
arithmetic is the per-step recurrence's). Under a ``Precision`` below
float32 the operands ``x``, ``B``, ``C`` of the recurrence are rounded as a
matrix product's would be; ``dt``, ``A`` and the state stay float32. What
``config.json`` does not say is listed under ``assumed`` in the
configuration's file. No auxiliary balancing loss.

``fault`` plants a mistake for reading the limits: ``state_dropped`` (the
state set to zero at every multiple of ``chunk_size`` steps: a chunked scan
that forgets to carry), ``conv_reversed`` (the convolution's taps applied in
reverse order), ``relu`` (``relu`` in place of ``relu^2`` in every expert).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import swa_lm_model
from benchmark.reference.lm_model import (
    F32, LOGIT_BLOCK_BYTES, _adam, _is_shape, dense, rms_norm, route,
)
from benchmark.reference.model import leaf_norms, learning_rate

FAULTS = (None, "state_dropped", "conv_reversed", "relu")
SIZE_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
    "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
    "time_step_min", "time_step_max", "time_step_floor",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size",
    "n_routed_experts", "router_width", "first_expert",
    "num_experts_per_tok", "routed_scaling_factor", "layer_norm_epsilon",
)
MIXERS = {"M": "ssm", "E": "moe", "*": "attn_global"}
SEGMENT = 128  # time steps walked under one jax.checkpoint

# ------------------------------------------------------------- parameters ---


def layer_kinds(sizes: dict) -> str:
    """The pattern's first ``num_hidden_layers`` characters, one a layer."""
    return sizes["hybrid_override_pattern"][:sizes["num_hidden_layers"]]


def param_shapes(sizes: dict) -> dict:
    """The parameter tree's shapes from the configuration's sizes alone.
    ``n_routed_experts`` experts are held (stacked leaves), of the
    ``router_width`` the router scores."""
    d = sizes["hidden_size"]
    heads, inner = sizes["mamba_num_heads"], \
        sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    conv = inner + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    q_heads, kv_heads, width = (sizes["num_attention_heads"],
                                sizes["num_key_value_heads"],
                                sizes["head_dim"])
    held, f = sizes["n_routed_experts"], sizes["moe_intermediate_size"]
    shared = sizes["moe_shared_expert_intermediate_size"]
    mixers = {
        "M": {
            "in_proj": {"kernel": (d, inner + conv + heads)},
            "conv": {"kernel": (conv, sizes["conv_kernel"]),
                     "bias": (conv,)},
            "A_log": (heads,), "dt_bias": (heads,), "D": (heads,),
            "gate_norm": {"scale": (inner,)},
            "out_proj": {"kernel": (inner, d)},
        },
        "E": {
            "router": (d, sizes["router_width"]),
            "router_bias": (sizes["router_width"],),
            "w_up": (held, d, f), "w_down": (held, f, d),
            "shared": {"up_proj": {"kernel": (d, shared)},
                       "down_proj": {"kernel": (shared, d)}},
        },
        "*": {
            "q_proj": {"kernel": (d, q_heads * width)},
            "k_proj": {"kernel": (d, kv_heads * width)},
            "v_proj": {"kernel": (d, kv_heads * width)},
            "o_proj": {"kernel": (q_heads * width, d)},
        },
    }
    return {"params": {
        "embed": {"embedding": (sizes["vocab_size"], d)},
        **{f"layer_{i}": {"norm": {"scale": (d,)}, MIXERS[kind]: mixers[kind]}
           for i, kind in enumerate(layer_kinds(sizes))},
        "final_norm": {"scale": (d,)},
        "head": {"kernel": (d, sizes["vocab_size"])},
    }}


def init_params(sizes: dict, seed: int) -> dict:
    """Float32 weights from ``seed`` in one jitted call on the device:
    matrices normal with variance 1 / fan-in (a stacked expert leaf's fan-in
    is its middle axis), the table's rows normal with variance 1 (as
    ``swa_lm_model.py``'s, for its reason: a token's own vector is not lost
    beside the first mixer's output), norm scales one, the router's bias
    zero; and the state-space leaves as the family publishes them, from the
    configuration's own keys, so that the seeded weights decay as a real
    model's do: ``A_log = log a``, ``a`` uniform on [1, 16]; ``dt_bias =
    softplus^-1(dt)``, ``dt`` log-uniform on [time_step_min, time_step_max]
    floored at time_step_floor; ``D = 1``; the depthwise convolution's
    weights and bias uniform on +-1/sqrt(taps)."""
    shapes = param_shapes(sizes)
    leaves, _ = jax.tree.flatten_with_path(shapes, is_leaf=_is_shape)
    t_min, t_max = sizes["time_step_min"], sizes["time_step_max"]

    @jax.jit
    def make(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            kind, k = path[-1].key, jax.random.fold_in(key, i)
            if kind in ("scale", "D"):
                out.append(jnp.ones(shape, jnp.float32))
            elif kind == "router_bias":
                out.append(jnp.zeros(shape, jnp.float32))
            elif kind == "A_log":
                out.append(jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0)))
            elif kind == "dt_bias":
                dt = jnp.maximum(jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(t_min),
                    math.log(t_max))), sizes["time_step_floor"])
                out.append(dt + jnp.log(-jnp.expm1(-dt)))
            elif path[-2].key == "conv":
                bound = sizes["conv_kernel"] ** -0.5
                out.append(jax.random.uniform(
                    k, shape, jnp.float32, -bound, bound))
            else:
                fan_in = 1 if kind == "embedding" else shape[-2]
                out.append(jax.random.normal(k, shape, jnp.float32)
                           * fan_in ** -0.5)
        return out

    return jax.tree.unflatten(
        jax.tree.structure(shapes, is_leaf=_is_shape),
        make(jax.random.key(seed)),
    )


# ---------------------------------------------------------------- forward ---


def relu2(a):
    return jnp.square(jnp.maximum(a, 0.0))


def causal_conv(p, c, taps: int, reverse=False):
    """``silu(b + sum_j w[:, j] * c_{t-taps+1+j})`` over (B, T, channels),
    zeros before the start, float32."""
    t = c.shape[1]
    padded = jnp.pad(c.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    kernel = p["kernel"][:, ::-1] if reverse else p["kernel"]
    out = p["bias"] + sum(
        kernel[:, j] * padded[:, j:j + t] for j in range(taps))
    return jax.nn.silu(out)


def recurrence(x, dt, a, b, c, state=None, reset_every=None):
    """The state-space recurrence, one time step at a time. ``x`` (B, T, H,
    P), ``dt`` (B, T, H), ``a`` (H,), ``b``, ``c`` (B, T, H, N) (already a
    head's own), all float32. Returns (``C_t^T H_t`` (B, T, H, P), the last
    state (B, H, N, P)). ``reset_every`` (the ``state_dropped`` fault): the
    state is zeroed before every step whose index is a multiple of it."""
    batch, length, heads, width = x.shape
    n = b.shape[-1]

    def step(h, at):
        x_t, dt_t, b_t, c_t, t = at
        if reset_every is not None:
            h = jnp.where(t % reset_every == 0, 0.0, h)
        h = jnp.exp(dt_t * a)[..., None, None] * h \
            + (dt_t[..., None] * b_t)[..., :, None] * x_t[..., None, :]
        return h, jnp.sum(c_t[..., :, None] * h, axis=-2)

    segment = SEGMENT if length % SEGMENT == 0 else length
    walk = jax.checkpoint(lambda h, seg: jax.lax.scan(step, h, seg))

    def in_segments(t):  # (B, T, ...) -> (T / segment, segment, B, ...)
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape(length // segment, segment, *t.shape[1:])

    if state is None:
        state = jnp.zeros((batch, heads, n, width), jnp.float32)
    state, y = jax.lax.scan(walk, state, (
        in_segments(x), in_segments(dt), in_segments(b), in_segments(c),
        jnp.arange(length).reshape(length // segment, segment)))
    return jnp.moveaxis(y.reshape(length, batch, heads, width), 0, 1), state


def mamba_mixer(p, u, sizes, prec, fault=None, carried=None):
    """The ``M`` mixer on the normed stream ``u`` (B, T, hidden). ``carried``
    (tests): (the state, the last ``conv_kernel - 1`` convolution inputs) a
    sequence's first half left behind; then returns (output, those two)."""
    batch, length, _ = u.shape
    heads, width = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, n, taps = (sizes["n_groups"], sizes["ssm_state_size"],
                       sizes["conv_kernel"])
    inner = heads * width
    proj = dense(p["in_proj"], u, prec)
    z, conv_in, d = jnp.split(proj, [inner, 2 * inner + 2 * groups * n], -1)
    state = None
    if carried is not None:
        state, before = carried
        conv_in = jnp.concatenate([before.astype(conv_in.dtype), conv_in], 1)
    conv_out = causal_conv(p["conv"], conv_in, taps,
                           reverse=fault == "conv_reversed")
    if carried is not None:
        conv_out = conv_out[:, taps - 1:]
    x, b, c = jnp.split(conv_out.astype(prec.act),
                        [inner, inner + groups * n], -1)
    x = x.reshape(batch, length, heads, width)
    # head h reads group h // (heads / groups)
    b, c = (jnp.repeat(t.reshape(batch, length, groups, n), heads // groups,
                       axis=2) for t in (b, c))
    dt = jax.nn.softplus(d.astype(jnp.float32) + p["dt_bias"])
    x32, b32, c32 = (prec.operand(t).astype(jnp.float32) for t in (x, b, c))
    y, state = recurrence(
        x32, dt, -jnp.exp(p["A_log"]), b32, c32, state,
        sizes["chunk_size"] if fault == "state_dropped" else None)
    y = y + p["D"][:, None] * x.astype(jnp.float32)
    g = y.reshape(batch, length, inner) * jax.nn.silu(z.astype(jnp.float32))
    grouped = g.reshape(batch, length, groups, inner // groups)
    var = jnp.mean(jnp.square(grouped), -1, keepdims=True)
    normed = (grouped * jax.lax.rsqrt(var + sizes["layer_norm_epsilon"])
              ).reshape(g.shape) * p["gate_norm"]["scale"]
    out = dense(p["out_proj"], normed.astype(prec.act), prec)
    if carried is not None:
        return out, (state, conv_in[:, -(taps - 1):])
    return out


def relu2_mlp(p, x, prec, act=relu2):
    up = dense(p["up_proj"], x, prec)
    return dense(p["down_proj"],
                 act(up.astype(jnp.float32)).astype(prec.act), prec)


def expert_layer(p, u, sizes, prec, fault=None):
    """(output, assignment counts over the router's whole width)."""
    batch, length, d = u.shape
    tokens = u.reshape(batch * length, d)
    act = jax.nn.relu if fault == "relu" else relu2
    experts, weights = route(p, tokens, sizes, sizes["num_experts_per_tok"])
    hist = jnp.zeros((sizes["router_width"],), jnp.int32).at[
        experts.reshape(-1)].add(1)
    out = relu2_mlp(p["shared"], tokens, prec, act).astype(jnp.float32)

    @jax.checkpoint
    def one(w_up, w_down, weight):
        up = prec.einsum("ti,if->tf", tokens, w_up)
        y = prec.einsum("tf,fo->to",
                        act(up.astype(jnp.float32)).astype(prec.act), w_down)
        return weight[:, None] * y.astype(jnp.float32)

    def add_expert(out, expert):  # one held expert, every token through it
        w_up, w_down, e = expert
        mine = experts == sizes["first_expert"] + e
        return out + one(w_up, w_down,
                         jnp.sum(jnp.where(mine, weights, 0.0), -1)), None

    out, _ = jax.lax.scan(add_expert, out, (
        p["w_up"], p["w_down"], jnp.arange(sizes["n_routed_experts"])))
    return out.astype(prec.act).reshape(batch, length, d), hist


def block(lp, x, sizes, kind, prec=F32, fault=None):
    """One layer on the stream ``x``: (the stream, assignment counts or
    None)."""
    u = rms_norm(lp["norm"], x, sizes["layer_norm_epsilon"], prec)
    mp, hist = lp[MIXERS[kind]], None
    if kind == "M":
        out = mamba_mixer(mp, u, sizes, prec, fault)
    elif kind == "E":
        out, hist = expert_layer(mp, u, sizes, prec, fault)
    else:
        out = swa_lm_model.attention(mp, u, sizes, prec, None, False)
    return x + out.astype(x.dtype), hist


def hidden(params, tokens, sizes, prec=F32, fault=None):
    """tokens (B, S) -> (the final norm's output (B, S, hidden), assignment
    counts (expert layers, router width))."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    p = params["params"]
    x = p["embed"]["embedding"][tokens].astype(prec.act)
    hists = []
    for i, kind in enumerate(layer_kinds(sizes)):
        # a layer is recomputed in the backward pass
        x, hist = jax.checkpoint(
            lambda lp, x, kind=kind: block(lp, x, sizes, kind, prec, fault))(
            p[f"layer_{i}"], x)
        if hist is not None:
            hists.append(hist)
    return rms_norm(p["final_norm"], x, sizes["layer_norm_epsilon"], prec), \
        jnp.stack(hists)


def forward(params, tokens, sizes, prec=F32, fault=None):
    """tokens (B, S) -> (float32 logits (B, S, vocab), assignment counts)."""
    x, hists = hidden(params, tokens, sizes, prec, fault)
    kernel = params["params"]["head"]["kernel"]
    return prec.einsum("bsi,iv->bsv", x, kernel, jnp.float32), hists


def nll_sum(params, tokens, sizes, prec=F32, fault=None):
    """(sum over positions 0..S-2 of -log softmax(logits[i])[tokens[i+1]],
    assignment counts), the head and the softmax in blocks of positions
    recomputed in the backward pass (``lm_model.py`` ``nll_sum`` for this
    model: that one names its own ``hidden``)."""
    x, hists = hidden(params, tokens, sizes, prec, fault)
    kernel = params["params"]["head"]["kernel"]
    b, s, d = x.shape
    block_len = s
    while block_len > 1 and block_len % 2 == 0 \
            and b * block_len * kernel.shape[1] * 4 > LOGIT_BLOCK_BYTES // 4:
        block_len //= 2

    @jax.checkpoint
    def one(args):
        xb, targets, weight = args
        logits = prec.einsum("bsi,iv->bsv", xb, kernel, jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * weight)

    # position i is scored against token i + 1; the last has none
    targets = jnp.roll(tokens, -1, axis=1)
    weight = jnp.broadcast_to(
        (jnp.arange(s) < s - 1).astype(jnp.float32), (b, s))

    def blocks(t):  # (B, S, ...) -> (S / block, B, block, ...)
        return jnp.moveaxis(
            t.reshape(b, s // block_len, block_len, *t.shape[2:]), 1, 0)

    if block_len == s:
        total = one((x, targets, weight))
    else:
        total = jnp.sum(jax.lax.map(
            one, (blocks(x), blocks(targets), blocks(weight))))
    return total, hists


def loss_fn(params, tokens, sizes, prec=F32, fault=None):
    """(mean next-token cross-entropy over positions 0..S-2, assignment
    counts)."""
    b, s = tokens.shape
    total, hists = nll_sum(params, tokens, sizes, prec, fault)
    return total / (b * (s - 1)), hists


# -------------------------------------------------------------- optimizer ---


@functools.partial(jax.jit, static_argnames=("sizes_key", "prec", "fault"))
def _loss_and_grad(params, tokens, sizes_key, prec, fault):
    (loss, hists), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, tokens, dict(sizes_key), prec, fault)
    raw = leaf_norms(grads)
    norm = jnp.sqrt(sum(jnp.square(v) for v in raw.values()))
    # clipping to global norm 1 is this factor on every leaf; _adam applies
    # it, so no second copy of the gradients is made
    return loss, grads, raw, jnp.where(norm < 1.0, 1.0, 1.0 / norm), hists


def train_steps(params, batches, sizes: dict, opt: dict, prec=F32,
                fault=None) -> dict:
    """``reference/lm_model.py`` ``train_steps`` for this model (that one
    names its own loss): follow the first ``len(batches)`` optimizer steps
    from ``params`` (which are consumed). Returns each step's loss, the
    per-leaf norms of the first gradient (clipped, and raw), the per-leaf
    norms of the parameters' change over the steps, and step 0's assignment
    counts (expert layers, router width). The start waits on the host
    throughout, Adam's two moments between updates."""
    sizes_key = tuple(sorted(sizes.items()))
    start = jax.device_get(params)
    mu = nu = None
    losses, first, first_raw, hist0 = [], None, None, None
    for t, tokens in enumerate(batches):
        loss, grads, raw, clip, hists = _loss_and_grad(
            params, tokens, sizes_key=sizes_key, prec=prec, fault=fault)
        if t == 0:
            first_raw, hist0 = raw, hists
            first = {k: v * clip for k, v in raw.items()}
            mu = jax.tree.map(jnp.zeros_like, params)
            nu = jax.tree.map(jnp.zeros_like, params)
        params, mu, nu = _adam(
            params, mu, nu, grads, clip,
            jnp.float32(learning_rate(t, opt)), jnp.float32(t + 1))
        del grads
        if t + 1 < len(batches):
            mu, nu = jax.device_get((mu, nu))
        losses.append(loss)
    del mu, nu
    change = leaf_norms(jax.tree.map(lambda a, b: a - b, params, start))
    return jax.device_get({
        "losses": losses, "grad_norms": first, "raw_grad_norms": first_raw,
        "change_norms": change, "route_hist": hist0,
    })
