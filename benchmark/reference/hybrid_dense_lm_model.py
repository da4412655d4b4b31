"""The plain reference of the ``train_hybrid_dense_lm`` cells: a dense hybrid
decoder whose every layer is a mixer (a Mamba-2 state-space mixer or
positionless grouped-query attention) and then a gated MLP, each added to the
stream times a residual multiplier, with a scaled embedding and a tied,
scaled head; next-token loss, gradients and Adam, for ONE pipeline stage's
share of the model (its first layers, a slice of the vocabulary).

Straightforward ``jax.numpy`` written from the published configuration
(``model_type`` granitemoehybrid with ``num_local_experts`` 0; the equations
are in the configuration's file and PERF.md section 4), float32 under
``jax.default_matmul_precision("highest")`` (every product also asks for
``precision="highest"`` itself). It imports nothing of ``alphafold2_tpu``;
``Precision``, ``rms_norm``, ``dense``, ``swiglu`` and the Adam update are
``reference/lm_model.py``'s, the masked attention in query blocks is
``reference/swa_lm_model.py``'s, the state-space mixer (the per-step
recurrence, one time step at a time) is ``reference/ssm_lm_model.py``'s read
through this configuration's key names, the learning-rate schedule and the
per-leaf norms ``reference/model.py``'s. It is handed nothing the program
made: weights come from :func:`init_params`, tokens from
``harness/traffic_lm.py``.

With ``r = residual_multiplier`` and ``E`` the embedding table (vocabulary
rows held here x hidden), no biases but the convolution's:

- ``x_0 = embedding_multiplier * E[tokens]``;
- layer ``l`` (``layer_types[l]``): ``h = x + r * Mixer_l(RMSNorm(x))``, then
  ``x' = h + r * W_down (silu(W_gate y) * W_up y)``, ``y = RMSNorm(h)`` (the
  source's ``shared_mlp``, whose ``input_linear`` is ``[W_gate, W_up]`` side
  by side: two leaves here, the same product);
- ``mamba``: ``ssm_lm_model.mamba_mixer``: ``[z, c, d] = W_in u``; the causal
  depthwise convolution with bias and SiLU; ``x`` (heads x width), ``B``,
  ``C`` (one group x N, read by every head); ``dt = softplus(d + dt_bias)``,
  ``A = -exp(A_log)``; the recurrence ``H_t = exp(dt_t A) H_{t-1} + dt_t B_t
  (x) x_t``, ``y_t = C_t^T H_t + D x_t`` as a ``lax.scan`` over time steps
  (no chunk, no decay matrix); ``RMSNorm(y * silu(z))`` over the one group
  of all channels; ``W_out``;
- ``attention``: ``q = W_q u`` (H heads), ``k``, ``v`` (G heads); no
  positional encoding; query head h reads key/value head h // (H / G); query
  i sees keys j <= i; ``softmax(q k^T * attention_multiplier)``; ``W_o``.
  Dense, in blocks of queries;
- ``logits = RMSNorm(x_L) E^T / logits_scaling`` over the same ``E``; the
  mean next-token cross-entropy over positions 0..S-2.

Departures from the published model: none in the equations. The time steps
are walked in segments of 128 under ``jax.checkpoint`` and a layer is
recomputed in the backward pass (memory; the arithmetic is the same). What
``config.json`` does not say is listed under ``assumed`` in the
configuration's file.

``fault`` plants a mistake for reading the limits: ``residual_one``
(``residual_multiplier`` taken as 1), ``scale_sqrt`` (the softmax scale
``head_dim ** -0.5`` in place of ``attention_multiplier``), ``state_dropped``
(the state set to zero at every multiple of ``mamba_chunk_size`` steps: a
chunked scan that forgets to carry), ``head_untied`` (the logits over a
second table drawn from another key: the embedding's gradient loses the
head's half), ``no_logits_scaling`` (the logits not divided).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import ssm_lm_model
from benchmark.reference.lm_model import (
    F32, LOGIT_BLOCK_BYTES, _adam, _is_shape, dense, rms_norm, swiglu,
)
from benchmark.reference.model import leaf_norms, learning_rate
from benchmark.reference.swa_lm_model import masked_attend

FAULTS = (None, "residual_one", "scale_sqrt", "state_dropped", "head_untied",
          "no_logits_scaling")
SIZE_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "layer_types",
    "intermediate_size", "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
    "mamba_d_state", "mamba_d_conv", "mamba_chunk_size",
    "time_step_min", "time_step_max", "time_step_floor",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "embedding_multiplier", "residual_multiplier", "attention_multiplier",
    "logits_scaling", "rms_norm_eps",
)
MIXERS = {"mamba": "ssm", "attention": "attn_global"}

# ------------------------------------------------------------- parameters ---


def model_sizes(config: dict) -> dict:
    """The sizes this file reads, hashable: ``layer_types`` as a tuple."""
    return {k: tuple(config[k]) if k == "layer_types" else config[k]
            for k in SIZE_KEYS}


def layer_kinds(sizes: dict) -> tuple:
    """``layer_types``' first ``num_hidden_layers`` entries, one a layer."""
    return tuple(sizes["layer_types"][:sizes["num_hidden_layers"]])


def ssm_sizes(sizes: dict) -> dict:
    """This configuration's state-space sizes under the names
    ``ssm_lm_model.mamba_mixer`` reads them by."""
    return {
        "mamba_num_heads": sizes["mamba_n_heads"],
        "mamba_head_dim": sizes["mamba_d_head"],
        "n_groups": sizes["mamba_n_groups"],
        "ssm_state_size": sizes["mamba_d_state"],
        "conv_kernel": sizes["mamba_d_conv"],
        "chunk_size": sizes["mamba_chunk_size"],
        "layer_norm_epsilon": sizes["rms_norm_eps"],
    }


def param_shapes(sizes: dict) -> dict:
    """The parameter tree's shapes from the configuration's sizes alone. No
    head: the logits read the embedding's table."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    heads = sizes["mamba_n_heads"]
    inner = heads * sizes["mamba_d_head"]
    conv = inner + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    q_heads, kv_heads, width = (sizes["num_attention_heads"],
                                sizes["num_key_value_heads"],
                                sizes["head_dim"])
    mixers = {
        "mamba": {
            "in_proj": {"kernel": (d, inner + conv + heads)},
            "conv": {"kernel": (conv, sizes["mamba_d_conv"]),
                     "bias": (conv,)},
            "A_log": (heads,), "dt_bias": (heads,), "D": (heads,),
            "gate_norm": {"scale": (inner,)},
            "out_proj": {"kernel": (inner, d)},
        },
        "attention": {
            "q_proj": {"kernel": (d, q_heads * width)},
            "k_proj": {"kernel": (d, kv_heads * width)},
            "v_proj": {"kernel": (d, kv_heads * width)},
            "o_proj": {"kernel": (q_heads * width, d)},
        },
    }
    return {"params": {
        "embed": {"embedding": (sizes["vocab_size"], d)},
        **{f"layer_{i}": {
            "mixer_norm": {"scale": (d,)}, MIXERS[kind]: mixers[kind],
            "ffn_norm": {"scale": (d,)},
            "dense_ffn": {"gate_proj": {"kernel": (d, f)},
                          "up_proj": {"kernel": (d, f)},
                          "down_proj": {"kernel": (f, d)}},
        } for i, kind in enumerate(layer_kinds(sizes))},
        "final_norm": {"scale": (d,)},
    }}


def init_params(sizes: dict, seed: int) -> dict:
    """Float32 weights from ``seed`` in one jitted call on the device, as
    ``ssm_lm_model.init_params`` draws them, but for the table: matrices
    normal with variance 1 / fan-in, norm scales one; the table's entries
    normal with variance 1 / hidden, so a row has length 1 (with rows of
    variance 1, as the other cells', the tie makes a token's logit for
    itself ``|E_t|^2 / logits_scaling`` = hidden / 8 = 256 at the published
    width, the softmax saturates on the token just read and neither the
    loss nor a gradient sees the layers; at length 1 that logit is
    sqrt(hidden) / 8 = 5.7 and the entering stream's root mean square is
    ``embedding_multiplier`` / sqrt(hidden) = 0.27, beside which a layer's
    0.22 x output counts); the state-space leaves as the family publishes them (``A_log = log a``, ``a``
    uniform on [1, 16]; ``dt_bias = softplus^-1(dt)``, ``dt`` log-uniform on
    [time_step_min, time_step_max] floored at time_step_floor; ``D = 1``;
    the depthwise convolution's weights and bias uniform on
    +-1/sqrt(taps))."""
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
            elif kind == "A_log":
                out.append(jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0)))
            elif kind == "dt_bias":
                dt = jnp.maximum(jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(t_min),
                    math.log(t_max))), sizes["time_step_floor"])
                out.append(dt + jnp.log(-jnp.expm1(-dt)))
            elif path[-2].key == "conv":
                bound = sizes["mamba_d_conv"] ** -0.5
                out.append(jax.random.uniform(
                    k, shape, jnp.float32, -bound, bound))
            else:
                fan_in = shape[-1 if kind == "embedding" else -2]
                out.append(jax.random.normal(k, shape, jnp.float32)
                           * fan_in ** -0.5)
        return out

    return jax.tree.unflatten(
        jax.tree.structure(shapes, is_leaf=_is_shape),
        make(jax.random.key(seed)),
    )


# ---------------------------------------------------------------- forward ---


def attention(p, u, sizes, prec, scale):
    """Full causal grouped-query attention without positions on the normed
    stream ``u`` (B, S, hidden), the logits times ``scale``."""
    b, s, _ = u.shape
    heads, groups, width = (sizes["num_attention_heads"],
                            sizes["num_key_value_heads"], sizes["head_dim"])
    q = dense(p["q_proj"], u, prec).reshape(b, s, heads, width)
    k = dense(p["k_proj"], u, prec).reshape(b, s, groups, width)
    v = dense(p["v_proj"], u, prec).reshape(b, s, groups, width)
    # query head h reads key/value head h // (heads / groups)
    k, v = (jnp.repeat(t, heads // groups, axis=2) for t in (k, v))

    def flat(t):  # (B, S, H, D) -> (B*H, S, D)
        return t.swapaxes(1, 2).reshape(b * heads, s, width)

    out = masked_attend(flat(q), flat(k), flat(v), scale, prec)
    out = out.reshape(b, heads, s, width).swapaxes(1, 2).reshape(
        b, s, heads * width)
    return dense(p["o_proj"], out, prec)


def block(lp, x, sizes, kind, prec=F32, fault=None):
    """One layer on the stream ``x``: the mixer, then the gated MLP."""
    eps = sizes["rms_norm_eps"]
    r = 1.0 if fault == "residual_one" else sizes["residual_multiplier"]
    u = rms_norm(lp["mixer_norm"], x, eps, prec)
    if kind == "mamba":
        out = ssm_lm_model.mamba_mixer(
            lp["ssm"], u, ssm_sizes(sizes), prec,
            "state_dropped" if fault == "state_dropped" else None)
    else:
        scale = sizes["head_dim"] ** -0.5 if fault == "scale_sqrt" \
            else sizes["attention_multiplier"]
        out = attention(lp["attn_global"], u, sizes, prec, scale)
    h = (x.astype(jnp.float32) + r * out.astype(jnp.float32)).astype(x.dtype)
    out = swiglu(lp["dense_ffn"], rms_norm(lp["ffn_norm"], h, eps, prec),
                 prec)
    return (h.astype(jnp.float32) + r * out.astype(jnp.float32)).astype(
        x.dtype)


def hidden(params, tokens, sizes, prec=F32, fault=None):
    """tokens (B, S) -> (the final norm's output (B, S, hidden), the root
    mean square of the stream entering the first layer and of the stream
    leaving the last)."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    p = params["params"]
    x = (p["embed"]["embedding"][tokens]
         * sizes["embedding_multiplier"]).astype(prec.act)
    rms = [jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32))))]
    for i, kind in enumerate(layer_kinds(sizes)):
        # a layer is recomputed in the backward pass
        x = jax.checkpoint(
            lambda lp, x, kind=kind: block(lp, x, sizes, kind, prec, fault))(
            p[f"layer_{i}"], x)
    rms.append(jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32)))))
    return rms_norm(p["final_norm"], x, sizes["rms_norm_eps"], prec), \
        jnp.stack(rms)


def head_table(params, sizes, fault=None, table=None):
    """The table the logits are taken over: the embedding's own (the tie);
    ``table`` (tests: the untied form, a second leaf); under ``head_untied``
    a second table drawn from a key of its own."""
    own = params["params"]["embed"]["embedding"]
    if fault == "head_untied":
        return jax.random.normal(jax.random.key(20_251_002), own.shape,
                                 jnp.float32)
    return own if table is None else table


def forward(params, tokens, sizes, prec=F32, fault=None, table=None):
    """tokens (B, S) -> (float32 logits (B, S, vocab), the stream's two root
    mean squares)."""
    x, rms = hidden(params, tokens, sizes, prec, fault)
    logits = prec.einsum("bsi,vi->bsv", x,
                         head_table(params, sizes, fault, table), jnp.float32)
    if fault != "no_logits_scaling":
        logits = logits / sizes["logits_scaling"]
    return logits, rms


def nll_sum(params, tokens, sizes, prec=F32, fault=None, table=None):
    """(sum over positions 0..S-2 of -log softmax(logits[i])[tokens[i+1]],
    the stream's two root mean squares), the head and the softmax in blocks
    of positions recomputed in the backward pass."""
    x, rms = hidden(params, tokens, sizes, prec, fault)
    table = head_table(params, sizes, fault, table)
    scaling = 1.0 if fault == "no_logits_scaling" else sizes["logits_scaling"]
    b, s, d = x.shape
    block_len = s
    while block_len > 1 and block_len % 2 == 0 \
            and b * block_len * table.shape[0] * 4 > LOGIT_BLOCK_BYTES // 4:
        block_len //= 2

    @jax.checkpoint
    def one(args):
        xb, targets, weight = args
        logits = prec.einsum("bsi,vi->bsv", xb, table, jnp.float32) / scaling
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
    return total, rms


def loss_fn(params, tokens, sizes, prec=F32, fault=None, table=None):
    """(mean next-token cross-entropy over positions 0..S-2, the stream's
    two root mean squares)."""
    b, s = tokens.shape
    total, rms = nll_sum(params, tokens, sizes, prec, fault, table)
    return total / (b * (s - 1)), rms


# -------------------------------------------------------------- optimizer ---


@functools.partial(jax.jit, static_argnames=("sizes_key", "prec", "fault"))
def _loss_and_grad(params, tokens, sizes_key, prec, fault):
    with jax.default_matmul_precision("highest"):
        (loss, rms), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, dict(sizes_key), prec, fault)
    raw = leaf_norms(grads)
    norm = jnp.sqrt(sum(jnp.square(v) for v in raw.values()))
    # clipping to global norm 1 is this factor on every leaf; _adam applies
    # it, so no second copy of the gradients is made
    return loss, grads, raw, jnp.where(norm < 1.0, 1.0, 1.0 / norm), rms


def train_steps(params, batches, sizes: dict, opt: dict, prec=F32,
                fault=None) -> dict:
    """Follow the first ``len(batches)`` optimizer steps from ``params``
    (which are consumed). Returns each step's loss, the per-leaf norms of the
    first gradient (clipped, and raw), the per-leaf norms of the parameters'
    change over the steps, and step 0's two root mean squares of the stream.
    The start waits on the host throughout, Adam's two moments between
    updates: at 772 M parameters the weights, the gradients and both
    moments are 12.4 GB, all the chip holds while an update runs."""
    sizes_key = tuple(sorted(sizes.items()))
    start = jax.device_get(params)
    mu = nu = None
    losses, first, first_raw, rms0 = [], None, None, None
    for t, tokens in enumerate(batches):
        loss, grads, raw, clip, rms = _loss_and_grad(
            params, tokens, sizes_key=sizes_key, prec=prec, fault=fault)
        if t == 0:
            first_raw, rms0 = raw, rms
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
        "change_norms": change, "stream_rms": rms0,
    })

