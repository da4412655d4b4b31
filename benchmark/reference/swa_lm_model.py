"""The plain reference of the ``train_swa_lm`` cells: grouped-query attention
with one global layer among window layers, a softmax router that reads the
layer's input, ReGLU experts, next-token loss, gradients and Adam, for ONE
chip's share of an expert-parallel layer.

Straightforward ``jax.numpy`` written from the published configuration (the
equations are in the configuration's file and PERF.md section 4). It imports
nothing of ``alphafold2_tpu``; ``Precision``, ``rms_norm``, ``dense`` and the
Adam update are ``reference/lm_model.py``'s, the learning-rate schedule and
the per-leaf norms ``reference/model.py``'s. It is handed nothing the program
made: weights come from :func:`init_params`, tokens from
``harness/traffic_lm.py``.

A pre-norm block on the stream ``x`` entering layer ``l``, no biases:

- router, before attention and on the raw stream: ``r = W_r x`` in float32,
  ``E`` the ``top_k`` largest, ``w = softmax(r[E])`` over the selected;
- attention: ``a = RMSNorm(x)``; ``q = W_q a`` (H heads), ``k = W_k a``, ``v =
  W_v a`` (G heads each); where ``rope_layout[l]`` is 1, q and k are turned
  by rotary positions over the whole head, pairs (i, i + width/2), angle
  ``pos * theta**(-2i/width)``; query head h reads key/value head h // (H/G)
  (keys and values repeated to H heads here: plain, not the program's way);
  query i sees keys j <= i, and where ``sliding_window_layout[l]`` is 1 only
  i - window < j; ``softmax(q k^T / sqrt(width))``; ``h = x + W_o attn``.
  Dense, in blocks of queries so that 28 heads x 16,384^2 logits fit;
- experts: ``y = RMSNorm(h)``; ``x' = h + sum over the experts HELD HERE
  and in E of w_e W_down,e (relu(W_gate,e y) * W_up,e y)``: a plain loop over
  the held experts with a mask, every token through every held expert. What
  the absent experts would add is left out, as in the program: the same
  share;
- embedding, final RMSNorm, an untied head over the vocabulary rows held
  here, the mean next-token cross-entropy over positions 0..S-2.

Departures from the published model: none in the equations; what
``config.json`` does not say (the expert's activation, the router's input,
the rotary convention, the window's count) is listed under ``assumed`` in
the configuration's file. No auxiliary balancing loss.

``fault`` plants a mistake for reading the limits: ``window_off`` (window
layers run full causal), ``rope_on_global`` (the global layers turned by
rotary positions too), ``route_from_y`` (the router reads the normed
post-attention stream, as the other language model's does).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.lm_model import (
    F32, LOGIT_BLOCK_BYTES, _adam, _is_shape, dense, rms_norm,
)
from benchmark.reference.model import leaf_norms, learning_rate

FAULTS = (None, "window_off", "rope_on_global", "route_from_y")
SIZE_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "sliding_window_size",
    "sliding_window_layout", "rope_layout", "moe_ffn_hidden_size",
    "moe_num_primary_experts", "router_width", "first_expert",
    "moe_num_active_primary_experts", "rope_theta", "rms_norm_eps",
)

# ------------------------------------------------------------- parameters ---


def attention_name(sizes: dict, i: int) -> str:
    return "attn_window" if sizes["sliding_window_layout"][i] else \
        "attn_global"


def param_shapes(sizes: dict) -> dict:
    """The parameter tree's shapes from the configuration's sizes alone.
    ``moe_num_primary_experts`` experts are held (stacked leaves), of the
    ``router_width`` the router scores."""
    d, width = sizes["hidden_size"], sizes["head_dim"]
    heads, groups = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    held, f = sizes["moe_num_primary_experts"], sizes["moe_ffn_hidden_size"]

    def layer(i):
        return {
            "attn_norm": {"scale": (d,)}, "ffn_norm": {"scale": (d,)},
            attention_name(sizes, i): {
                "q_proj": {"kernel": (d, heads * width)},
                "k_proj": {"kernel": (d, groups * width)},
                "v_proj": {"kernel": (d, groups * width)},
                "o_proj": {"kernel": (heads * width, d)},
            },
            "moe": {
                "router": (d, sizes["router_width"]),
                "w_gate": (held, d, f), "w_up": (held, d, f),
                "w_down": (held, f, d),
            },
        }

    return {"params": {
        "embed": {"embedding": (sizes["vocab_size"], d)},
        **{f"layer_{i}": layer(i)
           for i in range(sizes["num_hidden_layers"])},
        "final_norm": {"scale": (d,)},
        "head": {"kernel": (d, sizes["vocab_size"])},
    }}


def init_params(sizes: dict, seed: int) -> dict:
    """Float32 weights from ``seed`` in one jitted call on the device:
    matrices normal with variance 1 / fan-in (a stacked expert leaf's fan-in
    is its middle axis), the table's rows normal with variance 1 (as PaLM
    initialises: fan-in scaling for kernels, unit variance for the input
    embedding, which no norm precedes), norm scales one.

    The table's scale decides what the routers see at these weights, because
    they read the raw stream. With variance 1 / width (``lm_model.py``'s) a
    token's own vector has norm 1 beside sublayer outputs of norm 5-7 whose
    largest part is common to all positions (a mean of values over the
    prefix), so from the second layer on nearly every token picks the same
    six experts: 65-83% of a layer's assignments, and the rows the held
    experts get are 0 or 16,384 an expert by the seed's luck (a step 4%
    faster or slower). With unit-variance rows the token decides: no six
    experts get more than 24% (PERF.md section 6, PR 32)."""
    shapes = param_shapes(sizes)
    leaves, _ = jax.tree.flatten_with_path(shapes, is_leaf=_is_shape)

    @jax.jit
    def make(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            kind = path[-1].key
            if kind == "scale":
                out.append(jnp.ones(shape, jnp.float32))
            else:
                fan_in = 1 if kind == "embedding" else shape[-2]
                out.append(
                    jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * fan_in ** -0.5)
        return out

    return jax.tree.unflatten(
        jax.tree.structure(shapes, is_leaf=_is_shape),
        make(jax.random.key(seed)),
    )


# ---------------------------------------------------------------- forward ---


def rotary(x, theta, positions=None):
    """Pairs (i, i + width/2) of the last axis of ``x`` (B, S, H, width)
    turned by ``pos * theta**(-2i / width)``."""
    s, half = x.shape[1], x.shape[-1] // 2
    if positions is None:
        positions = jnp.arange(s)
    inv_freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32)
                         / x.shape[-1])
    angle = positions.astype(jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    low, high = x32[..., :half], x32[..., half:]
    return jnp.concatenate(
        [low * cos - high * sin, high * cos + low * sin], -1).astype(x.dtype)


def masked_attend(q, k, v, scale, prec, window=None):
    """softmax(q k^T * scale, keys j <= i for query i, and i - j < window
    where one is given) v over (N, S, D) each, in blocks of queries."""
    n, s, _ = q.shape
    block = s
    while block > 1 and block % 2 == 0 \
            and n * block * s * 4 > LOGIT_BLOCK_BYTES:
        block //= 2
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qb, start = args
        logits = prec.einsum("nid,njd->nij", qb, k, jnp.float32) * scale
        ahead = (start + jnp.arange(block))[:, None] - key_pos[None, :]
        keep = ahead >= 0
        if window is not None:
            keep = keep & (ahead < window)
        probs = jax.nn.softmax(
            jnp.where(keep[None], logits, -jnp.inf), axis=-1).astype(prec.act)
        return prec.einsum("nij,njd->nid", probs, v)

    if block == s:
        return one((q, 0))
    qs = q.reshape(n, s // block, block, q.shape[-1]).swapaxes(0, 1)
    out = jax.lax.map(one, (qs, jnp.arange(0, s, block)))
    return out.swapaxes(0, 1).reshape(n, s, v.shape[-1])


def attention(p, x, sizes, prec, window, rope, positions=None):
    """``window``: keys a query sees (None: all before it); ``rope``: whether
    q and k are turned by rotary positions."""
    b, s, _ = x.shape
    heads, groups, width = (sizes["num_attention_heads"],
                            sizes["num_key_value_heads"], sizes["head_dim"])
    q = dense(p["q_proj"], x, prec).reshape(b, s, heads, width)
    k = dense(p["k_proj"], x, prec).reshape(b, s, groups, width)
    v = dense(p["v_proj"], x, prec).reshape(b, s, groups, width)
    if rope:
        q = rotary(q, sizes["rope_theta"], positions)
        k = rotary(k, sizes["rope_theta"], positions)
    # query head h reads key/value head h // (heads / groups)
    k, v = (jnp.repeat(t, heads // groups, axis=2) for t in (k, v))

    def flat(t):  # (B, S, H, D) -> (B*H, S, D)
        return t.swapaxes(1, 2).reshape(b * heads, s, width)

    out = masked_attend(flat(q), flat(k), flat(v), width ** -0.5, prec,
                        window)
    out = out.reshape(b, heads, s, width).swapaxes(1, 2).reshape(
        b, s, heads * width)
    return dense(p["o_proj"], out, prec)


def route(p, x, top_k):
    """(experts (T, k), weights (T, k)) in float32, over the router's whole
    width: the top_k largest logits, softmax over the selected."""
    logits = jnp.einsum("ti,ie->te", x.astype(jnp.float32), p["router"],
                        precision="highest")
    picked, experts = jax.lax.top_k(logits, top_k)
    return experts, jax.nn.softmax(picked, axis=-1)


def expert_layer(p, y, route_from, sizes, prec):
    """(sum over the held experts of w_e Expert_e(y), assignment counts over
    the router's whole width); experts and weights chosen from
    ``route_from``."""
    b, s, d = y.shape
    tokens = y.reshape(b * s, d)
    experts, weights = route(p, route_from.reshape(b * s, d),
                             sizes["moe_num_active_primary_experts"])
    hist = jnp.zeros((sizes["router_width"],), jnp.int32).at[
        experts.reshape(-1)].add(1)

    @jax.checkpoint
    def one(w_gate, w_up, w_down, weight):
        gate = prec.einsum("ti,if->tf", tokens, w_gate)
        act = jax.nn.relu(gate.astype(jnp.float32)).astype(prec.act)
        out = prec.einsum("tf,fo->to", act * prec.einsum(
            "ti,if->tf", tokens, w_up), w_down)
        return weight[:, None] * out.astype(jnp.float32)

    def add_expert(out, expert):  # one held expert, every token through it
        w_gate, w_up, w_down, e = expert
        mine = experts == sizes["first_expert"] + e
        return out + one(w_gate, w_up, w_down,
                         jnp.sum(jnp.where(mine, weights, 0.0), -1)), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros((b * s, d), jnp.float32),
        (p["w_gate"], p["w_up"], p["w_down"],
         jnp.arange(sizes["moe_num_primary_experts"])))
    return out.astype(prec.act).reshape(b, s, d), hist


def block(lp, x, sizes, i, prec=F32, fault=None, positions=None):
    """Layer ``i`` on the stream ``x``: (the stream, assignment counts)."""
    eps = sizes["rms_norm_eps"]
    windowed = bool(sizes["sliding_window_layout"][i])
    window = sizes["sliding_window_size"] \
        if windowed and fault != "window_off" else None
    rope = bool(sizes["rope_layout"][i]) or fault == "rope_on_global"
    h = x + jax.checkpoint(
        lambda ap, a: attention(ap, a, sizes, prec, window, rope, positions))(
        lp[attention_name(sizes, i)], rms_norm(lp["attn_norm"], x, eps, prec))
    y = rms_norm(lp["ffn_norm"], h, eps, prec)
    out, hist = jax.checkpoint(
        lambda mp, y, r: expert_layer(mp, y, r, sizes, prec))(
        lp["moe"], y, y if fault == "route_from_y" else x)
    return h + out, hist


def hidden(params, tokens, sizes, prec=F32, fault=None):
    """tokens (B, S) -> (the final norm's output (B, S, hidden), assignment
    counts (layers, router width))."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    p = params["params"]
    x = p["embed"]["embedding"][tokens].astype(prec.act)
    hists = []
    for i in range(sizes["num_hidden_layers"]):
        # a layer is recomputed in the backward pass, and inside it each
        # half again: what is held at once is one half's intermediates
        x, hist = jax.checkpoint(
            lambda lp, x, i=i: block(lp, x, sizes, i, prec, fault))(
            p[f"layer_{i}"], x)
        hists.append(hist)
    return rms_norm(p["final_norm"], x, sizes["rms_norm_eps"], prec), \
        jnp.stack(hists)


def forward(params, tokens, sizes, prec=F32, fault=None):
    """tokens (B, S) -> (float32 logits (B, S, vocab), assignment counts)."""
    x, hists = hidden(params, tokens, sizes, prec, fault)
    kernel = params["params"]["head"]["kernel"]
    return prec.einsum("bsi,iv->bsv", x, kernel, jnp.float32), hists


def nll_sum(params, tokens, sizes, prec=F32, fault=None):
    """(sum over positions 0..S-2 of -log softmax(logits[i])[tokens[i+1]],
    assignment counts). The head and the softmax run in blocks of positions,
    recomputed in the backward pass: whole, the float32 logits of 16,384
    tokens over 18,992 ids, their log-softmax and both gradients are 5 GB."""
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
    counts (layers, router width). The start waits on the host throughout,
    Adam's two moments between updates."""
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
