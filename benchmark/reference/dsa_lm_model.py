"""The plain reference of the ``train_dsa_lm`` cells: grouped-query attention
with a per-head q/k norm that reads, for each query, only the keys a learned
indexer scores highest (DeepSeek-style sparse attention), the indexer's own
loss, softmax-routed SwiGLU experts, next-token loss, gradients and Adam, for
ONE chip's share of an expert-parallel layer.

Straightforward ``jax.numpy`` written from the published configuration (the
equations are in the configuration's file and PERF.md section 4). It imports
nothing of ``alphafold2_tpu``; ``Precision``, ``rms_norm`` (the q/k norm's
too), ``dense`` and the Adam update are ``reference/lm_model.py``'s, the
rotary turn and the router ``reference/swa_lm_model.py``'s, the blocked head
and the expert loop that file's pattern, the learning-rate schedule and
the per-leaf norms ``reference/model.py``'s. It is handed nothing the
program made: weights come from :func:`init_params`, tokens from
``harness/traffic_lm.py``.

A pre-norm block on the stream ``x`` entering a layer, no biases, ``u =
RMSNorm(x)``:

- attention: ``q = W_q u`` (H heads), ``k = W_k u``, ``v = W_v u`` (G heads
  each); q and k each through an RMSNorm over the head's width (one scale a
  side), then rotary positions over the whole head, pairs (i, i + width/2),
  angle ``pos * theta**(-2i/width)``; query head h reads key/value head
  h // (H/G) (keys and values repeated to H heads here: plain, not the
  program's way);
- the indexer, on ``stop_gradient(u)``: ``qI = W_qI u`` (J heads of Di),
  ``kI = LayerNorm(W_kI u)`` (one head of Di), rotary on the first Di/2 of
  each, ``w = (W_w u) / sqrt(J Di)`` in float32; ``I[t, s] = sum_j w[t, j]
  relu(qI[t, j] . kI[s])`` for s <= t, float32;
- selection: ``S_t`` = the ``topk`` indices ``lax.top_k`` returns for row t
  of ``I`` (every s <= t while t < topk), written into a dense mask;
- ``softmax(q k^T / sqrt(width))`` over ``S_t`` alone, ``h = x + W_o
  attn``. Dense, a block of 256 queries at a time;
- the indexer's loss: ``p`` the attention's probabilities summed over the H
  heads and renormalised, stopped; ``KL(p_t || softmax_{S_t} I[t])``,
  summed over the queries and divided by B x S;
- experts: ``y = RMSNorm(h)``; the router's ``top_k`` largest logits ``W_r
  y`` in float32, weights their softmax over the selected; ``x' = h + sum
  over the experts HELD HERE and selected of w_e W_down,e (silu(W_gate,e y)
  * W_up,e y)``: a plain loop over the held experts with a mask, every
  token through every held expert. What the absent experts would add is
  left out, as in the program: the same share;
- embedding, final RMSNorm, an untied head over the vocabulary rows held
  here; the loss is the mean next-token cross-entropy over positions
  0..S-2 plus ``index_loss_weight`` times the layers' indexer losses.

Departures from the published model: the vision tower is not built (the
cell trains text, for which mRoPE's three position ids are the token's
index, so it is the 1-D rotary above); what ``config.json`` does not say
(the q/k norm, the indexer's norm, rotary, weight scale and precision, the
loss weight) is listed under ``assumed`` in the configuration's file. The
step-0 selection of every layer is handed back packed 32 queries a word
(the layout ``harness/train_dsa_lm.py`` reads; query ``256 i + 8 j + r`` is
bit ``j`` of word row ``8 i + r``), so that the harness can compare it with
the program's.

``fault`` plants a mistake for reading the limits: ``dense_causal`` (every
earlier key in place of the top-k), ``indexer_no_stop`` (the indexer reads
``u`` itself, so its loss reaches the main model), ``kl_all_causal`` (the
indexer's softmax over every earlier key in place of ``S_t``),
``no_qk_norm`` (q and k without their norm).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.lm_model import (
    F32, LOGIT_BLOCK_BYTES, _adam, _is_shape, dense, rms_norm,
)
from benchmark.reference.model import leaf_norms, learning_rate
from benchmark.reference.swa_lm_model import rotary, route

FAULTS = (None, "dense_causal", "indexer_no_stop", "kl_all_causal",
          "no_qk_norm")
QUERY_BLOCK = 256  # queries a block of the attention and the packing
LAYER_NORM_EPS = 1e-6


def model_sizes(config: dict) -> dict:
    """The sizes the reference reads, from a configuration file (the
    indexer's from its ``sa_config``)."""
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise SystemExit("the indexer has one key head")
    return {
        "vocab_size": config["vocab_size"],
        "hidden_size": config["hidden_size"],
        "num_hidden_layers": config["num_hidden_layers"],
        "num_attention_heads": config["num_attention_heads"],
        "num_key_value_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "moe_intermediate_size": config["moe_intermediate_size"],
        "num_experts": config["num_experts"],
        "router_width": config["router_width"],
        "first_expert": config["first_expert"],
        "num_experts_per_tok": config["num_experts_per_tok"],
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": config["rms_norm_eps"],
        "index_num_heads": sa["indexer_num_heads"],
        "index_head_dim": sa["indexer_head_dim"],
        "index_topk": sa["topk"],
        "index_loss_weight": config["index_loss_weight"],
    }


# ------------------------------------------------------------- parameters ---


def param_shapes(sizes: dict) -> dict:
    """The parameter tree's shapes from the sizes alone. ``num_experts``
    experts are held (stacked leaves), of the ``router_width`` the router
    scores."""
    d, width = sizes["hidden_size"], sizes["head_dim"]
    heads, groups = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    held, f = sizes["num_experts"], sizes["moe_intermediate_size"]
    ih, iw = sizes["index_num_heads"], sizes["index_head_dim"]
    layer = {
        "attn_norm": {"scale": (d,)}, "ffn_norm": {"scale": (d,)},
        "attn": {
            "q_proj": {"kernel": (d, heads * width)},
            "k_proj": {"kernel": (d, groups * width)},
            "v_proj": {"kernel": (d, groups * width)},
            "o_proj": {"kernel": (heads * width, d)},
            "q_norm": {"scale": (width,)}, "k_norm": {"scale": (width,)},
            "indexer": {
                "q_proj": {"kernel": (d, ih * iw)},
                "k_proj": {"kernel": (d, iw)},
                "k_norm": {"scale": (iw,), "bias": (iw,)},
                "weights_proj": (d, ih),
            },
        },
        "moe": {
            "router": (d, sizes["router_width"]),
            "w_gate": (held, d, f), "w_up": (held, d, f),
            "w_down": (held, f, d),
        },
    }
    return {"params": {
        "embed": {"embedding": (sizes["vocab_size"], d)},
        **{f"layer_{i}": layer for i in range(sizes["num_hidden_layers"])},
        "final_norm": {"scale": (d,)},
        "head": {"kernel": (d, sizes["vocab_size"])},
    }}


def init_params(sizes: dict, seed: int) -> dict:
    """Float32 weights from ``seed`` in one jitted call on the device:
    matrices normal with variance 1 / fan-in (a stacked expert leaf's fan-in
    is its middle axis), the table's rows normal with variance 1 (PaLM's:
    no norm precedes the first layer, and with rows of norm 1 the
    sublayers' output, most of it common to all positions at these weights,
    would decide the routing: ``reference/swa_lm_model.py`` has the
    reading), norm scales one, the LayerNorm's bias zero."""
    shapes = param_shapes(sizes)
    leaves, _ = jax.tree.flatten_with_path(shapes, is_leaf=_is_shape)

    @jax.jit
    def make(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            kind = path[-1].key
            if kind == "scale":
                out.append(jnp.ones(shape, jnp.float32))
            elif kind == "bias":
                out.append(jnp.zeros(shape, jnp.float32))
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


def layer_norm(p, x):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), -1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + LAYER_NORM_EPS) * p["scale"]
            + p["bias"]).astype(x.dtype)


def rotary_first_half(x, theta):
    half = x.shape[-1] // 2
    return jnp.concatenate([rotary(x[..., :half], theta), x[..., half:]], -1)


def pack(keep):
    """(B, S, S) bool -> (B, S / 32, S) int32: query ``256 i + 8 j + r`` is
    bit ``j`` of word row ``8 i + r``."""
    b, s, k = keep.shape
    bits = keep.reshape(b, s // 256, 32, 8, k).astype(jnp.uint32)
    shifted = bits << jnp.arange(32, dtype=jnp.uint32)[:, None, None]
    return jax.lax.bitcast_convert_type(
        shifted.sum(2, dtype=jnp.uint32), jnp.int32).reshape(b, s // 32, k)


def attend_block(args, k, v, k_i, sizes, prec, fault):
    """One block of queries: (the attention's output (B, C, H, width), the
    indexer's KL summed over the block's queries, the block's selection
    (B, C, S) bool)."""
    q, q_i, w, start = args
    b, c, heads, width = q.shape
    s = k.shape[1]
    causal = (start + jnp.arange(c))[:, None] >= jnp.arange(s)[None, :]
    logits_i = prec.einsum("bcjd,bsd->bcjs", q_i, k_i, jnp.float32)
    scores = jnp.einsum("bcjs,bcj->bcs", jax.nn.relu(logits_i), w,
                        precision="highest")
    scores = jnp.where(causal, scores, -jnp.inf)
    topk = sizes["index_topk"]
    if fault == "dense_causal" or topk >= s:
        keep = jnp.broadcast_to(causal, (b, c, s))
    else:
        _, picked = jax.lax.top_k(jax.lax.stop_gradient(scores), topk)
        keep = jnp.zeros((b, c, s), bool).at[
            jnp.arange(b)[:, None, None], jnp.arange(c)[None, :, None],
            picked].set(True) & causal
    logits = prec.einsum("bchd,bshd->bhcs", q, k, jnp.float32) \
        * width ** -0.5
    probs = jax.nn.softmax(
        jnp.where(keep[:, None], logits, -jnp.inf), axis=-1)
    out = prec.einsum("bhcs,bshd->bchd", probs.astype(prec.act), v)
    p = jax.lax.stop_gradient(probs.sum(1))
    p = p / p.sum(-1, keepdims=True)
    scope = causal if fault == "kl_all_causal" else keep
    log_q = jax.nn.log_softmax(jnp.where(scope, scores, -jnp.inf), axis=-1)
    kl = jnp.where(keep & (p > 0), p * (
        jnp.log(jnp.where(p > 0, p, 1.0)) - jnp.where(keep, log_q, 0.0)), 0.0)
    return out, kl.sum(), keep


def attention(p, u, sizes, prec, fault):
    """(the attention's output (B, S, hidden), the indexer's loss, the
    packed selection)."""
    b, s, _ = u.shape
    heads, groups, width = (sizes["num_attention_heads"],
                            sizes["num_key_value_heads"], sizes["head_dim"])
    ih, iw = sizes["index_num_heads"], sizes["index_head_dim"]
    theta, eps = sizes["rope_theta"], sizes["rms_norm_eps"]
    q = dense(p["q_proj"], u, prec).reshape(b, s, heads, width)
    k = dense(p["k_proj"], u, prec).reshape(b, s, groups, width)
    v = dense(p["v_proj"], u, prec).reshape(b, s, groups, width)
    if fault != "no_qk_norm":
        q = rms_norm(p["q_norm"], q, eps, prec)
        k = rms_norm(p["k_norm"], k, eps, prec)
    q, k = rotary(q, theta), rotary(k, theta)
    # query head h reads key/value head h // (heads / groups)
    k, v = (jnp.repeat(t, heads // groups, axis=2) for t in (k, v))
    ip = p["indexer"]
    ui = u if fault == "indexer_no_stop" else jax.lax.stop_gradient(u)
    q_i = rotary_first_half(
        dense(ip["q_proj"], ui, prec).reshape(b, s, ih, iw), theta)
    k_i = rotary_first_half(
        layer_norm(ip["k_norm"], dense(ip["k_proj"], ui, prec))[:, :, None],
        theta)[:, :, 0]
    w = jnp.einsum("bsi,ij->bsj", ui.astype(jnp.float32),
                   ip["weights_proj"], precision="highest") \
        * (ih * iw) ** -0.5

    def blocks(t):  # (B, S, ...) -> (S / QUERY_BLOCK, B, QUERY_BLOCK, ...)
        return jnp.moveaxis(
            t.reshape(b, s // QUERY_BLOCK, QUERY_BLOCK, *t.shape[2:]), 1, 0)

    out, kl, keep = jax.lax.map(
        jax.checkpoint(functools.partial(
            attend_block, k=k, v=v, k_i=k_i, sizes=sizes, prec=prec,
            fault=fault)),
        (blocks(q), blocks(q_i), blocks(w), jnp.arange(0, s, QUERY_BLOCK)))

    def unblocks(t):
        return jnp.moveaxis(t, 0, 1).reshape(b, s, *t.shape[3:])

    out = unblocks(out).reshape(b, s, heads * width)
    return (dense(p["o_proj"], out, prec), kl.sum() / (b * s),
            pack(unblocks(keep)))


def expert_layer(p, y, sizes, prec):
    """(sum over the held experts of w_e Expert_e(y), assignment counts over
    the router's whole width)."""
    b, s, d = y.shape
    tokens = y.reshape(b * s, d)
    experts, weights = route(p, tokens, sizes["num_experts_per_tok"])
    hist = jnp.zeros((sizes["router_width"],), jnp.int32).at[
        experts.reshape(-1)].add(1)

    @jax.checkpoint
    def one(w_gate, w_up, w_down, weight):
        gate = prec.einsum("ti,if->tf", tokens, w_gate)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(prec.act)
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
         jnp.arange(sizes["num_experts"])))
    return out.astype(prec.act).reshape(b, s, d), hist


def block(lp, x, sizes, prec=F32, fault=None):
    """One layer on the stream ``x``: (the stream, assignment counts, the
    indexer's loss, the packed selection)."""
    eps = sizes["rms_norm_eps"]
    out, kl, packed = jax.checkpoint(
        lambda ap, u: attention(ap, u, sizes, prec, fault))(
        lp["attn"], rms_norm(lp["attn_norm"], x, eps, prec))
    h = x + out
    out, hist = jax.checkpoint(
        lambda mp, y: expert_layer(mp, y, sizes, prec))(
        lp["moe"], rms_norm(lp["ffn_norm"], h, eps, prec))
    return h + out, hist, kl, packed


def hidden(params, tokens, sizes, prec=F32, fault=None):
    """tokens (B, S) -> (the final norm's output, assignment counts (layers,
    router width), the indexer losses (layers,), the packed selections
    (layers, B, S / 32, S))."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    p = params["params"]
    x = p["embed"]["embedding"][tokens].astype(prec.act)
    hists, kls, selections = [], [], []
    for i in range(sizes["num_hidden_layers"]):
        x, hist, kl, packed = jax.checkpoint(
            lambda lp, x: block(lp, x, sizes, prec, fault))(
            p[f"layer_{i}"], x)
        hists.append(hist)
        kls.append(kl)
        selections.append(packed)
    return (rms_norm(p["final_norm"], x, sizes["rms_norm_eps"], prec),
            jnp.stack(hists), jnp.stack(kls), jnp.stack(selections))


def nll_sum(x, kernel, tokens, prec):
    """Sum over positions 0..S-2 of -log softmax(logits[i])[tokens[i+1]].
    The head and the softmax run in blocks of positions, recomputed in the
    backward pass."""
    b, s, _ = x.shape
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

    targets = jnp.roll(tokens, -1, axis=1)
    weight = jnp.broadcast_to(
        (jnp.arange(s) < s - 1).astype(jnp.float32), (b, s))

    def blocks(t):  # (B, S, ...) -> (S / block, B, block, ...)
        return jnp.moveaxis(
            t.reshape(b, s // block_len, block_len, *t.shape[2:]), 1, 0)

    if block_len == s:
        return one((x, targets, weight))
    return jnp.sum(jax.lax.map(
        one, (blocks(x), blocks(targets), blocks(weight))))


def forward(params, tokens, sizes, prec=F32, fault=None):
    """tokens (B, S) -> float32 logits (B, S, vocab)."""
    x = hidden(params, tokens, sizes, prec, fault)[0]
    return prec.einsum("bsi,iv->bsv", x, params["params"]["head"]["kernel"],
                       jnp.float32)


def loss_fn(params, tokens, sizes, prec=F32, fault=None):
    """(cross-entropy + index_loss_weight x the indexer losses summed,
    (assignment counts, indexer losses, packed selections))."""
    b, s = tokens.shape
    x, hists, kls, selections = hidden(params, tokens, sizes, prec, fault)
    ce = nll_sum(x, params["params"]["head"]["kernel"], tokens, prec) / (
        b * (s - 1))
    return ce + sizes["index_loss_weight"] * kls.sum(), (
        hists, kls, selections)


# -------------------------------------------------------------- optimizer ---


@functools.partial(jax.jit, static_argnames=("sizes_key", "prec", "fault"))
def _loss_and_grad(params, tokens, sizes_key, prec, fault):
    (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, tokens, dict(sizes_key), prec, fault)
    raw = leaf_norms(grads)
    norm = jnp.sqrt(sum(jnp.square(v) for v in raw.values()))
    return loss, grads, raw, jnp.where(norm < 1.0, 1.0, 1.0 / norm), aux


def train_steps(params, batches, sizes: dict, opt: dict, prec=F32,
                fault=None) -> dict:
    """``reference/swa_lm_model.py`` ``train_steps`` for this model: follow
    the first ``len(batches)`` optimizer steps from ``params`` (which are
    consumed). Returns each step's loss, the per-leaf norms of the first
    gradient (clipped, and raw), the per-leaf norms of the parameters'
    change over the steps, step 0's assignment counts (layers, router
    width), its indexer losses (layers,) and its packed selections (layers,
    B, S / 32, S). The start waits on the host throughout, Adam's two
    moments between updates."""
    sizes_key = tuple(sorted(sizes.items()))
    start = jax.device_get(params)
    mu = nu = None
    losses, first, first_raw, aux0 = [], None, None, None
    for t, tokens in enumerate(batches):
        loss, grads, raw, clip, aux = _loss_and_grad(
            params, tokens, sizes_key=sizes_key, prec=prec, fault=fault)
        if t == 0:
            first_raw, aux0 = raw, jax.device_get(aux)
            first = {k: v * clip for k, v in raw.items()}
            mu = jax.tree.map(jnp.zeros_like, params)
            nu = jax.tree.map(jnp.zeros_like, params)
        del aux
        params, mu, nu = _adam(
            params, mu, nu, grads, clip,
            jnp.float32(learning_rate(t, opt)), jnp.float32(t + 1))
        del grads
        if t + 1 < len(batches):
            mu, nu = jax.device_get((mu, nu))
        losses.append(loss)
    del mu, nu
    change = leaf_norms(jax.tree.map(lambda a, b: a - b, params, start))
    out = jax.device_get({
        "losses": losses, "grad_norms": first, "raw_grad_norms": first_raw,
        "change_norms": change})
    out["route_hist"], out["indexer_kl"], out["selection"] = aux0
    return out
