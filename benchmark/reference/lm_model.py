"""The plain reference of the language-model cells: latent attention (MLA),
sigmoid-routed experts beside shared ones, next-token loss, gradients and
Adam, for ONE chip's share of an expert-parallel layer.

Straightforward ``jax.numpy`` written from the published configuration
(``model_type`` deepseek_v3, ``q_lora_rank`` null, ``topk_method`` noaux_tc
with one group; the equations are in the configuration's file and PERF.md).
It imports nothing of ``alphafold2_tpu`` (the learning-rate schedule and the
per-leaf norms are ``reference/model.py``'s) and is handed nothing the program
made: weights come from :func:`init_params`, tokens from
``harness/traffic_lm.py``.

- Block: ``h = x + Attn(RMSNorm(x))``, ``x' = h + Mlp(RMSNorm(h))``, no
  biases. The first ``first_k_dense`` layers have ``Mlp = W_down(silu(W_gate
  x) * W_up x)``, every later one the expert layer.
- Attention: ``q = W_q x`` -> heads of nope + rope; ``[c, k_r] = W_kva x``;
  ``c = RMSNorm(c)``; ``[k_nope, v] = W_kvb c``; rotary on ``q_rope`` and on
  the one shared ``k_r`` head, pairs (2i, 2i+1) turned by ``pos *
  theta**(-2i/rope)``; ``softmax(q k^T / sqrt(nope + rope))`` under a causal
  mask; ``W_o``. Dense, in blocks of queries so that 8,192 positions fit.
- Expert layer: ``s = sigmoid(W_r x)`` over ALL experts, the ``top_k``
  largest ``s + b`` (``b`` stays zero and takes no gradient), ``w_e =
  scaling * s_e / (sum of the selected s + 1e-20)``, ``y = Shared(x) +
  sum over the experts HELD HERE of w_e Expert_e(x)``: a plain loop over the
  held experts with a mask, every token through every held expert. What the
  absent experts would add is left out, as in the program: the same share.
- Final RMSNorm, an untied head over the vocabulary rows held here, the mean
  next-token cross-entropy over positions 0..S-2.

``Precision`` (``reference/model.py``'s): ``f32`` is the reference; ``bf16``
what the configuration states; ``fp8`` the control. RMSNorm statistics, the
router, softmax and the loss are float32 in all three. ``fault`` plants a
mistake for reading the limits: ``top5`` (one expert a token too few),
``no_routed`` (shared expert only), ``no_causal`` (the mask left out).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from benchmark.reference.model import leaf_norms, learning_rate

# largest block of attention logits (float32 bytes) held at once
LOGIT_BLOCK_BYTES = 256 * 1024 * 1024
FAULTS = (None, "top5", "no_routed", "no_causal")
SIZE_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "first_k_dense_replace",
    "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "kv_lora_rank", "intermediate_size",
    "moe_intermediate_size", "n_routed_experts", "router_width",
    "first_expert", "n_shared_experts", "num_experts_per_tok",
    "routed_scaling_factor", "rope_theta", "rms_norm_eps",
)


@dataclasses.dataclass(frozen=True)
class Precision:
    name: str = "f32"

    @property
    def act(self):
        return jnp.float32 if self.name == "f32" else jnp.bfloat16

    def operand(self, t):
        if self.name == "f32":
            return t.astype(jnp.float32)
        if self.name == "fp8":
            t = t.astype(jnp.float8_e4m3fn)
        return t.astype(jnp.bfloat16)

    def einsum(self, spec, a, b, out=None):
        y = jnp.einsum(
            spec, self.operand(a), self.operand(b),
            precision="highest" if self.name == "f32" else None,
            preferred_element_type=jnp.float32,
        )
        return y.astype(out or self.act)


F32 = Precision("f32")

# ------------------------------------------------------------- parameters ---


def param_shapes(sizes: dict) -> dict:
    """The parameter tree's shapes from the configuration's sizes alone.
    ``n_routed_experts`` experts are held (stacked leaves), of the
    ``router_width`` the router scores."""
    d, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                      sizes["v_head_dim"])
    rank, held = sizes["kv_lora_rank"], sizes["n_routed_experts"]
    width = sizes["moe_intermediate_size"]

    def swiglu(f):
        return {"gate_proj": {"kernel": (d, f)}, "up_proj": {"kernel": (d, f)},
                "down_proj": {"kernel": (f, d)}}

    def layer(i):
        out = {
            "attn_norm": {"scale": (d,)}, "ffn_norm": {"scale": (d,)},
            "mla_attn": {
                "q_proj": {"kernel": (d, heads * (nope + rope))},
                "kv_down": {"kernel": (d, rank + rope)},
                "kv_norm": {"scale": (rank,)},
                "kv_up": {"kernel": (rank, heads * (nope + dv))},
                "o_proj": {"kernel": (heads * dv, d)},
            },
        }
        if i < sizes["first_k_dense_replace"]:
            out["dense_ffn"] = swiglu(sizes["intermediate_size"])
        else:
            out["moe"] = {
                "router": (d, sizes["router_width"]),
                "router_bias": (sizes["router_width"],),
                "w_gate": (held, d, width), "w_up": (held, d, width),
                "w_down": (held, width, d),
                "shared": swiglu(sizes["n_shared_experts"] * width),
            }
        return out

    return {"params": {
        "embed": {"embedding": (sizes["vocab_size"], d)},
        **{f"layer_{i}": layer(i)
           for i in range(sizes["num_hidden_layers"])},
        "final_norm": {"scale": (d,)},
        "head": {"kernel": (d, sizes["vocab_size"])},
    }}


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def init_params(sizes: dict, seed: int) -> dict:
    """Float32 weights from ``seed`` in one jitted call on the device:
    matrices normal with variance 1 / fan-in (a stacked expert leaf's fan-in
    is its middle axis, a table's its width), norm scales one, the router's
    bias zero."""
    shapes = param_shapes(sizes)
    leaves, _ = jax.tree.flatten_with_path(shapes, is_leaf=_is_shape)

    @jax.jit
    def make(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            kind = path[-1].key
            if kind == "scale":
                out.append(jnp.ones(shape, jnp.float32))
            elif kind == "router_bias":
                out.append(jnp.zeros(shape, jnp.float32))
            else:
                fan_in = shape[-1] if kind == "embedding" else shape[-2]
                out.append(
                    jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * fan_in ** -0.5)
        return out

    return jax.tree.unflatten(
        jax.tree.structure(shapes, is_leaf=_is_shape),
        make(jax.random.key(seed)),
    )


# ---------------------------------------------------------------- forward ---


def rms_norm(p, x, eps, prec):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * p["scale"]).astype(prec.act)


def dense(p, x, prec):
    return prec.einsum("...i,io->...o", x, p["kernel"])


def swiglu(p, x, prec):
    gate = dense(p["gate_proj"], x, prec)
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(prec.act)
    return dense(p["down_proj"], act * dense(p["up_proj"], x, prec), prec)


def rotary(x, theta):
    """Pairs (2i, 2i+1) of the last axis of ``x`` (B, S, H, width) turned by
    ``pos * theta**(-2i / width)``."""
    s, width = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


def causal_attend(q, k, v, scale, prec, causal=True):
    """softmax(q k^T * scale, keys 0..i for query i) v over (G, S, Dqk) x
    (G, S, Dqk) x (G, S, Dv), in blocks of queries."""
    g, s, _ = q.shape
    block = s
    while block > 1 and block % 2 == 0 \
            and g * block * s * 4 > LOGIT_BLOCK_BYTES:
        block //= 2
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qb, start = args
        logits = prec.einsum("gid,gjd->gij", qb, k, jnp.float32) * scale
        if causal:
            q_pos = start + jnp.arange(block)
            logits = jnp.where(
                key_pos[None, None, :] <= q_pos[None, :, None], logits,
                -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(prec.act)
        return prec.einsum("gij,gjd->gid", probs, v)

    if block == s:
        return one((q, 0))
    qs = q.reshape(g, s // block, block, q.shape[-1]).swapaxes(0, 1)
    out = jax.lax.map(one, (qs, jnp.arange(0, s, block)))
    return out.swapaxes(0, 1).reshape(g, s, v.shape[-1])


def mla_attention(p, x, sizes, prec, causal=True):
    b, s, _ = x.shape
    heads, nope, rope, dv = (
        sizes["num_attention_heads"], sizes["qk_nope_head_dim"],
        sizes["qk_rope_head_dim"], sizes["v_head_dim"])
    rank, theta = sizes["kv_lora_rank"], sizes["rope_theta"]
    q = dense(p["q_proj"], x, prec).reshape(b, s, heads, nope + rope)
    latent = dense(p["kv_down"], x, prec)
    c = rms_norm(p["kv_norm"], latent[..., :rank], sizes["rms_norm_eps"],
                 prec)
    kv = dense(p["kv_up"], c, prec).reshape(b, s, heads, nope + dv)
    k_rope = rotary(latent[..., None, rank:], theta)  # one head for all
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, heads, rope))], -1)

    def flat(t):  # (B, S, H, D) -> (B*H, S, D)
        return t.swapaxes(1, 2).reshape(b * heads, s, t.shape[-1])

    out = causal_attend(flat(q), flat(k), flat(kv[..., nope:]),
                        (nope + rope) ** -0.5, prec, causal)
    out = out.reshape(b, heads, s, dv).swapaxes(1, 2).reshape(b, s, heads * dv)
    return dense(p["o_proj"], out, prec)


def route(p, x, sizes, top_k):
    """(experts (T, k), weights (T, k)) in float32, over the router's whole
    width."""
    logits = jnp.einsum("ti,ie->te", x.astype(jnp.float32), p["router"],
                        precision="highest")
    scores = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["router_bias"]), top_k)
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    weights = sizes["routed_scaling_factor"] * picked / (
        picked.sum(-1, keepdims=True) + 1e-20)
    return experts, weights


def expert_layer(p, x, sizes, prec, fault=None):
    """(output, assignment counts over the router's whole width)."""
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    top_k = sizes["num_experts_per_tok"] - (1 if fault == "top5" else 0)
    experts, weights = route(p, tokens, sizes, top_k)
    hist = jnp.zeros((sizes["router_width"],), jnp.int32).at[
        experts.reshape(-1)].add(1)
    out = swiglu(p["shared"], tokens, prec).astype(jnp.float32)

    @jax.checkpoint
    def one(w_gate, w_up, w_down, weight):
        gate = prec.einsum("ti,if->tf", tokens, w_gate)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(prec.act)
        y = prec.einsum("tf,fo->to", act * prec.einsum(
            "ti,if->tf", tokens, w_up), w_down)
        return weight[:, None] * y.astype(jnp.float32)

    def add_expert(out, expert):  # one held expert, every token through it
        w_gate, w_up, w_down, e = expert
        mine = experts == sizes["first_expert"] + e
        return out + one(w_gate, w_up, w_down,
                         jnp.sum(jnp.where(mine, weights, 0.0), -1)), None

    if fault != "no_routed":
        out, _ = jax.lax.scan(add_expert, out, (
            p["w_gate"], p["w_up"], p["w_down"],
            jnp.arange(sizes["n_routed_experts"])))
    return out.astype(prec.act).reshape(b, s, d), hist


def hidden(params, tokens, sizes, prec=F32, fault=None):
    """tokens (B, S) -> (the final norm's output (B, S, hidden), assignment
    counts (expert layers, router width))."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    p = params["params"]
    eps = sizes["rms_norm_eps"]
    x = p["embed"]["embedding"][tokens].astype(prec.act)
    hists = []
    for i in range(sizes["num_hidden_layers"]):

        # a layer is recomputed in the backward pass, and inside it each
        # half again: what is held at once is one half's intermediates
        @jax.checkpoint
        def block(lp, x, dense_layer=i < sizes["first_k_dense_replace"]):
            h = x + jax.checkpoint(
                lambda ap, y: mla_attention(
                    ap, y, sizes, prec, causal=fault != "no_causal"))(
                lp["mla_attn"], rms_norm(lp["attn_norm"], x, eps, prec))
            y = rms_norm(lp["ffn_norm"], h, eps, prec)
            if dense_layer:
                return h + jax.checkpoint(
                    lambda fp, y: swiglu(fp, y, prec))(lp["dense_ffn"], y), \
                    None
            out, hist = jax.checkpoint(
                lambda mp, y: expert_layer(mp, y, sizes, prec, fault))(
                lp["moe"], y)
            return h + out, hist

        x, hist = block(p[f"layer_{i}"], x)
        if hist is not None:
            hists.append(hist)
    return rms_norm(p["final_norm"], x, eps, prec), jnp.stack(hists)


def forward(params, tokens, sizes, prec=F32, fault=None):
    """tokens (B, S) -> (float32 logits (B, S, vocab), assignment counts)."""
    x, hists = hidden(params, tokens, sizes, prec, fault)
    kernel = params["params"]["head"]["kernel"]
    return prec.einsum("bsi,iv->bsv", x, kernel, jnp.float32), hists


def nll_sum(params, tokens, sizes, prec=F32, fault=None):
    """(sum over positions 0..S-2 of -log softmax(logits[i])[tokens[i+1]],
    assignment counts). The head and the softmax run in blocks of positions,
    recomputed in the backward pass: whole, the float32 logits of 16,384
    tokens over 16,032 ids, their log-softmax and both gradients are 4 GB."""
    x, hists = hidden(params, tokens, sizes, prec, fault)
    kernel = params["params"]["head"]["kernel"]
    b, s, d = x.shape
    block = s
    while block > 1 and block % 2 == 0 \
            and b * block * kernel.shape[1] * 4 > LOGIT_BLOCK_BYTES // 4:
        block //= 2

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
            t.reshape(b, s // block, block, *t.shape[2:]), 1, 0)

    if block == s:
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


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(params, mu, nu, grads, clip, lr, count):
    b1, b2, eps = 0.9, 0.999, 1e-8
    grads = jax.tree.map(lambda g: g * clip, grads)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    return params, mu, nu


def train_steps(params, batches, sizes: dict, opt: dict, prec=F32,
                fault=None) -> dict:
    """Follow the first ``len(batches)`` optimizer steps from ``params``
    (which are consumed: the update is made in place). Returns what the
    comparison reads: each step's loss, the per-leaf norms of the first
    gradient (as Adam gets it, after clipping, and raw), the per-leaf norms
    of the parameters' change over the steps, and step 0's assignment counts
    (expert layers, router width).

    At the cell's size a copy of the parameters is 2.3 GB and a gradient's
    program holds 12.2 GB (weights, gradients, 7.6 GB of float32
    temporaries), so what a gradient does not need waits on the host: the
    start (for the change) throughout, Adam's two moments between updates."""
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
