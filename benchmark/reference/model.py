"""The plain reference: the distogram trunk, its loss, gradients and update.

Straightforward ``jax.numpy`` written from the model's equations (reference
``alphafold2_pytorch/alphafold2.py``: outer-sum pair embedding, an MSA grid,
``depth`` layers of [pair axial, MSA axial with tied rows, pair<-MSA and
MSA<-pair cross-attention, two GEGLU feed-forwards], symmetrised distogram
head), cross-entropy against bucketed CA distances, global-norm clipping and
Adam under a linear warm-up. It imports nothing of ``alphafold2_tpu`` and is
handed nothing the program made: weights come from :func:`init_params`, data
from ``harness/traffic.py``.

No kernels, no cache, no batching tricks. Two things keep it inside 16 GB at
the flagship size: attention runs in blocks of queries (the cross-attention
logits are 8.6 GB in float32), and every sub-block is ``jax.checkpoint``ed so
the backward pass recomputes logits instead of keeping them. Neither changes
a value.

``Precision`` selects what the arithmetic is done in:

- ``f32``: float32 everywhere, matrix multiplications at ``highest`` (the TPU
  would otherwise take them in one bfloat16 pass). This is the reference.
- ``bf16``: activations and matmul operands in bfloat16, float32 accumulate,
  parameters float32: what the configuration states. A diagnostic.
- ``fp8``: as ``bf16`` with both operands of every matmul rounded to
  float8_e4m3 first: the nearest precision below the stated one, the control.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

NUM_TOKENS = 21  # 20 amino acids + pad
MAX_NUM_MSA = 20
BUCKETS = 37
MIN_DIST, MAX_DIST = 2.0, 20.0
MASK_VALUE = -1e9
LN_EPS = 1e-6
# largest block of attention logits (float32 bytes) held at once
LOGIT_BLOCK_BYTES = 512 * 1024 * 1024


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

    def einsum(self, spec, a, b):
        out = jnp.einsum(
            spec, self.operand(a), self.operand(b),
            precision="highest" if self.name == "f32" else None,
            preferred_element_type=jnp.float32,
        )
        return out.astype(self.act)


F32 = Precision("f32")

# ------------------------------------------------------------- parameters ---


def param_shapes(sizes: dict) -> dict:
    """The parameter tree's shapes from the configuration's sizes alone."""
    d, inner = sizes["dim"], sizes["heads"] * sizes["dim_head"]
    n_pos = sizes["max_seq_len"]

    def norm():
        return {"scale": (d,), "bias": (d,)}

    def attn():
        return {
            "to_q": {"kernel": (d, inner)},
            "to_kv": {"kernel": (d, 2 * inner)},
            "to_out": {"kernel": (inner, d), "bias": (d,)},
        }

    def axial():
        return {"attn_width": attn(), "attn_height": attn()}

    def ff():
        return {
            "wi": {"kernel": (d, 8 * d), "bias": (8 * d,)},
            "wo": {"kernel": (4 * d, d), "bias": (d,)},
        }

    layer = lambda: {
        "pair_axial_norm": norm(), "pair_axial": axial(),
        "msa_axial_norm": norm(), "msa_axial": axial(),
        "pair_cross_norm": norm(), "pair_cross_ctx_norm": norm(),
        "pair_from_msa": attn(),
        "msa_cross_norm": norm(), "msa_cross_ctx_norm": norm(),
        "msa_from_pair": attn(),
        "pair_ff_norm": norm(), "pair_ff": ff(),
        "msa_ff_norm": norm(), "msa_ff": ff(),
    }
    return {"params": {
        "token_emb": {"embedding": (NUM_TOKENS, d)},
        "pos_emb": {"embedding": (n_pos, d)},
        "pos_emb_ax": {"embedding": (n_pos, d)},
        "msa_pos_emb": {"embedding": (n_pos, d)},
        "msa_num_pos_emb": {"embedding": (MAX_NUM_MSA, d)},
        "trunk": {f"layer_{i}": layer() for i in range(sizes["depth"])},
        "distogram_norm": norm(),
        "distogram_proj": {"kernel": (d, BUCKETS), "bias": (BUCKETS,)},
    }}


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def init_params(sizes: dict, seed: int) -> dict:
    """Float32 weights from ``seed`` in one jitted call on the device:
    matrices and embedding tables normal with variance 1 / fan-in (a table's
    fan-in is its width), norm scales one, every bias zero."""
    shapes = param_shapes(sizes)
    leaves, treedef = jax.tree.flatten_with_path(shapes, is_leaf=_is_shape)

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
                fan_in = shape[-1] if kind == "embedding" else shape[0]
                out.append(
                    jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * fan_in ** -0.5
                )
        return out

    return jax.tree.unflatten(
        jax.tree.structure(shapes, is_leaf=_is_shape),
        make(jax.random.key(seed)),
    )


# ---------------------------------------------------------------- forward ---


def layer_norm(p, x, prec):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]
    return y.astype(prec.act)


def dense(p, x, prec):
    y = prec.einsum("...i,io->...o", x, p["kernel"])
    if "bias" in p:
        y = (y + p["bias"].astype(prec.act)).astype(prec.act)
    return y


def _q_block(groups: int, nq: int, nk: int) -> int:
    block = nq
    while block > 1 and groups * block * nk * 4 > LOGIT_BLOCK_BYTES \
            and block % 2 == 0:
        block //= 2
    return block


def attend(q, k, v, k_mask, scale, prec):
    """softmax(q k^T * scale) v over (G, Nq, D) x (G, Nk, D), in blocks of
    queries; ``k_mask`` (G or 1, Nk) bool or None."""
    g, nq, d = q.shape
    nk = k.shape[1]
    block = _q_block(g, nq, nk)

    @jax.checkpoint
    def one(qb):
        logits = prec.einsum("gid,gjd->gij", qb, k).astype(jnp.float32)
        logits = logits * scale
        if k_mask is not None:
            logits = jnp.where(k_mask[:, None, :], logits, MASK_VALUE)
        probs = jax.nn.softmax(logits, axis=-1).astype(prec.act)
        return prec.einsum("gij,gjd->gid", probs, v)

    if block == nq:
        return one(q)
    qs = q.reshape(g, nq // block, block, d).swapaxes(0, 1)
    out = jax.lax.map(one, qs)  # (blocks, G, block, D)
    return out.swapaxes(0, 1).reshape(g, nq, d)


def attention(p, x, ctx, k_mask, sizes, prec):
    """Multi-head attention of ``x`` (B, Nq, dim) over ``ctx`` (B, Nk, dim)."""
    h, dh = sizes["heads"], sizes["dim_head"]
    b, nq, _ = x.shape
    nk = ctx.shape[1]
    q = dense(p["to_q"], x, prec)
    k, v = jnp.split(dense(p["to_kv"], ctx, prec), 2, axis=-1)

    def heads(t, n):  # (B, n, h*dh) -> (B*h, n, dh)
        return t.reshape(b, n, h, dh).swapaxes(1, 2).reshape(b * h, n, dh)

    km = None if k_mask is None else jnp.repeat(k_mask, h, axis=0)
    out = attend(heads(q, nq), heads(k, nk), heads(v, nk), km, dh ** -0.5,
                 prec)
    out = out.reshape(b, h, nq, dh).swapaxes(1, 2).reshape(b, nq, h * dh)
    return dense(p["to_out"], out, prec)


def tied_row_attention(p, x, sizes, prec):
    """Row attention of an MSA grid (B, R, N, dim) with ONE attention matrix
    per (batch, head): logits summed over the R rows, scaled by R**-0.5."""
    h, dh = sizes["heads"], sizes["dim_head"]
    b, r, n, _ = x.shape
    q = dense(p["to_q"], x, prec).reshape(b, r, n, h, dh)
    k, v = jnp.split(dense(p["to_kv"], x, prec), 2, axis=-1)
    k, v = k.reshape(b, r, n, h, dh), v.reshape(b, r, n, h, dh)
    logits = prec.einsum("brihd,brjhd->bhij", q, k).astype(jnp.float32)
    logits = logits * (dh ** -0.5 * r ** -0.5)
    probs = jax.nn.softmax(logits, axis=-1).astype(prec.act)
    out = prec.einsum("bhij,brjhd->brihd", probs, v)
    return dense(p["to_out"], out.reshape(b, r, n, h * dh), prec)


def axial_attention(p, x, mask, sizes, prec, tie_rows=False):
    """Column pass + row pass over a (B, H, W, dim) grid, summed. ``mask``
    (B, H, W) bool or None; tied rows take no mask."""
    b, hh, w, d = x.shape
    cols = x.swapaxes(1, 2).reshape(b * w, hh, d)
    cmask = None if mask is None else mask.swapaxes(1, 2).reshape(b * w, hh)
    out_w = attention(p["attn_width"], cols, cols, cmask, sizes, prec)
    out_w = out_w.reshape(b, w, hh, d).swapaxes(1, 2)
    if tie_rows:
        if mask is not None:
            raise ValueError("the reference's tied rows take no padding")
        out_h = tied_row_attention(p["attn_height"], x, sizes, prec)
    else:
        rows = x.reshape(b * hh, w, d)
        rmask = None if mask is None else mask.reshape(b * hh, w)
        out_h = attention(p["attn_height"], rows, rows, rmask, sizes, prec)
        out_h = out_h.reshape(b, hh, w, d)
    return out_w + out_h


def feed_forward(p, x, prec):
    h, gates = jnp.split(dense(p["wi"], x, prec), 2, axis=-1)
    h = h * jax.nn.gelu(gates, approximate=True)
    return dense(p["wo"], h, prec)


def trunk_layer(p, x, m, pair_mask, sizes, prec):
    ck = jax.checkpoint
    ln = layer_norm
    x = x + ck(lambda p_, x_: axial_attention(
        p_["pair_axial"], ln(p_["pair_axial_norm"], x_, prec), pair_mask,
        sizes, prec))(p, x)
    m = m + ck(lambda p_, m_: axial_attention(
        p_["msa_axial"], ln(p_["msa_axial_norm"], m_, prec), None, sizes,
        prec, tie_rows=sizes["msa_tie_row_attn"]))(p, m)
    b, n, _, d = x.shape
    _, rows, nm, _ = m.shape
    xf, mf = x.reshape(b, n * n, d), m.reshape(b, rows * nm, d)
    pm = None if pair_mask is None else pair_mask.reshape(b, n * n)
    xf = xf + ck(lambda p_, x_, m_: attention(
        p_["pair_from_msa"], ln(p_["pair_cross_norm"], x_, prec),
        ln(p_["pair_cross_ctx_norm"], m_, prec), None, sizes, prec))(p, xf, mf)
    mf = mf + ck(lambda p_, m_, x_: attention(
        p_["msa_from_pair"], ln(p_["msa_cross_norm"], m_, prec),
        ln(p_["msa_cross_ctx_norm"], x_, prec), pm, sizes, prec))(p, mf, xf)
    x, m = xf.reshape(b, n, n, d), mf.reshape(b, rows, nm, d)
    x = x + ck(lambda p_, x_: feed_forward(
        p_["pair_ff"], ln(p_["pair_ff_norm"], x_, prec), prec))(p, x)
    m = m + ck(lambda p_, m_: feed_forward(
        p_["msa_ff"], ln(p_["msa_ff_norm"], m_, prec), prec))(p, m)
    return x, m


def forward(params, batch, sizes, prec=F32):
    """Distogram logits (B, N, N, 37), float32. ``batch``: seq (B, N) int,
    msa (B, R, Nm) int, mask (B, N) bool; the MSA carries no padding."""
    p = params["params"]
    seq, msa, mask = batch["seq"], batch["msa"], batch["mask"]
    n, nm, rows = seq.shape[1], msa.shape[2], msa.shape[1]
    act = prec.act
    tok = p["token_emb"]["embedding"].astype(act)
    e = tok[seq]
    x = e[:, :, None, :] + e[:, None, :, :]
    x = x + p["pos_emb"]["embedding"].astype(act)[:n][None, :, None, :]
    x = x + p["pos_emb_ax"]["embedding"].astype(act)[:n][None, None, :, :]
    m = tok[msa]
    m = m + p["msa_pos_emb"]["embedding"].astype(act)[:nm][None, None]
    m = m + p["msa_num_pos_emb"]["embedding"].astype(act)[:rows][
        None, :, None]
    pair_mask = mask[:, :, None] & mask[:, None, :]
    for i in range(sizes["depth"]):
        x, m = trunk_layer(p["trunk"][f"layer_{i}"], x, m, pair_mask, sizes,
                           prec)
    x = (0.5 * (x + x.swapaxes(1, 2))).astype(act)
    x = layer_norm(p["distogram_norm"], x, prec)
    return dense(p["distogram_proj"], x, prec).astype(jnp.float32)


# ------------------------------------------------------------------- loss ---


def distance_labels(coords, mask):
    """Bucketed CA-CA distances (B, N, N) int; -100 where either residue is
    masked. 37 buckets over 2-20 A, boundaries closed on the right."""
    diff = coords[:, :, None, :] - coords[:, None, :, :]
    dist = jnp.sqrt(jnp.sum(diff * diff, -1))
    bounds = jnp.linspace(MIN_DIST, MAX_DIST, BUCKETS)[:-1]
    labels = jnp.searchsorted(bounds, dist, side="left")
    return jnp.where(mask[:, :, None] & mask[:, None, :], labels, -100)


def loss_fn(params, batch, sizes, prec=F32):
    logits = forward(params, batch, sizes, prec)
    labels = distance_labels(batch["coords"], batch["mask"])
    valid = labels != -100
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)


# -------------------------------------------------------------- optimizer ---


def learning_rate(step: int, opt: dict) -> float:
    """Linear warm-up from 0 to the peak over ``warmup_steps``, then a cosine
    to a tenth of the peak at ``num_steps``."""
    peak, warm, total = opt["learning_rate"], opt["warmup_steps"], \
        opt["num_steps"]
    if step < warm:
        return peak * step / warm
    frac = min(1.0, (step - warm) / max(total - warm, 1))
    return 0.1 * peak + 0.9 * peak * 0.5 * (1.0 + math.cos(math.pi * frac))


def leaf_norms(tree) -> dict:
    """{"a/b/c": norm} of every leaf, float32 on the device."""
    leaves = jax.tree.flatten_with_path(tree)[0]
    return {
        "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path):
        jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
        for path, leaf in leaves
    }


@functools.partial(jax.jit, static_argnames=("sizes_key", "prec"))
def _loss_and_clipped_grad(params, batch, sizes_key, prec):
    sizes = dict(sizes_key)
    loss, grads = jax.value_and_grad(loss_fn)(params, batch, sizes, prec)
    raw = leaf_norms(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    clipped = jax.tree.map(
        lambda g: jnp.where(norm < 1.0, g, g / norm), grads)
    return loss, clipped, raw


@jax.jit
def _adam(params, mu, nu, grads, lr, count):
    b1, b2, eps = 0.9, 0.999, 1e-8
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    return params, mu, nu


def train_steps(params, batches, sizes: dict, opt: dict, prec=F32) -> dict:
    """Follow the first ``len(batches)`` optimizer steps from ``params``.
    Returns what the comparison reads: each step's loss, the per-leaf norms
    of the first gradient (as Adam gets it, after clipping, and raw) and of
    the parameters' change over the steps."""
    sizes_key = tuple(sorted(sizes.items()))
    start = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first, first_raw = [], None, None
    for t, batch in enumerate(batches):
        loss, grads, raw = _loss_and_clipped_grad(
            params, batch, sizes_key=sizes_key, prec=prec)
        if t == 0:
            first, first_raw = leaf_norms(grads), raw
        params, mu, nu = _adam(
            params, mu, nu, grads,
            jnp.float32(learning_rate(t, opt)), jnp.float32(t + 1))
        losses.append(loss)
    change = leaf_norms(jax.tree.map(lambda a, b: a - b, params, start))
    return jax.device_get({
        "losses": losses, "grad_norms": first, "raw_grad_norms": first_raw,
        "change_norms": change,
    })
