"""True reversible trunk: inversion-based O(1)-activation-memory backward.

Direct TPU-native equivalent of the reference's reversible engine
(``alphafold2_pytorch/reversible.py``): ``ReversibleSelfAttnBlock`` /
``ReversibleCrossAttnBlock`` (:60-262) couple two halves of each stream with
additive updates, and a hand-written ``torch.autograd.Function`` (:266-300)
reconstructs activations in backward by *inverting* the coupling instead of
storing them.

Design (not a port):

- The coupling runs under ONE ``lax.scan`` over stacked per-depth parameters,
  wrapped in ``jax.custom_vjp``. Forward saves only the final carry; backward
  scans the layers in reverse, walking each layer's 8 additive updates
  backwards — every sub-function is evaluated ONCE under a local ``jax.vjp``,
  its output reused for both the inversion subtraction and the cotangent
  pull. Activation memory is O(1) in depth and recompute cost is one extra
  evaluation per sub-function, like the reference — but the schedule is
  compiled by XLA, not interpreted per-block by an autograd tape.
- The reference needs CUDA RNG state capture/replay (``Deterministic``,
  reversible.py:26-56) to make dropout recompute bit-exact. Stateless JAX
  PRNG keys make replay exact by construction: the same per-layer key is
  passed to the forward, the inversion, and the recompute.
- The reference doubles channels and halves them per block
  (reversible.py:319,327); here the two halves are two copies of the
  stream — same coupling math, no concat/split churn.

Where ``Trunk(remat=True)`` trades memory for a full forward recompute,
the reversible engine reconstructs activations by inversion (one extra
f/g/j/k evaluation per block, same as the reference's backward_pass). Both
are exposed; ``tests/test_reversible.py`` proves gradient parity of the
custom backward against plain autodiff — the analogue of the reference's
``tests/test_reversible.py`` oracle.

Coupling per depth step (reference reversible.py:76-83, 176-181):

    self block:   x1 += f_s(x2);        x2 += g_s(x1)
                  m1 += j_s(m2);        m2 += k_s(m1)
    cross block:  x1 += f_c(x2, m2);    x2 += g_c(x1)
                  m1 += j_c(m2, x2);    m2 += k_c(m1)

Each update writes one half from the other(s), so the whole step inverts
exactly by running the updates backwards with subtraction.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from alphafold2_tpu.ops.attention import Attention, AxialAttention, FeedForward
from alphafold2_tpu.parallel.sharding import shard_msa, shard_pair


def _float0_zeros(x):
    return np.zeros(np.shape(x), dtype=jax.dtypes.float0)


class RevLayerPair(nn.Module):
    """One reversible depth step: [self-attn block, cross-attn block] over the
    (x1, x2, m1, m2) halved two-stream state. ``__call__`` is the forward
    coupling; :meth:`invert` reconstructs inputs from outputs exactly."""

    dim: int
    heads: int = 8
    dim_head: int = 64
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    gelu_exact: bool = False  # erf GELU (the reference's torch F.gelu)
    sparse_attn: bool = False
    seq_len: Optional[int] = None
    sparse_config: Optional[object] = None
    sparse_use_pallas: Optional[bool] = None
    cross_attn_compress_ratio: int = 1
    msa_tie_row_attn: bool = False
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        dt = self.dtype
        ax = dict(
            dim=self.dim, heads=self.heads, dim_head=self.dim_head,
            dropout=self.attn_dropout, dtype=dt,
        )
        self.f_s_norm = nn.LayerNorm(dtype=dt)
        self.f_s = AxialAttention(
            sparse_attn=self.sparse_attn, seq_len=self.seq_len,
            sparse_config=self.sparse_config,
            sparse_use_pallas=self.sparse_use_pallas, **ax,
        )
        self.g_s_norm = nn.LayerNorm(dtype=dt)
        self.g_s = FeedForward(dim=self.dim, dropout=self.ff_dropout, gelu_exact=self.gelu_exact, dtype=dt)
        self.j_s_norm = nn.LayerNorm(dtype=dt)
        self.j_s = AxialAttention(tie_row_attn=self.msa_tie_row_attn, **ax)
        self.k_s_norm = nn.LayerNorm(dtype=dt)
        self.k_s = FeedForward(dim=self.dim, dropout=self.ff_dropout, gelu_exact=self.gelu_exact, dtype=dt)

        at = dict(
            dim=self.dim, heads=self.heads, dim_head=self.dim_head,
            dropout=self.attn_dropout, dtype=dt,
        )
        self.f_c_norm = nn.LayerNorm(dtype=dt)
        self.f_c_ctx_norm = nn.LayerNorm(dtype=dt)
        self.f_c = Attention(compress_ratio=self.cross_attn_compress_ratio, **at)
        self.g_c_norm = nn.LayerNorm(dtype=dt)
        self.g_c = FeedForward(dim=self.dim, dropout=self.ff_dropout, gelu_exact=self.gelu_exact, dtype=dt)
        self.j_c_norm = nn.LayerNorm(dtype=dt)
        self.j_c_ctx_norm = nn.LayerNorm(dtype=dt)
        self.j_c = Attention(**at)
        self.k_c_norm = nn.LayerNorm(dtype=dt)
        self.k_c = FeedForward(dim=self.dim, dropout=self.ff_dropout, gelu_exact=self.gelu_exact, dtype=dt)

    # --- the eight sub-functions (each used once per direction) ---

    def _f_s(self, x2, pm, det):
        return self.f_s(self.f_s_norm(x2), mask=pm, deterministic=det)

    def _g_s(self, x1, det):
        return self.g_s(self.g_s_norm(x1), deterministic=det)

    def _j_s(self, m2, mm, det):
        return self.j_s(self.j_s_norm(m2), mask=mm, deterministic=det)

    def _k_s(self, m1, det):
        return self.k_s(self.k_s_norm(m1), deterministic=det)

    def _f_c(self, x2, m2, pm, mm, det):
        b, n, n2, d = x2.shape
        xf = x2.reshape(b, n * n2, d)
        mf = m2.reshape(b, -1, d)
        out = self.f_c(
            self.f_c_norm(xf),
            context=self.f_c_ctx_norm(mf),
            mask=pm.reshape(b, -1) if pm is not None else None,
            context_mask=mm.reshape(b, -1) if mm is not None else None,
            deterministic=det,
        )
        return out.reshape(b, n, n2, d)

    def _g_c(self, x1, det):
        return self.g_c(self.g_c_norm(x1), deterministic=det)

    def _j_c(self, m2, x2, pm, mm, det):
        b = m2.shape[0]
        mf = m2.reshape(b, -1, m2.shape[-1])
        xf = x2.reshape(b, -1, x2.shape[-1])
        out = self.j_c(
            self.j_c_norm(mf),
            context=self.j_c_ctx_norm(xf),
            mask=mm.reshape(b, -1) if mm is not None else None,
            context_mask=pm.reshape(b, -1) if pm is not None else None,
            deterministic=det,
        )
        return out.reshape(m2.shape)

    def _k_c(self, m1, det):
        return self.k_c(self.k_c_norm(m1), deterministic=det)

    def __call__(self, h, pair_mask=None, msa_mask=None, deterministic=True):
        x1, x2, m1, m2 = h
        pm, mm, det = pair_mask, msa_mask, deterministic
        # self block
        x1 = shard_pair(x1 + self._f_s(x2, pm, det))
        x2 = shard_pair(x2 + self._g_s(x1, det))
        m1 = shard_msa(m1 + self._j_s(m2, mm, det))
        m2 = shard_msa(m2 + self._k_s(m1, det))
        # cross block
        x1 = shard_pair(x1 + self._f_c(x2, m2, pm, mm, det))
        x2 = shard_pair(x2 + self._g_c(x1, det))
        m1 = shard_msa(m1 + self._j_c(m2, x2, pm, mm, det))
        m2 = shard_msa(m2 + self._k_c(m1, det))
        return (x1, x2, m1, m2)

    def invert(self, h, pair_mask=None, msa_mask=None, deterministic=True):
        """Exact inverse of ``__call__``: the updates run in reverse order with
        subtraction (reference backward_pass, reversible.py:85-156,184-262 —
        minus the autograd bookkeeping, which custom_vjp supplies)."""
        x1, x2, m1, m2 = h
        pm, mm, det = pair_mask, msa_mask, deterministic
        # cross block
        m2 = shard_msa(m2 - self._k_c(m1, det))
        m1 = shard_msa(m1 - self._j_c(m2, x2, pm, mm, det))
        x2 = shard_pair(x2 - self._g_c(x1, det))
        x1 = shard_pair(x1 - self._f_c(x2, m2, pm, mm, det))
        # self block
        m2 = shard_msa(m2 - self._k_s(m1, det))
        m1 = shard_msa(m1 - self._j_s(m2, mm, det))
        x2 = shard_pair(x2 - self._g_s(x1, det))
        x1 = shard_pair(x1 - self._f_s(x2, pm, det))
        return (x1, x2, m1, m2)


def _make_rev_scan(forward_one, backward_one):
    """Build the custom-vjp reversible scan.

    ``forward_one(p, h, pm, mm, key) -> h`` and
    ``backward_one(p, h_out, gh, pm, mm, key) -> (h_in, gh_in, gp)`` are
    static closures over the (unbound) layer module and static config only —
    masks and keys are explicit operands, as custom_vjp requires.
    """

    @jax.custom_vjp
    def rev_scan(params, h, pm, mm, keys):
        def body(carry, xs):
            p, key = xs
            return forward_one(p, carry, pm, mm, key), None

        h, _ = jax.lax.scan(body, h, (params, keys))
        return h

    def fwd(params, h, pm, mm, keys):
        out = rev_scan(params, h, pm, mm, keys)
        # residuals: only the FINAL state (reference reversible.py:277) —
        # this is the O(1)-in-depth activation memory property
        return out, (params, out, pm, mm, keys)

    def bwd(res, g):
        params, out, pm, mm, keys = res

        def body(carry, xs):
            h_out, gh = carry
            p, key = xs
            h_in, gh_in, gp = backward_one(p, h_out, gh, pm, mm, key)
            return (h_in, gh_in), gp

        (h0, gh0), gparams = jax.lax.scan(
            body, (out, g), (params, keys), reverse=True
        )
        del h0
        return (gparams, gh0, _float0_zeros(pm), _float0_zeros(mm),
                _float0_zeros(keys))

    rev_scan.defvjp(fwd, bwd)
    return rev_scan


class ReversibleTrunk(nn.Module):
    """Drop-in trunk engine with inversion-based backward.

    Requires the MSA stream (the reference asserts the same,
    reversible.py:316). ``use_custom_vjp=False`` runs the identical coupling
    under plain autodiff — the differential oracle for the custom backward.
    """

    dim: int
    depth: int = 6
    heads: int = 8
    dim_head: int = 64
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    gelu_exact: bool = False  # erf GELU (the reference's torch F.gelu)
    sparse_attn: bool = False
    seq_len: Optional[int] = None
    sparse_config: Optional[object] = None
    sparse_use_pallas: Optional[bool] = None
    cross_attn_compress_ratio: int = 1
    msa_tie_row_attn: bool = False
    use_custom_vjp: bool = True
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, m, pair_mask=None, msa_mask=None, deterministic=True):
        if m is None:
            raise ValueError(
                "ReversibleTrunk requires the MSA stream (reference "
                "reversible.py:316); use Trunk(remat=True) without one"
            )
        # The carried state must stay float32 even under bf16 compute:
        # inversion reconstructs x1 as (x1 + f) - f, and in bf16 that
        # roundoff compounds across the 8 updates x depth steps, silently
        # perturbing the inputs the backward vjp is evaluated at. With an
        # f32 carry, block outputs (bf16) promote on add and the
        # reconstruction error stays at f32 roundoff. Blocks still compute
        # in self.dtype (their LayerNorms cast on entry).
        x = x.astype(jnp.float32)
        m = m.astype(jnp.float32)
        template = RevLayerPair(
            dim=self.dim, heads=self.heads, dim_head=self.dim_head,
            attn_dropout=self.attn_dropout, ff_dropout=self.ff_dropout,
            gelu_exact=self.gelu_exact,
            sparse_attn=self.sparse_attn, seq_len=self.seq_len,
            sparse_config=self.sparse_config,
            sparse_use_pallas=self.sparse_use_pallas,
            cross_attn_compress_ratio=self.cross_attn_compress_ratio,
            msa_tie_row_attn=self.msa_tie_row_attn,
            dtype=self.dtype,
        )
        h0 = (x, x, m, m)

        def init_stack(rng):
            def init_one(k):
                return template.init(
                    k, h0, pair_mask, msa_mask, True
                )["params"]

            return jax.vmap(init_one)(jax.random.split(rng, self.depth))

        params = self.param("layers", init_stack)

        has_dropout = (self.attn_dropout > 0 or self.ff_dropout > 0) and (
            not deterministic
        )
        key = self.make_rng("dropout") if has_dropout else jax.random.key(0)
        keys = jax.random.key_data(jax.random.split(key, self.depth))

        has_pm = pair_mask is not None
        has_mm = msa_mask is not None
        det = deterministic
        # placeholders keep the operand list static; the closures below bake
        # in the None-ness so the placeholders are never read
        pm_arr = pair_mask if has_pm else jnp.zeros((1,), bool)
        mm_arr = msa_mask if has_mm else jnp.zeros((1,), bool)

        def forward_one(p, h, pm, mm, key_data):
            return template.apply(
                {"params": p}, h,
                pm if has_pm else None,
                mm if has_mm else None,
                det,
                rngs={"dropout": jax.random.wrap_key_data(key_data)},
            )

        def backward_one(p, h, gh, pm, mm, key_data):
            """One layer of the reverse schedule: walk the 8 additive updates
            backwards; each sub-function is evaluated ONCE under jax.vjp and
            its output reused for both the inversion subtraction and the
            cotangent pull (the reference's backward_pass schedule,
            reversible.py:85-156 — one extra evaluation per sub-function,
            not a full forward re-run)."""
            pmq = pm if has_pm else None
            mmq = mm if has_mm else None
            rngs = {"dropout": jax.random.wrap_key_data(key_data)}

            def vjp(method, *args):
                def f(p_, *a):
                    return template.apply(
                        {"params": p_}, *a, rngs=rngs, method=method
                    )

                return jax.vjp(f, p, *args)

            x1, x2, m1, m2 = h
            gx1, gx2, gm1, gm2 = gh
            add = lambda a, b: jax.tree.map(jnp.add, a, b)

            # 8. m2 += k_c(m1)
            out, pull = vjp(lambda s, a: s._k_c(a, det), m1)
            m2 = m2 - out
            gp, gi = pull(gm2.astype(out.dtype))
            gm1 = gm1 + gi
            # 7. m1 += j_c(m2, x2)
            out, pull = vjp(lambda s, a, b: s._j_c(a, b, pmq, mmq, det), m2, x2)
            m1 = m1 - out
            gp_i, gi_m2, gi_x2 = pull(gm1.astype(out.dtype))
            gp, gm2, gx2 = add(gp, gp_i), gm2 + gi_m2, gx2 + gi_x2
            # 6. x2 += g_c(x1)
            out, pull = vjp(lambda s, a: s._g_c(a, det), x1)
            x2 = x2 - out
            gp_i, gi = pull(gx2.astype(out.dtype))
            gp, gx1 = add(gp, gp_i), gx1 + gi
            # 5. x1 += f_c(x2, m2)
            out, pull = vjp(lambda s, a, b: s._f_c(a, b, pmq, mmq, det), x2, m2)
            x1 = x1 - out
            gp_i, gi_x2, gi_m2 = pull(gx1.astype(out.dtype))
            gp, gx2, gm2 = add(gp, gp_i), gx2 + gi_x2, gm2 + gi_m2
            # 4. m2 += k_s(m1)
            out, pull = vjp(lambda s, a: s._k_s(a, det), m1)
            m2 = m2 - out
            gp_i, gi = pull(gm2.astype(out.dtype))
            gp, gm1 = add(gp, gp_i), gm1 + gi
            # 3. m1 += j_s(m2)
            out, pull = vjp(lambda s, a: s._j_s(a, mmq, det), m2)
            m1 = m1 - out
            gp_i, gi = pull(gm1.astype(out.dtype))
            gp, gm2 = add(gp, gp_i), gm2 + gi
            # 2. x2 += g_s(x1)
            out, pull = vjp(lambda s, a: s._g_s(a, det), x1)
            x2 = x2 - out
            gp_i, gi = pull(gx2.astype(out.dtype))
            gp, gx1 = add(gp, gp_i), gx1 + gi
            # 1. x1 += f_s(x2)
            out, pull = vjp(lambda s, a: s._f_s(a, pmq, det), x2)
            x1 = x1 - out
            gp_i, gi = pull(gx1.astype(out.dtype))
            gp, gx2 = add(gp, gp_i), gx2 + gi

            return (x1, x2, m1, m2), (gx1, gx2, gm1, gm2), gp

        if self.use_custom_vjp:
            h = _make_rev_scan(forward_one, backward_one)(
                params, h0, pm_arr, mm_arr, keys
            )
        else:

            def body(carry, xs):
                p, key_data = xs
                return forward_one(p, carry, pm_arr, mm_arr, key_data), None

            h, _ = jax.lax.scan(body, h0, (params, keys))

        x1, x2, m1, m2 = h
        # average the duplicated halves back out (reference reversible.py:327)
        return 0.5 * (x1 + x2), 0.5 * (m1 + m2)
