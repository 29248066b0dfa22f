"""Plain float32 reference of the DeepSeek-V2 cut model: MLA with YaRN
rope, the leading dense layer, the router over all E experts with greedy
top-k, the held routed experts, the shared experts, DeepSeek's
per-sequence balance loss, next-token NLL over the vocabulary slice,
and momentum LARS with a trust ratio per weight matrix: per layer, and
per (layer, expert) for the routed experts.

Written from the published description (arXiv:2405.04434 and the
model's config.json) with ``jax.numpy`` alone; it imports nothing of the
program under test, and from ``reference.py`` only its matmul, RMSNorm,
rounding and norm helpers. Every matrix product runs at
``precision="highest"``; ``rnd`` rounds the operands of every matrix
product (the identity here; a lower precision in the control).

Departures from the published model, each also the program's:

* the chip share: only the routed experts held here (ids
  ``expert_offset`` .. ``+ n_routed_experts - 1`` of the router's
  ``router_experts``) add their part; what the others would add is left
  out. Each held expert is computed densely over every token and weighted
  by its gate where the token was routed to it, zero elsewhere;
* the vocabulary is the slice held here;
* the rope dimensions are de-interleaved (a fixed permutation of the rope
  columns of ``wq`` and ``kv_down``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.counts_deepseek_v2 import dims
from perfbench.reference import (HIGHEST, fp8_round, ident, leaf_norms,  # noqa: F401
                                 mm, rmsnorm, to_f32)

EXPERTS = ("wi", "wg", "wo")


# ------------------------------------------------------------------ YaRN

def _m(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(cfg: dict, dim: int):
    """(inverse frequencies (dim/2,), cos/sin factor, softmax scale) of
    DeepSeek-V2's YaRN rotary embedding."""
    rs, base = cfg["rope_scaling"], float(cfg["rope_theta"])
    fac, orig = float(rs["factor"]), rs["original_max_position_embeddings"]
    i = jnp.arange(0, dim, 2, dtype=jnp.float32)
    extra = 1.0 / base ** (i / dim)
    inter = 1.0 / (fac * base ** (i / dim))

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(corr(rs["beta_fast"])), 0)
    hi = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    mask = 1.0 - ramp
    inv = inter * (1.0 - mask) + extra * mask
    m_all = _m(fac, rs["mscale_all_dim"])
    cos_scale = _m(fac, rs["mscale"]) / m_all
    m = dims(cfg)
    scale = (m["nope"] + m["rope"]) ** -0.5 * m_all ** 2
    return inv, cos_scale, scale


def rope(x, pos, inv, cos_scale):
    """x (B, S, H, D), pos (S,): rotate the two halves of each head."""
    D = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * inv                # (S, D/2)
    cos = (jnp.cos(ang) * cos_scale)[:, None, :]
    sin = (jnp.sin(ang) * cos_scale)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# ------------------------------------------------------------- attention

def attention(q, k, v, scale, rnd=ident, q_block=512):
    """Causal multi-head attention, q/k (B,S,H,D), v (B,S,H,Dv); query
    rows in blocks."""
    B, S, H, D = q.shape
    qb = min(q_block, S)
    nb = -(-S // qb)
    qp = jnp.pad(q, ((0, 0), (0, nb * qb - S), (0, 0), (0, 0)))

    @jax.checkpoint            # keeps no block's scores for the backward
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qp, i * qb, qb, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", rnd(qi), rnd(k),
                       precision=HIGHEST) * scale
        rows = i * qb + jnp.arange(qb)
        s = jnp.where(jnp.arange(S)[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", rnd(p), rnd(v),
                          precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(nb))
    out = jnp.moveaxis(out, 0, 1).reshape(B, nb * qb, H, v.shape[-1])
    return out[:, :S]


def mla(cfg: dict, a: dict, x, rnd=ident):
    """Multi-head latent attention, expanded, no q compression."""
    m = dims(cfg)
    B, S, _ = x.shape
    H, nope, rp, r = m["h"], m["nope"], m["rope"], m["r"]
    inv, cos_scale, scale = yarn(cfg, rp)
    pos = jnp.arange(S)
    q = mm(x, a["wq"], rnd).reshape(B, S, H, nope + rp)
    kv = mm(x, a["kv_down"], rnd)
    c = rmsnorm(kv[..., :r], a["kv_norm"], cfg["rms_norm_eps"])
    k_rope = rope(kv[..., r:][:, :, None, :], pos, inv, cos_scale)
    q = jnp.concatenate([q[..., :nope],
                         rope(q[..., nope:], pos, inv, cos_scale)], -1)
    k = jnp.concatenate([mm(c, a["k_up"], rnd).reshape(B, S, H, nope),
                         jnp.broadcast_to(k_rope, (B, S, H, rp))], -1)
    v = mm(c, a["v_up"], rnd).reshape(B, S, H, m["v"])
    o = attention(q, k, v, scale, rnd).reshape(B, S, H * m["v"])
    return mm(o, a["wo"], rnd)


# ------------------------------------------------------------------ FFNs

def mlp(p: dict, x, rnd=ident):
    return mm(jax.nn.silu(mm(x, p["wg"], rnd)) * mm(x, p["wi"], rnd),
              p["wo"], rnd)


def moe(cfg: dict, p: dict, x, rnd=ident):
    """(out, balance loss per row (B,)): shared experts plus the held
    routed experts' part."""
    m = dims(cfg)
    E, k = m["E"], m["k"]
    probs = jax.nn.softmax(mm(x, p["router"], rnd), axis=-1)   # (B,S,E)
    gates, ids = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * cfg["routed_scaling_factor"]
    counts = jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=2)
    f = jnp.sum(counts, axis=1) * (E / (k * x.shape[1]))       # (B,E)
    aux = cfg["aux_loss_alpha"] * jnp.sum(f * jnp.mean(probs, 1), -1)
    out = mlp(p["shared"], x, rnd)
    for j in range(m["E_held"]):
        e = cfg["expert_offset"] + j
        g = jnp.sum(jnp.where(ids == e, gates, 0.0), -1)       # (B,S)
        out = out + g[..., None] * mlp(
            {n: p[n][j] for n in EXPERTS}, x, rnd)
    return out, aux


def layer(cfg: dict, p: dict, x, rnd=ident):
    """One layer on x (B, S, d) -> (x, balance loss per row)."""
    eps = cfg["rms_norm_eps"]
    x = x + mla(cfg, p["attn"], rmsnorm(x, p["ln1"]["scale"], eps), rnd)
    h = rmsnorm(x, p["ln2"]["scale"], eps)
    if "moe" in p:
        y, aux = moe(cfg, p["moe"], h, rnd)
    else:
        y, aux = mlp(p["mlp"], h, rnd), jnp.zeros(x.shape[0], jnp.float32)
    return x + y, aux


def forward(cfg: dict, params: dict, tokens, rnd=ident):
    """(logits (B, S, V), balance loss per row summed over layers (B,))
    of tokens (B, S)."""
    x = params["embed"][tokens]

    def body(carry, p):
        x, aux = carry
        x, a = layer(cfg, p, x, rnd)
        return (x, aux + a), None

    carry = (x, jnp.zeros(tokens.shape[0], jnp.float32))
    for stack in ("dense_layers", "layers"):
        carry, _ = jax.lax.scan(jax.checkpoint(body), carry, params[stack])
    x, aux = carry
    h = rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return mm(h, params["unembed"], rnd), aux


def losses(cfg: dict, params: dict, tokens, rnd=ident):
    """(summed next-token NLL of tokens (B, S), balance loss summed over
    layers and rows)."""
    logits, aux = forward(cfg, params, tokens, rnd)
    logits = logits[:, :-1]
    tgt = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - tgt), jnp.sum(aux)


# ------------------------------------------------------------------ LARS

def slice_axes(params: dict) -> dict:
    """Leading axes of each leaf that index weight matrices apart: 1 for
    the layer stacks, 2 for the routed experts (layer, expert)."""
    def mark(path, _):
        keys = [getattr(q, "key", None) for q in path]
        if keys[0] not in ("dense_layers", "layers"):
            return 0
        return 2 if keys[-2] == "moe" and keys[-1] in EXPERTS else 1
    return jax.tree_util.tree_map_with_path(mark, params)


def lars_step(params, grads, mom, *, lr, momentum, weight_decay,
              trust_coefficient, eps=1e-9):
    """One momentum-LARS step, one trust ratio per weight matrix; slices
    of rank <= 1 keep trust ratio 1, as do those whose weight or
    gradient norm is zero:

        lam = eta ||w|| / (||g|| + beta ||w||)
        m  <- mu m + lr lam (g + beta w);   w <- w - m
    """
    def leaf(w, g, m, n):
        axes = tuple(range(n, w.ndim))
        wn = jnp.sqrt(jnp.sum(w * w, axis=axes, keepdims=True))
        gn = jnp.sqrt(jnp.sum(g * g, axis=axes, keepdims=True))
        lam = trust_coefficient * wn / (gn + weight_decay * wn + eps)
        lam = jnp.where((wn > 0) & (gn > 0) & (w.ndim - n > 1), lam, 1.0)
        m = momentum * m + lr * lam * (g + weight_decay * w)
        return w - m, m

    out = jax.tree_util.tree_map(leaf, params, grads, mom,
                                 slice_axes(params))
    is_pair = lambda t: isinstance(t, tuple)  # noqa: E731
    return (jax.tree_util.tree_map(lambda t: t[0], out, is_leaf=is_pair),
            jax.tree_util.tree_map(lambda t: t[1], out, is_leaf=is_pair))


class TrainReference:
    """Steps of training from the same weights and batches, in float32;
    gradients over the batch in blocks of rows. The loss is the mean NLL
    over the batch's predicted tokens plus the balance loss summed over
    layers and averaged over rows."""

    def __init__(self, cfg: dict, opt: dict, rows_per_block: int,
                 rnd=ident):
        self.cfg, self.rows, self.rnd = cfg, rows_per_block, rnd
        self._grad = jax.jit(jax.value_and_grad(self._block_loss))
        self._step = jax.jit(lambda p, g, m: lars_step(
            p, g, m, lr=opt["lr"], momentum=opt["momentum"],
            weight_decay=opt["weight_decay"],
            trust_coefficient=opt["trust_coefficient"]),
            donate_argnums=(0, 1, 2))
        self._add = jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.add, a, b), donate_argnums=(0,))

    def _block_loss(self, params, tokens, inv_tokens, inv_rows):
        with jax.default_matmul_precision("highest"):
            nll, aux = losses(self.cfg, params, tokens, self.rnd)
        return nll * inv_tokens + aux * inv_rows

    def loss_and_grads(self, params, tokens):
        B, S = tokens.shape
        inv_t, inv_r = 1.0 / (B * (S - 1)), 1.0 / B
        total, grads = 0.0, None
        for r in range(0, B, self.rows):
            l, g = self._grad(params, tokens[r:r + self.rows], inv_t, inv_r)
            total = total + l
            grads = g if grads is None else self._add(grads, g)
        return total, grads

    def run(self, params, batches):
        """Losses of each step, the norms of the first gradient, the
        momentum after the first step (on the host) and the params after
        the last. ``params`` is consumed: the steps update it in place.
        The momentum waits on the host while the gradients take the chip
        (at DeepSeek-V2-Lite's widths params, momentum, gradient sum and
        a block's gradient are 2 GiB each)."""
        mom = jax.tree_util.tree_map(
            lambda x: np.zeros(x.shape, np.float32), params)
        out, first = [], {}
        for i, tokens in enumerate(batches):
            loss, grads = self.loss_and_grads(params, tokens)
            out.append(float(loss))
            norms = leaf_norms(grads) if i == 0 else None
            params, mom = self._step(params, grads, mom)
            del grads
            mom = jax.device_get(mom)
            if i == 0:
                first = {"first_grad": norms, "first_momentum": mom}
        return dict(first, losses=out, params=params)
