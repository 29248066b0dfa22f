"""Plain float32 reference: the dense GQA decoder (RMSNorm, rotary
embeddings, grouped-query causal attention with optional q/k norms,
SwiGLU, tied or untied head), its next-token loss and gradients, and the
momentum LARS update (You et al., ICPP'18; the paper's Eq. 1-3).

Written from the published descriptions with ``jax.numpy`` alone; it
imports nothing of the program under test. Every matrix product runs at
``precision="highest"``. ``rnd`` rounds the operands of every matrix
product (the identity here; a lower precision in the control).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.counts import dims

HIGHEST = jax.lax.Precision.HIGHEST


def ident(x):
    return x


def fp8_round(x):
    """Round to float8 e4m3 and back: one step below bfloat16."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def mm(a, b, rnd=ident):
    return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (..., S, H, D), pos (S,): rotate the two halves of each head."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None] * inv          # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, rnd=ident, q_block=512):
    """Causal grouped-query attention. q (B,S,H,D), k/v (B,S,KV,D);
    query head h reads kv head h // (H // KV). Query rows in blocks."""
    B, S, H, D = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    qb = min(q_block, S)
    nb = -(-S // qb)
    qp = jnp.pad(q, ((0, 0), (0, nb * qb - S), (0, 0), (0, 0)))

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qp, i * qb, qb, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", rnd(qi), rnd(k),
                       precision=HIGHEST) * D ** -0.5
        rows = i * qb + jnp.arange(qb)
        s = jnp.where(jnp.arange(S)[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", rnd(p), rnd(v),
                          precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(nb))              # (nb,B,qb,H,D)
    out = jnp.moveaxis(out, 0, 1).reshape(B, nb * qb, H, D)
    return out[:, :S]


def layer(cfg: dict, p: dict, x, rnd=ident):
    """One decoder layer on x (B, S, d), all in float32."""
    m = dims(cfg)
    eps = cfg["rms_norm_eps"]
    B, S, _ = x.shape
    pos = jnp.arange(S)
    a = p["attn"]
    h = rmsnorm(x, p["ln1"]["scale"], eps)
    q = mm(h, a["wq"], rnd).reshape(B, S, m["h"], m["hd"])
    k = mm(h, a["wk"], rnd).reshape(B, S, m["kv"], m["hd"])
    v = mm(h, a["wv"], rnd).reshape(B, S, m["kv"], m["hd"])
    if cfg.get("qk_norm"):
        q = rmsnorm(q, a["q_norm"], eps)
        k = rmsnorm(k, a["k_norm"], eps)
    q = rope(q, pos, cfg["rope_theta"])
    k = rope(k, pos, cfg["rope_theta"])
    o = attention(q, k, v, rnd).reshape(B, S, m["h"] * m["hd"])
    x = x + mm(o, a["wo"], rnd)
    h = rmsnorm(x, p["ln2"]["scale"], eps)
    f = p["mlp"]
    g = jax.nn.silu(mm(h, f["wg"], rnd)) * mm(h, f["wi"], rnd)
    return x + mm(g, f["wo"], rnd)


def head_matrix(params: dict):
    return params["unembed"] if "unembed" in params else params["embed"].T


def hidden(cfg: dict, params: dict, tokens, rnd=ident):
    """Final-normed hidden states (B, S, d); layers stacked in params."""
    x = params["embed"][tokens]

    def body(x, p):
        return layer(cfg, p, x, rnd), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
    return rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def nll_sum(cfg: dict, params: dict, tokens, rnd=ident):
    """Summed next-token NLL of tokens (B, S): predict [1:] from [:-1]."""
    h = hidden(cfg, params, tokens, rnd)[:, :-1]
    logits = mm(h, head_matrix(params), rnd)
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.sum(lse - tgt)


def to_f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


# ------------------------------------------------------------------ LARS

def stacked_marker(params: dict) -> dict:
    """True for leaves whose leading axis stacks layers."""
    return {k: jax.tree_util.tree_map(lambda _: k == "layers", v)
            for k, v in params.items()}


def lars_step(params, grads, mom, *, lr, momentum, weight_decay,
              trust_coefficient, eps=1e-9):
    """One momentum-LARS step, per layer (each slice of a stacked leaf is
    a layer). Tensors of rank <= 1 per layer keep trust ratio 1, as do
    layers whose weight or gradient norm is zero:

        lam = eta ||w|| / (||g|| + beta ||w||)
        m  <- mu m + lr lam (g + beta w);   w <- w - m
    """
    def leaf(w, g, m, stacked):
        axes = tuple(range(1 if stacked else 0, w.ndim))
        rank = w.ndim - (1 if stacked else 0)
        wn = jnp.sqrt(jnp.sum(w * w, axis=axes, keepdims=True))
        gn = jnp.sqrt(jnp.sum(g * g, axis=axes, keepdims=True))
        lam = trust_coefficient * wn / (gn + weight_decay * wn + eps)
        lam = jnp.where((wn > 0) & (gn > 0) & (rank > 1), lam, 1.0)
        m = momentum * m + lr * lam * (g + weight_decay * w)
        return w - m, m

    out = jax.tree_util.tree_map(leaf, params, grads, mom,
                                 stacked_marker(params))
    is_pair = lambda t: isinstance(t, tuple)
    new_w = jax.tree_util.tree_map(lambda t: t[0], out, is_leaf=is_pair)
    new_m = jax.tree_util.tree_map(lambda t: t[1], out, is_leaf=is_pair)
    return new_w, new_m


def leaf_norms(tree) -> dict:
    """{path: float32 norm} of every leaf."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32)))) for p, x in flat}


class TrainReference:
    """Three (or more) steps of training from the same weights and
    batches, in float32; gradients over the batch in blocks of rows."""

    def __init__(self, cfg: dict, opt: dict, rows_per_block: int,
                 rnd=ident):
        self.cfg, self.opt, self.rows, self.rnd = cfg, opt, rows_per_block, rnd
        self._grad = jax.jit(jax.value_and_grad(
            functools.partial(self._block_loss)))
        self._step = jax.jit(functools.partial(
            lars_step, lr=opt["lr"], momentum=opt["momentum"],
            weight_decay=opt["weight_decay"],
            trust_coefficient=opt["trust_coefficient"]))
        self._add = jax.jit(lambda a, b: jax.tree_util.tree_map(
            jnp.add, a, b), donate_argnums=(0,))

    def _block_loss(self, params, tokens):
        with jax.default_matmul_precision("highest"):
            return nll_sum(self.cfg, params, tokens, self.rnd)

    def loss_and_grads(self, params, tokens):
        """Mean NLL over the batch and its gradient (float32)."""
        B, S = tokens.shape
        total, grads = 0.0, None
        for r in range(0, B, self.rows):
            l, g = self._grad(params, tokens[r:r + self.rows])
            total = total + l
            grads = g if grads is None else self._add(grads, g)
        n = B * (S - 1)
        return total / n, jax.tree_util.tree_map(lambda x: x / n, grads)

    def run(self, params, batches):
        """Losses of each step, the norms of the first gradient, the
        momentum after the first step and the params after the last."""
        mom = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, first = [], {}
        for i, tokens in enumerate(batches):
            loss, grads = self.loss_and_grads(params, tokens)
            losses.append(float(loss))
            params, mom = self._step(params, grads, mom)
            if i == 0:
                first = {"first_grad": leaf_norms(grads),
                         "first_momentum": mom}
        return dict(first, losses=losses, params=params)

