"""Mixture-of-Experts layer: a softmax router over all E experts with
greedy top-k, a dropless grouped-matmul dispatch over the experts this
layer holds, optional shared experts (DeepSeek-V2), and a load-balance
loss (DeepSeek's per-sequence form or Switch's).

Which experts a layer holds
---------------------------
Expert parallelism divides each layer's E routed experts over chips, and
the layer on one chip holds ``experts_held`` of them, ids
``expert_offset .. expert_offset + experts_held - 1`` (all E where
``experts_held`` is 0). It routes every token over all E, keeps the
(token, slot) pairs whose expert it holds and computes their part of
the result. The exchange that brings the other chips' parts is not
here: on one chip there is nothing to exchange with. With every expert
held this is the whole layer.

Dispatch: no capacity, no token dropped
---------------------------------------
  1. top-k route: (N, k) expert ids and gates;
  2. each of the N*k slots gets the local id of its expert, or E_held
     where this layer does not hold it; a stable argsort by that id puts
     each held expert's slots together and the others last;
  3. gather the slots' token rows into (N*k, d) and run one grouped
     matmul (``jax.lax.ragged_dot``) for each of wg, wi and wo over the
     held experts' group sizes; rows past the last group are zeroed;
  4. combine: each token sums its slots' rows times their gates.

The buffer has N*k rows whatever the routing, so no routing, however
skewed, can overflow it; the grouped matmuls work on the rows that
their groups cover.

Balance loss: with ``seq_aux`` DeepSeek-V2's per-sequence form
(arXiv:2405.04434 §4.2.2), alpha * mean over rows of sum_i f_i P_i with
f_i = E / (k S) * (slots of the row routed to i) and P_i the row's mean
router probability; else Switch's, alpha * E * sum_i f_i P_i with f_i
the share of the batch's tokens routed to i (summing to k) and P_i the
batch's mean probability. The two are the same quantity up to a factor
of 1/k and the grouping of tokens (a sequence against the whole batch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import phases
from repro.models import layers as L
from repro.models.mlp import init_mlp, mlp_block


# routing counters of a forward, beside its balance loss: rows the held
# experts computed (summed over layers), and the busiest held expert's
# rows over the mean (averaged over layers)
MOE_COUNTERS = ("moe_rows", "moe_load_max_over_mean")


def aux_zero(cfg) -> dict:
    """Zeros of the aux values a forward reports: the balance loss, and
    for MoE models the routing counters."""
    names = ("aux_loss",) + (MOE_COUNTERS if cfg.family == "moe" else ())
    return {k: jnp.zeros((), jnp.float32) for k in names}


def held_experts(cfg) -> int:
    """Routed experts this layer holds."""
    return cfg.experts_held or cfg.num_experts


def init_moe(key, cfg, d: int, dtype) -> dict:
    E, ff = held_experts(cfg), cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": L.dense_init(ks[0], d, cfg.num_experts, jnp.float32,
                               scale=0.1),
        "wi": _stack_init(ks[1], E, d, ff, dtype),
        "wo": _stack_init(ks[2], E, ff, d, dtype),
    }
    if L.gated(cfg):
        p["wg"] = _stack_init(ks[3], E, d, ff, dtype)
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d,
                               cfg.moe_d_ff * cfg.num_shared_experts, dtype)
    return p


def _stack_init(key, E, d_in, d_out, dtype):
    std = 1.0 / (d_in ** 0.5)
    return (jax.random.normal(key, (E, d_in, d_out), jnp.float32)
            * std).astype(dtype)


def route(cfg, router, x):
    """x (B, S, d) -> probs (B, S, E) f32 over all E experts, and the
    greedy top-k (ids, gates), each (B, S, k)."""
    logits = x.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-20)
    return probs, ids, gates * cfg.routed_scaling_factor


def balance_loss(cfg, probs, ids):
    """The un-weighted balance loss of routing (B, S, E) probs to
    (B, S, k) ids (module docstring)."""
    E, k = cfg.num_experts, cfg.experts_per_token
    counts = jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=2)
    if cfg.seq_aux:
        f = jnp.sum(counts, axis=1) * (E / (k * ids.shape[1]))   # (B, E)
        return jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), -1))
    f = jnp.mean(counts, axis=(0, 1))                           # (E,)
    return E * jnp.sum(f * jnp.mean(probs, axis=(0, 1)))


def held_slots(cfg, ids):
    """(N*k,) local expert id of every slot, E_held where not held."""
    E = held_experts(cfg)
    local = ids.reshape(-1) - cfg.expert_offset
    return jnp.where((local >= 0) & (local < E), local, E)


def grouped_mlp(cfg, p, xs, sizes):
    """The held experts' gated MLP over rows ``xs`` (M, d) sorted by
    expert, ``sizes`` (E_held,) rows each: one ``ragged_dot`` per
    weight."""
    act = L.act_fn(cfg)
    h = jax.lax.ragged_dot(xs, p["wi"], sizes)
    if "wg" in p:
        h = act(jax.lax.ragged_dot(xs, p["wg"], sizes)) * h
    else:
        h = act(h)
    return jax.lax.ragged_dot(h, p["wo"], sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather(x, perm, inv, fan: int):
    """x[perm // fan], where ``perm`` permutes range(fan * len(x)) and
    ``inv`` is its inverse. The gradient gathers by ``inv`` and sums the
    ``fan`` copies of each row, where that of an indexed read would
    scatter-add row by row (slow on the TPU)."""
    return x[perm // fan]


def _gather_fwd(x, perm, inv, fan):
    return x[perm // fan], inv


def _gather_bwd(fan, inv, g):
    rows = g[inv].astype(jnp.float32).reshape(-1, fan, g.shape[-1])
    return jnp.sum(rows, axis=1).astype(g.dtype), None, None


_gather.defvjp(_gather_fwd, _gather_bwd)


def _dispatch(cfg, p, xt, ids, gates):
    """xt (N, d), ids/gates (N, k) -> (out (N, d), rows each held
    expert computed (E_held,))."""
    N, d = xt.shape
    k = ids.shape[1]
    E = held_experts(cfg)
    with jax.named_scope(phases.MOE_DISPATCH):
        local = held_slots(cfg, ids)
        order = jnp.argsort(local, stable=True)
        sizes = jnp.bincount(local, length=E + 1)[:E].astype(jnp.int32)
        # rows past the held ones belong to no group: the TPU's grouped
        # matmul leaves them unwritten, in its outputs and in the
        # gradient it hands back, so both sides are masked
        live = (jnp.arange(N * k) < jnp.sum(sizes))[:, None]
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        xs = jnp.where(live, _gather(xt, order, back, k), 0)   # (N*k, d)
    with jax.named_scope(phases.MOE_EXPERTS):
        ys = grouped_mlp(cfg, p, xs, sizes)
    with jax.named_scope(phases.MOE_DISPATCH):
        ys = jnp.where(live, ys, 0)
        y = _gather(ys, back, order, 1).reshape(N, k, d)
        out = jnp.einsum("nkd,nk->nd", y, gates.astype(y.dtype),
                         preferred_element_type=jnp.float32)
    return out.astype(xt.dtype), sizes


def moe_block(cfg, p, x) -> tuple[jnp.ndarray, dict]:
    """x (B, S, d) -> (out (B, S, d), aux): ``aux_loss`` the weighted
    balance loss, ``moe_rows`` the rows the held experts computed and
    ``moe_load_max_over_mean`` the busiest held expert's rows over the
    mean.
    """
    B, S, d = x.shape
    N = B * S
    with jax.named_scope(phases.ROUTER):
        probs, ids, gates = route(cfg, p["router"], x)
        aux_loss = cfg.router_aux_coef * balance_loss(cfg, probs, ids)
    k = ids.shape[-1]
    xt, ids, gates = x.reshape(N, d), ids.reshape(N, k), gates.reshape(N, k)
    out, sizes = _dispatch(cfg, p, xt, ids, gates)
    if cfg.num_shared_experts:
        out = out + mlp_block(cfg, p["shared"], xt).astype(out.dtype)
    rows = jnp.sum(sizes).astype(jnp.float32)
    load = jnp.where(rows > 0, jnp.max(sizes) * sizes.shape[0]
                     / jnp.maximum(rows, 1.0), 0.0)
    return (out.reshape(B, S, d),
            {"aux_loss": aux_loss, "moe_rows": rows,
             "moe_load_max_over_mean": load})
