"""Generic decoder-only language model covering the dense / MoE / SSM /
hybrid / VLM families, assembled from the shared blocks.

Key structural decisions:
  * per-layer params are STACKED on a leading (L, ...) axis and the layer
    loop is a `lax.scan` — keeps HLO size O(1) in depth and is what the
    LARS `stacked` marker machinery exists for;
  * remat (`jax.checkpoint`) wraps the scan body, saving only the attention
    kernel's output and logsumexp (`causal_attention.RESIDUALS`) beside the
    residual-stream inputs, so the backward recomputes the layer but not
    the kernel's forward; where no kernel runs nothing carries the name
    and nothing is saved;
  * hybrid (zamba2): every `attn_every`-th scan step additionally applies a
    SHARED full attention+MLP block (same weights each application, its own
    KV cache per application) via `lax.cond` — the Zamba2 pattern;
  * VLM (paligemma): the text transformer consumes stub image patch
    embeddings as a bidirectional prefix (prefix-LM mask).

API: init / forward (train) / prefill / decode_step / init_cache /
stacked_marker.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import phases
from repro.kernels import causal_attention as CA
from repro.models import layers as L
from repro.models import attention as A
from repro.models import mla as MLA
from repro.models import ssm as SSM
from repro.models.mlp import init_mlp, mlp_block
from repro.models.moe import aux_zero, init_moe, moe_block
from repro.distributed.constrain import shard_batch

Pytree = Any

LAYER_STACKS = ("layers", "dense_layers")   # leaves stacked over layers
EXPERT_MATRICES = ("wi", "wg", "wo")         # the routed experts' stacks
# the layer body's remat policy: keep the attention kernel's residuals
LAYER_REMAT = jax.checkpoint_policies.save_only_these_names(CA.RESIDUALS)


@jax.custom_vjp
def _carry_barrier(x):
    """Differentiation-safe ``lax.optimization_barrier``.

    The raw primitive has no JVP/VJP rule, which kills `value_and_grad`
    through the layer scan. Straight-through custom_vjp: forward keeps
    the barrier; backward barriers the cotangent the same way (the
    transposed scan has the same hoisting exposure on its carry).
    """
    return jax.lax.optimization_barrier(x)


def _carry_barrier_fwd(x):
    return _carry_barrier(x), None


def _carry_barrier_bwd(_, ct):
    # recurse through the wrapper, not the raw primitive, so the VJP is
    # itself differentiable (second-order autodiff through the scan)
    return (_carry_barrier(ct),)


_carry_barrier.defvjp(_carry_barrier_fwd, _carry_barrier_bwd)


class LanguageModel:
    def __init__(self, cfg):
        assert cfg.family in ("dense", "moe", "ssm", "hybrid", "vlm"), cfg.family
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)

    # ------------------------------------------------------------------ init

    def _init_layer(self, key, dense: bool = False) -> dict:
        """One layer's params; ``dense``: a leading dense layer of an MoE
        model (a gated MLP of ``d_ff`` in place of the experts)."""
        cfg, d, dt = self.cfg, self.cfg.d_model, self.dtype
        ks = jax.random.split(key, 4)
        if cfg.family == "ssm":
            return {"ln1": L.init_norm(cfg, d),
                    "ssm": SSM.init_mamba1(ks[0], cfg, dt)}
        if cfg.family == "hybrid":
            return {"ln1": L.init_norm(cfg, d),
                    "ssm": SSM.init_mamba2(ks[0], cfg, dt)}
        p = {"ln1": L.init_norm(cfg, d), "ln2": L.init_norm(cfg, d)}
        if cfg.use_mla:
            p["attn"] = MLA.init_mla(ks[0], cfg, d, dt)
        else:
            p["attn"] = A.init_attention(ks[0], cfg, d, dt)
        if cfg.family == "moe" and not dense:
            p["moe"] = init_moe(ks[1], cfg, d, dt)
        else:
            p["mlp"] = init_mlp(ks[1], cfg, d, cfg.d_ff, dt)
        return p

    def init(self, key) -> Pytree:
        cfg, d, dt = self.cfg, self.cfg.d_model, self.dtype
        k_emb, k_layers, k_shared, k_out = jax.random.split(key, 4)
        k_dense = cfg.first_k_dense
        layer_keys = jax.random.split(k_layers, cfg.num_layers)
        params = {
            "embed": L.embed_init(k_emb, cfg.vocab_size, d, dt),
            "layers": jax.vmap(self._init_layer)(layer_keys[k_dense:]),
            "final_norm": L.init_norm(cfg, d),
        }
        if k_dense:
            params["dense_layers"] = jax.vmap(
                lambda k: self._init_layer(k, dense=True))(
                layer_keys[:k_dense])
        if not cfg.tie_embeddings:
            params["unembed"] = L.dense_init(k_out, d, cfg.vocab_size, dt)
        if cfg.family == "hybrid":
            params["shared"] = {
                "ln1": L.init_norm(cfg, d),
                "attn": A.init_attention(k_shared, cfg, d, dt),
                "ln2": L.init_norm(cfg, d),
                "mlp": init_mlp(jax.random.fold_in(k_shared, 1), cfg, d,
                                cfg.d_ff, dt),
            }
        return params

    def stacked_marker(self, params: Pytree) -> Pytree:
        """How many leading axes of each leaf stack slices that the
        layer-wise optimizers treat apart: 1 for (L, ...) layer stacks
        ('layers', 'dense_layers'), 2 for the routed experts'
        (L, E_held, ...) stacks (each expert is its own FFN), 0 else."""
        def mark(path, leaf):
            keys = [getattr(p, "key", None) for p in path]
            if not any(k in LAYER_STACKS for k in keys):
                return 0
            expert = keys[-2:-1] == ["moe"] and keys[-1] in EXPERT_MATRICES
            return 2 if expert else 1
        return jax.tree_util.tree_map_with_path(mark, params)

    def _stacks(self, params) -> list:
        """[(stacked layer params, first layer id, layers)]: the leading
        dense layers, if any, then the rest."""
        k = self.cfg.first_k_dense if "dense_layers" in params else 0
        head = [(params["dense_layers"], 0, k)] if k else []
        return head + [(params["layers"], k, self.cfg.num_layers - k)]

    def _layer_scan(self, body, carry, params, per_layer=()):
        """``carry, y = body(carry, (layer params, *per-layer slices,
        layer id))`` over every layer, stack by stack, as a ``lax.scan``.
        ``per_layer``: arrays with a leading num_layers axis. Returns
        (carry, ys over all layers)."""
        stacks = self._stacks(params)
        outs = []
        for sp, lo, n in stacks:
            sl = tuple(per_layer) if len(stacks) == 1 else tuple(
                jax.tree_util.tree_map(lambda t: t[lo:lo + n], a)
                for a in per_layer)
            carry, ys = jax.lax.scan(
                body, carry, (sp, *sl, jnp.arange(lo, lo + n)))
            outs.append(ys)
        if len(outs) == 1:
            return carry, outs[0]
        return carry, jax.tree_util.tree_map(
            lambda *a: jnp.concatenate(a), *outs)

    # ------------------------------------------------------------- embedding

    def embed_tokens(self, params, tokens):
        # pin the gather output to batch-sharded / d-replicated — the
        # vocab-parallel table would otherwise leave it ambiguous
        return shard_batch(params["embed"][tokens])

    def logits(self, params, x):
        cfg = self.cfg
        x = L.apply_norm(cfg, x, params["final_norm"])
        w = (params["embed"].T if cfg.tie_embeddings
             else params["unembed"])
        return shard_batch((x @ w).astype(jnp.float32), last="model")

    # ----------------------------------------------------------------- train

    def _layer_train(self, params_l, x, positions, prefix_len, layer_idx,
                     shared):
        cfg = self.cfg
        aux = aux_zero(cfg)
        if cfg.family in ("ssm", "hybrid"):
            h = L.apply_norm(cfg, x, params_l["ln1"])
            fwd = (SSM.mamba1_forward if cfg.family == "ssm"
                   else SSM.mamba2_forward)
            y, _ = fwd(cfg, params_l["ssm"], h)
            x = x + y
            if cfg.family == "hybrid" and cfg.attn_every:
                def with_attn(x):
                    h = L.apply_norm(cfg, x, shared["ln1"])
                    x = x + A.attention_block(cfg, shared["attn"], h,
                                              positions)
                    h = L.apply_norm(cfg, x, shared["ln2"])
                    return x + mlp_block(cfg, shared["mlp"], h)
                x = jax.lax.cond(layer_idx % cfg.attn_every == 0,
                                 with_attn, lambda x: x, x)
            return x, aux

        with jax.named_scope(phases.ATTENTION):
            h = L.apply_norm(cfg, x, params_l["ln1"])
            if cfg.use_mla:
                attn_out = MLA.mla_block(cfg, params_l["attn"], h, positions)
            else:
                attn_out = A.attention_block(cfg, params_l["attn"], h,
                                             positions, prefix_len=prefix_len)
            x = x + attn_out
        with jax.named_scope(phases.MLP):
            h = L.apply_norm(cfg, x, params_l["ln2"])
            if "moe" in params_l:
                y, aux = moe_block(cfg, params_l["moe"], h)
                x = x + y
            else:
                x = x + mlp_block(cfg, params_l["mlp"], h)
        return x, aux

    def forward(self, params, tokens, *, image_embeddings=None,
                return_hidden: bool = False) -> tuple[jnp.ndarray, dict]:
        """Train/eval forward. tokens (B, S_text).

        VLM: image_embeddings (B, n_img, d) stub prepended as bidirectional
        prefix; logits returned for the FULL sequence (loss masks prefix).
        Returns (logits (B, S, V) f32, aux dict) — or the final-norm
        hidden states (B, S, d) when ``return_hidden`` (the chunked-loss
        path computes the vocab matmul itself).
        """
        cfg = self.cfg
        with jax.named_scope(phases.EMBED):
            x = self.embed_tokens(params, tokens)
        prefix_len = None
        if cfg.family == "vlm":
            assert image_embeddings is not None, "vlm needs image stub"
            x = jnp.concatenate(
                [image_embeddings.astype(x.dtype), x], axis=1)
            prefix_len = image_embeddings.shape[1]
        B, S, d = x.shape
        positions = None    # 0..S-1, known while tracing (attention_core)
        shared = params.get("shared")

        def body(carry, inp):
            x, aux = carry
            # barrier: stops XLA hoisting the layer's first bf16->f32
            # convert (rmsnorm) into the scan's saved-carry stack, which
            # would store all L carries in f32 — 2x peak memory
            # (observed: 172 GB/device on qwen2-72b; §Perf iteration 2)
            x = _carry_barrier(x)
            params_l, idx = inp
            x, aux_l = self._layer_train(params_l, x, positions, prefix_len,
                                         idx, shared)
            return (shard_batch(x), jax.tree_util.tree_map(
                jnp.add, aux, aux_l)), None

        if cfg.remat:
            body = jax.checkpoint(body, policy=LAYER_REMAT)
        carry = (x, aux_zero(cfg))
        for stack, lo, n in self._stacks(params):
            carry, _ = jax.lax.scan(
                body, carry, (stack, jnp.arange(lo, lo + n)))
        x, aux = carry
        if "moe_load_max_over_mean" in aux:     # a mean over MoE layers
            n_moe = cfg.num_layers - cfg.first_k_dense
            aux["moe_load_max_over_mean"] = \
                aux["moe_load_max_over_mean"] / n_moe
        with jax.named_scope(phases.HEAD_LOSS):
            if return_hidden:
                return L.apply_norm(cfg, x, params["final_norm"]), aux
            return self.logits(params, x), aux

    def unembed_matrix(self, params):
        return (params["embed"].T if self.cfg.tie_embeddings
                else params["unembed"])

    # ----------------------------------------------------------------- cache

    def init_cache(self, batch: int, seq_len: int,
                   dtype: Optional[jnp.dtype] = None) -> Pytree:
        cfg = self.cfg
        dt = dtype or self.dtype
        Lk = cfg.num_layers
        H, Hkv, hd = cfg.attn_dims
        cache: dict[str, Any] = {
            "pos": jnp.zeros((batch,), jnp.int32)}
        if cfg.family == "ssm":
            din = cfg.ssm_d_inner
            cache["conv"] = jnp.zeros((Lk, batch, cfg.ssm_conv - 1, din), dt)
            cache["h"] = jnp.zeros((Lk, batch, din, cfg.ssm_state),
                                   jnp.float32)
        elif cfg.family == "hybrid":
            din = cfg.ssm_d_inner
            dxbc = din + 2 * cfg.ssm_groups * cfg.ssm_state
            heads = din // cfg.ssm_head_dim
            cache["conv"] = jnp.zeros((Lk, batch, cfg.ssm_conv - 1, dxbc), dt)
            cache["h"] = jnp.zeros(
                (Lk, batch, heads, cfg.ssm_head_dim, cfg.ssm_state),
                jnp.float32)
            n_attn = (Lk + cfg.attn_every - 1) // cfg.attn_every
            win = cfg.sliding_window or seq_len
            s_attn = min(seq_len, win)
            cache["attn_k"] = jnp.zeros((n_attn, batch, s_attn, Hkv, hd), dt)
            cache["attn_v"] = jnp.zeros((n_attn, batch, s_attn, Hkv, hd), dt)
        elif cfg.use_mla:
            cache["ckv"] = jnp.zeros((Lk, batch, seq_len, cfg.kv_lora_rank),
                                     dt)
            cache["krope"] = jnp.zeros((Lk, batch, seq_len, cfg.qk_rope_dim),
                                       dt)
        else:
            win = cfg.sliding_window or seq_len
            s_kv = min(seq_len, win) if cfg.sliding_window else seq_len
            cache["k"] = jnp.zeros((Lk, batch, s_kv, Hkv, hd), dt)
            cache["v"] = jnp.zeros((Lk, batch, s_kv, Hkv, hd), dt)
        return cache

    # ---------------------------------------------------------------- decode

    def _layer_decode(self, params_l, x, cache_l, pos, prefix_len, layer_idx,
                      shared, shared_cache, use_flash=False):
        """One layer, one token. cache_l: this layer's cache slices.
        Returns (x, new_cache_l, new_shared_cache)."""
        cfg = self.cfg
        if cfg.family in ("ssm", "hybrid"):
            h = L.apply_norm(cfg, x, params_l["ln1"])
            fwd = (SSM.mamba1_forward if cfg.family == "ssm"
                   else SSM.mamba2_forward)
            y, new_state = fwd(cfg, params_l["ssm"], h,
                               state={"conv": cache_l["conv"],
                                      "h": cache_l["h"]})
            x = x + y
            new_cache_l = dict(cache_l, conv=new_state["conv"],
                               h=new_state["h"])
            if cfg.family == "hybrid" and cfg.attn_every:
                k_all, v_all = shared_cache
                a_idx = layer_idx // cfg.attn_every

                def with_attn(args):
                    x, k_all, v_all = args
                    k_l = jax.lax.dynamic_index_in_dim(k_all, a_idx, 0,
                                                       keepdims=False)
                    v_l = jax.lax.dynamic_index_in_dim(v_all, a_idx, 0,
                                                       keepdims=False)
                    h = L.apply_norm(cfg, x, shared["ln1"])
                    out, k_l, v_l = A.decode_attention(
                        cfg, shared["attn"], h, k_l, v_l, pos,
                        use_flash=use_flash)
                    x = x + out
                    h = L.apply_norm(cfg, x, shared["ln2"])
                    x = x + mlp_block(cfg, shared["mlp"], h)
                    k_all = jax.lax.dynamic_update_index_in_dim(
                        k_all, k_l, a_idx, 0)
                    v_all = jax.lax.dynamic_update_index_in_dim(
                        v_all, v_l, a_idx, 0)
                    return x, k_all, v_all

                x, k_all, v_all = jax.lax.cond(
                    layer_idx % cfg.attn_every == 0, with_attn,
                    lambda a: a, (x, k_all, v_all))
                shared_cache = (k_all, v_all)
            return x, new_cache_l, shared_cache

        h = L.apply_norm(cfg, x, params_l["ln1"])
        if cfg.use_mla:
            out, ckv, krope = MLA.mla_decode(cfg, params_l["attn"], h,
                                             cache_l["ckv"], cache_l["krope"],
                                             pos)
            new_cache_l = dict(cache_l, ckv=ckv, krope=krope)
        else:
            out, k, v = A.decode_attention(cfg, params_l["attn"], h,
                                           cache_l["k"], cache_l["v"], pos,
                                           prefix_len=prefix_len,
                                           use_flash=use_flash)
            new_cache_l = dict(cache_l, k=k, v=v)
        x = x + out
        h = L.apply_norm(cfg, x, params_l["ln2"])
        if "moe" in params_l:
            y, _ = moe_block(cfg, params_l["moe"], h)
            x = x + y
        else:
            x = x + mlp_block(cfg, params_l["mlp"], h)
        return x, new_cache_l, shared_cache

    def decode_step(self, params, cache, tokens, *, prefix_len=None,
                    use_flash: bool = False) -> tuple[jnp.ndarray, Pytree]:
        """tokens (B, 1) -> (logits (B, 1, V), updated cache).

        ``use_flash`` routes GQA attention (dense layers and the hybrid
        shared block) through the Pallas flash-decode megakernel with
        the cache's real per-slot lengths; MLA/SSM layers are
        unaffected. Static — close it into the jitted serve step.
        """
        cfg = self.cfg
        x = self.embed_tokens(params, tokens)
        pos = cache["pos"]
        shared = params.get("shared")
        shared_cache = ((cache["attn_k"], cache["attn_v"])
                        if cfg.family == "hybrid" else None)
        layer_cache = {k: v for k, v in cache.items()
                       if k not in ("pos", "attn_k", "attn_v")}

        def body(carry, inp):
            x, shared_cache = carry
            params_l, cache_l, idx = inp
            x, new_cache_l, shared_cache = self._layer_decode(
                params_l, x, cache_l, pos, prefix_len, idx, shared,
                shared_cache, use_flash=use_flash)
            return (x, shared_cache), new_cache_l

        (x, shared_cache), new_layer_cache = self._layer_scan(
            body, (x, shared_cache), params, (layer_cache,))

        new_cache = dict(new_layer_cache)
        new_cache["pos"] = pos + 1
        if cfg.family == "hybrid":
            new_cache["attn_k"], new_cache["attn_v"] = shared_cache
        return self.logits(params, x), new_cache

    # --------------------------------------------------------------- prefill

    def prefill(self, params, tokens, *, image_embeddings=None,
                cache_len: Optional[int] = None, lengths=None
                ) -> tuple[jnp.ndarray, Pytree]:
        """Run the full prompt, building a decode cache.

        Implemented as forward + per-layer KV collection for attention
        archs, and a state-carrying pass for SSM/hybrid. Returns
        (last-token logits (B, V), cache ready for decode_step).

        ``lengths`` (B,) int32 marks per-row true prompt lengths of a
        right-padded token batch (heterogeneous-length slot admission):
        logits come from each row's last VALID position, the cache pos
        is set to ``lengths``, windowed KV rings are aligned per row,
        and SSM states are masked so pad tokens are identity steps.
        Causality makes the padded forward exact for valid positions;
        pad-position KV entries are never read back (decode masks
        kv_len = pos+1). Not supported for prefix-LM (vlm) prefill.
        """
        cfg = self.cfg
        with jax.named_scope(phases.EMBED):
            x = self.embed_tokens(params, tokens)
        prefix_len = None
        if cfg.family == "vlm":
            assert lengths is None, "vlm prefill has no lengths support"
            x = jnp.concatenate([image_embeddings.astype(x.dtype), x], axis=1)
            prefix_len = image_embeddings.shape[1]
        B, S, d = x.shape
        positions = jnp.arange(S)
        cap = cache_len or S
        cache = self.init_cache(B, cap)
        shared = params.get("shared")

        if cfg.family in ("ssm", "hybrid"):
            return self._prefill_recurrent(params, x, positions, cache,
                                           lengths=lengths)

        def body(carry, inp):
            x, = carry
            params_l, idx = inp
            h = L.apply_norm(cfg, x, params_l["ln1"])
            if cfg.use_mla:
                ckv, krope = MLA._latents(cfg, params_l["attn"], h, positions)
                out = MLA.mla_block(cfg, params_l["attn"], h, positions)
                kv_out = {"ckv": ckv, "krope": krope[:, :, 0, :]}
            else:
                q, k, v = A.qkv_project(cfg, params_l["attn"], h, positions)
                out = A.attention_core(
                    q, k, v, q_positions=None, causal=True,
                    window=cfg.sliding_window, prefix_len=prefix_len,
                    softcap=cfg.attn_logit_softcap)
                H, Hkv, hd = cfg.attn_dims
                out = out.reshape(B, S, H * hd) @ params_l["attn"]["wo"]
                kv_out = {"k": k, "v": v}
            x = x + out
            h = L.apply_norm(cfg, x, params_l["ln2"])
            if "moe" in params_l:
                y, _ = moe_block(cfg, params_l["moe"], h)
                x = x + y
            else:
                x = x + mlp_block(cfg, params_l["mlp"], h)
            return (x,), kv_out

        (x,), kvs = self._layer_scan(body, (x,), params)

        logits = self._last_valid_logits(params, x, lengths)
        if cfg.use_mla:
            cache["ckv"] = _fit(kvs["ckv"].astype(cache["ckv"].dtype),
                                cache["ckv"].shape[2], axis=2)
            cache["krope"] = _fit(kvs["krope"].astype(cache["krope"].dtype),
                                  cache["krope"].shape[2], axis=2)
        else:
            s_buf = cache["k"].shape[2]
            windowed = bool(cfg.sliding_window) and cfg.sliding_window <= s_buf
            if lengths is not None and windowed:
                # per-row ring alignment (heterogeneous true lengths)
                ring = functools.partial(_ring_gather, lengths=lengths,
                                         cap=s_buf)
                cache["k"] = jax.vmap(ring)(
                    kvs["k"].astype(cache["k"].dtype))
                cache["v"] = jax.vmap(ring)(
                    kvs["v"].astype(cache["v"].dtype))
            else:
                k_fit = _fit(kvs["k"].astype(cache["k"].dtype), s_buf, axis=2)
                v_fit = _fit(kvs["v"].astype(cache["v"].dtype), s_buf, axis=2)
                if cfg.sliding_window and S > s_buf:
                    # ring-align: absolute position p must sit at slot
                    # p % s_buf
                    k_fit = jnp.roll(k_fit, S % s_buf, axis=2)
                    v_fit = jnp.roll(v_fit, S % s_buf, axis=2)
                cache["k"], cache["v"] = k_fit, v_fit
        cache["pos"] = (jnp.full((B,), S, jnp.int32) if lengths is None
                        else lengths.astype(jnp.int32))
        return logits, cache

    def _last_valid_logits(self, params, x, lengths):
        """Logits of each row's last valid position ((B, V) f32)."""
        if lengths is None:
            return self.logits(params, x[:, -1:])[:, 0]
        idx = (lengths.astype(jnp.int32) - 1)[:, None, None]
        x_last = jnp.take_along_axis(x, idx, axis=1)         # (B, 1, d)
        return self.logits(params, x_last)[:, 0]

    # ------------------------------------------------------ slot admission

    def cache_capacity(self, cache: Pytree) -> Optional[int]:
        """Token capacity of a decode cache (None for pure-SSM caches,
        whose recurrent state is O(1) in sequence length)."""
        for k in ("k", "ckv", "attn_k"):
            if k in cache:
                return cache[k].shape[2]
        return None

    def prefill_at(self, params, cache, tokens, slots, *, lengths=None
                   ) -> tuple[jnp.ndarray, Pytree]:
        """Prefill prompts and write the resulting decode state into
        rows ``slots`` of a persistent slot cache (continuous-batching
        admission).

        cache: a live decode cache for ALL slots (``init_cache(slots,
        capacity)``); tokens (n, S) right-padded prompts; slots (n,)
        int32 slot ids; lengths (n,) true prompt lengths (None = all S).
        Returns (last-valid-token logits (n, V), updated cache). Pure —
        jit with the cache donated; the scatter touches only the
        admitted rows, so untouched slots keep decoding state intact.
        """
        cap = self.cache_capacity(cache)
        # a ring (sliding-window) buffer admits prompts LONGER than the
        # buffer — _ring_gather keeps the newest window per row; only a
        # linear buffer hard-bounds the prompt
        ring = (cap is not None and bool(self.cfg.sliding_window)
                and self.cfg.sliding_window <= cap)
        if cap is not None and not ring and tokens.shape[1] > cap:
            raise ValueError(f"prompt buffer {tokens.shape[1]} exceeds "
                             f"cache capacity {cap}")
        logits, small = self.prefill(params, tokens,
                                     cache_len=cap or tokens.shape[1],
                                     lengths=lengths)
        slots = slots.astype(jnp.int32)
        out = {}
        for name, big in cache.items():
            new = small[name]
            if name == "pos":                  # (B,) — batch axis 0
                out[name] = big.at[slots].set(new.astype(big.dtype))
            else:                              # (L, B, ...) — batch axis 1
                out[name] = big.at[:, slots].set(new.astype(big.dtype))
        return logits, out

    def prefill_chunk_at(self, params, cache, tokens, slots, *, start,
                         chunk_lengths) -> tuple[jnp.ndarray, Pytree]:
        """Resume prefill for a C-token chunk directly inside a
        persistent slot cache (chunked admission / prefix-suffix fill).

        tokens (n, C) right-padded chunk tokens; slots (n,) slot ids,
        or None meaning "all rows, in order" (the engine's fixed-shape
        chunk call — skips the row gather/scatter entirely);
        start (n,) resume positions (tokens[i, 0] is absolute position
        start[i] of its prompt — 0 for a cold chunk, the prefix length
        for a suffix resumed off a prefix-store copy, or a prior chunk
        boundary); chunk_lengths (n,) valid tokens in this chunk, with
        0 marking an INACTIVE row (its slot state passes through
        untouched — rows of a chunk group that already finished, or
        were cancelled, must not be re-written by later group chunks).
        Everything is traced, so one compile serves every (n, C) shape
        regardless of the per-row offsets.

        Returns (logits (n, V) of each row's last valid chunk token,
        updated cache). Pure — jit with the cache donated.
        """
        cfg = self.cfg
        assert cfg.family != "vlm", "vlm has no chunked prefill"
        start = start.astype(jnp.int32)
        chunk_lengths = chunk_lengths.astype(jnp.int32)
        if slots is None:
            small = cache
        else:
            slots = slots.astype(jnp.int32)
            small = {name: (big[slots] if name == "pos" else big[:, slots])
                     for name, big in cache.items()}
        logits, new_small = self._chunk_forward(params, small, tokens,
                                                start, chunk_lengths)
        active = chunk_lengths > 0
        out = {}
        for name, big in cache.items():
            new = new_small[name].astype(big.dtype)
            if name == "pos":                  # (n,) — batch axis 0
                merged = jnp.where(active, new, small[name])
                out[name] = (merged if slots is None
                             else big.at[slots].set(merged))
            else:                              # (L, n, ...) — batch axis 1
                act = active.reshape((1, -1) + (1,) * (new.ndim - 2))
                merged = jnp.where(act, new, small[name])
                out[name] = (merged if slots is None
                             else big.at[:, slots].set(merged))
        return logits, out

    def _chunk_forward(self, params, cache, tokens, start, lengths):
        """decode_step-shaped layer scan over a (B, C) chunk resumed at
        per-row absolute positions ``start``. Returns (last-valid
        logits (B, V), updated small cache with pos = start+lengths)."""
        cfg = self.cfg
        x = self.embed_tokens(params, tokens)
        B, C, d = x.shape
        qpos = start[:, None] + jnp.arange(C)[None, :]       # (B, C)
        shared = params.get("shared")
        shared_cache = ((cache["attn_k"], cache["attn_v"])
                        if cfg.family == "hybrid" else None)
        layer_cache = {k: v for k, v in cache.items()
                       if k not in ("pos", "attn_k", "attn_v")}
        # a COLD chunk (start == 0) lands in a freshly reacquired slot
        # whose recurrent state is the retired occupant's — stale KV is
        # masked by kv_len, but SSM conv/h carries in and must be zeroed
        for name in ("conv", "h"):
            if name in layer_cache:
                fresh = (start == 0).reshape(
                    (1, -1) + (1,) * (layer_cache[name].ndim - 2))
                layer_cache[name] = jnp.where(
                    fresh, jnp.zeros_like(layer_cache[name]),
                    layer_cache[name])

        def body(carry, inp):
            x, shared_cache = carry
            params_l, cache_l, idx = inp
            x, new_cache_l, shared_cache = self._layer_chunk(
                params_l, x, cache_l, qpos, start, lengths, idx, shared,
                shared_cache)
            return (x, shared_cache), new_cache_l

        (x, shared_cache), new_layer_cache = self._layer_scan(
            body, (x, shared_cache), params, (layer_cache,))

        new_cache = dict(new_layer_cache)
        new_cache["pos"] = start + lengths
        if cfg.family == "hybrid":
            new_cache["attn_k"], new_cache["attn_v"] = shared_cache
        # rows with lengths == 0 produce garbage logits the caller masks
        logits = self._last_valid_logits(params, x,
                                         jnp.maximum(lengths, 1))
        return logits, new_cache

    def _layer_chunk(self, params_l, x, cache_l, qpos, start, lengths,
                     layer_idx, shared, shared_cache):
        """One layer, one resumed chunk. Mirrors `_layer_decode` with the
        span/ring chunk attention and lengths-masked SSM recurrence."""
        cfg = self.cfg
        if cfg.family in ("ssm", "hybrid"):
            h = L.apply_norm(cfg, x, params_l["ln1"])
            fwd = (SSM.mamba1_forward if cfg.family == "ssm"
                   else SSM.mamba2_forward)
            # state carry-in + lengths: pad steps are identity for the
            # recurrence, and lengths == 0 returns the carried state
            y, st = fwd(cfg, params_l["ssm"], h,
                        state={"conv": cache_l["conv"], "h": cache_l["h"]},
                        lengths=lengths)
            x = x + y
            new_cache_l = dict(cache_l, conv=st["conv"], h=st["h"])
            if cfg.family == "hybrid" and cfg.attn_every:
                k_all, v_all = shared_cache
                a_idx = layer_idx // cfg.attn_every

                def with_attn(args):
                    x, k_all, v_all = args
                    k_l = jax.lax.dynamic_index_in_dim(k_all, a_idx, 0,
                                                       keepdims=False)
                    v_l = jax.lax.dynamic_index_in_dim(v_all, a_idx, 0,
                                                       keepdims=False)
                    h = L.apply_norm(cfg, x, shared["ln1"])
                    out, k_l, v_l = A.chunk_attention(
                        cfg, shared["attn"], h, k_l, v_l, qpos, start,
                        lengths)
                    x = x + out
                    h = L.apply_norm(cfg, x, shared["ln2"])
                    x = x + mlp_block(cfg, shared["mlp"], h)
                    k_all = jax.lax.dynamic_update_index_in_dim(
                        k_all, k_l, a_idx, 0)
                    v_all = jax.lax.dynamic_update_index_in_dim(
                        v_all, v_l, a_idx, 0)
                    return x, k_all, v_all

                x, k_all, v_all = jax.lax.cond(
                    layer_idx % cfg.attn_every == 0, with_attn,
                    lambda a: a, (x, k_all, v_all))
                shared_cache = (k_all, v_all)
            return x, new_cache_l, shared_cache

        h = L.apply_norm(cfg, x, params_l["ln1"])
        if cfg.use_mla:
            out, ckv, krope = MLA.mla_chunk(cfg, params_l["attn"], h,
                                            cache_l["ckv"],
                                            cache_l["krope"],
                                            qpos, start, lengths)
            new_cache_l = dict(cache_l, ckv=ckv, krope=krope)
        else:
            out, k, v = A.chunk_attention(cfg, params_l["attn"], h,
                                          cache_l["k"], cache_l["v"],
                                          qpos, start, lengths)
            new_cache_l = dict(cache_l, k=k, v=v)
        x = x + out
        h = L.apply_norm(cfg, x, params_l["ln2"])
        if "moe" in params_l:
            y, _ = moe_block(cfg, params_l["moe"], h)
            x = x + y
        else:
            x = x + mlp_block(cfg, params_l["mlp"], h)
        return x, new_cache_l, shared_cache

    def _prefill_recurrent(self, params, x, positions, cache, lengths=None):
        """SSM/hybrid prefill: full-sequence pass per layer, carrying the
        recurrent state; hybrid shared-attention KV is collected for the
        last `window` positions of each application.

        ``lengths``: see :meth:`prefill` — pad positions are identity
        steps for the recurrence, and the shared-attention ring is
        aligned per row (the scan then collects FULL-length KV so short
        rows keep their early positions)."""
        cfg = self.cfg
        B, S, d = x.shape
        shared = params.get("shared")
        hybrid = cfg.family == "hybrid"
        fwd = SSM.mamba1_forward if cfg.family == "ssm" else SSM.mamba2_forward
        zero_state = {"conv": jnp.zeros_like(cache["conv"][0]),
                      "h": jnp.zeros_like(cache["h"][0])}
        if hybrid:
            s_buf = cache["attn_k"].shape[2]
            kv_keep = s_buf if lengths is None else S
            H, Hkv, hd = cfg.attn_dims

        def body(carry, inp):
            x, = carry
            params_l, idx = inp
            h = L.apply_norm(cfg, x, params_l["ln1"])
            y, st = fwd(cfg, params_l["ssm"], h, state=zero_state,
                        lengths=lengths)
            x = x + y
            ys = {"conv": st["conv"], "h": st["h"]}
            if hybrid:
                def attn_branch(x):
                    h = L.apply_norm(cfg, x, shared["ln1"])
                    q, k, v = A.qkv_project(cfg, shared["attn"], h, positions)
                    out = A.attention_core(
                        q, k, v, q_positions=None, causal=True,
                        window=cfg.sliding_window)
                    x = x + out.reshape(B, S, H * hd) @ shared["attn"]["wo"]
                    hh = L.apply_norm(cfg, x, shared["ln2"])
                    x = x + mlp_block(cfg, shared["mlp"], hh)
                    return x, _fit(k, kv_keep, axis=1), _fit(v, kv_keep,
                                                            axis=1)

                def skip_branch(x):
                    z = jnp.zeros((B, kv_keep, Hkv, hd), x.dtype)
                    return x, z, z

                x, kk, vv = jax.lax.cond(idx % cfg.attn_every == 0,
                                         attn_branch, skip_branch, x)
                ys["kk"] = kk
                ys["vv"] = vv
            return (x,), ys

        (x,), ys = jax.lax.scan(body, (x,),
                                (params["layers"],
                                 jnp.arange(cfg.num_layers)))
        cache["conv"] = ys["conv"].astype(cache["conv"].dtype)
        cache["h"] = ys["h"]
        if hybrid:
            sel = jnp.arange(0, cfg.num_layers, cfg.attn_every)
            if lengths is not None:
                # full-length KV collected: ring-align each row by its
                # true length (vmap over shared-block applications)
                ring = functools.partial(_ring_gather, lengths=lengths,
                                         cap=s_buf)
                cache["attn_k"] = jax.vmap(ring)(
                    ys["kk"][sel].astype(cache["attn_k"].dtype))
                cache["attn_v"] = jax.vmap(ring)(
                    ys["vv"][sel].astype(cache["attn_v"].dtype))
            else:
                # ring-align: slot i of the window buffer must hold
                # absolute position (S - s_buf + i) ... which is
                # (S - s_buf + i) % s_buf in ring coordinates. Roll the
                # linear tail accordingly.
                shift = S % s_buf if S > s_buf else 0
                cache["attn_k"] = jnp.roll(
                    ys["kk"][sel].astype(cache["attn_k"].dtype), shift,
                    axis=2)
                cache["attn_v"] = jnp.roll(
                    ys["vv"][sel].astype(cache["attn_v"].dtype), shift,
                    axis=2)
        cache["pos"] = (jnp.full((B,), S, jnp.int32) if lengths is None
                        else lengths.astype(jnp.int32))
        logits = self._last_valid_logits(params, x, lengths)
        return logits, cache


def _ring_gather(kv, lengths, cap: int):
    """Per-row ring alignment of a full-length KV stripe.

    kv (B, S, ...) holds positions 0..S-1 of a right-padded batch whose
    true lengths are ``lengths`` (B,). Returns (B, cap, ...) where ring
    slot j holds the newest valid position p < lengths[b] with
    p % cap == j — exactly the layout the windowed decode ring expects
    (slot = pos % cap). Slots with no valid position (short rows) carry
    garbage that the decode validity mask (kv_len) never reads.
    """
    B, S = kv.shape[:2]
    j = jnp.arange(cap)[None, :]                        # (1, cap)
    base = lengths[:, None].astype(jnp.int32) - cap     # (B, 1)
    # smallest multiple of cap lifting j into [len-cap, len)
    extra = jnp.maximum(0, (base - j + cap - 1) // cap)
    p = jnp.clip(j + cap * extra, 0, S - 1)             # (B, cap)
    idx = p.reshape((B, cap) + (1,) * (kv.ndim - 2))
    return jnp.take_along_axis(kv, idx, axis=1)


def _fit(x, cap: int, *, axis: int):
    """Pad or crop x to capacity along axis (prefill -> decode cache)."""
    S = x.shape[axis]
    if S == cap:
        return x
    if S > cap:
        idx = [slice(None)] * x.ndim
        idx[axis] = slice(S - cap, S)
        return x[tuple(idx)]
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, cap - S)
    return jnp.pad(x, pad)
