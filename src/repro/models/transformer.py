"""Model assembly: decoder-only LMs (dense / MoE / SSM / hybrid / VLM) and
the Whisper-style encoder-decoder, with PSL client/server segmentation.

Parameter trees are split into ``client`` and ``server`` subtrees at the
paper's cut layer so the PSL protocol (repro.core.psl) and the sharding rules
(client replicated over data, server FSDP) can address them independently.

All long stacks are ``lax.scan``-ed over stacked parameters; attention is the
blockwise flash-style implementation from repro.models.layers (O(chunk²)
memory, causal block skipping), and large-vocab losses use a seq-chunked
rematerialized cross-entropy so (B, S, V) logits are never materialized.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ModelConfig, ParamSpec
from repro import sharding as _sharding


def stack_specs(specs, n: int):
    """Prepend a stacked `layers` dim of size n to every spec in a tree."""
    return L.tree_map_specs(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes,
                            init=s.init, dtype=s.dtype, scale=s.scale),
        specs)


def _remat(fn, mode: str):
    if mode == "full":
        return jax.checkpoint(fn,
                              policy=jax.checkpoint_policies.nothing_saveable)
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


def scan_stack(body, carry, *stacked):
    """lax.scan over stacked layer params."""
    return jax.lax.scan(body, carry, stacked if len(stacked) > 1
                        else stacked[0])


# ---------------------------------------------------------------------------
# Chunked cross-entropy (never materializes (B, S, V))
# ---------------------------------------------------------------------------

def chunked_xent(hidden, w_vocab, labels, weights, chunk: int = 512,
                 logit_dtype=jnp.float32):
    """Weighted mean cross-entropy, scanning over sequence chunks.

    hidden: (B, S, d); w_vocab: (d, V); labels, weights: (B, S).
    Returns (loss, (weighted_token_count, correct_count)).
    """
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    while s % chunk:
        chunk //= 2
    n = s // chunk
    hr = jnp.moveaxis(hidden.reshape(b, n, chunk, d), 1, 0)
    lr = jnp.moveaxis(labels.reshape(b, n, chunk), 1, 0)
    wr = jnp.moveaxis(weights.reshape(b, n, chunk), 1, 0)

    @jax.checkpoint
    def chunk_loss(h, lab, w):
        logits = (h @ w_vocab).astype(logit_dtype)            # (B, c, V)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        nll = (lse - tgt) * w
        correct = ((logits.argmax(-1) == lab) * w).sum()
        return nll.sum(), correct

    def body(carry, inp):
        tot, cnt, cor = carry
        nll, correct = chunk_loss(*inp)
        return (tot + nll, cnt + inp[2].sum(), cor + correct), None

    (tot, cnt, cor), _ = jax.lax.scan(
        body, (jnp.float32(0), jnp.float32(0), jnp.float32(0)), (hr, lr, wr))
    loss = tot / jnp.maximum(cnt, 1e-6)
    return loss, (cnt, cor)


# ---------------------------------------------------------------------------
# Decoder blocks: specs + apply (train / decode)
# ---------------------------------------------------------------------------

class _Blocks:
    """Per-family block definitions used by Model."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ----- specs -----
    def attn_block_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        specs: Dict[str, Any] = {
            "norm1": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
            "attn": L.attention_specs(cfg),
            "norm2": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
        }
        if cfg.is_moe:
            specs["moe"] = L.moe_specs(cfg)
        else:
            specs["mlp"] = L.mlp_specs(cfg)
        return specs

    def ssm_block_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        mixer = (L.mamba1_specs(cfg) if cfg.ssm_variant == "mamba1"
                 else L.mamba2_specs(cfg))
        return {"norm": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
                "mixer": mixer}

    def block_specs(self) -> Dict[str, Any]:
        if self.cfg.family in ("ssm", "hybrid"):
            return self.ssm_block_specs()
        return self.attn_block_specs()

    # ----- train/prefill apply -----
    def attn_block(self, p, x, positions, aux, *, window, fill_cache=False):
        cfg = self.cfg
        hn = L.rms_norm(x, p["norm1"], cfg.norm_eps)
        b, s, _ = x.shape
        q, k, v = L.attention_qkv(p["attn"], hn, cfg, positions)
        attn_out = L.blockwise_attention(
            q, k, v, causal=True, window=window,
            q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
            block_skip=cfg.causal_block_skip)
        x = x + attn_out.reshape(b, s, -1) @ p["attn"]["wo"]
        hn = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        if cfg.is_moe:
            y, a = L.moe_apply(p["moe"], hn, cfg)
            aux = aux + a
        else:
            y = L.mlp_apply(p["mlp"], hn)
        x = x + y
        if fill_cache:
            kc, vc = self._cache_from_kv(k, v)
            return x, aux, (kc, vc)
        return x, aux

    def ssm_block(self, p, x, aux):
        cfg = self.cfg
        hn = L.rms_norm(x, p["norm"], cfg.norm_eps)
        apply = (L.mamba1_apply if cfg.ssm_variant == "mamba1"
                 else L.mamba2_apply)
        return x + apply(p["mixer"], hn, cfg), aux

    def ssm_block_prefill(self, p, x):
        cfg = self.cfg
        hn = L.rms_norm(x, p["norm"], cfg.norm_eps)
        apply = (L.mamba1_apply if cfg.ssm_variant == "mamba1"
                 else L.mamba2_apply)
        y, st = apply(p["mixer"], hn, cfg, return_state=True)
        return x + y, st

    # ----- decode apply -----
    def attn_block_decode(self, p, x, cache, pos, *, window):
        """``pos`` may be a scalar (all rows share one position) or a (B,)
        vector (continuous batching: each cache slot at its own position)."""
        cfg = self.cfg
        b = x.shape[0]
        hn = L.rms_norm(x, p["norm1"], cfg.norm_eps)
        pos = jnp.broadcast_to(jnp.asarray(pos), (b,))
        positions = pos[:, None]
        q, k, v = L.attention_qkv(p["attn"], hn, cfg, positions)
        kc, vc = cache["k"], cache["v"]
        k_rep, v_rep = self._repeat_kv(k), self._repeat_kv(v)
        slot = pos % kc.shape[1]
        bidx = jnp.arange(b)
        kc = kc.at[bidx, slot].set(k_rep[:, 0])
        vc = vc.at[bidx, slot].set(v_rep[:, 0])
        # Ring cache (cache_len == window): slot-validity masking suffices.
        # Full cache with a window: pass the window so old keys are masked.
        eff_window = (None if (window is not None and kc.shape[1] <= window)
                      else window)
        attn_out = L.decode_attention(q, kc, vc, pos, window=eff_window)
        x = x + attn_out.reshape(b, 1, -1) @ p["attn"]["wo"]
        hn2 = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        if cfg.is_moe:
            y, _ = L.moe_apply(p["moe"], hn2, cfg)
        else:
            y = L.mlp_apply(p["mlp"], hn2)
        return x + y, {"k": kc, "v": vc}

    def attn_block_decode_paged(self, p, x, cache, pos, page_table):
        """Decode over a paged KV cache (repro.runtime.paging).

        ``cache["k"]/["v"]`` are page buffers (NP, P, Hc, hd) shared by
        all rows; ``page_table`` (B, M) int32 maps each row's logical
        pages to physical ones. The new token's kv is scattered to
        physical page ``table[b, pos//P]`` at offset ``pos % P`` —
        inactive rows must point their table at a scratch page so the
        scatter cannot land on a live request's page.
        """
        cfg = self.cfg
        b = x.shape[0]
        hn = L.rms_norm(x, p["norm1"], cfg.norm_eps)
        pos = jnp.broadcast_to(jnp.asarray(pos), (b,))
        positions = pos[:, None]
        q, k, v = L.attention_qkv(p["attn"], hn, cfg, positions)
        kc, vc = cache["k"], cache["v"]
        k_rep, v_rep = self._repeat_kv(k), self._repeat_kv(v)
        psize = kc.shape[1]
        page = jnp.take_along_axis(page_table, (pos // psize)[:, None],
                                   axis=1)[:, 0]
        off = pos % psize
        kc = kc.at[page, off].set(k_rep[:, 0])
        vc = vc.at[page, off].set(v_rep[:, 0])
        attn_out = L.paged_decode_attention(q, kc, vc, page_table, pos)
        x = x + attn_out.reshape(b, 1, -1) @ p["attn"]["wo"]
        hn2 = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        if cfg.is_moe:
            y, _ = L.moe_apply(p["moe"], hn2, cfg)
        else:
            y = L.mlp_apply(p["mlp"], hn2)
        return x + y, {"k": kc, "v": vc}

    def attn_block_decode_window_paged(self, p, x, cache, q_pos,
                                       page_table):
        """Speculative-window decode over a paged KV cache.

        Like :meth:`attn_block_decode_paged` but for W tokens per row at
        absolute positions ``q_pos`` (B, W): each position's kv is
        scattered to physical page ``table[b, q_pos // P]`` at offset
        ``q_pos % P`` (the engine pre-reserves the window's pages on the
        forked table; lanes past a row's window length carry a q_pos
        that resolves to a scratch column), then every query attends
        causally over the row's pages — key position k is visible to
        query i iff ``k <= q_pos[b, i]``, so in-window drafts see the
        drafts before them but never the ones after.
        """
        cfg = self.cfg
        b, w = x.shape[0], x.shape[1]
        hn = L.rms_norm(x, p["norm1"], cfg.norm_eps)
        q, k, v = L.attention_qkv(p["attn"], hn, cfg, q_pos)
        kc, vc = cache["k"], cache["v"]
        k_rep, v_rep = self._repeat_kv(k), self._repeat_kv(v)
        psize = kc.shape[1]
        pages = jnp.take_along_axis(page_table, q_pos // psize, axis=1)
        offs = q_pos % psize                                  # (B, W)
        kc = kc.at[pages, offs].set(k_rep)
        vc = vc.at[pages, offs].set(v_rep)
        attn_out = L.paged_window_attention(q, kc, vc, page_table, q_pos)
        x = x + attn_out.reshape(b, w, -1) @ p["attn"]["wo"]
        hn2 = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        if cfg.is_moe:
            y, _ = L.moe_apply(p["moe"], hn2, cfg)
        else:
            y = L.mlp_apply(p["mlp"], hn2)
        return x + y, {"k": kc, "v": vc}

    def ssm_block_decode(self, p, x, cache):
        cfg = self.cfg
        hn = L.rms_norm(x, p["norm"], cfg.norm_eps)
        apply = (L.mamba1_apply if cfg.ssm_variant == "mamba1"
                 else L.mamba2_apply)
        y, new_state = apply(p["mixer"], hn, cfg, state=cache)
        return x + y, new_state

    # ----- cache helpers -----
    def kv_cache_heads(self) -> int:
        cfg = self.cfg
        return cfg.num_kv_heads * self.kv_repeat()

    def kv_repeat(self) -> int:
        # Replicate kv heads so the cache head axis shards over the 16-way
        # model axis (DESIGN.md). Valid only when the factor also divides
        # the GQA group size (each cache copy must own an integer number of
        # q heads); otherwise the cache keeps kv heads and the *sequence*
        # axis is sharded instead (attn_cache_specs).
        cfg = self.cfg
        if cfg.num_kv_heads % 16 == 0 or cfg.num_heads == cfg.num_kv_heads:
            return 1
        group = cfg.num_heads // max(cfg.num_kv_heads, 1)
        if cfg.num_kv_heads < cfg.num_heads and 16 % cfg.num_kv_heads == 0:
            r = 16 // cfg.num_kv_heads
            if r <= group and group % r == 0:
                return r
        return 1

    def _repeat_kv(self, k):
        r = self.kv_repeat()
        return jnp.repeat(k, r, axis=2) if r > 1 else k

    def _cache_from_kv(self, k, v):
        return self._repeat_kv(k), self._repeat_kv(v)

    def attn_cache_specs(self, batch: int, cache_len: int):
        cfg = self.cfg
        heads = self.kv_cache_heads()
        shape = (batch, cache_len, heads, cfg.head_dim)
        if heads % 16 == 0:
            axes = ("batch", None, "kv_heads_cache", None)
        elif cache_len % 16 == 0:
            # heads don't divide the model axis: shard the sequence instead
            # (softmax over the sharded axis lowers to partial max/sum +
            # all-reduce under GSPMD).
            axes = ("batch", "cache_seq", None, None)
        else:
            axes = ("batch", None, None, None)
        return {"k": ParamSpec(shape, axes, init="zeros"),
                "v": ParamSpec(shape, axes, init="zeros")}

    def ssm_cache_specs(self, batch: int):
        cfg = self.cfg
        shapes = L.ssm_state_shapes(cfg, batch)
        if cfg.ssm_variant == "mamba1":
            return {"conv": ParamSpec(shapes["conv"],
                                      ("batch", None, "inner"), init="zeros"),
                    "ssm": ParamSpec(shapes["ssm"],
                                     ("batch", "inner", None), init="zeros",
                                     dtype=jnp.float32)}
        return {"conv": ParamSpec(shapes["conv"],
                                  ("batch", None, "inner"), init="zeros"),
                "ssm": ParamSpec(shapes["ssm"],
                                 ("batch", "inner", None, None),
                                 init="zeros", dtype=jnp.float32)}


# ---------------------------------------------------------------------------
# Decoder-only language model (dense / moe / ssm / hybrid / vlm)
# ---------------------------------------------------------------------------

class LanguageModel:
    """Decoder-only LM with a PSL cut. Families: dense, moe, ssm, hybrid, vlm."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.blocks = _Blocks(cfg)
        if cfg.family == "hybrid":
            rem = cfg.num_layers - cfg.cut_layer
            self.n_super = rem // cfg.attn_period
            self.n_pre = rem - self.n_super * cfg.attn_period
        else:
            self.n_super = self.n_pre = 0

    # ----- parameter specs -----
    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        d, v = cfg.d_model, cfg.vocab_size
        bs = self.blocks.block_specs()
        client: Dict[str, Any] = {
            "embed": ParamSpec((v, d), ("vocab", "embed"), init="embed"),
            "blocks": stack_specs(bs, cfg.cut_layer),
        }
        server: Dict[str, Any] = {
            "final_norm": ParamSpec((d,), ("embed",), init="ones"),
        }
        if cfg.family == "hybrid":
            shared = {"norm1": ParamSpec((d,), ("embed",), init="ones"),
                      "attn": L.attention_specs(cfg)}
            if self.n_pre:
                server["pre_blocks"] = stack_specs(bs, self.n_pre)
            server["shared_attn"] = shared
            server["superblocks"] = stack_specs(
                stack_specs(bs, cfg.attn_period), self.n_super)
        else:
            server["blocks"] = stack_specs(bs,
                                           cfg.num_layers - cfg.cut_layer)
        if not cfg.tie_embeddings:
            server["lm_head"] = ParamSpec((d, v), ("embed", "vocab"))
        return {"client": client, "server": server}

    def init(self, key):
        return L.materialize(self.param_specs(), key, self.cfg.jnp_dtype)

    def abstract_params(self):
        return L.abstractify(self.param_specs(), self.cfg.jnp_dtype)

    # ----- forward pieces -----
    def _embed(self, params, batch):
        cfg = self.cfg
        tok = batch["tokens"]
        x = params["client"]["embed"][tok]
        if cfg.family == "vlm" and "patches" in batch:
            patches = batch["patches"].astype(x.dtype)
            x = jnp.concatenate([patches, x], axis=1)
        return x

    def _positions(self, x):
        b, s, _ = x.shape
        return jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def _run_stack(self, stacked, x, aux, positions, window):
        cfg = self.cfg
        if cfg.family in ("ssm", "hybrid"):
            def body(carry, lp):
                xx, aa = carry
                xx = _sharding.constrain_activation(xx)
                xx, aa = self.blocks.ssm_block(lp, xx, aa)
                return (xx, aa), None
        else:
            def body(carry, lp):
                xx, aa = carry
                xx = _sharding.constrain_activation(xx)
                xx, aa = self.blocks.attn_block(lp, xx, positions, aa,
                                                window=window)
                return (xx, aa), None
        body = _remat(body, cfg.remat)
        (x, aux), _ = scan_stack(body, (x, aux), stacked)
        return x, aux

    def _shared_attn_apply(self, p, x, positions, window):
        cfg = self.cfg
        hn = L.rms_norm(x, p["norm1"], cfg.norm_eps)
        return x + L.self_attention(p["attn"], hn, cfg, causal=True,
                                    window=window, positions=positions)

    def _backbone(self, params, x, positions, window):
        """Client + server stacks; returns (hidden, aux_loss)."""
        cfg = self.cfg
        aux = jnp.float32(0)
        x, aux = self._run_stack(params["client"]["blocks"], x, aux,
                                 positions, window)
        srv = params["server"]
        if cfg.family == "hybrid":
            if self.n_pre:
                x, aux = self._run_stack(srv["pre_blocks"], x, aux,
                                         positions, window)

            def super_body(carry, lp):
                xx, aa = carry
                xx = self._shared_attn_apply(srv["shared_attn"], xx,
                                             positions, window)
                xx, aa = self._run_stack(lp, xx, aa, positions, window)
                return (xx, aa), None
            (x, aux), _ = scan_stack(super_body, (x, aux),
                                     srv["superblocks"])
        else:
            x, aux = self._run_stack(srv["blocks"], x, aux, positions, window)
        x = L.rms_norm(x, srv["final_norm"], cfg.norm_eps)
        return x, aux

    def _lm_head(self, params):
        if self.cfg.tie_embeddings:
            return params["client"]["embed"].T
        return params["server"]["lm_head"]

    def loss_fn(self, params, batch, window: Optional[int] = None):
        """Masked-mean LM loss over the PSL global batch.

        batch: tokens (B, S) int32, labels (B, S) int32, weights (B, S) f32
        (slot mask × token mask from the epoch plan), optional patches.
        """
        cfg = self.cfg
        window = window if window is not None else cfg.sliding_window
        x = self._embed(params, batch)
        positions = self._positions(x)
        h, aux = self._backbone(params, x, positions, window)
        labels, weights = batch["labels"], batch["weights"]
        if cfg.family == "vlm" and "patches" in batch:
            p = batch["patches"].shape[1]
            pad_lab = jnp.zeros((x.shape[0], p), labels.dtype)
            pad_w = jnp.zeros((x.shape[0], p), weights.dtype)
            labels = jnp.concatenate([pad_lab, labels], axis=1)
            weights = jnp.concatenate([pad_w, weights], axis=1)
        loss, (cnt, cor) = chunked_xent(h, self._lm_head(params), labels,
                                        weights)
        total = loss + aux
        return total, {"loss": loss, "aux_loss": aux, "tokens": cnt,
                       "accuracy": cor / jnp.maximum(cnt, 1.0)}

    # ----- PSL decomposition -----
    def client_forward(self, params, batch, window: Optional[int] = None):
        """Client-side FP: embedding + first `cut_layer` blocks → cut acts."""
        window = window if window is not None else self.cfg.sliding_window
        x = self._embed(params, batch)
        positions = self._positions(x)
        aux = jnp.float32(0)
        x, _ = self._run_stack(params["client"]["blocks"], x, aux,
                               positions, window)
        return x

    def server_loss(self, server_params, cut_acts, batch,
                    window: Optional[int] = None):
        """Server-side FP from the cut activations to the loss."""
        cfg = self.cfg
        window = window if window is not None else cfg.sliding_window
        positions = self._positions(cut_acts)
        aux = jnp.float32(0)
        x = cut_acts
        if cfg.family == "hybrid":
            if self.n_pre:
                x, aux = self._run_stack(server_params["pre_blocks"], x,
                                         aux, positions, window)

            def super_body(carry, lp):
                xx, aa = carry
                xx = self._shared_attn_apply(server_params["shared_attn"],
                                             xx, positions, window)
                xx, aa = self._run_stack(lp, xx, aa, positions, window)
                return (xx, aa), None
            (x, aux), _ = scan_stack(super_body, (x, aux),
                                     server_params["superblocks"])
        else:
            x, aux = self._run_stack(server_params["blocks"], x, aux,
                                     positions, window)
        x = L.rms_norm(x, server_params["final_norm"], cfg.norm_eps)
        labels, weights = batch["labels"], batch["weights"]
        if cfg.family == "vlm" and "patches" in batch:
            p = batch["patches"].shape[1]
            labels = jnp.concatenate(
                [jnp.zeros((x.shape[0], p), labels.dtype), labels], axis=1)
            weights = jnp.concatenate(
                [jnp.zeros((x.shape[0], p), weights.dtype), weights], axis=1)
        if cfg.tie_embeddings:
            raise ValueError("PSL decomposed loss needs untied lm_head")
        loss, (cnt, cor) = chunked_xent(x, server_params["lm_head"], labels,
                                        weights)
        return loss + aux

    # ----- decode path -----
    def cache_specs(self, batch: int, cache_len: int,
                    window: Optional[int] = None) -> Dict[str, Any]:
        cfg = self.cfg
        window = window if window is not None else cfg.sliding_window
        eff_len = min(cache_len, window) if window else cache_len
        if cfg.family in ("ssm", "hybrid"):
            ssm_c = self.blocks.ssm_cache_specs(batch)
            tree: Dict[str, Any] = {
                "client": stack_specs(ssm_c, cfg.cut_layer)}
            if cfg.family == "hybrid":
                attn_c = self.blocks.attn_cache_specs(batch, eff_len)
                if self.n_pre:
                    tree["server_pre"] = stack_specs(ssm_c, self.n_pre)
                tree["server_attn"] = stack_specs(attn_c, self.n_super)
                tree["server_super"] = stack_specs(
                    stack_specs(ssm_c, cfg.attn_period), self.n_super)
            else:
                tree["server"] = stack_specs(ssm_c,
                                             cfg.num_layers - cfg.cut_layer)
            return tree
        attn_c = self.blocks.attn_cache_specs(batch, eff_len)
        return {"client": stack_specs(attn_c, cfg.cut_layer),
                "server": stack_specs(attn_c,
                                      cfg.num_layers - cfg.cut_layer)}

    def init_cache(self, batch: int, cache_len: int,
                   window: Optional[int] = None):
        specs = self.cache_specs(batch, cache_len, window)
        return L.tree_map_specs(
            lambda s: jnp.zeros(s.shape, s.dtype or self.cfg.jnp_dtype),
            specs)

    # ----- prefill path -----
    @staticmethod
    def _to_ring(k_full, cache_len: int):
        """Convert full-sequence kv (B, S, Hc, hd) into a ring cache of
        length `cache_len`; positions keep their rotary phase so ring order
        is irrelevant to attention."""
        b, s, hc, hd = k_full.shape
        c = cache_len
        if c >= s:
            pad = jnp.zeros((b, c - s, hc, hd), k_full.dtype)
            return jnp.concatenate([k_full, pad], axis=1)
        tail = k_full[:, -c:]
        slots = (jnp.arange(s - c, s)) % c
        buf = jnp.zeros((b, c, hc, hd), k_full.dtype)
        return buf.at[:, slots].set(tail)

    def _prefill_stack(self, stacked, x, positions, window, cache_len):
        cfg = self.cfg
        if cfg.family in ("ssm", "hybrid"):
            def body(xx, lp):
                xx, st = self.blocks.ssm_block_prefill(lp, xx)
                return xx, st
            x, states = scan_stack(body, x, stacked)
            return x, states

        def body(xx, lp):
            aux = jnp.float32(0)
            xx, _, (kc, vc) = self.blocks.attn_block(
                lp, xx, positions, aux, window=window, fill_cache=True)
            return xx, {"k": self._to_ring(kc, cache_len),
                        "v": self._to_ring(vc, cache_len)}
        x, caches = scan_stack(body, x, stacked)
        return x, caches

    def prefill(self, params, batch, cache_len: Optional[int] = None,
                window: Optional[int] = None):
        """Full-sequence forward that fills the decode cache.

        Returns (last_logits (B, V) fp32, cache, next_pos)."""
        cfg = self.cfg
        window = window if window is not None else cfg.sliding_window
        x = self._embed(params, batch)
        b, s, _ = x.shape
        c = cache_len or s
        if window:
            c = min(c, window)
        positions = self._positions(x)
        cache: Dict[str, Any] = {}
        x, cache["client"] = self._prefill_stack(
            params["client"]["blocks"], x, positions, window, c)
        srv = params["server"]
        if cfg.family == "hybrid":
            if self.n_pre:
                x, cache["server_pre"] = self._prefill_stack(
                    srv["pre_blocks"], x, positions, window, c)

            def super_body(xx, lp):
                hn = L.rms_norm(xx, srv["shared_attn"]["norm1"],
                                cfg.norm_eps)
                q, k, v = L.attention_qkv(srv["shared_attn"]["attn"], hn,
                                          cfg, positions)
                a = L.blockwise_attention(
                    q, k, v, causal=True, window=window,
                    q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
                    block_skip=cfg.causal_block_skip)
                xx = xx + a.reshape(xx.shape[0], -1,
                                    cfg.num_heads * cfg.head_dim) \
                    @ srv["shared_attn"]["attn"]["wo"]
                attn_cache = {
                    "k": self._to_ring(self.blocks._repeat_kv(k), c),
                    "v": self._to_ring(self.blocks._repeat_kv(v), c)}
                xx, ssm_states = self._prefill_stack(lp, xx, positions,
                                                     window, c)
                return xx, (attn_cache, ssm_states)

            x, (attn_caches, super_states) = scan_stack(
                super_body, x, srv["superblocks"])
            cache["server_attn"] = attn_caches
            cache["server_super"] = super_states
        else:
            x, cache["server"] = self._prefill_stack(
                srv["blocks"], x, positions, window, c)
        x = L.rms_norm(x[:, -1:], srv["final_norm"], cfg.norm_eps)
        logits = (x[:, 0] @ self._lm_head(params)).astype(jnp.float32)
        return logits, cache, jnp.int32(s)

    def _decode_stack(self, stacked_params, stacked_cache, x, pos, window):
        cfg = self.cfg
        if cfg.family in ("ssm", "hybrid"):
            def body(xx, inp):
                lp, lc = inp
                xx, nc = self.blocks.ssm_block_decode(lp, xx, lc)
                return xx, nc
        else:
            def body(xx, inp):
                lp, lc = inp
                xx, nc = self.blocks.attn_block_decode(lp, xx, lc, pos,
                                                       window=window)
                return xx, nc
        x, new_cache = scan_stack(body, x, stacked_params,
                                  stacked_cache)
        return x, new_cache

    def decode_step(self, params, cache, tokens, pos,
                    window: Optional[int] = None):
        """One-token decode. tokens: (B, 1) int32; pos: scalar int32, or a
        (B,) int32 vector of per-slot positions (continuous batching).

        Returns (logits (B, 1, V), new_cache)."""
        cfg = self.cfg
        window = window if window is not None else cfg.sliding_window
        x = params["client"]["embed"][tokens]
        new_cache = dict(cache)
        x, new_cache["client"] = self._decode_stack(
            params["client"]["blocks"], cache["client"], x, pos, window)
        srv = params["server"]
        if cfg.family == "hybrid":
            if self.n_pre:
                x, new_cache["server_pre"] = self._decode_stack(
                    srv["pre_blocks"], cache["server_pre"], x, pos, window)

            def super_body(xx, inp):
                lp, attn_c, ssm_c = inp
                b = xx.shape[0]
                hn = L.rms_norm(xx, srv["shared_attn"]["norm1"], cfg.norm_eps)
                pos_v = jnp.broadcast_to(jnp.asarray(pos), (b,))
                positions = pos_v[:, None]
                q, k, v = L.attention_qkv(srv["shared_attn"]["attn"], hn,
                                          cfg, positions)
                k_rep = self.blocks._repeat_kv(k)
                v_rep = self.blocks._repeat_kv(v)
                slot = pos_v % attn_c["k"].shape[1]
                bidx = jnp.arange(b)
                kc = attn_c["k"].at[bidx, slot].set(k_rep[:, 0])
                vc = attn_c["v"].at[bidx, slot].set(v_rep[:, 0])
                a_out = L.decode_attention(q, kc, vc, pos_v, window=None)
                xx = xx + a_out.reshape(b, 1, -1) \
                    @ srv["shared_attn"]["attn"]["wo"]
                xx, new_ssm = self._decode_stack(lp, ssm_c, xx, pos, window)
                return xx, ({"k": kc, "v": vc}, new_ssm)

            x, (new_attn, new_super) = scan_stack(
                super_body, x, srv["superblocks"], cache["server_attn"],
                cache["server_super"])
            new_cache["server_attn"] = new_attn
            new_cache["server_super"] = new_super
        else:
            x, new_cache["server"] = self._decode_stack(
                srv["blocks"], cache["server"], x, pos, window)
        x = L.rms_norm(x, srv["final_norm"], cfg.norm_eps)
        logits = (x @ self._lm_head(params)).astype(jnp.float32)
        return logits, new_cache

    def _decode_stack_paged(self, stacked_params, stacked_cache, x, pos,
                            page_table):
        def body(xx, inp):
            lp, lc = inp
            xx, nc = self.blocks.attn_block_decode_paged(lp, xx, lc, pos,
                                                         page_table)
            return xx, nc
        return scan_stack(body, x, stacked_params, stacked_cache)

    def decode_step_paged(self, params, cache, tokens, pos, page_table):
        """One-token decode over a paged KV cache. tokens: (B, 1) int32;
        pos: (B,) int32 per-row positions; page_table: (B, M) int32 from
        :class:`repro.runtime.paging.PagePool` (one table shared by every
        layer — the cache leaves carry a leading layer axis, so a page id
        addresses the same physical page in each layer's buffers).

        Attention-cache families only (dense/moe/vlm); the ssm/hybrid
        recurrent state is per-row, not per-position, so paging does not
        apply — the paged engine rejects those configs up front. Sliding
        windows are likewise rejected there (a ring over pages is a
        different allocator).

        Returns (logits (B, 1, V), new_cache)."""
        cfg = self.cfg
        if cfg.family in ("ssm", "hybrid"):
            raise NotImplementedError(
                "paged decode supports attention-cache families only")
        x = params["client"]["embed"][tokens]
        new_cache = dict(cache)
        x, new_cache["client"] = self._decode_stack_paged(
            params["client"]["blocks"], cache["client"], x, pos, page_table)
        srv = params["server"]
        x, new_cache["server"] = self._decode_stack_paged(
            srv["blocks"], cache["server"], x, pos, page_table)
        x = L.rms_norm(x, srv["final_norm"], cfg.norm_eps)
        logits = (x @ self._lm_head(params)).astype(jnp.float32)
        return logits, new_cache

    def _decode_window_stack_paged(self, stacked_params, stacked_cache, x,
                                   q_pos, page_table):
        def body(xx, inp):
            lp, lc = inp
            xx, nc = self.blocks.attn_block_decode_window_paged(
                lp, xx, lc, q_pos, page_table)
            return xx, nc
        return scan_stack(body, x, stacked_params, stacked_cache)

    def decode_window_paged(self, params, cache, tokens, q_pos, page_table):
        """W-token speculative-verify decode over a paged KV cache.

        tokens: (B, W) int32 — per row, the last emitted token followed
        by the draft's W-1 proposals; q_pos: (B, W) int32 absolute
        positions (``pos + i`` inside a row's window; lanes beyond it
        point at a scratch column of ``page_table``); page_table:
        (B, M) int32. One batched target step scores the whole window:
        logits[:, i] is the next-token distribution after consuming
        ``tokens[:, :i+1]``, and every window position's kv lands in the
        paged cache exactly where a sequence of W single-token
        :meth:`decode_step_paged` calls would have put it — so the
        accept-prefix state after speculative verification is
        indistinguishable from plain decode. Attention-cache families
        only, as for single-token paged decode.

        Returns (logits (B, W, V) float32, new_cache)."""
        cfg = self.cfg
        if cfg.family in ("ssm", "hybrid"):
            raise NotImplementedError(
                "paged decode supports attention-cache families only")
        x = params["client"]["embed"][tokens]
        new_cache = dict(cache)
        x, new_cache["client"] = self._decode_window_stack_paged(
            params["client"]["blocks"], cache["client"], x, q_pos,
            page_table)
        srv = params["server"]
        x, new_cache["server"] = self._decode_window_stack_paged(
            srv["blocks"], cache["server"], x, q_pos, page_table)
        x = L.rms_norm(x, srv["final_norm"], cfg.norm_eps)
        logits = (x @ self._lm_head(params)).astype(jnp.float32)
        return logits, new_cache


# ---------------------------------------------------------------------------
# Encoder-decoder (Whisper-style); frontend stubbed — consumes precomputed
# frame embeddings (B, T_enc, d) per the assignment carve-out.
# ---------------------------------------------------------------------------

class EncDecModel:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.blocks = _Blocks(cfg)

    def _enc_block_specs(self):
        cfg = self.cfg
        return {"norm1": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
                "attn": L.attention_specs(cfg),
                "norm2": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
                "mlp": L.mlp_specs(cfg, gelu=True)}

    def _dec_block_specs(self):
        cfg = self.cfg
        return {"norm1": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
                "attn": L.attention_specs(cfg),
                "norm_x": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
                "xattn": L.cross_attention_specs(cfg),
                "norm2": ParamSpec((cfg.d_model,), ("embed",), init="ones"),
                "mlp": L.mlp_specs(cfg, gelu=True)}

    def param_specs(self):
        cfg = self.cfg
        d, v = cfg.d_model, cfg.vocab_size
        client = {  # the encoder lives on the client (edge holds the audio)
            "enc_pos": ParamSpec((cfg.encoder_seq, d), (None, "embed"),
                                 init="embed"),
            "enc_blocks": stack_specs(self._enc_block_specs(),
                                      cfg.encoder_layers),
            "enc_norm": ParamSpec((d,), ("embed",), init="ones"),
        }
        server = {
            "embed": ParamSpec((v, d), ("vocab", "embed"), init="embed"),
            "dec_pos": ParamSpec((cfg.max_seq_len, d), (None, "embed"),
                                 init="embed"),
            "dec_blocks": stack_specs(self._dec_block_specs(),
                                      cfg.num_layers),
            "final_norm": ParamSpec((d,), ("embed",), init="ones"),
            "lm_head": ParamSpec((d, v), ("embed", "vocab")),
        }
        return {"client": client, "server": server}

    def init(self, key):
        return L.materialize(self.param_specs(), key, self.cfg.jnp_dtype)

    def abstract_params(self):
        return L.abstractify(self.param_specs(), self.cfg.jnp_dtype)

    def encode(self, params, frames):
        """frames: (B, T_enc, d) precomputed conv-frontend embeddings."""
        cfg = self.cfg
        c = params["client"]
        x = frames.astype(cfg.jnp_dtype) + c["enc_pos"][None]

        def body(xx, lp):
            hn = L.rms_norm(xx, lp["norm1"], cfg.norm_eps)
            b, s, _ = xx.shape
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            q, k, v = L.attention_qkv(lp["attn"], hn, cfg, positions,
                                      rope=False)
            a = L.blockwise_attention(q, k, v, causal=False,
                                      q_chunk=cfg.attn_q_chunk,
                                      kv_chunk=cfg.attn_kv_chunk)
            xx = xx + a.reshape(b, s, -1) @ lp["attn"]["wo"]
            hn2 = L.rms_norm(xx, lp["norm2"], cfg.norm_eps)
            return xx + L.mlp_apply(lp["mlp"], hn2, gelu=True), None

        body = _remat(body, cfg.remat)
        x, _ = scan_stack(body, x, c["enc_blocks"])
        return L.rms_norm(x, c["enc_norm"], cfg.norm_eps)

    def _decoder(self, server_params, enc, tokens, pos_offset=0,
                 cache=None, pos=None, fill_len: Optional[int] = None):
        cfg = self.cfg
        s = server_params
        b, slen = tokens.shape
        x = s["embed"][tokens]
        if cache is None:
            x = x + s["dec_pos"][None, :slen]
            positions = jnp.broadcast_to(jnp.arange(slen)[None], (b, slen))
        else:
            x = x + jax.lax.dynamic_slice(s["dec_pos"], (pos, 0),
                                          (1, cfg.d_model))[None]
            positions = jnp.broadcast_to(pos, (b, 1))

        def body(carry, inp):
            xx = carry
            if cache is None:
                lp = inp
                hn = L.rms_norm(xx, lp["norm1"], cfg.norm_eps)
                q, k, v = L.attention_qkv(lp["attn"], hn, cfg, positions,
                                          rope=False)
                a = L.blockwise_attention(q, k, v, causal=True,
                                          q_chunk=cfg.attn_q_chunk,
                                          kv_chunk=cfg.attn_kv_chunk,
                                          block_skip=cfg.causal_block_skip)
                xx = xx + a.reshape(b, -1, cfg.num_heads * cfg.head_dim) \
                    @ lp["attn"]["wo"]
                if fill_len is not None:
                    new_c = {"k": LanguageModel._to_ring(
                                 self.blocks._repeat_kv(k), fill_len),
                             "v": LanguageModel._to_ring(
                                 self.blocks._repeat_kv(v), fill_len)}
                else:
                    new_c = None
            else:
                lp, lc = inp
                hn = L.rms_norm(xx, lp["norm1"], cfg.norm_eps)
                q, k, v = L.attention_qkv(lp["attn"], hn, cfg, positions,
                                          rope=False)
                k_rep = self.blocks._repeat_kv(k)
                v_rep = self.blocks._repeat_kv(v)
                slot = pos % lc["k"].shape[1]
                kc = jax.lax.dynamic_update_slice(lc["k"], k_rep,
                                                  (0, slot, 0, 0))
                vc = jax.lax.dynamic_update_slice(lc["v"], v_rep,
                                                  (0, slot, 0, 0))
                a = L.decode_attention(q, kc, vc, pos)
                xx = xx + a.reshape(b, 1, -1) @ lp["attn"]["wo"]
                new_c = {"k": kc, "v": vc}
            hx = L.rms_norm(xx, lp["norm_x"], cfg.norm_eps)
            xx = xx + L.cross_attention(lp["xattn"], hx, enc, cfg)
            hn2 = L.rms_norm(xx, lp["norm2"], cfg.norm_eps)
            xx = xx + L.mlp_apply(lp["mlp"], hn2, gelu=True)
            return xx, new_c

        if cache is None:
            if fill_len is not None:
                x, new_cache = scan_stack(body, x, s["dec_blocks"])
            else:
                bodyr = _remat(lambda c, i: body(c, i), cfg.remat)
                x, _ = scan_stack(bodyr, x, s["dec_blocks"])
                new_cache = None
        else:
            x, new_cache = scan_stack(body, x, s["dec_blocks"],
                                      cache)
        x = L.rms_norm(x, s["final_norm"], cfg.norm_eps)
        return x, new_cache

    def loss_fn(self, params, batch, window=None):
        """batch: frames (B,T,d), tokens (B,S), labels (B,S), weights (B,S)."""
        enc = self.encode(params, batch["frames"])
        h, _ = self._decoder(params["server"], enc, batch["tokens"])
        loss, (cnt, cor) = chunked_xent(h, params["server"]["lm_head"],
                                        batch["labels"], batch["weights"])
        return loss, {"loss": loss, "aux_loss": jnp.float32(0),
                      "tokens": cnt, "accuracy": cor / jnp.maximum(cnt, 1.0)}

    def client_forward(self, params, batch, window=None):
        return self.encode(params, batch["frames"])

    def server_loss(self, server_params, cut_acts, batch, window=None):
        h, _ = self._decoder(server_params, cut_acts, batch["tokens"])
        loss, _ = chunked_xent(h, server_params["lm_head"], batch["labels"],
                               batch["weights"])
        return loss

    def cache_specs(self, batch: int, cache_len: int, window=None):
        cfg = self.cfg
        attn_c = self.blocks.attn_cache_specs(batch, cache_len)
        return {"self": stack_specs(attn_c, cfg.num_layers),
                "enc": ParamSpec((batch, cfg.encoder_seq, cfg.d_model),
                                 ("batch", None, "embed"), init="zeros")}

    def init_cache(self, batch: int, cache_len: int, window=None):
        specs = self.cache_specs(batch, cache_len, window)
        return L.tree_map_specs(
            lambda s: jnp.zeros(s.shape, s.dtype or self.cfg.jnp_dtype),
            specs)

    def prefill(self, params, batch, cache_len: Optional[int] = None,
                window: Optional[int] = None):
        """Encode frames + run the decoder prompt, filling the self cache."""
        enc = self.encode(params, batch["frames"])
        s = batch["tokens"].shape[1]
        c = cache_len or s
        h, self_cache = self._decoder(params["server"], enc,
                                      batch["tokens"], fill_len=c)
        logits = (h[:, -1] @ params["server"]["lm_head"]).astype(jnp.float32)
        return logits, {"self": self_cache, "enc": enc}, jnp.int32(s)

    def decode_step(self, params, cache, tokens, pos, window=None):
        h, new_self = self._decoder(params["server"], cache["enc"], tokens,
                                    cache=cache["self"], pos=pos)
        logits = (h @ params["server"]["lm_head"]).astype(jnp.float32)
        return logits, {"self": new_self, "enc": cache["enc"]}


def build_model(cfg: ModelConfig):
    if cfg.family == "audio":
        return EncDecModel(cfg)
    return LanguageModel(cfg)
