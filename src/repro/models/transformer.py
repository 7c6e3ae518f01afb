"""Composable decoder/encoder transformer covering all assigned families.

One parameterized model: dense / MoE / hybrid(RG-LRU) / SSM(RWKV6) / encoder,
built from ``ModelConfig``.  Layers are *scanned*: the layer sequence is
grouped into its repeating pattern unit; each group's parameters are stacked
along a leading axis and applied with ``jax.lax.scan`` (+ optional remat),
so the HLO stays small for 95-layer models and compile time is bounded.

Entry points:
  init_params / param_axes           — materialize params / logical axes
  loss_fn(params, batch, cfg, ...)   — training loss (per-position weights)
  per_slot_lm_loss(cfg)              — per-slot losses for the coded step
  prefill(params, batch, cfg)        — forward + build decode cache
  decode_step(params, batch, cfg)    — one-token serve step with cache
  init_cache(cfg, batch, cap)        — empty cache pytree
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import rglru, rwkv6 as rwkv
from repro.models.attention import decode_attention, flash_attention
from repro.models.common import (Spec, activation, apply_rope,
                                 axes_from_specs, init_from_specs, layer_norm,
                                 rms_norm, rope)
from repro.models.moe import moe_ffn
from repro.models.settings import (constrain_activations,
                                   scan_maybe_unrolled)

__all__ = ["GroupDef", "group_layout", "model_specs", "init_params",
           "param_axes", "loss_fn", "per_slot_lm_loss", "prefill",
           "decode_step", "init_cache", "forward"]


# ===================================================================== #
# layer layout
# ===================================================================== #
@dataclasses.dataclass(frozen=True)
class GroupDef:
    kinds: tuple           # ((mixer, ffn), ...) pattern unit
    n_repeat: int
    first_layer: int


def group_layout(cfg: ModelConfig) -> list:
    kinds = cfg.layer_kinds()
    L = len(kinds)
    P = len(cfg.layer_pattern)
    if cfg.n_experts and cfg.moe_every > 1:
        P = _lcm(P, cfg.moe_every)
    P = min(P, L)
    n_full, tail = divmod(L, P)
    groups = [GroupDef(kinds=tuple(kinds[:P]), n_repeat=n_full, first_layer=0)]
    if tail:
        groups.append(GroupDef(kinds=tuple(kinds[n_full * P:]), n_repeat=1,
                               first_layer=n_full * P))
    return groups


def _lcm(a, b):
    return a * b // math.gcd(a, b)


# ===================================================================== #
# parameter specs
# ===================================================================== #
def _norm_spec(cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm == "layer":
        return {"w": Spec((d,), (None,), "ones"), "b": Spec((d,), (None,), "zeros")}
    return {"w": Spec((d,), (None,), "zeros")}


def _mixer_specs(cfg: ModelConfig, mixer: str) -> dict:
    d = cfg.d_model
    if mixer in ("attn", "local"):
        qd, kd = cfg.attn_dim, cfg.n_kv_heads * cfg.head_dim
        p = {
            "ln": _norm_spec(cfg),
            "wq": Spec((d, qd), ("embed", "qkv")),
            "wk": Spec((d, kd), ("embed", "kv")),
            "wv": Spec((d, kd), ("embed", "kv")),
            "wo": Spec((qd, d), ("qkv", "embed"), "normal",
                       1.0 / math.sqrt(2 * cfg.n_layers)),
        }
        if cfg.qk_norm:
            p["q_norm"] = Spec((cfg.head_dim,), (None,), "zeros")
            p["k_norm"] = Spec((cfg.head_dim,), (None,), "zeros")
        return p
    if mixer == "rec":
        dr = cfg.d_rnn or d
        hr = cfg.rnn_heads
        dh = dr // hr
        return {
            "ln": _norm_spec(cfg),
            "w_in": Spec((d, dr), ("embed", "rnn")),
            "w_gate": Spec((d, dr), ("embed", "rnn")),
            "conv_w": Spec((cfg.conv_width, dr), (None, "rnn"), "normal", 0.3),
            "conv_b": Spec((dr,), ("rnn",), "zeros"),
            "w_a": Spec((hr, dh, dh), ("rnn_heads", None, None)),
            "b_a": Spec((hr, dh), ("rnn_heads", None), "zeros"),
            "w_x": Spec((hr, dh, dh), ("rnn_heads", None, None)),
            "b_x": Spec((hr, dh), ("rnn_heads", None), "zeros"),
            "lam": Spec((hr, dh), ("rnn_heads", None), "ones"),
            "w_out": Spec((dr, d), ("rnn", "embed"), "normal",
                          1.0 / math.sqrt(2 * cfg.n_layers)),
        }
    if mixer == "rwkv":
        H = d // cfg.rwkv_head_dim
        hd = cfg.rwkv_head_dim
        r = cfg.lora_rank
        return {
            "ln": _norm_spec(cfg),
            "mu": Spec((5, d), (None, None), "zeros"),      # r,k,v,w,g lerps
            "w0": Spec((d,), (None,), "zeros"),
            "w_lora_a": Spec((d, r), ("embed", None)),
            "w_lora_b": Spec((r, d), (None, "embed"), "zeros"),
            "wr": Spec((d, d), ("embed", "qkv")),
            "wk": Spec((d, d), ("embed", "qkv")),
            "wv": Spec((d, d), ("embed", "qkv")),
            "wg": Spec((d, d), ("embed", "qkv")),
            "u": Spec((H, hd), ("heads", None), "zeros"),
            "gn": Spec((H, hd), ("heads", None), "zeros"),
            "wo": Spec((d, d), ("qkv", "embed"), "normal",
                       1.0 / math.sqrt(2 * cfg.n_layers)),
        }
    raise ValueError(mixer)


def _ffn_specs(cfg: ModelConfig, ffn: str, mixer: str) -> dict:
    d = cfg.d_model
    if mixer == "rwkv":                       # rwkv channel-mix
        f = cfg.d_ff
        return {
            "ln": _norm_spec(cfg),
            "mu": Spec((2, d), (None, None), "zeros"),      # k, r lerps
            "wk": Spec((d, f), ("embed", "mlp")),
            "wv": Spec((f, d), ("mlp", "embed"), "normal",
                       1.0 / math.sqrt(2 * cfg.n_layers)),
            "wr": Spec((d, d), ("embed", "qkv")),
        }
    if ffn == "moe":
        f = cfg.d_ff
        E = cfg.n_experts
        p = {
            "ln": _norm_spec(cfg),
            "router": Spec((d, E), ("embed", None)),
            "wg": Spec((E, d, f), ("experts", "embed", "expert_mlp")),
            "wu": Spec((E, d, f), ("experts", "embed", "expert_mlp")),
            "wd": Spec((E, f, d), ("experts", "expert_mlp", "embed"),
                       "normal", 1.0 / math.sqrt(2 * cfg.n_layers)),
        }
        if cfg.shared_expert:
            p["ws_g"] = Spec((d, f), ("embed", "mlp"))
            p["ws_u"] = Spec((d, f), ("embed", "mlp"))
            p["ws_d"] = Spec((f, d), ("mlp", "embed"), "normal",
                             1.0 / math.sqrt(2 * cfg.n_layers))
        return p
    f = cfg.ffn_width(ffn)
    p = {"ln": _norm_spec(cfg),
         "wu": Spec((d, f), ("embed", "mlp")),
         "wd": Spec((f, d), ("mlp", "embed"), "normal",
                    1.0 / math.sqrt(2 * cfg.n_layers))}
    if cfg.gated_ffn:
        p["wg"] = Spec((d, f), ("embed", "mlp"))
    return p


def _stack_specs(specs: Any, n: int) -> Any:
    return jax.tree.map(
        lambda s: Spec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale),
        specs, is_leaf=lambda x: isinstance(x, Spec))


def model_specs(cfg: ModelConfig) -> dict:
    d, V = cfg.d_model, cfg.vocab
    specs: dict = {"embed": Spec((V, d), ("vocab", "embed"), "embed")}
    if cfg.frontend in ("audio", "vision"):
        specs["adapter"] = Spec((d, d), ("embed", None))
    groups = []
    for g in group_layout(cfg):
        unit = {}
        for j, (mixer, ffn) in enumerate(g.kinds):
            unit[f"l{j}"] = {"mixer": _mixer_specs(cfg, mixer),
                             "ffn": _ffn_specs(cfg, ffn, mixer)}
        groups.append(_stack_specs(unit, g.n_repeat))
    specs["groups"] = groups
    specs["final_norm"] = _norm_spec(cfg)
    if not cfg.tie_embeddings:
        specs["lm_head"] = Spec((d, V), ("embed", "vocab"))
    return specs


def init_params(cfg: ModelConfig, key) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    return init_from_specs(key, model_specs(cfg), dtype)


def param_axes(cfg: ModelConfig) -> dict:
    return axes_from_specs(model_specs(cfg))


# ===================================================================== #
# layer application
# ===================================================================== #
def _norm(x, p, cfg):
    if cfg.norm == "layer":
        return layer_norm(x, p["w"], p["b"], cfg.norm_eps)
    return rms_norm(x, p["w"], cfg.norm_eps)


def _sincos(cfg: ModelConfig, positions, mixer: str):
    theta = cfg.rope_theta
    if mixer == "local" and cfg.rope_theta_local:
        theta = cfg.rope_theta_local
    return rope(positions, cfg.head_dim, theta)


def _qkv(h, p, cfg: ModelConfig):
    B, S, _ = h.shape
    KV, G, hd = cfg.n_kv_heads, cfg.group_size, cfg.head_dim
    q = (h @ p["wq"]).reshape(B, S, KV, G, hd)
    k = (h @ p["wk"]).reshape(B, S, KV, hd)
    v = (h @ p["wv"]).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _attn_train(x, p, cfg: ModelConfig, mixer, positions):
    B, S, d = x.shape
    h = _norm(x, p["ln"], cfg)
    q, k, v = _qkv(h, p, cfg)
    sin, cos = _sincos(cfg, positions, mixer)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    window = cfg.window if mixer == "local" else 0
    from repro.models.settings import unroll_enabled
    chunk = 2048 if unroll_enabled() else 1024  # bound unrolled-HLO size
    o = flash_attention(q, k, v, causal=cfg.causal, window=window,
                        q_chunk=chunk, kv_chunk=chunk)
    o = o.reshape(B, S, cfg.attn_dim) @ p["wo"]
    return x + o, (k, v)


def _attn_decode(x, p, cfg: ModelConfig, mixer, cache, pos):
    """x: (B,1,d); cache: {'k','v': (B, cap, KV, hd)}; pos: () int32."""
    B = x.shape[0]
    h = _norm(x, p["ln"], cfg)
    q, k, v = _qkv(h, p, cfg)
    sin, cos = _sincos(cfg, pos[None].astype(jnp.int32), mixer)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    cap = cache["k"].shape[1]
    window = cfg.window if mixer == "local" else 0
    ring = bool(window) and cap <= window         # ring buffer cache
    slot = pos % cap if ring else jnp.minimum(pos, cap - 1)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
    idx = jnp.arange(cap)
    if ring:   # all slots valid after warm-up; only slots <= pos before
        valid = jnp.broadcast_to((idx[None] <= pos) | (pos >= cap), (B, cap))
    else:
        valid = jnp.broadcast_to(idx[None] <= pos, (B, cap))
    o = decode_attention(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
                         valid)
    o = o.reshape(B, 1, cfg.attn_dim) @ p["wo"]
    return x + o, {"k": k_cache, "v": v_cache}


def _rec_train(x, p, cfg: ModelConfig):
    B, S, d = x.shape
    dr = cfg.d_rnn or d
    hr = cfg.rnn_heads
    h = _norm(x, p["ln"], cfg)
    xb = h @ p["w_in"]
    gate = jax.nn.gelu(h @ p["w_gate"])
    conv_state = xb[:, -(cfg.conv_width - 1):]              # pre-conv tail
    xb = rglru.causal_conv1d(xb, p["conv_w"], p["conv_b"])
    y, h_last = rglru.rglru_scan(xb.reshape(B, S, hr, dr // hr), p)
    y = y.reshape(B, S, dr)
    o = (y * gate) @ p["w_out"]
    return x + o, {"h": h_last.astype(jnp.float32), "conv": conv_state}


def _rec_decode(x, p, cfg: ModelConfig, cache):
    B = x.shape[0]
    d = x.shape[-1]
    dr = cfg.d_rnn or d
    hr = cfg.rnn_heads
    h = _norm(x, p["ln"], cfg)[:, 0]
    xb = h @ p["w_in"]
    gate = jax.nn.gelu(h @ p["w_gate"])
    xb, conv_state = rglru.conv1d_step(xb, cache["conv"].astype(xb.dtype),
                                       p["conv_w"], p["conv_b"])
    y, h_new = rglru.rglru_step(xb.reshape(B, hr, dr // hr), cache["h"], p)
    o = (y.reshape(B, dr) * gate) @ p["w_out"]
    return x + o[:, None], {"h": h_new.astype(jnp.float32), "conv": conv_state}


def _rwkv_mix(h, prev, mu):
    """token-shift lerp; h: (B,S,d), prev: (B,d) state; mu: (d,)."""
    hh = jnp.concatenate([prev[:, None].astype(h.dtype), h[:, :-1]], axis=1)
    return h + (hh - h) * mu


def _rwkv_decay(mix_w, p):
    lora = jnp.tanh(mix_w @ p["w_lora_a"]) @ p["w_lora_b"]
    return jnp.exp(-jnp.exp(
        jnp.clip(p["w0"] + lora.astype(jnp.float32), -8.0, 2.0)))


def _rwkv_train(x, p, cfg: ModelConfig, chunked: bool = True):
    B, S, d = x.shape
    hd = cfg.rwkv_head_dim
    H = d // hd
    h = _norm(x, p["ln"], cfg)
    prev = jnp.zeros((B, d), h.dtype)
    mr, mk, mv, mw, mg = [p["mu"][i] for i in range(5)]
    heads = lambda t: t.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    r = heads(_rwkv_mix(h, prev, mr) @ p["wr"])
    k = heads(_rwkv_mix(h, prev, mk) @ p["wk"])
    v = heads(_rwkv_mix(h, prev, mv) @ p["wv"])
    g = _rwkv_mix(h, prev, mg) @ p["wg"]
    w = heads(_rwkv_decay(_rwkv_mix(h, prev, mw), p))
    fn = rwkv.wkv_chunked if chunked else rwkv.wkv_sequential
    kwargs = {"chunk": min(cfg.rwkv_chunk, S)} if chunked else {}
    out, S_last = fn(r, k, v, w, p["u"], **kwargs)
    out = out.transpose(0, 2, 1, 3)                         # (B,S,H,hd)
    out = rms_norm(out, p["gn"], cfg.norm_eps).reshape(B, S, d)
    o = (out * jax.nn.silu(g)) @ p["wo"]
    return x + o, {"S": S_last, "tm": h[:, -1].astype(jnp.float32)}


def _rwkv_decode(x, p, cfg: ModelConfig, cache):
    B, _, d = x.shape
    hd = cfg.rwkv_head_dim
    H = d // hd
    h = _norm(x, p["ln"], cfg)[:, 0]
    prev = cache["tm"].astype(h.dtype)
    mr, mk, mv, mw, mg = [p["mu"][i] for i in range(5)]
    mix = lambda mu: h + (prev - h) * mu
    heads = lambda t: t.reshape(B, H, hd)
    r = heads(mix(mr) @ p["wr"])
    k = heads(mix(mk) @ p["wk"])
    v = heads(mix(mv) @ p["wv"])
    g = mix(mg) @ p["wg"]
    w = heads(_rwkv_decay(mix(mw)[None], p)[0])
    out, S_new = rwkv.wkv_step(r.astype(jnp.float32), k.astype(jnp.float32),
                               v.astype(jnp.float32), w.astype(jnp.float32),
                               p["u"].astype(jnp.float32), cache["S"])
    out = rms_norm(out.reshape(B, H, hd), p["gn"], cfg.norm_eps)
    o = (out.reshape(B, d).astype(x.dtype) * jax.nn.silu(g)) @ p["wo"]
    return x + o[:, None], {"S": S_new, "tm": h.astype(jnp.float32)}


def _ffn_apply(x, p, cfg: ModelConfig, ffn, mixer, dp_shards, cache=None,
               decode=False):
    """Returns (x, aux, new_cache)."""
    act = activation(cfg.act)
    if mixer == "rwkv":                        # channel mix (stateful)
        h = _norm(x, p["ln"], cfg)
        if decode:
            prev = cache["cm"].astype(h.dtype)[:, None]
        else:
            prev = jnp.zeros((x.shape[0], 1, x.shape[-1]), h.dtype)
        hh = jnp.concatenate([prev, h[:, :-1]], axis=1) if h.shape[1] > 1 \
            else prev
        mk, mr = p["mu"][0], p["mu"][1]
        xk = h + (hh - h) * mk
        xr = h + (hh - h) * mr
        kk = jnp.square(jax.nn.relu(xk @ p["wk"]))
        out = jax.nn.sigmoid(xr @ p["wr"]) * (kk @ p["wv"])
        new_cache = {"cm": h[:, -1].astype(jnp.float32)}
        return x + out, jnp.zeros(()), new_cache
    if ffn == "moe":
        h = _norm(x, p["ln"], cfg)
        out, aux = moe_ffn(h, p, top_k=cfg.top_k,
                           capacity_factor=cfg.capacity_factor, act=act,
                           dp_shards=dp_shards)
        if cfg.shared_expert:
            out = out + (act(h @ p["ws_g"]) * (h @ p["ws_u"])) @ p["ws_d"]
        return x + out, aux, None
    h = _norm(x, p["ln"], cfg)
    if cfg.gated_ffn:
        out = (act(h @ p["wg"]) * (h @ p["wu"])) @ p["wd"]
    else:
        out = act(h @ p["wu"]) @ p["wd"]
    return x + out, jnp.zeros(()), None


def _apply_unit(x, unit_params, cfg: ModelConfig, kinds, dp_shards, positions,
                caches=None, pos=None, decode=False):
    """Apply one pattern unit (list of layers). Returns (x, aux, new_caches)."""
    aux_total = jnp.zeros(())
    new_caches = {}
    for j, (mixer, ffn) in enumerate(kinds):
        lp = unit_params[f"l{j}"]
        cache_j = caches[f"l{j}"] if caches is not None else None
        if mixer in ("attn", "local"):
            if decode:
                x, mix_cache = _attn_decode(x, lp["mixer"], cfg, mixer,
                                            cache_j["mix"], pos)
            else:
                x, kv = _attn_train(x, lp["mixer"], cfg, mixer, positions)
                mix_cache = kv            # (k, v) full-seq; trimmed by caller
        elif mixer == "rec":
            if decode:
                x, mix_cache = _rec_decode(x, lp["mixer"], cfg,
                                           cache_j["mix"])
            else:
                x, mix_cache = _rec_train(x, lp["mixer"], cfg)
        elif mixer == "rwkv":
            if decode:
                x, mix_cache = _rwkv_decode(x, lp["mixer"], cfg,
                                            cache_j["mix"])
            else:
                x, mix_cache = _rwkv_train(x, lp["mixer"], cfg)
        else:
            raise ValueError(mixer)
        ffn_cache_in = cache_j["ffn"] if (decode and cache_j is not None
                                          and "ffn" in cache_j) else None
        x, aux, ffn_cache = _ffn_apply(x, lp["ffn"], cfg, ffn, mixer,
                                       dp_shards, cache=ffn_cache_in,
                                       decode=decode)
        aux_total = aux_total + aux
        entry = {"mix": mix_cache}
        if ffn_cache is not None:
            entry["ffn"] = ffn_cache
        new_caches[f"l{j}"] = entry
    return x, aux_total, new_caches


# ===================================================================== #
# embedding / head / loss
# ===================================================================== #
def _embed_inputs(params, batch, cfg: ModelConfig):
    dt = jnp.dtype(cfg.compute_dtype)
    # gather from the param-dtype table, then cast: the backward then
    # scatter-adds a repeated token's gradients in that dtype, not in bf16
    emb = params["embed"]
    if cfg.frontend == "audio":
        x = batch["frames"].astype(dt) @ params["adapter"].astype(dt)
        S = x.shape[1]
        pos = jnp.arange(S)
        half = cfg.d_model // 2
        freq = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
        pe = jnp.concatenate([jnp.sin(pos[:, None] * freq),
                              jnp.cos(pos[:, None] * freq)], axis=-1)
        return x + pe[None].astype(dt)
    if cfg.frontend == "vision":
        tok = jnp.take(emb, batch["tokens"], axis=0).astype(dt)
        patches = batch["patches"].astype(dt) @ params["adapter"].astype(dt)
        return jnp.concatenate([patches, tok], axis=1)
    return jnp.take(emb, batch["tokens"], axis=0).astype(dt)


def _lm_head(params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def chunked_ce(x, head_w, labels, weights, cfg: ModelConfig,
               chunk: int = 512):
    """Σ weights ⊙ CE without materializing full (B,S,V) logits.

    x: (B,S,d) final hidden; labels: (B,S) int32; weights: (B,S) f32
    (zero = masked).  Each chunk is rematerialized in the backward pass.
    """
    B, S, d = x.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    dt = jnp.dtype(cfg.compute_dtype)

    @jax.checkpoint
    def chunk_loss(x_c, head, labels_c, w_c):
        logits = (x_c.astype(dt) @ head).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels_c[..., None],
                                 axis=-1)[..., 0]
        return jnp.sum((lse - ll) * w_c)

    total = jnp.zeros(())
    for i in range(0, S, chunk):
        total = total + chunk_loss(
            jax.lax.slice_in_dim(x, i, i + chunk, axis=1), head_w,
            jax.lax.slice_in_dim(labels, i, i + chunk, axis=1),
            jax.lax.slice_in_dim(weights, i, i + chunk, axis=1))
    return total


# ===================================================================== #
# forward passes
# ===================================================================== #
def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def forward(params, batch, cfg: ModelConfig, *, dp_shards: int = 1,
            collect_cache: bool = False):
    """Full-sequence forward. Returns (hidden (B,S,d), aux, caches|None)."""
    x = _embed_inputs(params, batch, cfg)
    dt = jnp.dtype(cfg.compute_dtype)
    x = x.astype(dt)
    S = x.shape[1]
    positions = jnp.arange(S)
    aux_total = jnp.zeros(())
    all_caches = []
    for g, gp in zip(group_layout(cfg), params["groups"]):
        def body(carry, unit_params, kinds=g.kinds):
            xx, aux = carry
            xx = constrain_activations(xx)
            up = jax.tree.map(lambda t: t.astype(dt)
                              if jnp.issubdtype(t.dtype, jnp.floating) else t,
                              unit_params)
            xx, aux_u, caches = _apply_unit(xx, up, cfg, kinds, dp_shards,
                                            positions)
            xx = constrain_activations(xx)
            out = caches if collect_cache else None
            return (xx, aux + aux_u), out

        scan_body = _remat(body, cfg) if not collect_cache else body
        (x, aux_total), caches = scan_maybe_unrolled(scan_body,
                                                     (x, aux_total), gp)
        all_caches.append(caches)
    x = _norm(x, params["final_norm"], cfg)
    return x, aux_total, (all_caches if collect_cache else None)


def loss_fn(params, batch, cfg: ModelConfig, *, dp_shards: int = 1):
    """Weighted CE training loss.

    batch: tokens/frames/patches + 'labels' (B,S) + 'weights' (B,S).
    The coded gradient step feeds per-partition coefficients through
    'weights' — gradient linearity makes the encode free (DESIGN.md §2).
    """
    x, aux, _ = forward(params, batch, cfg, dp_shards=dp_shards)
    from repro.models.settings import constrain_head
    head = _lm_head(params, cfg).astype(jnp.dtype(cfg.compute_dtype))
    head = constrain_head(head)   # hoist the FSDP gather out of CE chunks
    loss = chunked_ce(x, head, batch["labels"], batch["weights"], cfg)
    return loss + 0.01 * aux


def per_slot_lm_loss(cfg: ModelConfig):
    """(params, slot_batch) -> (M, n_slots) mean next-token CE per slot,
    the per-slot loss of the coded step (``core/coded_step.py``)."""
    def fn(params, slot_batch):
        toks = slot_batch["tokens"]          # (M, n_slots, b, S)
        labs = slot_batch["labels"]
        w = slot_batch["weights"]            # (M, n_slots, b, S)
        M_, K_, b, S = toks.shape
        batch = {"tokens": toks.reshape(M_ * K_ * b, S),
                 "labels": labs.reshape(M_ * K_ * b, S),
                 "weights": jnp.ones((M_ * K_ * b, S), jnp.float32)}
        x, aux, _ = forward(params, batch, cfg)
        head = _lm_head(params, cfg).astype(x.dtype)
        logits = (x @ head).astype(jnp.float32)
        ll = jax.nn.log_softmax(logits)
        ce = -jnp.take_along_axis(ll, batch["labels"][..., None],
                                  axis=-1)[..., 0]
        ce = (ce * w.reshape(M_ * K_ * b, S)).sum(-1) \
            / jnp.maximum(w.reshape(M_ * K_ * b, S).sum(-1), 1e-9)
        return ce.reshape(M_, K_, b).mean(-1)
    return fn


# ---------------------------------------------------------------------- #
def _cache_spec_for_layer(cfg: ModelConfig, mixer, ffn, B, cap):
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    d = cfg.d_model
    cdt = jnp.dtype(cfg.compute_dtype)
    if mixer in ("attn", "local"):
        c = min(cap, cfg.window) if (mixer == "local" and cfg.window) else cap
        entry = {"mix": {"k": jnp.zeros((B, c, KV, hd), cdt),
                         "v": jnp.zeros((B, c, KV, hd), cdt)}}
    elif mixer == "rec":
        dr = cfg.d_rnn or d
        hr = cfg.rnn_heads
        entry = {"mix": {"h": jnp.zeros((B, hr, dr // hr), jnp.float32),
                         "conv": jnp.zeros((B, cfg.conv_width - 1, dr), cdt)}}
    elif mixer == "rwkv":
        H = d // cfg.rwkv_head_dim
        entry = {"mix": {"S": jnp.zeros((B, H, cfg.rwkv_head_dim,
                                         cfg.rwkv_head_dim), jnp.float32),
                         "tm": jnp.zeros((B, d), jnp.float32)},
                 "ffn": {"cm": jnp.zeros((B, d), jnp.float32)}}
    else:
        raise ValueError(mixer)
    return entry


def init_cache(cfg: ModelConfig, B: int, cap: int) -> list:
    caches = []
    for g in group_layout(cfg):
        unit = {}
        for j, (mixer, ffn) in enumerate(g.kinds):
            e = _cache_spec_for_layer(cfg, mixer, ffn, B, cap)
            unit[f"l{j}"] = e
        stacked = jax.tree.map(
            lambda t: jnp.broadcast_to(t, (g.n_repeat,) + t.shape).copy()
            if g.n_repeat > 1 else t[None], unit)
        caches.append(stacked)
    return caches


def prefill(params, batch, cfg: ModelConfig, *, dp_shards: int = 1):
    """Forward + build decode caches.  Returns (last_logits, caches, pos)."""
    x, aux, raw = forward(params, batch, cfg, dp_shards=dp_shards,
                          collect_cache=True)
    S = x.shape[1]
    caches = []
    for g, rc in zip(group_layout(cfg), raw):
        unit = {}
        for j, (mixer, ffn) in enumerate(g.kinds):
            src = rc[f"l{j}"]
            if mixer in ("attn", "local"):
                k, v = src["mix"]               # (R, B, S, KV, hd)
                if mixer == "local" and cfg.window and cfg.window < S:
                    W = cfg.window
                    sl = jnp.arange(S - W, S) % W
                    k = jnp.zeros_like(k[:, :, :W]).at[:, :, sl].set(
                        k[:, :, S - W:])
                    v = jnp.zeros_like(v[:, :, :W]).at[:, :, sl].set(
                        v[:, :, S - W:])
                unit[f"l{j}"] = {"mix": {
                    "k": k.astype(jnp.dtype(cfg.compute_dtype)),
                    "v": v.astype(jnp.dtype(cfg.compute_dtype))}}
            else:
                unit[f"l{j}"] = src
        caches.append(unit)
    head = _lm_head(params, cfg).astype(jnp.dtype(cfg.compute_dtype))
    last = x[:, -1].astype(jnp.dtype(cfg.compute_dtype)) @ head
    return last.astype(jnp.float32), caches, jnp.asarray(S, jnp.int32)


def pad_cache(caches, cfg: ModelConfig, extra: int):
    """Grow full-attention k/v cache capacity by ``extra`` decode slots.

    Ring (local-window) and recurrent caches are fixed-size and untouched.
    """
    out = []
    for g, gc in zip(group_layout(cfg), caches):
        unit = {}
        for j, (mixer, ffn) in enumerate(g.kinds):
            e = gc[f"l{j}"]
            if mixer == "attn" or (mixer == "local" and not cfg.window):
                k, v = e["mix"]["k"], e["mix"]["v"]
                pad = [(0, 0)] * k.ndim
                pad[2] = (0, extra)
                e = {"mix": {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}}
            unit[f"l{j}"] = e
        out.append(unit)
    return out


def decode_step(params, tokens, caches, pos, cfg: ModelConfig, *,
                dp_shards: int = 1):
    """One serve step: tokens (B,1) -> logits (B,V), updated caches.

    For full-attention layers the cache has capacity ``cap`` and the new
    token is written at ``pos`` (callers keep pos < cap); local layers use a
    ring buffer of size ``window``.
    """
    dt = jnp.dtype(cfg.compute_dtype)
    if cfg.frontend == "audio":
        raise ValueError("encoder-only architecture has no decode step")
    x = jnp.take(params["embed"].astype(dt), tokens, axis=0)
    aux = jnp.zeros(())
    new_caches = []
    for g, gp, gc in zip(group_layout(cfg), params["groups"], caches):
        def body(x, xs, kinds=g.kinds):
            unit_params, unit_cache = xs
            x = constrain_activations(x)
            up = jax.tree.map(lambda t: t.astype(dt)
                              if jnp.issubdtype(t.dtype, jnp.floating) else t,
                              unit_params)
            xx, _, new_cache = _apply_unit(x, up, cfg, kinds, dp_shards,
                                           None, caches=unit_cache, pos=pos,
                                           decode=True)
            return xx, new_cache

        x, nc = scan_maybe_unrolled(body, x, (gp, gc))
        new_caches.append(nc)
    x = _norm(x, params["final_norm"], cfg)
    head = _lm_head(params, cfg).astype(dt)
    logits = (x[:, 0] @ head).astype(jnp.float32)
    return logits, new_caches
