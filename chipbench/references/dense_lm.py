"""Plain reference of a dense decoder LM trained with AdamW.

Written from the architecture's equations in straightforward ``jax.numpy``
and float32 at ``Precision.HIGHEST``, with no kernels, remat, slots or
coding.  It imports nothing of the system under test.  What it shares with
the system is only a container layout: :func:`init_params` returns the
weights as the nested dict the trainer takes, so the benchmark can hand the
same weights, made from the seed, to both.

The layer, as the configuration runs it (``configs/*.json``: ``"norm"``,
``"partial_rotary_factor"``, ``"use_qkv_bias"``, ``"architecture"``):

    h   = rms(x) · (1 + ln1),     rms(x) = x / sqrt(mean(x²) + eps)
    q,k,v = h Wq, h Wk, h Wv      heads of ``head_dim``, rotary on all
    x  += softmax(q kᵀ / sqrt(head_dim), causal) v Wo
    h   = rms(x) · (1 + ln2)
    x  += (silu(h Wg) ⊙ h Wu) Wd
    logits = (rms(x) · (1 + ln_f)) W_head   embedding gathered, head untied

The loss of one sequence is the weighted mean of its next-token cross
entropies; a step's loss is the sum over its partitions, which is what a
decoded coded step computes (every partition's decode weights sum to 1).

``precision="fp8"`` is the control: every matrix product takes operands
rounded to float8 e4m3 with one scale per tensor, one step below the
bfloat16 the configuration states for compute.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0          # largest finite float8_e4m3fn


def sizes(config: dict) -> dict:
    """The reference's sizes from a configuration file's keys."""
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    return {"d": d, "heads": heads, "head_dim": d // heads,
            "kv_heads": int(config["num_key_value_heads"]),
            "ffn": int(config["intermediate_size"]),
            "vocab": int(config["vocab_size"]),
            "layers": int(config["num_hidden_layers"]),
            "theta": float(config["rope_theta"]),
            "eps": float(config["layer_norm_eps"]),
            "init_std": float(config["initializer_range"])}


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words (low, high)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def _shapes(s: dict) -> dict:
    d, f, V, L = s["d"], s["ffn"], s["vocab"], s["layers"]
    kv = s["kv_heads"] * s["head_dim"]
    return {"embed": (V, d),
            "groups": [{"l0": {
                "mixer": {"ln": {"w": (L, d)}, "wq": (L, d, d),
                          "wk": (L, d, kv), "wv": (L, d, kv),
                          "wo": (L, d, d)},
                "ffn": {"ln": {"w": (L, d)}, "wu": (L, d, f),
                        "wd": (L, f, d), "wg": (L, d, f)}}}],
            "final_norm": {"w": (d,)},
            "lm_head": (d, V)}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _is_norm(path) -> bool:
    keys = {getattr(k, "key", None) for k in path}
    return bool(keys & {"ln", "final_norm"})


@functools.lru_cache(maxsize=8)
def _init_fn(config_key: tuple):
    s = dict(config_key)
    paths, tdef = jax.tree_util.tree_flatten_with_path(_shapes(s),
                                                       is_leaf=_is_shape)

    def init(words):
        key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
        keys = jax.random.split(key, len(paths))
        out = [jnp.zeros(shape, jnp.float32) if _is_norm(path)
               else s["init_std"] * jax.random.normal(k, shape, jnp.float32)
               for k, (path, shape) in zip(keys, paths)]
        return jax.tree.unflatten(tdef, out)

    return jax.jit(init)


def init_params(config: dict, seed: int):
    """The weights from ``seed``, made on the device in one jitted call:
    normal(0, initializer_range) matrices, zero norm gains (gain 1)."""
    s = sizes(config)
    return _init_fn(tuple(sorted(s.items())))(jnp.asarray(seed_words(seed)))


# --------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------- #
def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _mm(spec: str, a, b, precision: str):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif precision != "f32":
        raise ValueError(f"precision must be 'f32' or 'fp8', got "
                         f"{precision!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rotary(x, theta):
    """x: (B, S, H, D); rotate-half rotary over all D."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, s, precision):
    B, S, d = x.shape
    H, D, KV = s["heads"], s["head_dim"], s["kv_heads"]
    a = p["mixer"]
    h = _rms(x, a["ln"]["w"], s["eps"])
    q = _mm("bsd,de->bse", h, a["wq"], precision).reshape(B, S, H, D)
    k = _mm("bsd,de->bse", h, a["wk"], precision).reshape(B, S, KV, D)
    v = _mm("bsd,de->bse", h, a["wv"], precision).reshape(B, S, KV, D)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    q, k = _rotary(q, s["theta"]), _rotary(k, s["theta"])
    scores = _mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v, precision)
    x = x + _mm("bsd,de->bse", o.reshape(B, S, H * D), a["wo"], precision)
    f = p["ffn"]
    h = _rms(x, f["ln"]["w"], s["eps"])
    gate = jax.nn.silu(_mm("bsd,df->bsf", h, f["wg"], precision))
    up = _mm("bsd,df->bsf", h, f["wu"], precision)
    return x + _mm("bsf,fd->bsd", gate * up, f["wd"], precision)


def logits(params, tokens, s: dict, precision: str = "f32"):
    """(B, S) int tokens -> (B, S, vocab) float32 logits."""
    x = jnp.take(params["embed"], tokens, axis=0)
    stack = params["groups"][0]["l0"]
    for i in range(s["layers"]):
        x = _layer(x, jax.tree.map(lambda t: t[i], stack), s, precision)
    x = _rms(x, params["final_norm"]["w"], s["eps"])
    return _mm("bsd,dv->bsv", x, params["lm_head"], precision)


def sequence_losses(params, tokens, labels, weights, s, precision="f32"):
    """(B,) weighted mean next-token cross entropy of each sequence."""
    lg = logits(params, tokens, s, precision)
    lse = jax.nn.logsumexp(lg, -1)
    picked = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    ce = lse - picked
    return (ce * weights).sum(-1) / jnp.maximum(weights.sum(-1), 1e-9)


@functools.lru_cache(maxsize=8)
def _loss_and_grad_fn(config_key: tuple, precision: str):
    s = dict(config_key)

    def partition_loss(params, part):
        return sequence_losses(params, part["tokens"], part["labels"],
                               part["weights"], s, precision).mean()

    def fn(params, parts):
        """parts: (K, b, S) arrays; loss and gradient of Σ_k loss_k,
        one partition at a time."""
        zero = jax.tree.map(jnp.zeros_like, params)

        def body(carry, part):
            loss, grad = carry
            l, g = jax.value_and_grad(partition_loss)(params, part)
            return (loss + l, jax.tree.map(jnp.add, grad, g)), None

        (loss, grad), _ = jax.lax.scan(body, (jnp.zeros(()), zero), parts)
        return loss, grad

    return jax.jit(fn)


def loss_and_grad(params, parts: dict, config: dict, precision="f32"):
    s = sizes(config)
    return _loss_and_grad_fn(tuple(sorted(s.items())), precision)(params,
                                                                  parts)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                   static_argnames=("lr", "b1", "b2", "eps", "wd"))
def adamw_step(params, m, v, grad, t, *, lr, b1, b2, eps, wd):
    """One AdamW update at step number ``t`` (1-based)."""
    t = t.astype(jnp.float32)
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grad)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grad)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m_, v_):
        u = (m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
        return p - lr * (u + wd * p)

    return jax.tree.map(upd, params, m, v), m, v


@jax.jit
def leaf_norms(tree):
    """Float32 L2 norm of every leaf, in ``jax.tree.leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def leaf_diff_norms(a, b):
    """‖a − b‖ of every leaf pair, in ``jax.tree.leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


@jax.jit
def row_support(table):
    """(rows,) bool: the rows of a 2-D leaf with any nonzero entry."""
    return jnp.any(table != 0, axis=-1)


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(p).replace(" ", "")
            for p, _ in jax.tree_util.tree_leaves_with_path(tree)]


def train_readings(config: dict, job: dict, seed: int, batches: list,
                   precision: str = "f32") -> dict:
    """What the reference's first steps give, from the seed alone.

    ``batches[t]`` holds step t's K partitions as (K, b, S) arrays.
    Returns the loss of each step, the per-leaf norm of the first
    gradient, the first gradient's embedding-row support, and the
    per-leaf norm of the parameters' change over the steps.
    """
    opt = job["optimizer"]
    hyper = dict(lr=float(opt["lr"]), b1=float(opt["b1"]),
                 b2=float(opt["b2"]), eps=float(opt["eps"]),
                 wd=float(opt["weight_decay"]))
    params = init_params(config, seed)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norms, support = [], None, None
    for t, parts in enumerate(batches):
        loss, grad = loss_and_grad(params, parts, config, precision)
        losses.append(float(loss))
        if t == 0:
            grad_norms = np.asarray(leaf_norms(grad), np.float64)
            support = np.asarray(row_support(grad["embed"]))
        params, m, v = adamw_step(params, m, v, grad,
                                  jnp.asarray(t + 1, jnp.int32), **hyper)
        del grad
    del m, v
    start = init_params(config, seed)
    change = np.asarray(leaf_diff_norms(params, start), np.float64)
    names = leaf_names(params)
    del params, start
    return {"loss": losses, "grad_norm": grad_norms, "embed_rows": support,
            "change_norm": change, "leaves": names}


# --------------------------------------------------------------------- #
# the training data
# --------------------------------------------------------------------- #
def lm_partition(vocab: int, seq_len: int, per_partition: int, seed: int,
                 epoch: int, k: int) -> dict:
    """Partition ``k`` of ``epoch``: noisy Markov-chain token sequences.

    The generator the trainer's synthetic dataset uses, restated: a
    transition table drawn from ``seed``, 15% of positions replaced by a
    uniform token, labels the sequence shifted left by one, and weight 0
    at the last position (it has no target).
    """
    trans = np.random.default_rng(seed).integers(0, vocab, size=(vocab,))
    rng = np.random.default_rng((seed * 1_000_003 + epoch) * 131_071 + k)
    B, S = per_partition, seq_len
    toks = np.empty((B, S), np.int64)
    toks[:, 0] = rng.integers(0, vocab, size=B)
    noise = rng.random((B, S)) < 0.15
    rand_tok = rng.integers(0, vocab, size=(B, S))
    for t in range(1, S):
        toks[:, t] = np.where(noise[:, t], rand_tok[:, t],
                              trans[toks[:, t - 1]])
    labels = np.concatenate([toks[:, 1:], toks[:, :1]], axis=1)
    w = np.ones((B, S), np.float32)
    w[:, -1] = 0.0
    return {"tokens": toks.astype(np.int32), "labels": labels.astype(np.int32),
            "weights": w}


def lm_step_batch(vocab, seq_len, per_partition, seed, epoch, K) -> dict:
    """All K partitions of one step, stacked as (K, b, S) device arrays."""
    parts = [lm_partition(vocab, seq_len, per_partition, seed, epoch, k)
             for k in range(K)]
    return {key: jnp.asarray(np.stack([p[key] for p in parts]))
            for key in parts[0]}
