"""Plain float32 reference of the dense decoder that InternLM2 and Qwen3
share: pre-norm RMSNorm, GQA attention with rotate-half RoPE (Qwen3 adds
an RMSNorm over each query and key head before RoPE), SwiGLU MLP, final
RMSNorm, an untied or tied head over the published vocabulary.

It follows the published configuration (``configs/<name>.json``: sizes,
``rms_norm_eps``, ``rope_theta``), imports nothing of the program, and
reads only the weights tree that ``weights.py`` makes, by its keys.
Every matrix product runs at ``Precision.HIGHEST`` in float32, so on a
TPU it is a true float32 product.  Logits are over the first
``vocab_size`` columns only: the padded rows of the program's table are
not part of the model.

``quant="fp8"`` is the control: the same arithmetic with every matrix
operand rounded to float8 (e4m3, scaled per tensor by its largest
magnitude) and, under differentiation, every cotangent that reaches such
an operand rounded to e5m2 the same way.

Attention is computed in blocks of query rows and the loss in blocks of
positions, each under ``jax.checkpoint``, so the reference fits one
chip beside the model's weights.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["unstack", "loss", "loss_and_grads",
           "forward_hidden", "served_gaps"]

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 256           # query rows per attention block
LOSS_BLOCK = 256        # positions per loss block


# --------------------------------------------------------------------------
# the control's rounding


def _round8(x, dtype):
    s = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(F32) * s


@jax.custom_vjp
def _fp8(x):
    return _round8(x, jnp.float8_e4m3fn)


_fp8.defvjp(lambda x: (_fp8(x), None),
            lambda _, g: (_round8(g, jnp.float8_e5m2),))


def _q(quant: str):
    if quant == "float32":
        return lambda x: x
    if quant == "fp8":
        return _fp8
    raise ValueError(f"unknown reference precision {quant!r}")


def _mm(spec, a, b, q):
    return jnp.einsum(spec, q(a), q(b), precision=HI,
                      preferred_element_type=F32)


# --------------------------------------------------------------------------
# the model


def _rms(x, w, eps):
    # the weights tree stores a norm's gain as the offset w of 1 + w
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def _rope(x, pos, theta):
    """x: (B, S, H, Dh); rotate-half RoPE at positions ``pos`` (S,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, quant):
    """Causal GQA attention. q: (B, S, H, Dh); k, v: (B, S, Hkv, Dh)."""
    B, S, H, Dh = q.shape
    Hkv = k.shape[2]
    qq = _q(quant)
    q = q.reshape(B, S, Hkv, H // Hkv, Dh) / math.sqrt(Dh)
    nb = S // Q_BLOCK
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(args):
        qb, start = args                         # (B, Qb, G, R, Dh)
        s = _mm("bqgrd,bkgd->bgrqk", qb, k, qq)
        qpos = start + jnp.arange(qb.shape[1])
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("bgrqk,bkgd->bqgrd", p, v, qq)

    qb = q.reshape(B, nb, Q_BLOCK, *q.shape[2:]).swapaxes(0, 1)
    o = jax.lax.map(block, (qb, jnp.arange(nb) * Q_BLOCK))
    return o.swapaxes(0, 1).reshape(B, S, H, Dh)


def _layer(cfg, quant, x, p):
    """One decoder layer on f32 weights ``p`` (one layer's dict)."""
    qq = _q(quant)
    eps = cfg["rms_norm_eps"]
    B, S, D = x.shape
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    pos = jnp.arange(S)
    h = _rms(x, p["ln1"], eps)
    q = _mm("bsd,de->bse", h, p["wq"], qq).reshape(B, S, H, Dh)
    k = _mm("bsd,de->bse", h, p["wk"], qq).reshape(B, S, Hkv, Dh)
    v = _mm("bsd,de->bse", h, p["wv"], qq).reshape(B, S, Hkv, Dh)
    if cfg["qk_norm"]:
        q = _rms(q, p["q_norm"], eps)
        k = _rms(k, p["k_norm"], eps)
    q = _rope(q, pos, cfg["rope_theta"])
    k = _rope(k, pos, cfg["rope_theta"])
    o = _attention(q, k, v, quant).reshape(B, S, H * Dh)
    x = x + _mm("bse,ed->bsd", o, p["wo"], qq)
    h = _rms(x, p["ln2"], eps)
    g = _mm("bsd,df->bsf", h, p["gate"], qq)
    u = _mm("bsd,df->bsf", h, p["up"], qq)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["down"], qq)


def _head(cfg, params):
    """(D, V) head over the published vocabulary."""
    V = cfg["vocab_size"]
    if cfg["tie_word_embeddings"]:
        return params["embed"][:V].T
    return params["lm_head"][:, :V]


# --------------------------------------------------------------------------
# loss and gradients on per-layer f32 leaves


def unstack(tree, dtype=F32) -> dict:
    """Weights tree → {"embed", "final_norm", ["lm_head"], "layers":
    [one dict per layer]} in ``dtype`` (stacked leaves split by layer)."""
    blocks = tree["blocks"]["sub0"]
    flat = {"ln1": blocks["ln1"], "ln2": blocks["ln2"], **blocks["attn"],
            **blocks["mlp"]}
    L = flat["ln1"].shape[0]
    out = {k: tree[k].astype(dtype) for k in ("embed", "final_norm",
                                              "lm_head") if k in tree}
    out["layers"] = [{k: v[i].astype(dtype) for k, v in flat.items()}
                     for i in range(L)]
    return out


def loss(cfg, quant, params, tokens, z_loss: float):
    """Mean next-token cross-entropy over the published vocabulary plus
    ``z_loss`` times the mean squared log-partition, for tokens (B, S)."""
    x = params["embed"][tokens].astype(F32)
    layer = jax.checkpoint(partial(_layer, cfg, quant))
    for p in params["layers"]:
        x = layer(x, p)
    x = _rms(x, params["final_norm"], cfg["rms_norm_eps"])
    head = _head(cfg, params)
    B, S, D = x.shape
    qq = _q(quant)
    xs = x[:, :-1]
    tg = tokens[:, 1:]
    n = S - 1
    nb = -(-n // LOSS_BLOCK)
    pad = nb * LOSS_BLOCK - n
    xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
    tg = jnp.pad(tg, ((0, 0), (0, pad)))
    valid = (jnp.arange(nb * LOSS_BLOCK) < n).astype(F32)

    @jax.checkpoint
    def block(args):
        xb, tb, vb = args
        lg = _mm("bsd,dv->bsv", xb, head, qq)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, tb[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - gold) * vb), jnp.sum(lse * lse * vb)

    split = lambda a: a.reshape(B, nb, LOSS_BLOCK, *a.shape[2:]).swapaxes(0, 1)
    ce, zl = jax.lax.map(block, (split(xs), split(tg),
                                 jnp.broadcast_to(valid.reshape(nb, 1,
                                                                LOSS_BLOCK),
                                                  (nb, B, LOSS_BLOCK))))
    return (ce.sum() + z_loss * zl.sum()) / (B * n)


@partial(jax.jit, static_argnums=(0, 1, 4))
def _loss_and_grads(cfg_items, quant, params, tokens, z_loss):
    return jax.value_and_grad(partial(loss, dict(cfg_items), quant))(
        params, tokens, z_loss)


def loss_and_grads(cfg: dict, quant: str, params, tokens, z_loss: float):
    return _loss_and_grads(_frozen(cfg), quant, params, tokens, z_loss)


def _frozen(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


# --------------------------------------------------------------------------
# serving: logits of a prompt with its served tokens


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2,))
def _layer_at(cfg_items, quant, x, blocks, i):
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False).astype(F32), blocks)
    flat = {"ln1": p["ln1"], "ln2": p["ln2"], **p["attn"], **p["mlp"]}
    return _layer(dict(cfg_items), quant, x, flat)


@partial(jax.jit, static_argnums=(0, 1))
def _gaps_at(cfg_items, quant, params, x, rows, served, other):
    """For hidden rows ``rows`` of x (1, S, D): the gap by which the token
    in ``served`` (and in ``other``) lies below the best logit, and the
    argmax token."""
    cfg = dict(cfg_items)
    qq = _q(quant)
    h = _rms(x[0][rows], params["final_norm"].astype(F32),
             cfg["rms_norm_eps"])
    head = _head(cfg, params).astype(F32)
    lg = _mm("sd,dv->sv", h, head, qq)
    best = lg.max(-1)
    V = lg.shape[-1]

    def gap(tok):
        ok = (tok >= 0) & (tok < V)
        val = jnp.take_along_axis(lg, jnp.clip(tok, 0, V - 1)[:, None],
                                  -1)[:, 0]
        return jnp.where(ok, best - val, jnp.inf)

    return gap(served), gap(other), jnp.argmax(lg, -1).astype(jnp.int32)


def forward_hidden(cfg: dict, quant: str, params, tokens: np.ndarray):
    """Hidden states (1, S_pad, D) after the last layer, S padded to a
    multiple of ``Q_BLOCK`` at the end (causal: padding changes nothing
    before it)."""
    S = len(tokens)
    S_pad = -(-S // Q_BLOCK) * Q_BLOCK
    tok = np.zeros(S_pad, np.int32)
    tok[:S] = tokens
    items = _frozen(cfg)
    x = jnp.take(params["embed"], jnp.asarray(tok), axis=0).astype(F32)[None]
    blocks = params["blocks"]["sub0"]
    for i in range(cfg["num_hidden_layers"]):
        x = _layer_at(items, quant, x, blocks, jnp.int32(i))
    return x


def served_gaps(cfg: dict, quant: str, params, prompt: np.ndarray,
                served: np.ndarray, other: np.ndarray | None = None):
    """Teacher-forced over ``prompt + served``: for each served token, the
    gap by which its logit lies below the best (from the position before
    it); the same for ``other`` tokens at those positions; and the
    argmax token at each of those positions."""
    seq = np.concatenate([prompt, served]).astype(np.int32)
    x = forward_hidden(cfg, quant, params, seq)
    n = len(served)
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    other = served if other is None else other
    out = [], [], []
    for a in range(0, n, LOSS_BLOCK):
        r = np.zeros(LOSS_BLOCK, np.int32)
        m = min(LOSS_BLOCK, n - a)
        r[:m] = rows[a:a + m]
        s = np.zeros(LOSS_BLOCK, np.int32)
        s[:m] = served[a:a + m]
        o = np.zeros(LOSS_BLOCK, np.int32)
        o[:m] = other[a:a + m]
        res = _gaps_at(_frozen(cfg), quant, params, x, jnp.asarray(r),
                       jnp.asarray(s), jnp.asarray(o))
        for acc, v in zip(out, res):
            acc.append(np.asarray(v)[:m])
    return tuple(np.concatenate(a) for a in out)
