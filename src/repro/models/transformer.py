"""Unified LM: dense / MoE / SSM / hybrid / VLM / enc-dec assembly.

One model covers all 10 assigned architectures through a per-arch *layer
plan*: the sequence of (mixer, ffn) sublayers that one ``lax.scan`` step
executes.  Uniform archs scan over ``n_layers`` identical blocks; Jamba scans
over superblocks of 8 sublayers (7 SSD + 1 attention, alternating dense/MoE
FFN); Whisper adds a separately-scanned bidirectional encoder and
cross-attention in the decoder.

Parameters are stacked on the scan dimension — one compiled block body per
sublayer *kind*, independent of depth (critical for dry-run compile time at
48 layers × 512 devices).

Entry points (all jit/pjit-able, ShapeDtypeStruct-friendly):
    loss_fn(params, batch, cfg)              -- training loss (+ MoE aux)
    prefill(params, batch, cfg)              -- last-token logits + KV/SSM cache
    decode_step(params, cache, batch, cfg)   -- one-token step with cache
    init_params(cfg, seed) / param_specs(cfg)
    init_cache(cfg, batch, max_len) / cache_specs(...)
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.layers import (
    _maybe_constrain,
    attention_block,
    mlp_block,
    moe_block,
    rms_norm,
    ssd_block,
)

__all__ = [
    "layer_plan",
    "param_specs",
    "init_params",
    "loss_fn",
    "forward",
    "prefill",
    "decode_step",
    "cache_specs",
    "init_cache",
    "paged_cache_shapes",
    "init_paged_cache",
    "input_specs",
    "warm_autotune",
]

DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# Layer plans
# ---------------------------------------------------------------------------


def layer_plan(cfg: ArchConfig) -> tuple[int, list[tuple[str, str | None]]]:
    """(n_scan, [(mixer, ffn), ...] per scan step)."""
    if cfg.family == "ssm":
        return cfg.n_layers, [("ssm", None)]
    if cfg.hybrid_period:
        assert cfg.n_layers % cfg.hybrid_period == 0
        plan = []
        for i in range(cfg.hybrid_period):
            mixer = "attn" if i == cfg.hybrid_attn_index else "ssm"
            ffn = "moe" if (cfg.moe_every and i % cfg.moe_every == 1) else "mlp"
            plan.append((mixer, ffn))
        return cfg.n_layers // cfg.hybrid_period, plan
    mixer = "attn_cross" if cfg.n_encoder_layers else "attn"
    ffn = "moe" if cfg.is_moe else "mlp"
    return cfg.n_layers, [(mixer, ffn)]


# ---------------------------------------------------------------------------
# Parameter shapes / init
# ---------------------------------------------------------------------------


def _attn_shapes(cfg) -> dict:
    D, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    s = {
        "wq": (D, H * Dh),
        "wk": (D, Hkv * Dh),
        "wv": (D, Hkv * Dh),
        "wo": (H * Dh, D),
    }
    if cfg.qk_norm:
        s["q_norm"] = (Dh,)
        s["k_norm"] = (Dh,)
    if cfg.attn_bias:
        s["bq"] = (H * Dh,)
        s["bk"] = (Hkv * Dh,)
        s["bv"] = (Hkv * Dh,)
    return s


def _mlp_shapes(cfg) -> dict:
    return {"gate": (cfg.d_model, cfg.d_ff), "up": (cfg.d_model, cfg.d_ff),
            "down": (cfg.d_ff, cfg.d_model)}


def _moe_shapes(cfg) -> dict:
    D, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff_
    return {"router": (D, E), "gate": (E, D, F), "up": (E, D, F), "down": (E, F, D)}


def _ssm_shapes(cfg) -> dict:
    # Separate projections per segment (z, x, B, C, dt) so tensor-parallel
    # sharding of d_inner/heads stays clean (no mixed-sharded concat dim).
    D = cfg.d_model
    d_inner = cfg.ssm_expand * D
    P, N, G, W = cfg.ssm_head_dim, cfg.ssm_state, 1, cfg.ssm_conv_width
    H = d_inner // P
    d_conv_ch = d_inner + 2 * G * N
    return {
        "w_z": (D, d_inner),
        "w_x": (D, d_inner),
        "w_B": (D, G * N),
        "w_C": (D, G * N),
        "w_dt": (D, H),
        "conv_w": (W, d_conv_ch),
        "conv_b": (d_conv_ch,),
        "dt_bias": (H,),
        "A_log": (H,),
        "D": (H,),
        "norm": (d_inner,),
        "out_proj": (d_inner, D),
    }


def _block_shapes(cfg, plan) -> dict:
    out = {}
    for i, (mixer, ffn) in enumerate(plan):
        sub: dict = {"ln1": (cfg.d_model,)}
        if mixer.startswith("attn"):
            sub["attn"] = _attn_shapes(cfg)
            if mixer == "attn_cross":
                sub["cross"] = _attn_shapes(cfg)
                sub["ln_cross"] = (cfg.d_model,)
        else:
            sub["ssm"] = _ssm_shapes(cfg)
        if ffn is not None:
            sub["ln2"] = (cfg.d_model,)
            sub[ffn] = _mlp_shapes(cfg) if ffn == "mlp" else _moe_shapes(cfg)
        out[f"sub{i}"] = sub
    return out


def _shape_tree(cfg: ArchConfig) -> dict:
    n_scan, plan = layer_plan(cfg)
    V = cfg.padded_vocab()
    tree: dict = {
        "embed": (V, cfg.d_model),
        "final_norm": (cfg.d_model,),
        "blocks": jax.tree.map(
            lambda s: (n_scan, *s), _block_shapes(cfg, plan),
            is_leaf=lambda x: isinstance(x, tuple),
        ),
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = (cfg.d_model, V)
    if cfg.n_encoder_layers:
        enc_plan = [("attn", "mlp")]
        tree["encoder"] = jax.tree.map(
            lambda s: (cfg.n_encoder_layers, *s), _block_shapes(cfg, enc_plan),
            is_leaf=lambda x: isinstance(x, tuple),
        )
        tree["enc_final_norm"] = (cfg.d_model,)
    return tree


def param_specs(cfg: ArchConfig) -> dict:
    """ShapeDtypeStruct pytree (for AOT lowering — no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, DTYPE),
        _shape_tree(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def init_params(cfg: ArchConfig, seed: int = 0) -> dict:
    """Real (numpy) init for smoke tests / the training driver."""
    rng = np.random.default_rng(seed)

    def leaf(path, s):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("ln1", "ln2", "ln_cross", "final_norm", "enc_final_norm",
                    "norm", "q_norm", "k_norm"):
            return np.zeros(s, np.float32).astype(jnp.bfloat16)
        if name in ("conv_b", "bq", "bk", "bv", "dt_bias", "D"):
            return (np.zeros(s) if name != "D" else np.ones(s)).astype(jnp.bfloat16)
        if name == "A_log":
            return np.log(rng.uniform(1.0, 16.0, s)).astype(jnp.bfloat16)
        fan_in = s[-2] if len(s) >= 2 else s[-1]
        return (rng.standard_normal(s) * (1.0 / math.sqrt(fan_in))).astype(jnp.bfloat16)

    return jax.tree_util.tree_map_with_path(
        leaf, _shape_tree(cfg), is_leaf=lambda x: isinstance(x, tuple)
    )


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _mask_kind(cfg: ArchConfig) -> str:
    if cfg.attention == "chunked":
        return "chunked"
    if cfg.n_prefix:
        return "prefix"
    return "causal"


def _run_stack(
    blocks, x, cfg, plan, *,
    positions, mask_kind, memory=None,
    cache=None, cache_len=None, want_cache=False, remat=True,
    pos_offset=None, block_table=None,
):
    """Scan the (stacked) blocks over x.  Returns (x, aux_loss, new_cache).

    ``pos_offset`` (B,) marks left-padding per row (ragged prompts):
    attention masks the pad slots, SSD mixers treat them as zero-input
    unit-decay steps.  ``block_table`` routes attention K/V through a
    paged block pool (see :func:`paged_cache_shapes`).

    A paged pool rides in the scan's carry, whole, and the body gets its
    layer index: each layer scatters its new tokens into the stacked pool
    in place and the kernel reads its layer from it.  As ``xs``/``ys``
    the scan would slice every layer's pool out and stack it back, which
    copies the whole pool on every step.  Every other cache (dense K/V,
    SSD state) is sliced per layer through ``xs``/``ys``.
    """
    # per-row validity for SSD mixers: pad positions carry negatives
    ssm_valid = positions >= 0 if positions.ndim == 2 else None
    paged = cache is not None and all(
        "k_pool" in sub for sub in cache.values())

    def body(carry, inp):
        x, aux, pool = carry
        if paged:
            (lp, layer), lc = inp, pool       # the whole stacked pool
        else:
            lp, lc = inp if cache is not None else (inp, None)
            layer = None
        new_lc = {} if (want_cache or cache is not None) else None
        for i, (mixer, ffn) in enumerate(plan):
            sp = lp[f"sub{i}"]
            sc = lc[f"sub{i}"] if lc is not None else None
            h = rms_norm(x, sp["ln1"])
            if mixer.startswith("attn"):
                mo, nc = attention_block(
                    h, sp["attn"], cfg, positions=positions, mask_kind=mask_kind,
                    cache=sc, cache_len=cache_len,
                    pos_offset=pos_offset, block_table=block_table,
                    layer=layer,
                )
                x = x + mo
                if mixer == "attn_cross":
                    h = rms_norm(x, sp["ln_cross"])
                    co, _ = attention_block(
                        h, sp["cross"], cfg, positions=positions,
                        mask_kind="full", kv_source=memory,
                    )
                    x = x + co
            else:
                mo, nc = ssd_block(h, sp["ssm"], cfg, cache=sc, valid=ssm_valid)
                x = x + mo
            if new_lc is not None:
                new_lc[f"sub{i}"] = nc
            if ffn is not None:
                h = rms_norm(x, sp["ln2"])
                if ffn == "mlp":
                    x = x + mlp_block(h, sp["mlp"])
                else:
                    fo, a = moe_block(h, sp["moe"], cfg)
                    x = x + fo
                    aux = aux + a
        # Sequence parallelism (perf iteration B2): the scan carry — the
        # remat-saved residual stream — is sharded over the model axis on its
        # sequence dim, shrinking saved activations by the TP degree and
        # turning boundary all-reduces into reduce-scatter/all-gather pairs.
        # No-op without a hint mesh or when S doesn't divide (decode S=1).
        from repro.models import layers as _L

        if _L.SP_HINT:
            x = _maybe_constrain(x, "dp", "model", None)
        if paged:
            return (x, aux, new_lc), None
        return (x, aux, None), new_lc

    if remat:
        body = jax.checkpoint(body)
    if paged:
        n_scan = jax.tree.leaves(blocks)[0].shape[0]
        (x, aux, new_cache), _ = jax.lax.scan(
            body, (x, jnp.float32(0.0), cache),
            (blocks, jnp.arange(n_scan, dtype=jnp.int32)))
        return x, aux, new_cache
    xs = (blocks, cache) if cache is not None else blocks
    (x, aux, _), new_cache = jax.lax.scan(
        body, (x, jnp.float32(0.0), None), xs)
    return x, aux, new_cache


def _prefill_like(cfg, params, batch, *, max_len, want_cache):
    """Shared forward: embeddings → stack → final norm.  Used by training
    (want_cache=False) and prefill (want_cache=True, cache written).

    batch: tokens (B,S) int32 [+ patches (B,P,D) | frames (B,F,D)
    | pos_offset (B,)].  ``pos_offset`` marks per-row left-padding (ragged
    prompts): positions become per-row, pad slots carry negatives and are
    masked out of attention keys / SSD state updates.
    """
    n_scan, plan = layer_plan(cfg)
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.n_prefix:
        x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
    S_total = x.shape[1]
    positions = jnp.arange(S_total)
    pos_offset = batch.get("pos_offset")
    if pos_offset is not None:
        assert not cfg.n_prefix and not cfg.n_encoder_layers, \
            "ragged (left-padded) prompts need a plain self-attention stack"
        positions = positions[None, :] - pos_offset[:, None]  # (B, S_total)

    memory = None
    if cfg.n_encoder_layers:
        enc_pos = jnp.arange(batch["frames"].shape[1])
        memory, _, _ = _run_stack(
            params["encoder"], batch["frames"].astype(x.dtype), cfg,
            [("attn", "mlp")], positions=enc_pos, mask_kind="full",
        )
        memory = rms_norm(memory, params["enc_final_norm"])

    cache = None
    if want_cache:
        cache = init_cache(cfg, B, max_len, dtype=DTYPE, stacked=True, zeros=jnp)
        cache_len = jnp.int32(0)
    else:
        cache_len = None

    x, aux, new_cache = _run_stack(
        params["blocks"], x, cfg, plan,
        positions=positions, mask_kind=_mask_kind(cfg), memory=memory,
        cache=cache, cache_len=cache_len, want_cache=want_cache,
        pos_offset=pos_offset,
    )
    x = rms_norm(x, params["final_norm"])
    return x, aux, new_cache, memory


def _logits(cfg, params, x):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", x, head)


def forward(params, batch, cfg: ArchConfig):
    """Training-mode forward → (logits over text positions, aux loss)."""
    x, aux, _, _ = _prefill_like(cfg, params, batch, max_len=0, want_cache=False)
    if cfg.n_prefix:
        x = x[:, cfg.n_prefix:]
    return _logits(cfg, params, x), aux


def loss_fn(params, batch, cfg: ArchConfig, *, z_loss: float = 1e-4,
            moe_aux: float = 1e-2, seq_chunk: int | None = None):
    """Next-token CE (f32 logsumexp) + z-loss + MoE load-balance aux.

    ``seq_chunk``: compute logits+CE over sequence chunks via ``lax.map`` so
    the (B, S, V) logits tensor is never materialised (perf iteration B2) —
    peak goes from B·S·V to B·seq_chunk·V.
    """
    if seq_chunk is None:
        logits, aux = forward(params, batch, cfg)
        logits = logits[:, :-1].astype(jnp.float32)
        targets = batch["tokens"][:, 1:]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        ce = jnp.mean(lse - gold)
        zl = jnp.mean(lse**2)
        return ce + z_loss * zl + moe_aux * aux, {"ce": ce, "aux": aux}

    x, aux, _, _ = _prefill_like(cfg, params, batch, max_len=0, want_cache=False)
    if cfg.n_prefix:
        x = x[:, cfg.n_prefix:]
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    B, S, D = x.shape
    # drop the final position (no next-token target), pad S-1 up to chunks
    xs = x[:, :-1]
    targets = batch["tokens"][:, 1:]
    n_tok = B * (S - 1)
    nc = -(-(S - 1) // seq_chunk)
    pad = nc * seq_chunk - (S - 1)
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    xs = xs.reshape(B, nc, seq_chunk, D).transpose(1, 0, 2, 3)
    tg = targets.reshape(B, nc, seq_chunk).transpose(1, 0, 2)
    valid = (jnp.arange(nc * seq_chunk) < (S - 1)).reshape(nc, 1, seq_chunk)

    def chunk_ce(args):
        xc, tc, vc = args
        lg = jnp.einsum("bsd,dv->bsv", xc, head).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, tc[..., None], axis=-1)[..., 0]
        ce_sum = jnp.sum((lse - gold) * vc)
        zl_sum = jnp.sum((lse**2) * vc)
        return ce_sum, zl_sum

    ce_sums, zl_sums = jax.lax.map(chunk_ce, (xs, tg, valid))
    ce = ce_sums.sum() / n_tok
    zl = zl_sums.sum() / n_tok
    return ce + z_loss * zl + moe_aux * aux, {"ce": ce, "aux": aux}


def prefill(params, batch, cfg: ArchConfig, *, max_len: int | None = None):
    """Process the prompt; return (last-token logits, cache, memory)."""
    S = batch["tokens"].shape[1] + cfg.n_prefix
    max_len = max_len if max_len is not None else S
    x, _, cache, memory = _prefill_like(cfg, params, batch, max_len=max_len,
                                        want_cache=True)
    logits = _logits(cfg, params, x[:, -1:])
    out = {"logits": logits, "cache": cache, "cache_len": jnp.int32(S)}
    if memory is not None:
        out["memory"] = memory
    return out


def decode_step(params, cache, batch, cfg: ArchConfig):
    """One-token decode.  batch: tokens (B,1), cache_len (), [memory].
    With a paged cache, tokens may be (B,S) — chunked prefill feeds
    prompt chunks through this same path (scatter S tokens, attend
    causally from each row's cache_len offset).

    Ragged / continuous-batching extensions (serve path):

    * ``cache_len`` may be a per-row (B,) vector — slots at different fill
      levels decode together, each writing its new KV at its own offset;
    * ``pos_offset`` (B,) shifts per-row positions for left-padded prompts
      (legacy ``generate`` ragged mode);
    * ``block_table`` (B, NB) routes K/V through a paged block pool
      (``cache`` then holds ``k_pool``/``v_pool`` leaves, see
      :func:`paged_cache_shapes`).
    """
    n_scan, plan = layer_plan(cfg)
    tokens, cache_len = batch["tokens"], batch["cache_len"]
    pos_offset = batch.get("pos_offset")
    x = jnp.take(params["embed"], tokens, axis=0)
    steps = jnp.arange(x.shape[1])
    if jnp.ndim(cache_len) or pos_offset is not None:
        cl = jnp.broadcast_to(jnp.asarray(cache_len), (tokens.shape[0],))
        if pos_offset is not None:
            cl = cl - pos_offset
        positions = cl[:, None] + steps[None, :]             # (B, S)
    else:
        positions = cache_len + steps
    x, _, new_cache = _run_stack(
        params["blocks"], x, cfg, plan,
        positions=positions, mask_kind=_mask_kind(cfg),
        memory=batch.get("memory"), cache=cache, cache_len=cache_len,
        want_cache=False, remat=False,
        pos_offset=pos_offset, block_table=batch.get("block_table"),
    )
    x = rms_norm(x, params["final_norm"])
    return _logits(cfg, params, x), new_cache


# ---------------------------------------------------------------------------
# Caches and input specs
# ---------------------------------------------------------------------------


def _sub_cache_shape(cfg, mixer, B, max_len):
    if mixer.startswith("attn"):
        Hkv, Dh = cfg.n_kv_heads, cfg.head_dim_
        return {"k": (B, max_len, Hkv, Dh), "v": (B, max_len, Hkv, Dh)}
    d_inner = cfg.ssm_expand * cfg.d_model
    H = d_inner // cfg.ssm_head_dim
    d_conv_ch = d_inner + 2 * cfg.ssm_state
    return {
        "conv": (B, cfg.ssm_conv_width - 1, d_conv_ch),
        "state": (B, H, cfg.ssm_head_dim, cfg.ssm_state),
    }


def cache_shapes(cfg: ArchConfig, B: int, max_len: int) -> dict:
    n_scan, plan = layer_plan(cfg)
    out = {}
    for i, (mixer, _) in enumerate(plan):
        shapes = _sub_cache_shape(cfg, mixer, B, max_len)
        out[f"sub{i}"] = {k: (n_scan, *s) for k, s in shapes.items()}
    return out


def _cache_dtype(name: str):
    return jnp.float32 if name == "state" else DTYPE


def cache_specs(cfg: ArchConfig, B: int, max_len: int) -> dict:
    return jax.tree_util.tree_map_with_path(
        lambda p, s: jax.ShapeDtypeStruct(s, _cache_dtype(p[-1].key)),
        cache_shapes(cfg, B, max_len),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def init_cache(cfg: ArchConfig, B: int, max_len: int, *, dtype=DTYPE,
               stacked=True, zeros=np) -> dict:
    def mk(path, s):
        if not stacked:
            s = s[1:]
        name = path[-1].key
        if zeros is jnp:
            return jnp.zeros(s, _cache_dtype(name))
        np_dt = np.float32 if name == "state" else jnp.bfloat16
        return np.zeros(s, np_dt)

    return jax.tree_util.tree_map_with_path(
        mk, cache_shapes(cfg, B, max_len), is_leaf=lambda x: isinstance(x, tuple)
    )


def paged_cache_shapes(cfg: ArchConfig, n_blocks: int, block_size: int) -> dict:
    """Shapes of the paged KV block pool (the serve path's cache layout).

    Each self-attention sublayer stores K/V in a pool of ``n_blocks``
    fixed-size blocks of ``block_size`` tokens; a per-slot block table maps
    logical positions to physical blocks (``decode_step``'s
    ``block_table``).  Pool capacity is a *budget*, not ``n_slots ×
    max_len`` — long-context configs no longer allocate dense caches they
    never fill.  Physical block 0 is reserved as scratch for idle slots.

    Blocks are head-major, ``(n_scan, n_blocks, Hkv, block_size, Dh)``:
    one KV head of one block is a contiguous ``(block_size, Dh)`` tile,
    the unit the ``paged_decode`` kernel DMAs per grid step.
    """
    n_scan, plan = layer_plan(cfg)
    out = {}
    for i, (mixer, _) in enumerate(plan):
        if mixer != "attn":
            raise ValueError(
                f"paged KV cache needs a pure self-attention stack; "
                f"{cfg.name} has a {mixer!r} mixer (use the dense cache)")
        s = (n_scan, n_blocks, cfg.n_kv_heads, block_size, cfg.head_dim_)
        out[f"sub{i}"] = {"k_pool": s, "v_pool": s}
    return out


def init_paged_cache(cfg: ArchConfig, n_blocks: int, block_size: int) -> dict:
    """Zero-filled device block pool (see :func:`paged_cache_shapes`)."""
    return jax.tree.map(
        lambda s: jnp.zeros(s, DTYPE),
        paged_cache_shapes(cfg, n_blocks, block_size),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def warm_autotune(cfg: ArchConfig, *, batch_size: int, seq_len: int,
                  stages: tuple = ("train", "prefill", "decode")) -> dict:
    """Pre-populate the kernel tuning cache for one workload cell.

    Abstractly traces the requested entry points (``jax.eval_shape`` — no
    compile, no allocation), which fires every trace-time autotune lookup
    in ``models/layers.py`` with exactly the shapes the real jit will see
    and persists the winners to the device-keyed
    :class:`~repro.kernels.autotune.TuningCache`.  Launchers call this
    once before building the jitted step so compilation never blocks on a
    cold tuning search.  Returns the tuner's {hits, misses} delta.
    """
    from repro.kernels.autotune import autotune_enabled, get_tuner
    from repro.configs.base import ShapeSpec

    if not autotune_enabled():
        return {"hits": 0, "misses": 0}
    tuner = get_tuner()
    h0, m0 = tuner.hits, tuner.misses
    params = param_specs(cfg)
    for stage in stages:
        kind = stage if stage in ("train", "prefill", "decode") else "train"
        spec = input_specs(
            cfg, ShapeSpec("warm", seq_len, batch_size, kind),
            include_params=False)
        if kind == "decode":
            jax.eval_shape(
                lambda p, c, b: decode_step(p, c, b, cfg),
                params, spec["cache"], spec["batch"])
        elif kind == "prefill":
            jax.eval_shape(
                lambda p, b: prefill(p, b, cfg, max_len=seq_len),
                params, spec["batch"])
        else:
            jax.eval_shape(
                lambda p, b: loss_fn(p, b, cfg)[0], params, spec["batch"])
    return {"hits": tuner.hits - h0, "misses": tuner.misses - m0}


def input_specs(cfg: ArchConfig, shape, *, include_params: bool = True) -> dict:
    """ShapeDtypeStruct stand-ins for every step input of a workload cell.

    train   → {params, batch={tokens, labels-implicit, [patches|frames]}}
    prefill → {params, batch={tokens, [patches|frames]}}
    decode  → {params, cache, batch={tokens(B,1), cache_len, [memory]}}
    """
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    specs: dict = {}
    if include_params:
        specs["params"] = param_specs(cfg)
    if shape.kind in ("train", "prefill"):
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.n_prefix:
            batch["patches"] = jax.ShapeDtypeStruct((B, cfg.n_prefix, cfg.d_model), DTYPE)
        if cfg.n_encoder_layers:
            batch["frames"] = jax.ShapeDtypeStruct((B, cfg.n_audio_frames, cfg.d_model), DTYPE)
        specs["batch"] = batch
    else:  # decode: one new token against a cache of size S
        batch = {
            "tokens": jax.ShapeDtypeStruct((B, 1), i32),
            "cache_len": jax.ShapeDtypeStruct((), i32),
        }
        if cfg.n_encoder_layers:
            batch["memory"] = jax.ShapeDtypeStruct((B, cfg.n_audio_frames, cfg.d_model), DTYPE)
        specs["batch"] = batch
        specs["cache"] = cache_specs(cfg, B, S)
    return specs
