"""Paged decode attention: ref == kernel (interpret) == the serve path's
gather + dense fallback, across ragged cache_len, block-boundary fills,
GQA head counts and split-KV; plus the scatter/mask boundary regression
(ISSUE 10 satellite) on both gather and kernel paths."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_decode import paged_decode_attention, paged_decode_ref
from repro.kernels.paged_decode.kernel import paged_decode_kernel
from repro.models.layers import blocked_attention

TOL = {"float32": 2e-4, "bfloat16": 3e-2}


def _case(B, H, Hkv, Dh, NB, bs, dtype, cache_lens, seed=0):
    rng = np.random.default_rng(seed)
    P = B * NB + 1                       # block 0 = scratch, like the pool
    q = jnp.asarray(rng.standard_normal((B, H, Dh)), dtype)
    kp = jnp.asarray(rng.standard_normal((P, Hkv, bs, Dh)), dtype)
    vp = jnp.asarray(rng.standard_normal((P, Hkv, bs, Dh)), dtype)
    bt = jnp.asarray(rng.permutation(B * NB).reshape(B, NB) + 1, jnp.int32)
    cl = jnp.asarray(cache_lens, jnp.int32)
    return q, kp, vp, bt, cl


def _gather_oracle(q, kp, vp, bt, cl, layer=None):
    """The layers.py fallback, verbatim semantics: gather the logical
    view (of layer ``layer`` of a stacked pool), dense causal attention
    with q at position cache_len."""
    B, H, Dh = q.shape
    Hkv = kp.shape[-3]
    at = bt if layer is None else (layer, bt)
    k = kp[at].transpose(0, 1, 3, 2, 4).reshape(B, -1, Hkv, Dh)
    v = vp[at].transpose(0, 1, 3, 2, 4).reshape(B, -1, Hkv, Dh)
    o = blocked_attention(
        q[:, None], k, v,
        q_positions=cl[:, None], k_positions=jnp.arange(k.shape[1]),
        mask_kind="causal", chunk=8192, prefix=0, kv_len=cl)
    return o[:, 0]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("H,Hkv", [(4, 4), (8, 2), (8, 1)])
@pytest.mark.parametrize("n_splits", [1, 2, 4])
def test_ref_kernel_gather_agree(dtype, H, Hkv, n_splits):
    # ragged fills incl. block-boundary values (bs-1, bs, 2·bs)
    q, kp, vp, bt, cl = _case(4, H, Hkv, 64, 4, 16, dtype,
                              [0, 15, 16, 32])
    ref = paged_decode_ref(q, kp, vp, bt, cl)
    ker = paged_decode_kernel(q, kp, vp, bt, cl, n_splits=n_splits,
                              interpret=True)
    gat = _gather_oracle(q, kp, vp, bt, cl)
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(ker, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(gat, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("n_splits", [1, 2, 4])
def test_stacked_pool_layer_matches_slice(layer, n_splits):
    """A 3-layer pool read at ``layer`` gives exactly what the same call
    gives on the slice ``pool[layer]``, on every path."""
    B, H, Hkv, Dh, NB, bs = 4, 8, 2, 64, 4, 16
    rng = np.random.default_rng(layer)
    q, *_, bt, cl = _case(B, H, Hkv, Dh, NB, bs, "bfloat16",
                          [bs - 1, bs, 2 * bs, 0], seed=layer)
    shape = (3, B * NB + 1, Hkv, bs, Dh)
    kp = jnp.asarray(rng.standard_normal(shape), "bfloat16")
    vp = jnp.asarray(rng.standard_normal(shape), "bfloat16")
    ly = jnp.int32(layer)
    pairs = [
        (paged_decode_kernel(q, kp, vp, bt, cl, ly, n_splits=n_splits,
                             interpret=True),
         paged_decode_kernel(q, kp[layer], vp[layer], bt, cl,
                             n_splits=n_splits, interpret=True)),
        (paged_decode_ref(q, kp, vp, bt, cl, ly),
         paged_decode_ref(q, kp[layer], vp[layer], bt, cl)),
        (_gather_oracle(q, kp, vp, bt, cl, ly),
         _gather_oracle(q, kp[layer], vp[layer], bt, cl)),
    ]
    for stacked, sliced in pairs:
        np.testing.assert_array_equal(np.asarray(stacked, np.float32),
                                      np.asarray(sliced, np.float32))


@pytest.mark.parametrize("block_kv", [8, 16])
def test_block_kv_sweep(block_kv):
    q, kp, vp, bt, cl = _case(2, 8, 2, 64, 4, 16, "float32", [7, 55])
    ref = paged_decode_ref(q, kp, vp, bt, cl)
    ker = paged_decode_kernel(q, kp, vp, bt, cl, block_kv=block_kv,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_wrapper_auto_matches_ref_on_cpu():
    # impl=None off-TPU routes to the jnp ref — exact
    q, kp, vp, bt, cl = _case(2, 4, 2, 32, 3, 16, "float32", [10, 40])
    out = paged_decode_attention(q, kp, vp, bt, cl)
    ref = paged_decode_ref(q, kp, vp, bt, cl)
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0


def test_scatter_mask_boundary_off_by_one():
    """Regression (ISSUE 10 satellite): the freshly written token at
    ``cache_len`` sitting exactly on a block boundary (off == 0, first
    slot of a new block) is attended; the position one past it is not.
    A huge-norm K marker makes attention collapse onto its V if and only
    if the marker position is <= cache_len."""
    B, H, Hkv, Dh, NB, bs = 1, 4, 2, 32, 3, 16
    q, kp, vp, bt, _ = _case(B, H, Hkv, Dh, NB, bs, "float32", [0])
    cl_val = bs                                 # block 1, offset 0
    phys = int(bt[0, cl_val // bs])
    q = jnp.ones_like(q)                        # q·k_marker >> any other
    kp = kp.at[phys, :, cl_val % bs].set(
        100.0 * math.sqrt(Dh) * jnp.ones((Hkv, Dh)))
    marker_v = vp[phys, :, cl_val % bs]         # (Hkv, Dh)
    want = jnp.broadcast_to(marker_v[:, None],
                            (Hkv, H // Hkv, Dh)).reshape(1, H, Dh)

    cl = jnp.asarray([cl_val], jnp.int32)
    for out in (paged_decode_ref(q, kp, vp, bt, cl),
                paged_decode_kernel(q, kp, vp, bt, cl, interpret=True),
                _gather_oracle(q, kp, vp, bt, cl)):
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-2, atol=1e-2)

    # one before the marker: it must be invisible on every path
    cl = jnp.asarray([cl_val - 1], jnp.int32)
    ref = paged_decode_ref(q, kp, vp, bt, cl)
    assert float(jnp.max(jnp.abs(ref - want))) > 0.1  # didn't collapse
    for out in (paged_decode_kernel(q, kp, vp, bt, cl, interpret=True),
                _gather_oracle(q, kp, vp, bt, cl)):
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


def test_empty_row_cache_len_zero():
    # cache_len == 0 attends exactly one position (the fresh token)
    q, kp, vp, bt, cl = _case(2, 4, 2, 32, 2, 16, "float32", [0, 0])
    ref = paged_decode_ref(q, kp, vp, bt, cl)
    want = jnp.broadcast_to(
        kp[bt[:, 0], :, 0][:, :, None].astype(jnp.float32) * 0
        + vp[bt[:, 0], :, 0][:, :, None], (2, 2, 2, 32)).reshape(2, 4, 32)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    ker = paged_decode_kernel(q, kp, vp, bt, cl, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_layers_paged_branch_kernel_vs_gather(monkeypatch):
    """attention_block's paged decode branch produces the same output
    under REPRO_PAGED_DECODE=interpret (Pallas kernel) as under gather
    (the XLA fallback), KV scatter included."""
    import jax

    from repro.configs.registry import get_config
    from repro.models import transformer as T

    cfg = get_config("internlm2-1.8b", reduced=True)
    params = T.init_params(cfg, 0)
    bs, n_blocks, B = 16, 7, 2
    rng = np.random.default_rng(3)

    def run(mode):
        monkeypatch.setenv("REPRO_PAGED_DECODE", mode)
        pool = T.init_paged_cache(cfg, n_blocks, bs)
        # identical random history in both runs, incl. a block-boundary
        # fill (cache_len[1] == bs): scatter lands at off == 0
        for sub in pool.values():
            for name in ("k_pool", "v_pool"):
                sub[name] = jnp.asarray(
                    rng.standard_normal(sub[name].shape), sub[name].dtype)
        rng2 = np.random.default_rng(7)
        batch = {
            "tokens": jnp.asarray(rng2.integers(2, cfg.vocab, (B, 1)),
                                  jnp.int32),
            "cache_len": jnp.asarray([5, bs], jnp.int32),
            "block_table": jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32),
        }
        logits, new_pool = jax.jit(
            lambda p, c, b: T.decode_step(p, c, b, cfg))(params, pool, batch)
        return np.asarray(logits, np.float32), new_pool

    lg_gather, pool_g = run("gather")
    # re-seed: the two runs must see identical pools
    rng = np.random.default_rng(3)
    lg_kernel, pool_k = run("interpret")
    # full-stack bf16: the kernel keeps f32 probabilities where the XLA
    # fallback casts them to bf16 before p·V, so logits drift a little
    np.testing.assert_allclose(lg_kernel, lg_gather, rtol=8e-2, atol=8e-2)
    # The first layer's scatter input (embeddings) is identical on both
    # paths, so its pool slice must match bitwise (sub-caches stack the
    # scanned layers on axis 0); deeper layers' K/V are projections of
    # earlier attention outputs and inherit the bf16 drift.
    for name in ("k_pool", "v_pool"):
        np.testing.assert_array_equal(np.asarray(pool_g["sub0"][name][0]),
                                      np.asarray(pool_k["sub0"][name][0]))
    for sub in pool_g:
        for name in ("k_pool", "v_pool"):
            np.testing.assert_allclose(
                np.asarray(pool_g[sub][name], np.float32),
                np.asarray(pool_k[sub][name], np.float32),
                rtol=8e-2, atol=8e-2)


def test_unknown_paged_decode_mode_raises(monkeypatch):
    from repro.models.layers import _paged_decode_fast_path

    q, kp, vp, bt, cl = _case(1, 4, 2, 32, 2, 16, "float32", [3])
    monkeypatch.setenv("REPRO_PAGED_DECODE", "kernal")
    with pytest.raises(ValueError, match="REPRO_PAGED_DECODE"):
        _paged_decode_fast_path(q[:, None], kp, vp, bt, cl)


def _layer_scan_routes(cfg, cache, batch):
    """Shapes of the layer scan's (consts, carry, xs, ys) in decode_step's
    jaxpr."""
    import jax

    from repro.models import transformer as T

    params = T.param_specs(cfg)
    jaxpr = jax.make_jaxpr(lambda p, c, b: T.decode_step(p, c, b, cfg))(
        params, cache, batch).jaxpr
    n_scan = T.layer_plan(cfg)[0]
    (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "scan"
              and e.params["length"] == n_scan]
    nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
    shapes = [tuple(v.aval.shape) for v in eqn.invars]
    return {"consts": shapes[:nc], "carry": shapes[nc:nc + nk],
            "xs": shapes[nc + nk:],
            "ys": [tuple(v.aval.shape) for v in eqn.outvars[nk:]]}


@pytest.mark.parametrize("kind,S", [("paged", 1), ("paged", 8),
                                    ("dense", 1)])
def test_layer_scan_cache_route(kind, S):
    """The paged pool rides decode_step's layer scan as carry only: no
    stacked or per-layer pool in xs, ys or consts, so the scan never
    slices a layer's pool out and stacks it back.  A dense cache keeps
    the xs/ys route, one layer's cache per scan step."""
    import jax

    from repro.configs.registry import get_config
    from repro.models import transformer as T

    cfg = get_config("qwen3-4b", reduced=True)
    B, bs, n_blocks, NB = 2, 16, 7, 3
    i32 = jnp.int32
    if kind == "paged":
        cache = jax.eval_shape(lambda: T.init_paged_cache(cfg, n_blocks, bs))
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                 "cache_len": jax.ShapeDtypeStruct((B,), i32),
                 "block_table": jax.ShapeDtypeStruct((B, NB), i32)}
    else:
        cache = T.cache_specs(cfg, B, 32)
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                 "cache_len": jax.ShapeDtypeStruct((), i32)}
    stacked = {tuple(leaf.shape) for leaf in jax.tree.leaves(cache)}
    per_layer = {s[1:] for s in stacked}
    routes = _layer_scan_routes(cfg, cache, batch)
    if kind == "paged":
        assert stacked <= set(routes["carry"])
        for where in ("consts", "xs", "ys"):
            assert not (stacked | per_layer) & set(routes[where]), where
    else:
        assert stacked <= set(routes["xs"]) and stacked <= set(routes["ys"])
        assert not (stacked | per_layer) & set(routes["carry"])
