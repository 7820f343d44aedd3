"""The plain reference against the program's own forward pass and loss,
at a small size on the CPU, for both tiny configurations, each reached
through the model family it names."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cells
import tiny
import weights


@pytest.fixture(params=sorted(tiny.CONFIGS))
def model(request):
    from repro.models import transformer as T

    cfg = dict(tiny.CONFIGS[request.param])
    params = weights.make(cfg, 2**31 + 3)
    acfg = cells.program_config(cfg)
    spec = jax.tree.map(lambda s: (s.shape, s.dtype), T.param_specs(acfg))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == spec
    return cfg, acfg, params, T, cells.family(cfg).reference


def _tokens(cfg, shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], shape).astype(np.int32)


def test_forward_logits_agree(model):
    cfg, acfg, params, T, R = model
    tok = _tokens(cfg, (1, 256))
    prog, _ = T.forward(params, {"tokens": jnp.asarray(tok)}, acfg)
    prog = np.asarray(prog.astype(jnp.float32))[0]
    x = R.forward_hidden(cfg, "float32", params, tok[0])
    head = R._head(cfg, params).astype(jnp.float32)
    ref = np.asarray(R._rms(x[0], params["final_norm"].astype(jnp.float32),
                            cfg["rms_norm_eps"]) @ head)[:256]
    scale = np.abs(ref).max()
    # bfloat16 weights and activations through 2 layers: about 1% of the
    # largest logit
    assert np.abs(prog - ref).max() <= 0.03 * scale
    # the program's greedy choice costs at most a rounding-sized gap
    pick = prog.argmax(-1)
    gap = ref.max(-1) - np.take_along_axis(ref, pick[:, None], -1)[:, 0]
    assert gap.max() <= 0.05


def test_served_gaps_teacher_forced(model):
    cfg, acfg, params, T, R = model
    tok = _tokens(cfg, (1, 200), seed=1)[0]
    prog, _ = T.forward(params, {"tokens": jnp.asarray(tok[None])}, acfg)
    pick = np.asarray(prog[0].astype(jnp.float32)).argmax(-1)[99:199]
    g, g_pick, best = R.served_gaps(cfg, "float32", params, tok[:100],
                                    tok[100:], pick.astype(np.int32))
    assert (g >= 0).all() and g.max() > 1.0      # random tokens: far off
    assert g_pick.max() <= 0.05                  # the program's choices
    assert np.isinf(R.served_gaps(cfg, "float32", params, tok[:100],
                                  np.array([cfg["vocab_size"]], np.int32))[0]
                    ).all()                      # a padded id never passes


def test_loss_and_gradient_agree(model):
    cfg, acfg, params, T, R = model
    tok = _tokens(cfg, (2, 256), seed=2)
    prog, grads = jax.value_and_grad(
        lambda p: T.loss_fn(p, {"tokens": jnp.asarray(tok)}, acfg)[0])(params)
    ref, rgrads = R.loss_and_grads(cfg, "float32", R.unstack(params),
                                   jnp.asarray(tok), 1e-4)
    assert abs(float(prog) - float(ref)) <= 2e-3 * abs(float(ref))
    a = np.asarray(grads["blocks"]["sub0"]["mlp"]["down"][1], np.float32)
    b = np.asarray(rgrads["layers"][1]["down"])
    assert np.linalg.norm(a - b) <= 0.05 * np.linalg.norm(b)


def test_control_is_the_reference_in_float8(model):
    cfg, acfg, params, T, R = model
    tok = _tokens(cfg, (1, 256), seed=3)[0]
    _, _, pick8 = R.served_gaps(cfg, "fp8", params, tok[:100], tok[100:])
    _, g8, _ = R.served_gaps(cfg, "float32", params, tok[:100], tok[100:],
                             pick8)
    _, _, pick32 = R.served_gaps(cfg, "float32", params, tok[:100],
                                 tok[100:])
    assert (pick8 != pick32).any() and g8.max() > 0.1
