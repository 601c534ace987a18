"""Cross-path consistency: prefill+decode must reproduce the training
forward's next-token logits; MoE dispatch modes agree; sharding rules are
divisibility-safe."""
import jax
import jax.numpy as jnp
from jax.sharding import AxisType
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, get_smoke_config
from repro.models.model import build_model

DECODE_MATCH_ARCHS = ["minitron-8b", "qwen2-1.5b", "gemma3-12b",
                      "qwen2-moe-a2.7b", "deepseek-v3-671b", "mamba2-1.3b",
                      "zamba2-7b"]


@pytest.mark.slow
@pytest.mark.parametrize("arch", DECODE_MATCH_ARCHS)
def test_decode_matches_teacher_forcing(arch):
    """Greedy decode logits at position t == full-forward logits at t.

    MoE capacity is raised so no token drops (capacity dropping makes the
    paths legitimately diverge); tolerances cover bf16 reassociation
    (absorbed-MLA decode, conv-state decode paths)."""
    import dataclasses
    cfg = get_smoke_config(arch).replace(remat=False)
    if cfg.moe:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=8.0))
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    rng = np.random.default_rng(0)
    b, l = 2, 12
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (b, l)), jnp.int32)

    # full forward logits (training path)
    if cfg.family in ("dense", "moe", "vlm"):
        from repro.models.transformer import decoder_forward, logits_fn
        h, _ = decoder_forward(params, cfg, toks)
        full = logits_fn(params, cfg, h)
    elif cfg.family == "hybrid":
        from repro.models.hybrid import hybrid_forward
        from repro.models.transformer import logits_fn
        full = logits_fn(params, cfg, hybrid_forward(params, cfg, toks))
    else:
        from repro.models.ssm import ssm_forward
        from repro.models.transformer import logits_fn
        full = logits_fn(params, cfg, ssm_forward(params, cfg, toks))

    # prefill on the first l-1 tokens, then decode token l-1
    cap = l + 4
    logits_p, cache = model.prefill_fn(params, {"tokens": toks[:, :l - 1]},
                                       cap)
    logits_d, _ = model.decode_fn(params, cache, toks[:, l - 1:l],
                                  jnp.int32(l - 1))
    v = cfg.vocab
    np.testing.assert_allclose(
        np.asarray(logits_p[:, 0, :v], np.float32),
        np.asarray(full[:, l - 2, :v], np.float32), rtol=6e-2, atol=8e-2)
    np.testing.assert_allclose(
        np.asarray(logits_d[:, 0, :v], np.float32),
        np.asarray(full[:, l - 1, :v], np.float32), rtol=6e-2, atol=8e-2)


def test_moe_hierarchical_matches_flat():
    """On a 1-shard mesh the hierarchical dispatch must equal the flat
    path exactly (same capacity, same order)."""
    import dataclasses
    from repro.models import moe as MOE
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    m_flat = cfg.moe
    m_hier = dataclasses.replace(cfg.moe, dispatch="hierarchical")
    p = MOE.init_moe(jax.random.key(1), cfg.d_model, m_flat, jnp.float32)
    x = jax.random.normal(jax.random.key(2), (2, 16, cfg.d_model),
                          jnp.float32)
    y1, a1 = MOE.moe_ffn(p, x, m_flat)
    from repro.distributed.meshctx import mesh_context
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with mesh_context(mesh):
        y2, a2 = jax.jit(lambda p, x: MOE.moe_ffn(p, x, m_hier))(p, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4,
                               atol=2e-4)


def test_expert_padding_masks_padded_experts():
    import dataclasses
    from repro.models import moe as MOE
    m = dataclasses.replace(get_smoke_config("qwen2-moe-a2.7b").moe,
                            n_experts=6, n_experts_padded=8, top_k=2)
    logits = jax.random.normal(jax.random.key(0), (64, 8), jnp.float32)
    probs, idx, aux = MOE.router_topk(logits, m)
    assert int(jnp.max(idx)) < 6          # never routes to padded experts


def test_sharding_rules_divisibility():
    """No parameter ever gets a spec whose dim doesn't divide the mesh."""
    from repro.distributed.sharding import abstract_mesh, param_shardings
    mesh = abstract_mesh(("data", "model"), (1, 2))
    for arch in ARCH_IDS:
        cfg = get_smoke_config(arch)
        model = build_model(cfg)
        abs_p = model.abstract_params()
        shardings = param_shardings(abs_p, mesh)
        for leaf, sh in zip(jax.tree.leaves(abs_p),
                            jax.tree.leaves(shardings)):
            spec = sh.spec
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                size = int(np.prod([mesh.shape[a] for a in axes]))
                assert leaf.shape[dim] % size == 0, (arch, leaf.shape, spec)


def test_grad_accum_matches_single_batch():
    """grad_accum=2 over a batch == one step over the same batch."""
    from repro.launch.steps import make_train_step
    cfg = get_smoke_config("qwen2-1.5b")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)),
                              jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)),
                               jnp.int32),
        "mask": jnp.ones((4, 16), jnp.float32),
    }
    for ga in (1, 2):
        opt_init, step = make_train_step(model, grad_accum=ga)
        p2, _, m = jax.jit(step)(params, opt_init(params), batch,
                                 jnp.int32(0))
        if ga == 1:
            base = m["loss"]
        else:
            np.testing.assert_allclose(float(m["loss"]), float(base),
                                       rtol=2e-2)
