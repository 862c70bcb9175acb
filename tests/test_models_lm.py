"""The transformer's smoke tests and the graft entry (`__graft_entry__.py`:
the language model's forward pass and its 8-device dry run), beside
`tests/test_models.py`'s image models."""

import jax
import numpy as np

from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import MeshSpec, build_mesh


def test_transformer_forward_shapes():
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, d_ff=64,
                                n_layers=2, max_seq=64)
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    mesh = build_mesh(MeshSpec(), jax.devices()[:1])
    fwd = jax.jit(tfm.build_forward(cfg, mesh))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    logits = fwd(params, tokens)
    assert logits.shape == (2, 16, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_transformer_flash_attention_matches_local():
    """attn='flash' (Pallas kernel, ops/flash_attention.py) must produce
    the same logits and gradients as the exact 'local' attention."""
    import jax.numpy as jnp
    mk = lambda attn: tfm.TransformerConfig(  # noqa: E731
        vocab=64, d_model=32, n_heads=4, d_ff=64, n_layers=2, max_seq=64,
        attn=attn)
    params = tfm.init(jax.random.PRNGKey(0), mk("local"))
    mesh = build_mesh(MeshSpec(), jax.devices()[:1])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 64)

    out = {}
    for attn in ("local", "flash"):
        fwd = jax.jit(tfm.build_forward(mk(attn), mesh))
        out[attn] = np.asarray(fwd(params, tokens))
    np.testing.assert_allclose(out["flash"], out["local"],
                               rtol=2e-4, atol=2e-4)

    grads = {}
    for attn in ("local", "flash"):
        cfg = mk(attn)
        fwd = tfm.build_forward(cfg, mesh)

        def loss(p):
            return jnp.mean(jnp.square(fwd(p, tokens)))
        grads[attn] = jax.jit(jax.grad(loss))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4),
        grads["flash"], grads["local"])


def test_graft_entry_hooks():
    import __graft_entry__ as ge
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.ndim == 3
    ge.dryrun_multichip(8)
