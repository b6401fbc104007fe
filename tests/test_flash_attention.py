"""Pallas flash attention vs the dense reference (ops/flash_attention.py;
interpret mode on CPU — the same kernel code path the TPU compiles). The
compiled kernels are checked against dense on the chip by chip_smoke.py."""

import numpy as np
import pytest

import pathway_tpu  # noqa: F401  (jax cpu config via conftest)


def _rand(b, t, h, d, seed=0, dtype="float32"):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(b, t, h, d)), getattr(jnp, dtype)
    )
    return mk(), mk(), mk()


class TestFlashAttention:
    @pytest.mark.parametrize("t", [8, 64, 256])  # 256 = multi q/k blocks
    def test_matches_dense_with_padding_mask(self, t):
        import jax.numpy as jnp

        from pathway_tpu.models.transformer import dense_attention
        from pathway_tpu.ops.flash_attention import flash_attention

        q, k, v = _rand(2, t, 4, 32)
        rng = np.random.default_rng(1)
        mask_np = rng.random((2, t)) > 0.3
        mask_np[:, 0] = True  # at least one real token per row
        mask = jnp.asarray(mask_np)
        ours = np.asarray(flash_attention(q, k, v, mask))
        ref = np.asarray(dense_attention(q, k, v, mask))
        # compare only real-query positions (pad queries attend too in
        # both, but their values are irrelevant downstream)
        assert np.abs(ours - ref).max() < 2e-5

    def test_mask_none(self):
        from pathway_tpu.models.transformer import dense_attention
        from pathway_tpu.ops.flash_attention import flash_attention

        q, k, v = _rand(1, 16, 2, 16, seed=3)
        ours = np.asarray(flash_attention(q, k, v, None))
        ref = np.asarray(dense_attention(q, k, v, None))
        assert np.abs(ours - ref).max() < 2e-5

    def test_bf16_inputs(self):
        import jax.numpy as jnp

        from pathway_tpu.models.transformer import dense_attention
        from pathway_tpu.ops.flash_attention import flash_attention

        q, k, v = _rand(1, 32, 2, 32, seed=5, dtype="bfloat16")
        mask = jnp.ones((1, 32), bool)
        ours = np.asarray(flash_attention(q, k, v, mask), np.float32)
        ref = np.asarray(dense_attention(q, k, v, mask), np.float32)
        assert np.abs(ours - ref).max() < 2e-2  # bf16 output tolerance

    def test_encoder_forward_accepts_flash(self):
        """The attn_fn seam: a full encoder forward under the kernel stays
        numerically on top of the dense path."""
        import jax
        import jax.numpy as jnp

        from pathway_tpu.models import (
            embed,
            init_encoder_params,
        )
        from pathway_tpu.models.transformer import EncoderConfig
        from pathway_tpu.ops.flash_attention import flash_attention

        cfg = EncoderConfig(
            vocab_size=128, hidden=64, layers=2, heads=4, intermediate=128,
            dtype=jnp.float32,
        )
        params = init_encoder_params(jax.random.key(0), cfg)
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(1, 128, (2, 16)), jnp.int32)
        mask = jnp.asarray([[True] * 16, [True] * 9 + [False] * 7])
        dense = np.asarray(embed(params, ids, mask, cfg))
        flash = np.asarray(
            embed(params, ids, mask, cfg, attn_fn=flash_attention)
        )
        assert np.abs(dense - flash).max() < 1e-4

    def test_non_multiple_sequence_length_padded_correctly(self):
        import jax.numpy as jnp

        from pathway_tpu.models.transformer import dense_attention
        from pathway_tpu.ops.flash_attention import flash_attention

        q, k, v = _rand(1, 160, 2, 16, seed=7)  # 160 % 128 != 0
        mask = jnp.ones((1, 160), bool)
        ours = np.asarray(flash_attention(q, k, v, mask))
        ref = np.asarray(dense_attention(q, k, v, mask))
        assert not np.isnan(ours).any()
        assert np.abs(ours - ref).max() < 2e-5

    def test_gradients_flow(self):
        import jax
        import jax.numpy as jnp

        from pathway_tpu.models.transformer import dense_attention
        from pathway_tpu.ops.flash_attention import flash_attention

        q, k, v = _rand(1, 16, 2, 8, seed=9)
        mask = jnp.asarray([[True] * 12 + [False] * 4])

        def loss(fn, q_, k_, v_):
            return (fn(q_, k_, v_, mask) ** 2).sum()

        g_flash = jax.grad(lambda *a: loss(flash_attention, *a), (0, 1, 2))(
            q, k, v
        )
        g_dense = jax.grad(lambda *a: loss(dense_attention, *a), (0, 1, 2))(
            q, k, v
        )
        for gf, gd in zip(g_flash, g_dense):
            assert np.abs(np.asarray(gf) - np.asarray(gd)).max() < 2e-4

    def test_tiled_backward_matches_dense_multi_tile(self):
        """The flash backward kernels (dQ / dK+dV, lse-based recompute)
        must match dense gradients across MULTIPLE k/q tiles (t > block),
        ragged masks, and a padded tail tile."""
        import jax
        import jax.numpy as jnp

        import importlib

        fa = importlib.import_module("pathway_tpu.ops.flash_attention")
        from pathway_tpu.models.transformer import dense_attention

        old_block = fa._BLOCK
        fa._BLOCK = 32  # force several tiles at a test-sized t
        try:
            for t, lens in ((96, (96, 50)), (80, (77, 33))):  # 80: padded tail
                q, k, v = _rand(2, t, 2, 16, seed=t)
                mask = jnp.asarray(
                    [[i < n for i in range(t)] for n in lens]
                )

                def loss(fn, q_, k_, v_):
                    out = fn(q_, k_, v_, mask)
                    return (out * jnp.cos(out)).sum()

                g_flash = jax.grad(
                    lambda *a: loss(fa.flash_attention, *a), (0, 1, 2)
                )(q, k, v)
                g_dense = jax.grad(
                    lambda *a: loss(dense_attention, *a), (0, 1, 2)
                )(q, k, v)
                for gf, gd in zip(g_flash, g_dense):
                    err = np.abs(np.asarray(gf) - np.asarray(gd)).max()
                    assert err < 3e-4, (t, err)
        finally:
            fa._BLOCK = old_block

    def test_default_attn_fn_is_dense(self):
        """The kernel is opt-in (``attn_fn=flash_attention``): on the chip
        it lost to XLA's attention at every shape timed (PR 21)."""
        from pathway_tpu.models.transformer import (
            default_attn_fn,
            dense_attention,
        )

        assert default_attn_fn() is dense_attention

    def test_vision_forward_accepts_flash(self):
        import jax

        from pathway_tpu.models import (
            init_vision_params,
            vision_forward,
            vit_tiny,
        )
        from pathway_tpu.ops.flash_attention import flash_attention

        cfg = vit_tiny()
        params = init_vision_params(jax.random.key(0), cfg)
        pixels = np.random.default_rng(0).normal(
            size=(2, cfg.image_size, cfg.image_size, 3)
        ).astype(np.float32)
        dense = np.asarray(vision_forward(params, pixels, cfg))
        flash = np.asarray(
            vision_forward(params, pixels, cfg, attn_fn=flash_attention)
        )
        assert np.abs(dense - flash).max() < 1e-4
