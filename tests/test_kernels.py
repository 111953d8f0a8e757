"""Per-kernel correctness sweeps: Pallas (interpret mode on CPU) vs ref.py
pure-jnp oracles across shapes and dtypes.

The §3/§5 engine twins (``block_sub``, ``cache_events``) are compared
against the *jitted* refs with ``assert_array_equal``: the fused engine
runs fully under ``jax.jit``, so bit-exactness is defined against XLA's
jitted fusion of the same expressions (which differs from eager dispatch
at the last ulp — matching eager would be matching the wrong contract).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.block_sub import logreg_block_sub, pack_rows, pca_block_sub
from repro.kernels.cache_events import grid_cache_update
from repro.kernels.ops import (
    dsag_cache_update_op,
    dsag_update_ref,
    flash_attention_op,
    flash_attention_ref,
    gram_matvec_op,
    gram_matvec_ref,
)
from repro.precision import x64


class TestGramMatvec:
    @pytest.mark.parametrize(
        "n,d,k", [(256, 64, 3), (512, 128, 8), (1024, 96, 16), (300, 50, 3)]
    )
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_reference(self, n, d, k, dtype):
        kx, kv = jax.random.split(jax.random.key(0))
        x = jax.random.normal(kx, (n, d), dtype)
        v = jax.random.normal(kv, (d, k), dtype)
        got = gram_matvec_op(x, v, block_rows=128, interpret=True)
        want = gram_matvec_ref(x, v)
        tol = 1e-4 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=tol, atol=tol * np.abs(want).max()
        )

    def test_single_hbm_pass_shape(self):
        x = jnp.ones((512, 64))
        v = jnp.ones((64, 4))
        out = gram_matvec_op(x, v, interpret=True)
        assert out.shape == (64, 4)
        np.testing.assert_allclose(np.asarray(out), 512.0 * 64 * np.ones((64, 4)), rtol=1e-5)

    @pytest.mark.parametrize("n,d,k", [(0, 8, 3), (16, 0, 3), (16, 8, 0)])
    def test_degenerate_shapes_route_to_oracle(self, n, d, k):
        """Zero-size dims would launch empty/never-written Pallas grids;
        the wrapper must return the oracle's exact empty-contraction."""
        x = jnp.zeros((n, d), jnp.float32)
        v = jnp.zeros((d, k), jnp.float32)
        out = gram_matvec_op(x, v, interpret=True)
        assert out.shape == (d, k)
        np.testing.assert_array_equal(np.asarray(out), np.zeros((d, k)))

    @settings(max_examples=15, deadline=None)
    @given(
        n=st.integers(min_value=1, max_value=300),
        d=st.integers(min_value=1, max_value=40),
        k=st.integers(min_value=1, max_value=12),
    )
    def test_shape_sweep_non_multiple_n_small_k(self, n, d, k):
        """Non-multiple n and k < 128 exercise both padding paths."""
        kx, kv = jax.random.split(jax.random.key(n * 1000 + d * 16 + k))
        x = jax.random.normal(kx, (n, d), jnp.float32)
        v = jax.random.normal(kv, (d, k), jnp.float32)
        got = gram_matvec_op(x, v, block_rows=128, interpret=True)
        want = gram_matvec_ref(x, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4,
            atol=1e-4 * max(np.abs(np.asarray(want)).max(), 1.0),
        )


class TestDsagUpdate:
    @pytest.mark.parametrize("p,n", [(4, 4096), (2, 2048), (8, 6000), (1, 2048)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_reference(self, p, n, dtype):
        k1, k2, k3 = jax.random.split(jax.random.key(1), 3)
        g = jax.random.normal(k1, (p, n), dtype)
        c = jax.random.normal(k2, (p, n), dtype)
        h = jax.random.normal(k3, (n,), jnp.float32)
        mask = (jnp.arange(p) % 2 == 0).astype(jnp.float32)
        new_c, new_h = dsag_cache_update_op(g, c, h, mask, interpret=True)
        ref_c, ref_h = dsag_update_ref(g, c, h, mask)
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(new_c, np.float32), np.asarray(ref_c, np.float32), atol=tol
        )
        np.testing.assert_allclose(np.asarray(new_h), np.asarray(ref_h), atol=tol * 4)

    def test_invariant_h_equals_sum_of_cache_deltas(self):
        """After updating from a zero cache with full mask, h == Σ_i g_i."""
        p, n = 3, 2048
        g = jax.random.normal(jax.random.key(2), (p, n))
        c = jnp.zeros((p, n))
        h = jnp.zeros((n,))
        new_c, new_h = dsag_cache_update_op(g, c, h, jnp.ones(p), interpret=True)
        np.testing.assert_allclose(np.asarray(new_h), np.asarray(g.sum(0)), atol=1e-4)
        np.testing.assert_allclose(np.asarray(new_c), np.asarray(g), atol=1e-6)

    def test_masked_groups_untouched(self):
        p, n = 4, 2048
        g = jax.random.normal(jax.random.key(3), (p, n))
        c = jax.random.normal(jax.random.key(4), (p, n))
        h = jnp.zeros((n,))
        new_c, new_h = dsag_cache_update_op(g, c, h, jnp.zeros(p), interpret=True)
        np.testing.assert_allclose(np.asarray(new_c), np.asarray(c), atol=1e-6)
        np.testing.assert_allclose(np.asarray(new_h), 0.0, atol=1e-6)

    @pytest.mark.parametrize("p,n", [(0, 64), (3, 0), (0, 0)])
    def test_degenerate_shapes_route_to_oracle(self, p, n):
        """p == 0 makes the inner grid empty (the h accumulator scratch is
        never initialized — its output would be garbage, not zeros); the
        wrapper must detect it and return the oracle's empty-sum."""
        g = jnp.zeros((p, n), jnp.float32)
        c = jnp.zeros((p, n), jnp.float32)
        h = jnp.arange(n, dtype=jnp.float32)
        mask = jnp.ones((p,), jnp.float32)
        new_c, new_h = dsag_cache_update_op(g, c, h, mask, interpret=True)
        assert new_c.shape == (p, n) and new_h.shape == (n,)
        np.testing.assert_array_equal(np.asarray(new_h), np.asarray(h))

    @settings(max_examples=10, deadline=None)
    @given(
        p=st.integers(min_value=1, max_value=6),
        n=st.integers(min_value=1, max_value=3000),
    )
    def test_shape_sweep_non_multiple_n(self, p, n):
        """n not a multiple of the row block (including n < block)."""
        k1, k2, k3 = jax.random.split(jax.random.key(p * 5000 + n), 3)
        g = jax.random.normal(k1, (p, n), jnp.float32)
        c = jax.random.normal(k2, (p, n), jnp.float32)
        h = jax.random.normal(k3, (n,), jnp.float32)
        mask = (jnp.arange(p) % 2 == 0).astype(jnp.float32)
        new_c, new_h = dsag_cache_update_op(g, c, h, mask, block=2048, interpret=True)
        ref_c, ref_h = dsag_update_ref(g, c, h, mask)
        np.testing.assert_allclose(np.asarray(new_c), np.asarray(ref_c), atol=1e-5)
        np.testing.assert_allclose(np.asarray(new_h), np.asarray(ref_h), atol=4e-5)


class TestFlashAttention:
    @pytest.mark.parametrize(
        "b,h,s,d", [(1, 2, 256, 64), (2, 1, 384, 128), (1, 4, 128, 80)]
    )
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, b, h, s, d, causal):
        if not causal and s % 128 != 0:
            pytest.skip("non-causal requires aligned sk")
        k1, k2, k3 = jax.random.split(jax.random.key(5), 3)
        q = jax.random.normal(k1, (b, h, s, d), jnp.float32)
        k = jax.random.normal(k2, (b, h, s, d), jnp.float32)
        v = jax.random.normal(k3, (b, h, s, d), jnp.float32)
        got = flash_attention_op(q, k, v, causal=causal, interpret=True)
        want = flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)

    def test_bf16_io(self):
        q = jax.random.normal(jax.random.key(6), (1, 2, 256, 64), jnp.bfloat16)
        k = jax.random.normal(jax.random.key(7), (1, 2, 256, 64), jnp.bfloat16)
        v = jax.random.normal(jax.random.key(8), (1, 2, 256, 64), jnp.bfloat16)
        got = flash_attention_op(q, k, v, causal=True, interpret=True)
        want = flash_attention_ref(q, k, v, causal=True)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2
        )

    def test_long_context_streaming_blocks(self):
        """Many kv blocks: the online softmax must stay numerically exact."""
        q = jax.random.normal(jax.random.key(9), (1, 1, 128, 64))
        k = jax.random.normal(jax.random.key(10), (1, 1, 2048, 64))
        v = jax.random.normal(jax.random.key(11), (1, 1, 2048, 64))
        got = flash_attention_op(q, k, v, causal=False, interpret=True)
        want = flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize(
        "sq,sk", [(128, 256), (128, 300), (64, 200), (96, 300), (100, 100)]
    )
    def test_causal_decode_shapes_match_reference(self, sq, sk):
        """sq != sk causal (decode-style): the mask must align bottom-right
        to the true lengths and exclude padded tail keys — the pre-fix
        kernel silently applied a top-left mask over padded buffers."""
        k1, k2, k3 = jax.random.split(jax.random.key(12), 3)
        q = jax.random.normal(k1, (1, 2, sq, 64), jnp.float32)
        k = jax.random.normal(k2, (1, 2, sk, 64), jnp.float32)
        v = jax.random.normal(k3, (1, 2, sk, 64), jnp.float32)
        got = flash_attention_op(q, k, v, causal=True, interpret=True)
        want = flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)

    def test_causal_sq_gt_sk_raises(self):
        """Bottom-right alignment gives leading queries zero attendable
        keys (an empty softmax): reject instead of mis-masking."""
        q = jnp.zeros((1, 1, 256, 64))
        k = jnp.zeros((1, 1, 128, 64))
        with pytest.raises(ValueError, match="sq <= sk"):
            flash_attention_op(q, k, v=k, causal=True, interpret=True)

    def test_noncausal_unaligned_sk_raises(self):
        q = jnp.zeros((1, 1, 128, 64))
        k = jnp.zeros((1, 1, 200, 64))
        with pytest.raises(ValueError, match="sk % block_k"):
            flash_attention_op(q, k, v=k, causal=False, interpret=True)


class TestInterpretResolution:
    """S2 discipline: interpret=None is resolved from the *current* default
    backend at every call, never baked into a cached jit executable."""

    def test_default_resolved_per_call(self, monkeypatch):
        calls = []
        real = ops._interpret_default

        def recorder():
            calls.append(True)
            return real()

        monkeypatch.setattr(ops, "_interpret_default", recorder)
        x = jnp.ones((8, 4))
        v = jnp.ones((4, 2))
        ops.gram_matvec_op(x, v)
        ops.gram_matvec_op(x, v)
        assert len(calls) == 2, (
            "interpret default must be re-read on every call — a trace-time "
            "read would be cached with the first executable and go stale"
        )

    def test_explicit_interpret_skips_default(self, monkeypatch):
        monkeypatch.setattr(
            ops, "_interpret_default",
            lambda: (_ for _ in ()).throw(AssertionError("must not be read")),
        )
        x = jnp.ones((8, 4))
        v = jnp.ones((4, 2))
        out = ops.gram_matvec_op(x, v, interpret=True)
        assert out.shape == (4, 2)


def _jit_ref(fn, static_argnums):
    return jax.jit(fn, static_argnums=static_argnums)


class TestBlockSubTwins:
    """§3 engine twins: Pallas rows bit-identical to the jitted XLA form."""

    def _problem_data(self, n, d, seed):
        kx, ky = jax.random.split(jax.random.key(seed))
        X = jax.random.normal(kx, (n, d), jnp.float32)
        y = jnp.where(jax.random.uniform(ky, (n,)) < 0.5, 1.0, -1.0).astype(
            jnp.float32
        )
        return X, y

    @settings(max_examples=10, deadline=None)
    @given(
        n=st.integers(min_value=4, max_value=200),
        d=st.integers(min_value=1, max_value=32),
        g=st.integers(min_value=1, max_value=8),
        seed=st.integers(min_value=0, max_value=2**20),
    )
    def test_logreg_bitexact_vs_jitted_ref(self, n, d, g, seed):
        with x64():
            key = jax.random.key(seed)
            X, y = self._problem_data(n, d, seed)
            pad = int(min(1 << int(np.random.default_rng(seed).integers(0, 4)), n))
            k1, k2, k3 = jax.random.split(key, 3)
            starts = jax.random.randint(k1, (g,), 1, n - pad + 2).astype(jnp.int64)
            widths = jax.random.randint(k2, (g,), 1, pad + 1).astype(jnp.int64)
            Vb = jax.random.normal(k3, (g, d), jnp.float32)
            got = logreg_block_sub(pack_rows(X, y), Vb, starts, widths, pad, interpret=True)
            want = _jit_ref(ref.block_sub_logreg_ref, 5)(X, y, Vb, starts, widths, pad)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @settings(max_examples=10, deadline=None)
    @given(
        n=st.integers(min_value=4, max_value=200),
        d=st.integers(min_value=1, max_value=24),
        k=st.integers(min_value=1, max_value=4),
        g=st.integers(min_value=1, max_value=8),
        seed=st.integers(min_value=0, max_value=2**20),
    )
    def test_pca_bitexact_vs_jitted_ref(self, n, d, k, g, seed):
        with x64():
            key = jax.random.key(seed)
            X = (jax.random.uniform(key, (n, d)) < 0.3).astype(jnp.float32)
            pad = int(min(1 << int(np.random.default_rng(seed).integers(0, 4)), n))
            k1, k2, k3 = jax.random.split(key, 3)
            starts = jax.random.randint(k1, (g,), 1, n - pad + 2).astype(jnp.int64)
            widths = jax.random.randint(k2, (g,), 1, pad + 1).astype(jnp.int64)
            Vb = jax.random.normal(k3, (g, d, k), jnp.float32)
            got = pca_block_sub(pack_rows(X), Vb, starts, widths, pad, interpret=True)
            want = _jit_ref(ref.block_sub_pca_ref, 4)(X, Vb, starts, widths, pad)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_full_range_width(self):
        """pad == n (the gd/coded full-dataset bucket): off = 0, no roll."""
        with x64():
            n, d = 50, 7
            X, y = self._problem_data(n, d, 0)
            Vb = jax.random.normal(jax.random.key(1), (2, d), jnp.float32)
            starts = jnp.ones((2,), jnp.int64)
            widths = jnp.full((2,), n, jnp.int64)
            got = logreg_block_sub(pack_rows(X, y), Vb, starts, widths, n, interpret=True)
            want = _jit_ref(ref.block_sub_logreg_ref, 5)(X, y, Vb, starts, widths, n)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_single_row_blocks(self):
        """pad == 1 (width-1 intervals): every window is one row."""
        with x64():
            n, d = 20, 5
            X, y = self._problem_data(n, d, 3)
            Vb = jax.random.normal(jax.random.key(2), (4, d), jnp.float32)
            starts = jnp.asarray([1, 7, 19, 20], jnp.int64)
            widths = jnp.ones((4,), jnp.int64)
            got = logreg_block_sub(pack_rows(X, y), Vb, starts, widths, 1, interpret=True)
            want = _jit_ref(ref.block_sub_logreg_ref, 5)(X, y, Vb, starts, widths, 1)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_bad_pad_width_rejected(self):
        with x64():
            n, d = 20, 5
            X, y = self._problem_data(n, d, 4)
            Vb = jnp.zeros((1, d), jnp.float32)
            idx = jnp.ones((1,), jnp.int64)
            for bad in (0, n + 1):
                with pytest.raises(ValueError, match="pad_width"):
                    logreg_block_sub(pack_rows(X, y), Vb, idx, idx, bad, interpret=True)
            with pytest.raises(ValueError, match="pad_width"):
                pca_block_sub(pack_rows(X), jnp.zeros((1, d, 2)), idx, idx, 0, interpret=True)


class TestGridCacheUpdateTwin:
    """§5 engine twin: the fused rank walk bit-identical to the jitted ref."""

    def _random_case(self, seed, S, R, E, F):
        rng = np.random.default_rng(seed)
        valid_r = jnp.asarray(rng.random((S, R)) < 0.7)
        slot_r = jnp.asarray(rng.integers(0, E, (S, R)), jnp.int64)
        tag_r = jnp.asarray(rng.integers(0, 5, (S, R)), jnp.int64)
        vals_r = jnp.asarray(rng.normal(size=(S, R, F)))
        sums = jnp.asarray(rng.normal(size=(S, F)))
        values = jnp.asarray(rng.normal(size=(S, E, F)))
        iters = jnp.asarray(rng.integers(-1, 4, (S, E)), jnp.int64)
        covered = jnp.asarray(rng.integers(0, 30, (S,)), jnp.int64)
        rejected = jnp.asarray(rng.integers(0, 5, (S,)), jnp.int64)
        slot_width = jnp.asarray(rng.integers(1, 9, (E,)), jnp.int64)
        return (valid_r, slot_r, tag_r, vals_r, sums, values, iters,
                covered, rejected, slot_width)

    @settings(max_examples=10, deadline=None)
    @given(
        S=st.integers(min_value=1, max_value=4),
        R=st.integers(min_value=1, max_value=10),
        E=st.integers(min_value=1, max_value=8),
        F=st.integers(min_value=1, max_value=6),
        seed=st.integers(min_value=0, max_value=2**20),
    )
    def test_bitexact_vs_jitted_ref(self, S, R, E, F, seed):
        with x64():
            args = self._random_case(seed, S, R, E, F)
            got = grid_cache_update(*args, interpret=True)
            want = jax.jit(ref.grid_cache_update_ref)(*args)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_stale_dominated_events_rejected(self):
        """An event older than its slot's resident iteration must bump the
        rejected counter and leave the table untouched."""
        with x64():
            S, R, E, F = 1, 1, 2, 3
            valid_r = jnp.ones((S, R), bool)
            slot_r = jnp.zeros((S, R), jnp.int64)
            tag_r = jnp.zeros((S, R), jnp.int64)  # tag 0 vs resident iter 5
            vals_r = jnp.ones((S, R, F), jnp.float64)
            sums = jnp.zeros((S, F), jnp.float64)
            values = jnp.full((S, E, F), 7.0, jnp.float64)
            iters = jnp.full((S, E), 5, jnp.int64)
            covered = jnp.zeros((S,), jnp.int64)
            rejected = jnp.zeros((S,), jnp.int64)
            slot_width = jnp.ones((E,), jnp.int64)
            out = grid_cache_update(
                valid_r, slot_r, tag_r, vals_r, sums, values, iters,
                covered, rejected, slot_width, interpret=True,
            )
            np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(values))
            np.testing.assert_array_equal(np.asarray(out[4]), np.ones((S,)))
