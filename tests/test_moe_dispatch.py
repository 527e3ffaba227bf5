"""Dispatch-pipeline tests: sort-based dropless (gmm) vs capacity (dense).

Pins the new Router->Dispatch->Compute->Combine pieces: per-token output
equivalence of ``gmm`` against dropless ``dense`` (including T=1 decode
shapes and empty expert groups), the SortPlan invariants, the ragged
grouped-matmul Pallas kernel against its pure-jnp oracle, and the LExI-plan
round trip through the serving engine on the gmm path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import models
from repro.configs import get_config
from repro.core import iter_moe_layer_params
from repro.kernels import ref
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.models.moe import (
    available_impls,
    make_sort_plan,
    moe,
    moe_dense,
    moe_gmm,
    sort_combine,
    sort_dispatch,
)


def _layer(e, k, dtype="float32", seed=0):
    cfg = get_config("olmoe-1b-7b").reduced().with_(
        num_experts=e, moe_top_k=k, dtype=dtype,
        moe_capacity_factor=float(e))  # dense dropless -> exact equivalence
    params = models.init_params(jax.random.PRNGKey(seed), cfg)
    _, mp = next(iter_moe_layer_params(params, cfg))
    return cfg, mp


class TestGmmEqualsDense:
    @pytest.mark.parametrize("e,k,t", [
        (8, 2, 64),
        (8, 4, 1),      # T=1 decode shape
        (4, 2, 7),      # T not tile-aligned
        (16, 3, 33),
        (8, 8, 16),     # k == E: every expert takes every token
    ])
    def test_per_token_outputs_match(self, e, k, t):
        cfg, mp = _layer(e, k)
        x = jax.random.normal(jax.random.PRNGKey(t), (t, cfg.d_model))
        y0, a0 = moe_dense(mp, cfg, x, k)
        y1, a1 = moe_gmm(mp, cfg, x, k)
        y2, _ = moe_gmm(mp, cfg, x, k, use_kernel=True)  # Pallas interpret
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y2),
                                   rtol=2e-5, atol=2e-5)
        assert float(a0) == pytest.approx(float(a1), rel=1e-6)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(2, 16), st.integers(1, 4), st.integers(1, 50))
    def test_property_random_shapes(self, e, k, t):
        k = min(k, e)
        cfg, mp = _layer(e, k, seed=e * 7 + k)
        x = jax.random.normal(jax.random.PRNGKey(t), (t, cfg.d_model))
        y0, _ = moe_dense(mp, cfg, x, k)
        y1, _ = moe_gmm(mp, cfg, x, k)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=2e-5, atol=2e-5)

    def test_registry_entry_point(self):
        assert set(available_impls()) >= {"dense", "gmm", "ep_a2a", "ep_psum"}
        cfg, mp = _layer(8, 2)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, cfg.d_model))
        y0, _ = moe(mp, cfg, x, 2, impl="dense")
        y1, _ = jax.jit(lambda p, xx: moe(p, cfg, xx, 2, impl="gmm"))(mp, x)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=2e-5, atol=2e-5)
        with pytest.raises(ValueError, match="unknown moe impl"):
            moe(mp, cfg, x, 2, impl="nope")

    @pytest.mark.parametrize("impl", ["ep_a2a", "ep_psum"])
    def test_expert_parallel_without_mesh_raises(self, impl):
        """No silent substitution of the capacity-dropping dense impl."""
        cfg, mp = _layer(8, 2)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, cfg.d_model))
        with pytest.raises(ValueError, match="mesh"):
            moe(mp, cfg, x, 2, impl=impl)

    def test_gmm_grads_match_dense(self):
        cfg, mp = _layer(8, 2)
        x = jax.random.normal(jax.random.PRNGKey(3), (24, cfg.d_model))

        def loss(p, fn):
            y, aux = fn(p, cfg, x, 2)
            return jnp.sum(y ** 2) + 0.01 * aux

        g0 = jax.grad(lambda p: loss(p, moe_dense))(mp)
        g1 = jax.grad(lambda p: loss(p, moe_gmm))(mp)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)


class TestSortPlan:
    def test_dest_is_injective_and_token_major(self):
        rng = np.random.default_rng(0)
        idx = jnp.asarray(rng.integers(0, 8, size=(32, 2)))
        plan = make_sort_plan(idx, 8, block_m=8)
        dest = np.asarray(plan.dest)
        assert len(set(dest.tolist())) == dest.size          # no collisions
        assert dest.max() < plan.num_rows
        # token-major within each expert: earlier flat copies get lower rows
        flat_e = np.asarray(idx).reshape(-1)
        for e in range(8):
            rows = dest[flat_e == e]
            assert np.all(np.diff(rows) > 0)

    def test_group_sizes_and_padding(self):
        idx = jnp.asarray([[0, 3], [3, 3], [7, 0]])          # experts 1-2,4-6 empty
        plan = make_sort_plan(idx, 8, block_m=8)
        sizes = np.asarray(plan.group_sizes)
        assert sizes.tolist() == [2, 0, 0, 3, 0, 0, 0, 1]
        padded = np.asarray(plan.padded_group_sizes)
        assert np.all(padded % 8 == 0)
        assert np.all(padded >= sizes)
        # every real row maps into its expert's padded range
        valid_tiles = np.asarray(plan.tile_valid)
        te = np.asarray(plan.tile_expert)
        assert set(te[valid_tiles == 1].tolist()) == {0, 3, 7}

    def test_empty_expert_groups_roundtrip(self):
        """All tokens on one expert: the other groups are empty and the
        pipeline still reproduces dense dropless output."""
        cfg, mp = _layer(8, 1)
        # bias the router so expert argmax collapses to one expert
        mp = dict(mp)
        mp["router"] = jnp.zeros_like(mp["router"]).at[:, 5].set(10.0)
        x = jax.random.normal(jax.random.PRNGKey(1), (17, cfg.d_model))
        y0, _ = moe_dense(mp, cfg, x, 1)
        y1, _ = moe_gmm(mp, cfg, x, 1)
        y2, _ = moe_gmm(mp, cfg, x, 1, use_kernel=True)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y2),
                                   rtol=2e-5, atol=2e-5)

    def test_dispatch_combine_inverse(self):
        """combine(dispatch(x)) with identity compute == sum_k w * x."""
        rng = np.random.default_rng(1)
        idx = jnp.asarray(rng.integers(0, 4, size=(9, 2)))
        w = jnp.asarray(rng.random((9, 2)), jnp.float32)
        x = jnp.asarray(rng.standard_normal((9, 16)), jnp.float32)
        plan = make_sort_plan(idx, 4, block_m=8)
        xs = sort_dispatch(x, plan, 2)
        y = sort_combine(xs, w, plan)
        exp = np.asarray(x) * np.asarray(w.sum(1))[:, None]
        np.testing.assert_allclose(np.asarray(y), exp, rtol=1e-5, atol=1e-6)


class TestGmmKernel:
    @pytest.mark.parametrize("e,sizes,d,f,bm", [
        (4, (8, 0, 16, 8), 64, 32, 8),     # empty group
        (3, (4, 5, 3), 64, 96, 8),          # ragged, multi f-step
        (2, (0, 0), 32, 32, 8),             # fully empty
        (5, (40, 0, 8, 1, 15), 128, 64, 16),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, e, sizes, d, f, bm, dtype):
        """Kernel over the padded tile layout == jnp oracle over the same."""
        sizes = jnp.asarray(sizes, jnp.int32)
        padded = ((sizes + bm - 1) // bm) * bm
        n_tiles = int(jnp.sum(padded)) // bm + 1    # +1 dead trailing tile
        m = n_tiles * bm
        ks = jax.random.split(jax.random.PRNGKey(int(jnp.sum(sizes))), 3)
        w1 = (jax.random.normal(ks[0], (e, d, 2 * f)) * 0.05).astype(dtype)
        w2 = (jax.random.normal(ks[1], (e, f, d)) * 0.05).astype(dtype)
        # build the padded sorted buffer directly
        xs = np.zeros((m, d), np.float32)
        pstarts = np.asarray(jnp.cumsum(padded) - padded)
        rows = np.asarray(jax.random.normal(ks[2], (int(jnp.sum(sizes)), d)))
        r = 0
        for ei in range(e):
            s = int(sizes[ei])
            xs[pstarts[ei]:pstarts[ei] + s] = rows[r:r + s]
            r += s
        xs = jnp.asarray(xs, dtype)
        tile_row0 = np.arange(n_tiles) * bm
        pends = np.asarray(jnp.cumsum(padded))
        te = np.searchsorted(pends, tile_row0, side="right")
        valid = te < e
        te_c = np.minimum(te, e - 1)
        local = tile_row0 - pstarts[te_c]
        tv = (valid & (local < np.asarray(sizes)[te_c])).astype(np.int32)
        out = moe_gmm_pallas(xs, w1, w2, jnp.asarray(te_c, jnp.int32),
                             jnp.asarray(tv), block_m=bm, block_f=32,
                             interpret=True)
        exp = ref.moe_gmm_ref(xs, w1, w2, padded)
        tol = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 \
            else dict(rtol=5e-2, atol=5e-2)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32), **tol)


class TestDecodeShapeBlockM:
    """default_block_m clamps to the copy count (pow2) so decode-shaped
    dispatches stop padding every expert group to mostly-empty tiles."""

    def test_clamps_to_copy_count_pow2(self):
        from repro.models.moe import default_block_m
        assert [default_block_m(n) for n in (1, 2, 3, 6, 8, 64)] == \
            [1, 2, 4, 8, 8, 64]
        # 8+ copies keep the round-to-8 sizing (pow2 would grow padding)
        assert [default_block_m(n) for n in (40, 100, 4096)] == [40, 104, 128]
        assert default_block_m(40, cap=16) == 16
        # the kernel path reimposes its Mosaic sublane floor
        assert default_block_m(2, floor=8) == 8

    @pytest.mark.parametrize("t", [1, 2, 8])
    def test_sub8_tiles_run_through_kernel_in_interpret(self, t):
        """Explicit sub-8 block_m through moe_gmm_pallas (interpret) stays
        exact -- the small-tile layout itself is sound; only Mosaic's
        sublane minimum keeps the default kernel path at >= 8."""
        cfg, mp = _layer(8, 2)
        x = jax.random.normal(jax.random.PRNGKey(t + 7), (t, cfg.d_model))
        y0, _ = moe_dense(mp, cfg, x, 2)
        y1, _ = moe_gmm(mp, cfg, x, 2, use_kernel=True, block_m=2)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("t", [1, 2, 8])
    def test_kernel_matches_ref_at_decode_shapes(self, t):
        """gmm with the clamped default tile (kernel and jnp) still equals
        dropless dense at decode-shaped T -- tiles smaller than the old
        floor of 8 run through moe_gmm_pallas correctly."""
        cfg, mp = _layer(8, 2)
        x = jax.random.normal(jax.random.PRNGKey(t), (t, cfg.d_model))
        y0, _ = moe_dense(mp, cfg, x, 2)
        y1, _ = moe_gmm(mp, cfg, x, 2)
        y2, _ = moe_gmm(mp, cfg, x, 2, use_kernel=True)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y2),
                                   rtol=2e-5, atol=2e-5)


class TestEnginePlanRoundtrip:
    def _engine_tokens(self, cfg, params, prompt, **kw):
        from repro.serving import Engine, Request
        eng = Engine(cfg, params, max_batch=2, max_len=64, prefill_pad=8, **kw)
        return eng.serve([Request(uid=0, prompt=prompt,
                                  max_new_tokens=6)])[0].tokens

    def test_per_layer_k_plan_serves_on_gmm(self):
        """A LExI plan decodes greedily on the gmm path and matches the
        dropless dense path token-for-token."""
        from repro.models.opts import ModelOpts
        cfg = get_config("olmoe-1b-7b").reduced().with_(
            num_experts=8, moe_top_k=4, dtype="float32",
            moe_capacity_factor=8.0)  # dense engine dropless -> comparable
        n = cfg.num_moe_layers
        cfg = cfg.with_lexi_plan(tuple(1 + (i % 3) for i in range(n)))
        params = models.init_params(jax.random.PRNGKey(0), cfg)
        prompt = np.arange(3, 11).astype(np.int32)
        toks_dense = self._engine_tokens(cfg, params, prompt)
        toks_gmm = self._engine_tokens(cfg, params, prompt,
                                       opts=ModelOpts(moe_impl="gmm"))
        assert toks_dense == toks_gmm
        assert len(toks_gmm) == 6


class TestPerSlotTemperature:
    def test_greedy_slot_unaffected_by_hot_neighbour(self):
        """One temperature=1.0 request must not make a concurrent greedy
        request stochastic (serving/engine.py per-slot sampling)."""
        from repro.serving import Engine, Request
        cfg = get_config("olmo-1b").reduced().with_(
            num_layers=2, d_model=64, num_heads=2, num_kv_heads=2,
            head_dim=32, d_ff=128, vocab_size=128, vocab_pad_multiple=16,
            dtype="float32")
        params = models.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        p_greedy = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
        p_hot = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)

        solo = Engine(cfg, params, max_batch=1, max_len=64, prefill_pad=8)
        ref_toks = solo.serve([Request(uid=0, prompt=p_greedy,
                                       max_new_tokens=6)])[0].tokens
        eng = Engine(cfg, params, max_batch=2, max_len=64, prefill_pad=8)
        out = eng.serve([
            Request(uid=0, prompt=p_greedy, max_new_tokens=6, temperature=0.0),
            Request(uid=1, prompt=p_hot, max_new_tokens=6, temperature=1.0),
        ])
        assert out[0].tokens == ref_toks
