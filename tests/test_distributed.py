"""Distribution-layer tests.

Multi-device behaviour (shard_map MoE equivalence, elastic checkpoint
restore, dry-run plumbing) runs in subprocesses with
``xla_force_host_platform_device_count`` -- the main test process must keep
seeing 1 device (assignment requirement).
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    return r.stdout


# --------------------------------------------------------------------------- #
# sharding rules (single device; pure spec logic)
# --------------------------------------------------------------------------- #


class TestShardingRules:
    def test_param_specs_cover_all_archs(self):
        out = run_py("""
            import jax
            from repro.launch.mesh import make_test_mesh
            from jax.sharding import PartitionSpec as P
            from repro.configs import ASSIGNED, get_config
            from repro import models
            from repro.sharding import rules
            mesh = make_test_mesh((2, 4), ("data", "model"))
            for name in ASSIGNED:
                cfg = get_config(name)
                abs_p = models.abstract_params(cfg)
                specs = rules.param_specs(abs_p, cfg, mesh)
                n_sharded = 0
                for leaf, spec in zip(jax.tree.leaves(abs_p), jax.tree.leaves(
                        specs, is_leaf=lambda x: isinstance(x, P))):
                    entries = list(spec) + [None] * (leaf.ndim - len(spec))
                    for dim, e in zip(leaf.shape, entries):
                        if e == "model":
                            assert dim % 4 == 0, (name, leaf.shape, spec)
                            n_sharded += 1
                assert n_sharded > 0, name
                print(name, "ok", n_sharded)
        """, devices=8)
        assert out.count("ok") == 10

    def test_vocab_padding_divisible(self):
        from repro.configs import ASSIGNED, get_config
        for name in ASSIGNED:
            cfg = get_config(name)
            assert cfg.padded_vocab % 16 == 0, name


# --------------------------------------------------------------------------- #
# shard_map MoE equivalence
# --------------------------------------------------------------------------- #


class TestMoEImplEquivalence:
    def test_dense_vs_ep_a2a_vs_ep_psum(self):
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_test_mesh
            from repro.configs import get_config
            from repro import models
            from repro.models.moe import moe
            from repro.core import iter_moe_layer_params

            cfg = get_config("olmoe-1b-7b").reduced().with_(
                num_experts=8, moe_top_k=2, dtype="float32",
                moe_capacity_factor=8.0)   # dropless: exact equivalence
            params = models.init_params(jax.random.PRNGKey(0), cfg)
            _, mp = next(iter_moe_layer_params(params, cfg))
            mesh = make_test_mesh((2, 4), ("data", "model"))
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

            y0, a0 = moe(mp, cfg, x, 2, impl="dense")
            y1, a1 = jax.jit(lambda p, xx: moe(p, cfg, xx, 2, impl="ep_a2a",
                                               mesh=mesh))(mp, x)
            y2, a2 = jax.jit(lambda p, xx: moe(p, cfg, xx, 2, impl="ep_psum",
                                               mesh=mesh))(mp, x)
            np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                       rtol=2e-4, atol=2e-4)
            np.testing.assert_allclose(np.asarray(y0), np.asarray(y2),
                                       rtol=2e-4, atol=2e-4)
            # aux under EP is the pmean of per-shard stats (standard local
            # approximation of the load-balance loss) -- close, not equal
            assert abs(float(a1) - float(a0)) / float(a0) < 0.5, (a0, a1)
            print("EQUIV OK")
        """, devices=8)
        assert "EQUIV OK" in out

    def test_ep_a2a_grads_match_dense(self):
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_test_mesh
            from repro.configs import get_config
            from repro import models
            from repro.models.moe import moe
            from repro.core import iter_moe_layer_params

            cfg = get_config("mixtral-8x7b").reduced().with_(
                num_experts=4, moe_top_k=2, dtype="float32",
                moe_capacity_factor=4.0)
            params = models.init_params(jax.random.PRNGKey(0), cfg)
            _, mp = next(iter_moe_layer_params(params, cfg))
            mesh = make_test_mesh((2, 4), ("data", "model"))
            x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))

            def loss(p, impl, m=None):
                y, aux = moe(p, cfg, x, 2, impl=impl, mesh=m)
                return jnp.sum(y ** 2) + 0.01 * aux

            g0 = jax.grad(lambda p: loss(p, "dense"))(mp)
            g1 = jax.jit(jax.grad(lambda p: loss(p, "ep_a2a", mesh)))(mp)
            for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=5e-3, atol=5e-4)
            print("GRADS OK")
        """, devices=8)
        assert "GRADS OK" in out

    def test_lexi_per_layer_k_under_shard_map(self):
        """Per-layer static k runs through the EP path with distinct shapes."""
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_test_mesh
            from repro.configs import get_config
            from repro import models
            from repro.models.opts import ModelOpts

            cfg = get_config("qwen3-moe-235b-a22b").reduced().with_(
                num_experts=8, moe_top_k=4, dtype="float32",
                moe_impl="ep_a2a")
            n = cfg.num_moe_layers
            cfg = cfg.with_lexi_plan(tuple(1 + (i % 4) for i in range(n)))
            mesh = make_test_mesh((2, 4), ("data", "model"))
            params = models.init_params(jax.random.PRNGKey(0), cfg)
            batch = models.make_train_batch(cfg, jax.random.PRNGKey(1), 4, 32)
            loss, _ = jax.jit(lambda p, b: models.loss_fn(p, cfg, b,
                                                          mesh=mesh))(params, batch)
            assert np.isfinite(float(loss))
            print("LEXI EP OK", float(loss))
        """, devices=8)
        assert "LEXI EP OK" in out


class TestSeqShardDecode:
    def test_context_parallel_decode_exact(self):
        """Sequence-sharded KV decode (flash-decoding combine) == baseline,
        across two steps (cache written into the sharded layout)."""
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_test_mesh
            from repro.configs import get_config
            from repro import models
            from repro.models.opts import ModelOpts
            cfg = get_config('qwen3-32b').reduced().with_(
                dtype='float32', num_layers=2, num_kv_heads=2)
            params = models.init_params(jax.random.PRNGKey(0), cfg)
            mesh = make_test_mesh((2, 4), ('data', 'model'))
            B, plen, S = 4, 16, 32
            tokens = jax.random.randint(jax.random.PRNGKey(1), (B, plen), 0,
                                        cfg.vocab_size)
            caches = models.init_caches(cfg, B, S)
            logits, caches = models.prefill_fn(params, cfg,
                                               {'tokens': tokens}, caches)
            pos = jnp.full((B,), plen, jnp.int32)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            opts = ModelOpts(decode_kv_seq_shard=True)
            step = jax.jit(lambda p, t, po, c: models.decode_fn(
                p, cfg, t, po, c, mesh=mesh, opts=opts))
            l0, c0 = models.decode_fn(params, cfg, nxt, pos, caches)
            l1, c1 = step(params, nxt, pos, caches)
            np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                       rtol=1e-4, atol=1e-4)
            n2 = jnp.argmax(l0, -1).astype(jnp.int32)
            l0b, _ = models.decode_fn(params, cfg, n2, pos + 1, c0)
            l1b, _ = step(params, n2, pos + 1, c1)
            np.testing.assert_allclose(np.asarray(l0b), np.asarray(l1b),
                                       rtol=1e-4, atol=1e-4)
            print('SEQSHARD OK')
        """, devices=8)
        assert "SEQSHARD OK" in out


# --------------------------------------------------------------------------- #
# elastic checkpoint restore (mesh reshape)
# --------------------------------------------------------------------------- #


class TestElasticRestore:
    def test_restore_across_mesh_shapes(self, tmp_path):
        ck = str(tmp_path / "ck")
        out = run_py(f"""
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_test_mesh
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.checkpoint import CheckpointManager

            mesh_a = make_test_mesh((2, 4), ("data", "model"))
            w = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
            sharded = jax.device_put(w, NamedSharding(mesh_a, P("data", "model")))
            mgr = CheckpointManager({ck!r})
            mgr.save(7, {{"w": sharded}})

            mesh_b = make_test_mesh((4, 2), ("data", "model"))
            target_sh = {{"w": NamedSharding(mesh_b, P("model", "data"))}}
            restored, meta = mgr.restore({{"w": w}}, shardings=target_sh)
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.asarray(w))
            assert restored["w"].sharding.spec == P("model", "data")
            assert meta["step"] == 7
            print("ELASTIC OK")
        """, devices=8)
        assert "ELASTIC OK" in out

    def test_train_resume_across_device_counts(self, tmp_path):
        """Train on 4 fake devices, resume restore on 1 (elastic down-scale)."""
        ck = str(tmp_path / "ck2")
        run_py(f"""
            import jax
            from repro.configs import get_config
            from repro.data import DataConfig
            from repro.optim import AdamW
            from repro.training import train
            cfg = get_config("olmo-1b").reduced().with_(
                num_layers=2, d_model=64, num_heads=2, num_kv_heads=2,
                head_dim=32, d_ff=128, vocab_size=128, vocab_pad_multiple=16)
            dc = DataConfig(cfg.vocab_size, 32, 8)
            train(cfg, dc, total_steps=6, optimizer=AdamW(total_steps=6),
                  ckpt_dir={ck!r}, ckpt_every=5, ckpt_async=False)
            print("TRAINED", jax.device_count())
        """, devices=4)
        out = run_py(f"""
            import jax
            from repro.configs import get_config
            from repro.data import DataConfig
            from repro.optim import AdamW
            from repro.training import train
            cfg = get_config("olmo-1b").reduced().with_(
                num_layers=2, d_model=64, num_heads=2, num_kv_heads=2,
                head_dim=32, d_ff=128, vocab_size=128, vocab_pad_multiple=16)
            dc = DataConfig(cfg.vocab_size, 32, 8)
            res = train(cfg, dc, total_steps=10, optimizer=AdamW(total_steps=10),
                        ckpt_dir={ck!r}, ckpt_every=5, ckpt_async=False)
            assert res.resumed_from == 6, res.resumed_from
            print("RESUMED OK on", jax.device_count(), "device(s)")
        """, devices=1)
        assert "RESUMED OK" in out


# --------------------------------------------------------------------------- #
# dry-run plumbing at reduced device count
# --------------------------------------------------------------------------- #


class TestDryrunPlumbing:
    def test_hlo_parser_tuple_results_and_conventions(self):
        """XLA combiners emit tuple-shaped collectives; -done must not
        double-count; all-gather/reduce-scatter use operand-size convention."""
        from repro.analysis.hlo import collective_stats
        text = """
  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups={{0,1,2,3}}
  %art = (f32[256]{0}, bf16[512]{0}) all-reduce(%a, %b), replica_groups=[2,4]<=[8]
  %a2a = (f32[1,2,12,128]{3,2,1,0}, f32[1,2,12,128]{3,2,1,0}) all-to-all(%p, %q), dimensions={0}
  %ag = bf16[2,512,128]{2,1,0} all-gather(bf16[2,128,128]{2,1,0} %y), replica_groups=[2,4]<=[8], dimensions={1}
  %agd = f32[8]{0} all-gather-done(%st)
  %rs = f32[64]{0} reduce-scatter(f32[64]{0} %z), replica_groups={{0,1}}
"""
        s = collective_stats(text)
        assert s.bytes_by_kind["all-reduce"] == 1024 * 4 + 256 * 4 + 512 * 2
        assert s.bytes_by_kind["all-to-all"] == 2 * (1 * 2 * 12 * 128 * 4)
        assert s.bytes_by_kind["all-gather"] == (2 * 512 * 128 * 2) // 4
        assert s.bytes_by_kind["reduce-scatter"] == 64 * 4 * 2
        assert s.count_by_kind.get("all-gather") == 1

    def test_shard_map_a2a_visible_to_parser(self):
        """The EP dispatch all-to-all must appear in parsed collectives."""
        out = run_py("""
            import jax, jax.numpy as jnp
            from repro.launch.mesh import make_test_mesh
            from repro.configs import get_config
            from repro import models
            from repro.models.moe import moe
            from repro.core import iter_moe_layer_params
            from repro.analysis.hlo import collective_stats
            cfg = get_config("olmoe-1b-7b").reduced().with_(
                num_experts=8, moe_top_k=2, dtype="float32")
            params = models.init_params(jax.random.PRNGKey(0), cfg)
            _, mp = next(iter_moe_layer_params(params, cfg))
            mesh = make_test_mesh((2, 4), ("data", "model"))
            x = jax.ShapeDtypeStruct((16, 16, cfg.d_model), jnp.float32)
            c = jax.jit(lambda p, xx: moe(p, cfg, xx, 2, impl="ep_a2a",
                                          mesh=mesh)).lower(mp, x).compile()
            s = collective_stats(c.as_text())
            assert s.bytes_by_kind.get("all-to-all", 0) > 0, s.summary()
            print("A2A VISIBLE", s.bytes_by_kind["all-to-all"])
        """, devices=8)
        assert "A2A VISIBLE" in out

    def test_hlo_collective_parser(self):
        out = run_py("""
            import jax, jax.numpy as jnp
            from repro.launch.mesh import make_test_mesh
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.analysis.hlo import collective_stats
            mesh = make_test_mesh((8,), ("x",))
            def f(a):
                return jax.lax.with_sharding_constraint(
                    a.sum(0, keepdims=True), NamedSharding(mesh, P()))
            a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
            c = jax.jit(f, in_shardings=NamedSharding(mesh, P("x", None))).lower(a).compile()
            stats = collective_stats(c.as_text())
            print("kinds:", sorted(stats.bytes_by_kind), "total:",
                  stats.total_bytes)
            assert stats.total_bytes > 0
        """, devices=8)
        assert "total:" in out

    def test_input_specs_shapes(self):
        from repro.launch.dryrun import input_specs  # safe: sets flags only on run
        from repro.configs import get_config
        from repro.configs.shapes import SHAPE_BY_NAME
        cfg = get_config("pixtral-12b")
        s = input_specs(cfg, SHAPE_BY_NAME["train_4k"])
        assert s["batch"]["tokens"].shape == (256, 4096 - 1024)
        assert s["batch"]["prefix_embeds"].shape == (256, 1024, 5120)
        d = input_specs(cfg, SHAPE_BY_NAME["decode_32k"])
        assert d["tokens"].shape == (128,)

    def test_whisper_input_specs(self):
        from repro.launch.dryrun import input_specs
        from repro.configs import get_config
        from repro.configs.shapes import SHAPE_BY_NAME
        cfg = get_config("whisper-base")
        s = input_specs(cfg, SHAPE_BY_NAME["train_4k"])
        assert s["batch"]["frames"].shape == (256, 1500, 512)
