"""Distribution layer: sharding rules + real multi-device execution.

Multi-device tests run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the main pytest
process keeps its single CPU device (the dry-run is the only place that
sets 512).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, smoke_config
from repro.distributed.sharding import act_rules, param_rules
from repro.models.layers import logical_to_pspec
from repro.zoo import get_api

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


class TestShardingRules:
    def test_param_specs_resolve(self):
        """Every arch's full-size ParamSpec tree resolves to valid specs."""
        class FakeMesh:
            axis_names = ("data", "model")

        rules = param_rules(FakeMesh())
        for arch, cfg in ARCHS.items():
            specs = get_api(cfg)
            tree = specs.param_specs(cfg)
            leaves = jax.tree.leaves(
                tree, is_leaf=lambda x: hasattr(x, "axes"))
            for s in leaves:
                spec = logical_to_pspec(s.axes, rules)
                assert isinstance(spec, P)
                assert len(spec) == len(s.shape)

    def test_fsdp_shards_weights_over_data(self):
        class FakeMesh:
            axis_names = ("pod", "data", "model")

        r = param_rules(FakeMesh())
        assert r["embed"] == ("pod", "data")
        assert r["mlp"] == "model"

    def test_act_rules_batch(self):
        class M1:
            axis_names = ("data", "model")

        class M2:
            axis_names = ("pod", "data", "model")

        assert act_rules(M1())["batch"] == "data"
        assert act_rules(M2())["batch"] == ("pod", "data")


@pytest.mark.slow
def test_train_step_executes_on_8_devices():
    """Actually run (not just lower) a sharded train step on a 4x2 mesh."""
    res = _run_subprocess("""
        import json
        import jax, jax.numpy as jnp
        from repro.configs import ARCHS, smoke_config
        from repro.distributed.sharding import act_rules, state_shardings
        from repro.models.layers import init_params, mesh_context
        from repro.train.train_step import TrainHParams, init_train_state, make_train_step
        from repro.zoo import get_api
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        cfg = smoke_config(ARCHS["qwen2.5-3b"])
        api = get_api(cfg)
        hp = TrainHParams(total_steps=4, warmup=1, microbatches=2)
        step = make_train_step(api, cfg, hp)
        rules = act_rules(mesh)

        def fn(state, batch):
            with mesh_context(mesh, rules):
                return step(state, batch)

        specs = api.param_specs(cfg)
        p_shard = state_shardings(specs, mesh)
        state_shard = {"params": p_shard, "opt": {"m": p_shard, "v": p_shard,
                       "count": NamedSharding(mesh, P())}}
        params = init_params(specs, jax.random.PRNGKey(0))
        state = init_train_state(params, hp)
        state = jax.device_put(state, state_shard)
        t = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab)
        batch = {"tokens": t[:, :-1], "targets": t[:, 1:],
                 "loss_mask": jnp.ones((8, 32), jnp.float32)}
        bshard = jax.tree.map(
            lambda x: NamedSharding(mesh, P("data", *([None]*(x.ndim-1)))), batch)
        batch = jax.device_put(batch, bshard)
        jitted = jax.jit(fn, in_shardings=(state_shard, bshard),
                         out_shardings=(state_shard, None), donate_argnums=0)
        state, metrics = jitted(state, batch)
        state, metrics = jitted(state, batch)
        print(json.dumps({"loss": float(metrics["loss"]),
                          "devices": len(jax.devices())}))
    """)
    assert res["devices"] == 8
    assert res["loss"] == res["loss"]  # finite


@pytest.mark.slow
def test_elastic_checkpoint_across_mesh_sizes(tmp_path):
    """Save params on a (4,2) mesh, restore onto (2,2): elastic scaling."""
    res = _run_subprocess(f"""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import ARCHS, smoke_config
        from repro.distributed.sharding import state_shardings
        from repro.models.layers import init_params
        from repro.train.checkpoint import restore, save
        from repro.zoo import get_api

        cfg = smoke_config(ARCHS["starcoder2-3b"])
        api = get_api(cfg)
        specs = api.param_specs(cfg)
        big = jax.make_mesh((4, 2), ("data", "model"))
        params = jax.device_put(
            init_params(specs, jax.random.PRNGKey(0)),
            state_shardings(specs, big))
        save({str(tmp_path)!r}, 1, params)

        from jax.sharding import Mesh
        small = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                     ("data", "model"))
        like = jax.eval_shape(lambda: params)
        back = restore({str(tmp_path)!r}, 1, like,
                       shardings=state_shardings(specs, small))
        ok = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)))
        print(json.dumps({{"ok": ok}}))
    """)
    assert res["ok"]


@pytest.mark.slow
def test_crosspod_compressed_psum():
    """shard_map int8 psum over a 'pod' axis reproduces the mean gradient."""
    res = _run_subprocess("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.optim.grad_compress import ef_compress_grads, make_crosspod_psum

        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        crosspod = make_crosspod_psum(mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P

        g_global = jnp.asarray(
            np.random.default_rng(0).normal(0, 1, (2, 64)), jnp.float32)

        def per_pod(g):
            q, s, e = ef_compress_grads({"g": g[0]}, {"g": jnp.zeros_like(g[0])})
            out = crosspod(q, s)
            return out["g"][None]

        f = jax.shard_map(per_pod, mesh=mesh,
                          in_specs=P("pod", None), out_specs=P("pod", None))
        got = f(g_global)
        want = jnp.mean(g_global, axis=0)
        err = float(jnp.max(jnp.abs(got[0] - want)))
        scale = float(jnp.max(jnp.abs(want)))
        print(json.dumps({"rel": err / scale}))
    """)
    assert res["rel"] < 0.02
