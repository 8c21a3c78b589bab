"""Partition-rule unit tests (pure: no devices needed) + multi-device
sharded execution in a subprocess with forced host devices."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.models import sharding as shd

MESH = {"data": 4, "model": 4}


def S(path, shape, mode="fsdp_tp", stacked=False):
    return shd.spec_for(path, shape, mode, MESH, stacked=stacked)


def test_embed_rule():
    # §Perf A1: vocab over model, d replicated (no contracted-dim sharding
    # -> no full-logits all-reduce).
    assert S("embed/w", (256, 64)) == P("model")


def test_head_rule():
    assert S("lm_head/w", (64, 256)) == P(None, "model")


def test_fsdp_pure_mode():
    # §Perf A5: ZeRO-3 over combined axes, no TP.
    assert S("prefix/0/attn/wq/w", (64, 128), mode="fsdp_pure") == \
        P(("data", "model"))
    assert S("embed/w", (256, 64), mode="fsdp_pure") == P(("data", "model"))
    assert S("prefix/0/ffn/down/w", (128, 64), mode="fsdp_pure") == \
        P(None, ("data", "model"))


def test_attention_rules():
    assert S("prefix/0/attn/wq/w", (64, 128)) == P("data", "model")
    assert S("prefix/0/attn/wo/w", (128, 64)) == P("model", "data")


def test_stacked_shift():
    # Scan-stacked params get a leading unsharded layer dim.
    assert S("blocks/0/attn/wq/w", (8, 64, 128), stacked=True) == \
        P(None, "data", "model")
    assert S("blocks/0/ffn/moe/experts/w_up", (8, 16, 64, 128),
             stacked=True) == P(None, "model", "data")


def test_tp_mode_drops_fsdp():
    assert S("prefix/0/attn/wq/w", (64, 128), mode="tp") == P(None, "model")


def test_indivisible_dim_replicates():
    # vocab 122753 (minicpm) not divisible by 4 -> replicate that dim.
    assert S("embed/w", (122753, 64)) == P()
    assert S("prefix/0/attn/wq/w", (63, 128)) == P(None, "model")


def test_scalars_replicate():
    assert S("blocks/0/attn/wq/s_w", ()) == P()
    assert S("blocks/0/ln1/scale", (64,)) == P()


def test_moe_expert_rules():
    assert S("ffn/moe/experts/w_up", (16, 64, 128)) == \
        P("model", "data")
    assert S("ffn/moe/experts/w_down", (16, 128, 64)) == \
        P("model", None, "data")
    assert S("ffn/moe/router/w", (64, 16)) == P()


def test_codes_inherit_via_param_specs():
    """int8 w_codes get the float weight's spec (suffix stripped)."""
    struct = {"blocks": ({"attn": {"wq": {
        "w_codes": jax.ShapeDtypeStruct((8, 64, 128), jax.numpy.int8),
        "w_scale": jax.ShapeDtypeStruct((8,), jax.numpy.float32),
    }}},)}

    class FakeMesh:
        axis_names = ("data", "model")

        class devices:
            shape = (4, 4)

    specs = shd.param_specs(struct, "fsdp_tp", FakeMesh)
    assert specs["blocks"][0]["attn"]["wq"]["w_codes"] == \
        P(None, "data", "model")
    assert specs["blocks"][0]["attn"]["wq"]["w_scale"] == P()


_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_arch
    from repro.core.quant import QuantConfig
    from repro.data import synthetic
    from repro.launch import mesh as mesh_mod
    from repro.models import transformer as T
    from repro.optim import adam, schedules
    from repro.train import trainer, elastic

    arch = get_arch("minitron-4b")
    cfg = arch.smoke
    mesh = mesh_mod.make_mesh((2, 2, 2), ("pod", "data", "model"))
    opt = adam.make(schedules.constant(1e-3))
    step, (ps, os_, bs) = trainer.jit_train_step(
        cfg, arch.qcfg, opt, trainer.TrainConfig(), mesh, arch.mode)
    params = T.make_params(jax.random.key(0), cfg)
    opt_state = opt.init(params)
    batch = synthetic.lm_batch(jax.random.key(1), batch=8, seq_len=16,
                               vocab=cfg.vocab)
    from repro.models import sharding as shd
    with mesh, shd.use_mesh(mesh, ("pod", "data")):
        params = elastic.reshard_with_specs(params, mesh, ps)
        opt_state = elastic.reshard_with_specs(opt_state, mesh, os_)
        p2, o2, m = step(params, opt_state, batch, jnp.int32(0))
        l1 = float(m["loss"])
    # single-device reference for the same step
    p_ref = T.make_params(jax.random.key(0), cfg)
    s_ref = opt.init(p_ref)
    step1 = jax.jit(trainer.make_train_step(cfg, arch.qcfg, opt,
                                            trainer.TrainConfig()))
    _, _, m_ref = step1(p_ref, s_ref, batch, jnp.int32(0))
    l_ref = float(m_ref["loss"])
    assert abs(l1 - l_ref) < 1e-3, (l1, l_ref)

    # elastic resize: 8 -> 4 devices, re-shard restored params
    mesh2 = mesh_mod.make_mesh((2, 2), ("data", "model"))
    host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), p2)
    re = elastic.reshard(host, mesh2, arch.mode)
    assert elastic.check_batch(8, mesh2)
    print("SUBPROCESS_OK", l1, l_ref)
""")


def test_sharded_train_step_subprocess():
    """2x2x2 multi-pod mesh: sharded train step == single-device step; then
    an elastic 8->4 device resize re-shards the state."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr
