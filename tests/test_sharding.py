"""Sharding rules: resolver semantics on CPU, plus a subprocess 8-device
mini dry-run (lower + compile reduced configs on a (2,4) mesh) -- the
in-process test suite must keep seeing exactly 1 device."""
import json
import os
import subprocess
import sys
import textwrap

import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.runtime.sharding import make_rules, resolve_pspec


class FakeMesh:
    """Axis-name/shape stand-in so resolver tests are mesh-size-accurate."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)
        self.size = 1
        for v in shape.values():
            self.size *= v


M16 = FakeMesh({"data": 16, "model": 16})


def test_divisible_dims_shard():
    spec = resolve_pspec(("vocab", "embed"), (32000, 4096),
                         make_rules(get_config("yi-6b"), M16), M16)
    assert spec == P("model")                  # embed unsharded (tp mode)


def test_non_divisible_falls_back_to_replication():
    cfg = get_config("yi-6b")                  # kv=4 < 16
    spec = resolve_pspec(("embed", "kv", None), (4096, 4, 128),
                         make_rules(cfg, M16), M16)
    assert spec == P()                         # kv dropped, trailing None cut


def test_axis_used_once_per_tensor():
    cfg = get_config("deepseek-v3-671b")
    rules = make_rules(cfg, M16, SHAPES["decode_32k"])
    # cache tensor: kv_seq gets "model" first; kv cannot reuse it
    spec = resolve_pspec(("layers", "batch", "kv_seq", "kv", None),
                         (61, 128, 32768, 128, 128), rules, M16)
    assert spec == P(None, "data", "model")
    # weight tensor in the same program still shards heads on "model"
    wspec = resolve_pspec(("embed", "heads", "head_dim"), (7168, 128, 128),
                          rules, M16)
    assert "model" in str(wspec)


def test_long_context_tiny_batch_gets_all_axes():
    cfg = get_config("mamba2-370m")
    rules = make_rules(cfg, M16, SHAPES["long_500k"])
    assert rules["batch"] == ()                # B=1 cannot shard
    spec = resolve_pspec(("layers", "batch", "kv_seq", "kv", None),
                         (48, 1, 524288, 8, 64), rules, M16)
    assert spec == P(None, None, ("data", "model"))


def test_fsdp_vs_tp_param_rules():
    fs = make_rules(get_config("mixtral-8x7b"), M16)   # fsdp
    tp = make_rules(get_config("yi-6b"), M16)          # tp
    assert fs["embed"] == "data" and tp["embed"] is None


@pytest.mark.slow
def test_subprocess_8dev_mini_dryrun():
    """Reduced configs lower+compile on a real 8-device (2,4) host mesh."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, json
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import reduced_config, ShapeConfig
        from repro.models import transformer as tf
        from repro.models.layers import spec_tree_to_sds
        from repro.runtime import sharding as shd
        from repro.runtime.optim import opt_state_specs
        from repro.runtime.steps import input_specs, step_fn_for
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        out = {}
        for arch in ["yi-6b", "mixtral-8x7b", "mamba2-370m", "hymba-1.5b"]:
            cfg = reduced_config(arch).replace(train_microbatches=2)
            shape = ShapeConfig("t", "train", 32, 8)
            rules = shd.make_rules(cfg, mesh, shape)
            ps = tf.param_specs(cfg)
            os_ = opt_state_specs(cfg, ps)
            bs = input_specs(cfg, shape)
            fn, don = step_fn_for(cfg, shape, shard_ctx=(mesh, rules))
            jf = jax.jit(fn,
                in_shardings=(shd.spec_shardings(ps, mesh, rules),
                              shd.spec_shardings(os_, mesh, rules),
                              shd.spec_shardings(bs, mesh, rules),
                              NamedSharding(mesh, P())),
                donate_argnums=don)
            with mesh:
                c = jf.lower(spec_tree_to_sds(ps), spec_tree_to_sds(os_),
                             spec_tree_to_sds(bs),
                             jax.ShapeDtypeStruct((), jax.numpy.int32)).compile()
            out[arch] = bool(c.cost_analysis())
        print("RESULT:" + json.dumps(out))
    """)
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath(
                   os.path.join(os.path.dirname(__file__), "..", "src")))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT:")][0]
    result = json.loads(line[len("RESULT:"):])
    assert all(result.values()), result
