"""Sharding rules + a true (miniature) multi-device dry-run.

Runs in a SUBPROCESS with --xla_force_host_platform_device_count=8 so
the main pytest process keeps its single real device.  Validates that
every param PartitionSpec divides its dims and that lower+compile works
on a (2,4) data×model mesh for a smoke arch per family — the same path
the production 16×16 dry-run exercises.
"""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax
import numpy as np
from jax.sharding import NamedSharding
from repro.configs import get_config
from repro.models import Model
from repro.models.model import set_activation_sharding
from repro.launch.sharding import param_shardings, batch_shardings
from repro.training.optim import OptimizerConfig, adamw_init
from repro.training.train_loop import make_train_step

out = {}
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
for arch in sys.argv[1:]:
    cfg = get_config(arch + "-smoke")
    model = Model(cfg)
    set_activation_sharding(mesh, ("data",))
    pshape = model.abstract_params()
    pshard = param_shardings(pshape, mesh, ("data",))
    # every spec must divide
    def check(path, leaf, shard):
        spec = shard.spec
        for dim, ax in zip(leaf.shape, spec):
            if ax is None: continue
            axes = (ax,) if isinstance(ax, str) else ax
            size = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % size == 0, (arch, path, leaf.shape, spec)
    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), pshape, pshard)
    B, S = 4, 32
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jax.numpy.int32),
             "targets": jax.ShapeDtypeStruct((B, S), jax.numpy.int32)}
    if cfg.is_encdec:
        batch["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.enc_seq, cfg.enc_d_model), jax.numpy.bfloat16)
    if cfg.n_patches:
        batch["patch_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.n_patches, 1152), jax.numpy.bfloat16)
    opt_cfg = OptimizerConfig()
    oshape = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), pshape)
    with mesh:
        bshard = batch_shardings(batch, mesh, ("data",))
        step = make_train_step(model, opt_cfg, remat=True)
        lowered = jax.jit(step, in_shardings=(pshard, None, bshard)) \
            .lower(pshape, oshape, batch)
        compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):      # older jax: list of dicts
        ca = ca[0] if ca else {}
    out[arch] = {"ok": True, "flops": float((ca or {}).get("flops", 0))}
print(json.dumps(out))
"""


@pytest.mark.slow
def test_mini_mesh_dryrun_per_family():
    archs = ["qwen3_4b", "granite_moe_3b_a800m", "xlstm_350m",
             "recurrentgemma_9b", "whisper_medium", "paligemma_3b"]
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", SCRIPT] + archs,
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    for a in archs:
        assert out[a]["ok"], a
