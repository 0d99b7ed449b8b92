import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import re, jax
from collections import Counter
from repro.launch.dryrun import build_lowered
import repro.launch.mesh as meshmod

shape = sys.argv[2] if len(sys.argv)>2 else "train_4k"
# monkeypatch mesh for probe
if len(sys.argv)>3 and sys.argv[3] == "small":
    meshmod.make_production_mesh = lambda multi_pod=False: jax.make_mesh((4,4), ("data","model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
lowered, skip, cfg = build_lowered(sys.argv[1], shape, False)
compiled = lowered.compile()
mem = compiled.memory_analysis()
print("arg GiB", mem.argument_size_in_bytes/2**30, "temp GiB", mem.temp_size_in_bytes/2**30)
txt = compiled.as_text()
sizes = Counter()
for m in re.finditer(r"= ([a-z0-9]+)\[([0-9,]+)\]", txt):
    dt, dims = m.groups()
    b = {"bf16":2,"f16":2,"f32":4,"s32":4,"pred":1,"u32":4,"s8":1,"f64":8,"s64":8,"u8":1}.get(dt)
    if not b: continue
    n = 1
    for d in dims.split(","): n *= int(d)
    sizes[f"{dt}[{dims}]"] = max(sizes[f"{dt}[{dims}]"], n*b)
for k, v in sorted(sizes.items(), key=lambda kv:-kv[1])[:12]:
    print(f"{v/2**30:8.2f} GiB  {k}")
