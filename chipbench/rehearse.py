"""Compile every entry a cell warms, for a described v5e, on a host with
no chip, and print what the TPU compiler says of it:

    JAX_PLATFORMS=cpu python chipbench/rehearse.py [cell ...]

For each (network, bucket, device group) the served jit entry is lowered
with the benchmark's weight shapes and compiled for ``v5e:2x2``; the line
gives the compile seconds, the Pallas kernels in the program
(``tpu_custom_call``) and ``memory_analysis()``.  Nothing runs, so it
says nothing about times or results.  Exit code 1 where an entry does
not compile.
"""
from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def rehearse(cell_name: str) -> bool:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from chipbench import harness, weights
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro.serving.vision import ModelRegistry, create_engine
    from repro.vision import zoo

    cell = harness.load_cell(cell_name)
    config, mix, chips = cell["config"], cell["mix"], cell["chips"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devs = list(topo.devices[:chips])
    registry = ModelRegistry(backend=config["backend"],
                             mesh=Mesh(devs, ("data",)) if chips > 1
                             else None)
    shapes = jax.eval_shape(lambda: weights.make(config, 0))
    for n, p in zip(config["networks"], shapes):
        net = zoo.ZOO[n["zoo"]](num_classes=n["classes"],
                                width_mult=n["width"],
                                resolution=n["resolution"])
        registry.register(net, n["variant"], key=n["key"], params=p)
    groups = [None]
    if chips > 1:
        engine = create_engine(registry, "pipelined",
                               buckets=tuple(mix["buckets"]))
        groups = [tuple(devs)] + engine._reachable_groups(
            len(config["networks"]))
    ok = True
    for key in registry.keys():
        m = registry.get(key)
        for grp in groups:
            for b in mix["buckets"]:
                if grp is None:
                    fn = registry.apply_fn(key, b)
                    shard = None
                    put = jax.sharding.SingleDeviceSharding(devs[0])
                    pshard = put
                else:
                    gm = Mesh(list(grp), ("data",))
                    shard = len(grp) > 1 and b % len(grp) == 0
                    fn = registry._build_apply(m, gm, shard)
                    put = NamedSharding(gm, P("data") if shard else P())
                    pshard = NamedSharding(gm, P())
                x = jax.ShapeDtypeStruct(
                    (b, m.resolution, m.resolution, m.net.in_channels),
                    jnp.float32, sharding=put)
                ps = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=pshard), m.params)
                where = "1 chip" if grp is None else \
                    f"group {[d.id for d in grp]} sharded={shard}"
                t = time.perf_counter()
                try:
                    c = fn.lower(ps, x).compile()
                except Exception as e:     # report every refusal, go on
                    ok = False
                    print(f"REFUSED {key} bucket={b} {where}: "
                          f"{str(e).splitlines()[0][:300]}", flush=True)
                    continue
                mem = c.memory_analysis()
                n_k = c.as_text().count('custom_call_target="tpu_custom_call"')
                print(f"ok {key} bucket={b} {where} "
                      f"seconds={time.perf_counter() - t:.1f} "
                      f"tpu_custom_call={n_k} "
                      f"temp_bytes={getattr(mem, 'temp_size_in_bytes', None)} "
                      f"argument_bytes="
                      f"{getattr(mem, 'argument_size_in_bytes', None)} "
                      f"output_bytes={getattr(mem, 'output_size_in_bytes', None)}",
                      flush=True)
    return ok


def main(argv) -> int:
    from chipbench import harness
    import json
    names = argv or [w["name"] for w in json.load(open(os.path.join(
        harness.ROOT, "BENCHMARK.json")))["workloads"]]
    ok = True
    for name in names:
        print(f"== {name}", flush=True)
        ok = rehearse(name) and ok
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
