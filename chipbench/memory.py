"""Where a one-chip cell's device memory goes, read on the chip:

    python chipbench/memory.py <cell> [cell ...]

For each (network, bucket) entry the cell warms: the served jit entry is
compiled on the chip with the benchmark's weights, its
``memory_analysis()`` printed, and then it runs once on zeros, with the
device's ``memory_stats()`` printed before and after.  This is how the
compiler's figure for the program's temporaries is set beside the
``peak_bytes_in_use`` that a run reports.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def probe(cell_name: str) -> None:
    import jax
    import jax.numpy as jnp
    from chipbench import harness, weights
    cell = harness.load_cell(cell_name)
    if cell["chips"] != 1:
        raise harness.BenchError(f"{cell_name}: one-chip cells only")
    config, mix = cell["config"], cell["mix"]
    dev = jax.devices()[0]
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    harness.enable_cache()
    params = weights.make(config, 0, dev)
    system = harness.build_system(config, mix, params, 1)

    def stats() -> dict:
        return dict(dev.memory_stats() or {})

    print(json.dumps({"cell": cell_name, "stage": "weights",
                      "memory_stats": stats()}), flush=True)
    for key in system.keys:
        m = system.registry.get(key)
        for b in mix["buckets"]:
            fn = system.registry.apply_fn(key, b)
            x = jnp.zeros((b, m.resolution, m.resolution, m.net.in_channels),
                          jnp.float32)
            c = fn.lower(m.params, x).compile()
            mem = c.memory_analysis()
            before = stats()
            jax.block_until_ready(c(m.params, x))
            print(json.dumps({
                "cell": cell_name, "entry": key, "bucket": b,
                "memory_analysis": {k: getattr(mem, k) for k in dir(mem)
                                    if k.endswith("_in_bytes")},
                "before": before, "after": stats()}), flush=True)
    system.engine.close()


def main(argv) -> int:
    from chipbench import harness
    import jax
    if jax.devices()[0].platform != "tpu":
        print("memory: no TPU", file=sys.stderr)
        return 3
    try:
        for name in argv:
            probe(name)
    except harness.BenchError as e:
        print(f"memory: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
