#!/usr/bin/env python3
"""Compile the Pallas merge-candidates kernel for a described TPU v5e at
the merge bound and Psi width of each configuration, with no chip
attached, and print its compile-time memory. Shapes only: nothing is
allocated at full size.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_v5e.py
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))

# (configuration, K = pow2(clients), D = |theta|)
SHAPES = [("stocfl-mnist-mlp", 256, 1_628_170),
          ("stocfl-cifar-cnn", 1024, 545_098)]


def main() -> int:
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.kernels.cosine_sim import merge_candidates

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    for name, k, d in SHAPES:
        x = jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=one)
        live = jax.ShapeDtypeStruct((k,), jnp.bool_, sharding=one)
        fn = jax.jit(functools.partial(merge_candidates, tau=0.5))
        try:
            c = fn.lower(x, live).compile()
            ma = c.memory_analysis()
            out = {"config": name, "k": k, "d": d, "compiles": True,
                   "argument_bytes": ma.argument_size_in_bytes,
                   "temp_bytes": ma.temp_size_in_bytes,
                   "output_bytes": ma.output_size_in_bytes,
                   "custom_calls": c.as_text().count("tpu_custom_call")}
        except Exception as e:  # the compiler's refusal is the finding
            out = {"config": name, "k": k, "d": d, "compiles": False,
                   "error": str(e)[:300]}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
