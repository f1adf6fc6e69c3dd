"""Compile a cell's decode step and its widest chunk step for a described
TPU v5e (no chip attached) at the cell's geometry, and print what the
compiler says they hold in device memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py <workload>

Nothing runs: the TPU compiler refuses what the chip would refuse (VMEM,
tiling, a program that does not fit) and `memory_analysis` gives the
bytes of arguments, outputs and temporaries of each program.
"""

from __future__ import annotations

import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(workload: str) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    from repro.core.chips import get_chip
    from repro.kernels import ops
    from repro.models.registry import get_model

    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    spec = harness.cell_spec(workload)
    cell = harness.Cell(spec, 0)
    chip = get_chip("tpu_v5e")
    cell.install_tuner(chip)
    ops.force_mode("pallas")
    ops.force_chip(chip.name)
    cfg = cell.model_config()
    model = get_model(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.key(0), cfg)))
    B, W = cell.geo["max_batch"], 2 * cell.geo["max_batch"]
    C, L = cell.geo["chunk_tokens"], cell.geo["max_len"]

    def state(n):
        return on_chip(jax.eval_shape(lambda: model.init_state(cfg, n, L)))

    def nbytes(tree):
        return sum(int(x.size) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    out = {"workload": workload, "params_bytes": nbytes(params),
           "decode_state_bytes": nbytes(state(B)),
           "lane_state_bytes": nbytes(state(W))}
    progs = {
        "decode": (jax.jit(lambda p, t, s: model.decode_step(p, t, s, cfg),
                           donate_argnums=(2,)),
                   (params, jax.ShapeDtypeStruct((B,), jnp.int32,
                                                 sharding=one), state(B))),
        f"chunk_{W}x{C}": (
            jax.jit(lambda p, t, ln, s: model.prefill_chunk(p, t, ln, s,
                                                            cfg),
                    donate_argnums=(3,)),
            (params, jax.ShapeDtypeStruct((W, C), jnp.int32, sharding=one),
             jax.ShapeDtypeStruct((W,), jnp.int32, sharding=one),
             state(W))),
    }
    for name, (fn, args) in progs.items():
        mem = fn.lower(*args).compile().memory_analysis()
        out[name] = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes)}
        print(name, out[name], flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
