"""Scratch script, no chip needed: ask the TPU compiler (installed in the
sandbox) whether a training cell's step compiles for a described v5e, and
what it takes on each device. ``JAX_PLATFORMS=cpu python3 -m
benchmark.compile_for_chip <config name> [batch ...]``. Nothing runs, so
nothing here is a device result; the batch it settles goes into the
configuration's file by hand.
"""
from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from benchmark import harness
    from benchmark.drivers import train

    name, batches = argv[0], [int(b) for b in argv[1:]] or [1]
    config = harness.load_json(os.path.join(harness.HERE, "configs",
                                            name + ".json"))
    seq = 4096
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    # SDPA asks jax.default_backend() which tier to take: steer it to the
    # flash kernel, as on the chip (the on-chip guide's rehearsal 3)
    jax.default_backend = lambda: "tpu"
    from paddle_tpu.framework.functional import FunctionalModule
    model = train.build_model(config, config["trainer"]["dtype"])
    model.train()
    fm = FunctionalModule(model, training=True)
    degrees = config["trainer"].get("mesh")
    specs = None
    if degrees:
        # the hybrid cell: the program's own mesh over the described chips
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.distributed import mesh as mesh_mod
        from paddle_tpu.models import LlamaForCausalLM
        mesh = mesh_mod.init_mesh(dict(degrees, dp=-1), devices=topo.devices)
        specs = fm.param_specs(LlamaForCausalLM.sharding_rules(),
                               fsdp_axis="sharding",
                               fsdp_size=degrees["sharding"])
        shardings = [NamedSharding(mesh, s) for s in specs]
        data = NamedSharding(mesh, P(("dp", "sharding"), "sep"))
        rep = NamedSharding(mesh, P())
        print(f"mesh {dict(mesh.shape)}")
    else:
        shardings = [one] * len(fm.params)
        data = rep = one
    step = train.make_train_step(fm, config["trainer"]["optimizer"],
                                 specs=specs)
    state = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
             for a, sh in zip(fm.param_arrays(), shardings)]
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=rep)
    for b in batches:
        ids = jax.ShapeDtypeStruct((b, seq), jnp.int32, sharding=data)
        t0 = time.perf_counter()
        try:
            compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
                state, state, state, key, ids, ids).compile()
        except Exception as e:            # the compiler's refusal IS the answer
            print(f"batch {b}: refused: {str(e)[:400]}")
            continue
        ma = compiled.memory_analysis()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        text = compiled.as_text()
        colls = {k: text.count(f" {k}(") + text.count(f" {k}-start(")
                 for k in ("all-gather", "all-reduce", "reduce-scatter",
                           "collective-permute", "all-to-all")}
        print(f"collectives in the program: {colls}")
        print(f"batch {b} x {seq}: compiled in {time.perf_counter() - t0:.0f}"
              f" s; arguments {ma.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {ma.temp_size_in_bytes / 1e9:.2f} GB, total "
              f"{total / 1e9:.2f} GB; tpu_custom_call x "
              f"{text.count('tpu_custom_call')}")


if __name__ == "__main__":
    main(sys.argv[1:])
