"""Compile the latent-attention expert cell's programs at their real size for
a described v5e chip, without the chip, and print each program's memory
analysis.

    JAX_PLATFORMS=cpu python bench/compile_rehearsal_mla_moe.py [--workload NAME]

As ``compile_rehearsal.py`` does for the dense cells: the scheduler's decode
step over every slot at the cache width, the B=1 prefill at each prompt
length of the mix and the slot install, with the Pallas kernels compiled
for the chip (not interpreted).  Nothing runs; a compile that passes is not
a chip run.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="moonlight-w2-decode")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import traffic
    from bench.drivers.serve_mla_moe import packed_params, program_config
    from bench.run import cell_spec
    from repro.kernels import ops
    from repro.launch.scheduler import compile_sched_steps

    # the kernels' CPU branch would compile the Pallas interpreter
    ops._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    spec = cell_spec(args.workload)
    cfg, mix = spec["config"], spec["mix"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    params = on_chip(jax.eval_shape(lambda: packed_params(0, cfg)))
    steps = compile_sched_steps(program_config(cfg),
                                max_seq=traffic.width(mix),
                                kernel_backend=cfg["quant"]["kernel_backend"])
    slots, S = mix["slots"], traffic.width(mix)
    cache = on_chip(jax.eval_shape(lambda: steps.model.init_cache(slots, S)))
    cache1 = on_chip(jax.eval_shape(lambda: steps.model.init_cache(1, S)))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
    act = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=chip)
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    progs = {f"decode {slots} slots x {S}":
             lambda: steps.decode.lower(params, cache, vec, vec, act)}
    for n in mix["prompt_lens"]:
        tok = {"tokens": jax.ShapeDtypeStruct((1, n), jnp.int32,
                                              sharding=chip)}
        progs[f"prefill 1 x {n}"] = (
            lambda tok=tok: steps.prefill.lower(params, tok, cache1))
    progs["slot install"] = lambda: steps.write_slot.lower(cache, cache1,
                                                           slot)
    for name, lower in progs.items():
        t0 = time.time()
        lowered = lower()
        mem = lowered.compile().memory_analysis()
        kernels = lowered.as_text().count("tpu_custom_call")
        print(f"{name}: compiled in {time.time() - t0:.1f}s; "
              f"{kernels} Mosaic kernel call sites; "
              f"arguments {mem.argument_size_in_bytes / 2**30:.3f} GiB, "
              f"outputs {mem.output_size_in_bytes / 2**30:.3f} GiB, "
              f"aliased {mem.alias_size_in_bytes / 2**30:.3f} GiB, "
              f"temporaries {mem.temp_size_in_bytes / 2**30:.3f} GiB",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
