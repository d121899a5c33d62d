#!/usr/bin/env python3
"""The third rehearsal: compile a training cell's step at its real
sizes for a v5e that is described and not attached, and print what the
chip's compiler says of its memory.  No chip time, no result: a compile
that passes is not a run.

    JAX_PLATFORMS=cpu python perf/rehearse_compile.py --workload <cell> \
        [--layers N] [--sequences B]

Only `train_executor` cells are built here (the LM step is the one
whose depth and batch are sized against the chip's 16 GB).  The step is
the program's own function (`program_to_fn`) under `jax.jit` with its
states donated, compiled for one described chip; the lowering is told
it targets a TPU, so the flash kernel is in it as it is on the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)
sys.path.insert(0, ROOT)
sys.path.insert(0, PERF_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int)
    ap.add_argument("--sequences", type=int)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import common
    import train_lib

    jax.config.update("jax_enable_compilation_cache", False)
    bench = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = common.Cell(bench, args.workload, seed=0, seconds=0, trace=False,
                       rehearse=False, t_process_start=0.0)
    if cell.traffic["job"] != "train_executor":
        raise SystemExit("only train_executor cells are compiled here")
    m, t = dict(cell.config), cell.traffic
    if args.layers:
        m["num_hidden_layers"] = args.layers
    seq = int(t["sequence_length"])
    batch = args.sequences or int(t["sequences_per_step"])

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"     # what the lowerings ask

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import program_to_fn

    fluid.amp.enable_bf16()
    main_p, _, avg = train_lib.build_lm(fluid, m, seq, 0)
    fn = program_to_fn(main_p, ["ids", "lbl"], [avg.name])
    blk = main_p.global_block()

    def shape_of(name):
        v = blk.vars[name]
        dt = {"int64": jnp.int32, "float64": jnp.float32}.get(
            str(v.dtype), str(v.dtype))
        return jax.ShapeDtypeStruct(tuple(int(d) for d in v.shape),
                                    jnp.dtype(dt), sharding=chip)

    states = {n: shape_of(n) for n in fn.state_in_names}
    feeds = {"ids": jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                         sharding=chip),
             "lbl": jax.ShapeDtypeStruct((batch, seq, 1), jnp.int32,
                                         sharding=chip)}
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=chip)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        feeds, states, key).compile()
    mem = compiled.memory_analysis()
    params = sum(int(jnp.prod(jnp.asarray(s.shape))) for n, s in
                 states.items() if n in {v.name for v in
                                         blk.all_parameters()})
    out = {"workload": cell.name, "layers": m["num_hidden_layers"],
           "sequences": batch, "tokens_per_step": batch * seq,
           "parameters": params,
           "argument_gb": mem.argument_size_in_bytes / 1e9,
           "output_gb": mem.output_size_in_bytes / 1e9,
           "alias_gb": mem.alias_size_in_bytes / 1e9,
           "temp_gb": mem.temp_size_in_bytes / 1e9,
           "peak_estimate_gb": (mem.argument_size_in_bytes
                                + mem.output_size_in_bytes
                                - mem.alias_size_in_bytes
                                + mem.temp_size_in_bytes) / 1e9,
           "mosaic_calls": compiled.as_text().count("tpu_custom_call"),
           "note": "compiled for a described v5e, not run"}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
