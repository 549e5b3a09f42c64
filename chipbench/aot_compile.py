"""Compile each cell's LocalTrain program for a described TPU v5e chip
(no chip attached) and print what the compiler says it needs.

    JAX_PLATFORMS=cpu python chipbench/aot_compile.py [cell ...]

For every cell in ``BENCHMARK.json`` (or those named): the batched
executor's one jitted round program (vmap over the cohort of scan over
local steps of scan over microbatches) at the cell's cohort size and
knobs, lowered from ``jax.eval_shape`` shapes and compiled by the TPU
compiler for one chip of a ``v5e:2x2`` topology. Prints
``compiled.memory_analysis()``: argument, output, temporary and
generated-code bytes. A program that cannot fit the chip fails here,
before any chip time is spent. Not part of a benchmark run.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def cohort_knobs(t):
    """The knob tuple every client of a cell's window trains with."""
    from reference import fl
    lam = {c: float(t.get("init_duals", {}).get(c, 0.0))
           for c in fl.CONSTRAINTS}
    return fl.knobs(t, lam)


def main(names):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import files, traffic
    from repro.configs.base import ModelConfig
    from repro.core.client import ClientRunner
    from repro.fl.executor import BatchedExecutor
    from repro.models import build

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=chip), tree)
    bench = files.bench_file()
    for w in bench["workloads"]:
        if names and w["name"] not in names:
            continue
        cell = files.cell(w["name"])
        t = cell.traffic
        model = build(ModelConfig(**cell.model))
        fl_cfg = traffic.fl_config(t, seed=0)
        runner = ClientRunner(model, fl_cfg, data=None, resources=None)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        k, s, b, q, ga = cohort_knobs(t)
        mask, _ = runner.mask_for(params, k)
        batch = jax.ShapeDtypeStruct(
            (t["clients_per_round"], s, ga, b, t["seq_len"]), jnp.int32,
            sharding=chip)
        compiled = BatchedExecutor(runner)._batched.lower(
            on_chip(params), on_chip(mask),
            {"tokens": batch, "targets": batch}).compile()
        mem = compiled.memory_analysis()
        row = {k_: getattr(mem, k_) for k_ in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")}
        row["total_bytes"] = (row["argument_size_in_bytes"]
                              + row["output_size_in_bytes"]
                              + row["temp_size_in_bytes"]
                              - row["alias_size_in_bytes"])
        print(json.dumps({"cell": w["name"], "knobs": [k, s, b, q, ga],
                          "cohort": t["clients_per_round"], **row}),
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
