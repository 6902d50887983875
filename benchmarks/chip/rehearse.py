"""Compile a cell's step programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py \\
        --workload qwen3-8b-12l.docs

Compiles the engine's chunked-prefill (extend) step at every chunk bucket
and its paged decode step at every power-of-two batch up to the cell's
``max_running``, at the cell's ``max_len`` and published widths, for one
chip of a described ``v5e:2x2``.  Nothing runs.  Prints each program's
``memory_analysis()`` and the KV pool the benchmark's sizing rule would
give on a v5e.  A refusal by the chip's compiler raises here, at
no chip time.
"""
import argparse
import functools
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
HBM_LIMIT = 15.75 * 2**30  # what the v5e compiler allows a program
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness, spec
    from repro import kernels
    from repro.launch import specs
    from repro.models import get_model, nn
    from repro.serving import engine as eng_mod

    kernels.pallas_interpret = lambda: False  # what a TPU backend chooses
    cell = spec.load_cell(args.workload)
    cfg = cell.block.model_config(cell.config)
    dims = cell.block.dims(cell.config)
    api = get_model(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    params = on_chip(jax.eval_shape(lambda k: nn.split(api.init(k, cfg))[0],
                                    jax.random.PRNGKey(0)))
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(params))
    eng = cell.engine
    bs = harness.BLOCK_SIZE
    mb = -(-int(eng["max_len"]) // bs)
    block_bytes = harness.kv_block_bytes(cell.block, dims)
    util = harness.HBM_UTILIZATION
    budget = util * HBM_LIMIT - weight_bytes
    n_blocks, info = harness.pool_blocks(
        lambda n: max(harness.step_temps(cfg, api, params, eng, n,
                                        chip).values()),
        budget, block_bytes, mb + 1)
    temps = ", ".join(f"{t / 1e9:.3f} GB at {n} blocks"
                      for n, t in info["temp"].items())
    print(f"{cell.name}: weights {weight_bytes / 1e9:.3f} GB; largest "
          f"transient {temps}; {block_bytes} bytes per block of {bs}; at "
          f"{util} of "
          f"{HBM_LIMIT / 2**30:.2f} GiB the pool gets {n_blocks} blocks = "
          f"{n_blocks * bs} positions ({n_blocks * block_bytes / 1e9:.3f} "
          f"GB)", flush=True)

    def i32(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    store = on_chip(specs.cache_template(cfg, n_blocks, bs))
    for what, step, sizes, shapes in (
            ("extend T", eng_mod.paged_extend_step,
             harness.chunk_buckets(eng),
             lambda T: (i32((1, mb)), i32((1,)), i32((1, T)), i32((1, T)),
                        i32((1, T)))),
            ("decode B", eng_mod.paged_decode_step,
             harness.decode_batches(eng),
             lambda B: (i32((B, mb)), i32((B,)), i32((B,)), i32((B,)),
                        i32((B,))))):
        for s in sizes:
            fn = jax.jit(functools.partial(step, api, cfg),
                         donate_argnums=(1,))
            t0 = time.perf_counter()
            c = fn.lower(params, store, *shapes(s)).compile()
            secs = time.perf_counter() - t0
            ma = c.memory_analysis()
            kernel = "tpu_custom_call" in c.as_text()
            print(f"{what}={s}: compiled in {secs:.1f} s; arguments "
                  f"{ma.argument_size_in_bytes / 1e9:.3f} GB, outputs "
                  f"{ma.output_size_in_bytes / 1e9:.3f} GB, aliased "
                  f"{ma.alias_size_in_bytes / 1e9:.3f} GB, temp "
                  f"{ma.temp_size_in_bytes / 1e9:.3f} GB; Pallas kernel "
                  f"{kernel}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
